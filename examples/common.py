"""Shared example plumbing: the repo on sys.path + synthetic data."""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))


def token_batches(steps, batch, seq, vocab, seed=0):
    import numpy as np

    rng = np.random.RandomState(seed)
    for _ in range(steps):
        toks = np.zeros((batch, seq + 1), np.int32)
        toks[:, 0] = rng.randint(0, vocab, batch)
        stride = rng.randint(1, 5, batch)
        for t in range(1, seq + 1):
            toks[:, t] = (toks[:, t - 1] + stride) % vocab
        yield toks[:, :-1], toks[:, 1:]


def print_curve(name, losses):
    head = " ".join(f"{l:.3f}" for l in losses[:3])
    tail = " ".join(f"{l:.3f}" for l in losses[-3:])
    print(f"{name}: {head} ... {tail}")
