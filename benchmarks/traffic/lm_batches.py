"""Language-model batches: a fresh batch of uniform random token ids every
step, from a seeded host generator.  Parameters (traffic/<mix>.json):
`seq_len`, `micro_batch` (rows per chip)."""

import numpy as np

from benchmarks.harness import numpy_seed


def tokens_per_step(traffic: dict, chips: int) -> int:
    return traffic["micro_batch"] * chips * traffic["seq_len"]


def batches(traffic: dict, *, seed: int, chips: int, config: dict, family):
    """Yields (tokens, labels), each int32 [micro_batch * chips, seq_len]."""
    rng = np.random.RandomState(numpy_seed(seed))
    rows, seq = traffic["micro_batch"] * chips, traffic["seq_len"]
    vocab = family.prompt_vocab(config)
    while True:
        t = rng.randint(0, vocab, (rows, seq + 1)).astype(np.int32)
        yield t[:, :-1], t[:, 1:]
