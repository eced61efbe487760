"""Open-loop request traffic (from tools/serve_bench.py::build_timeline):
arrivals with exponential gaps at a fixed mean rate, prompt and output
lengths log-uniform between bounds, prompts of uniform random token ids
with nothing shared.

The schedule (the gaps and each request's prompt and output length) is
fixed by the mix: `shape_seed`, `rate_rps` and the run's length.  It is the
same for every `--seed`; the seed draws the token ids (and, in the runner,
the weights).  Measured on the chip before this was settled (PR 24, four
seeds, 50 requests in 50 s at 1 request/s): with the same set of gaps and
lengths dealt in another order by the seed, the 95th percentile of time to
first token read 582, 698, 942 and 1135 ms and tokens/s 49 to 57, because at
some fifty requests a window which long prompts meet decides the tail.  A
seed that changes the work that much measures the deal, not the system, so
the deal is the mix's, not the seed's: a tail read on this mix is the tail
of ONE deal, and a cell on another deal is one more mix file with another
`shape_seed`.  The gaps are scaled so that the last request is due just
before sending ends.

Parameters (traffic/<mix>.json): `rate_rps`, `prompt_tokens` [lo, hi],
`output_tokens` [lo, hi], `max_total_tokens`, `shape_seed`."""

import math

import numpy as np

from benchmarks.harness import numpy_seed


def _log_uniform(rng, lo, hi, n):
    return np.exp(rng.uniform(math.log(lo), math.log(hi + 1), n)) \
        .astype(np.int64).clip(lo, hi)


def shapes(traffic: dict, seconds: float):
    """-> (gaps between arrivals, prompt_len [n], output_len [n]): the
    fixed deal."""
    n = max(1, int(round(traffic["rate_rps"] * seconds)))
    rng = np.random.RandomState(traffic["shape_seed"])
    gaps = rng.exponential(1.0, n)
    gaps *= seconds * (n / (n + 1.0)) / gaps.sum()
    p = _log_uniform(rng, *traffic["prompt_tokens"], n)
    o = _log_uniform(rng, *traffic["output_tokens"], n)
    o = np.minimum(o, traffic["max_total_tokens"] - p).clip(1)
    return gaps, p, o


def timeline(traffic: dict, *, seed: int, seconds: float, config: dict,
             family):
    """-> [(due_s, prompt token ids, max_new_tokens)], by due time."""
    gaps, p, o = shapes(traffic, seconds)
    due = np.cumsum(gaps)
    rng = np.random.RandomState(numpy_seed(seed))
    vocab = family.prompt_vocab(config)
    return [(float(t), rng.randint(0, vocab, (int(n),)).tolist(), int(m))
            for t, n, m in zip(due, p, o)]
