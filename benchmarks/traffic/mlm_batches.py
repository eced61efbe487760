"""BERT pre-training batches (copied from tools/bert_bench.py::mlm_batch,
the reference's recipe): uniform random ids, `mask_share` of the positions
replaced by the mask token and labelled, all other labels -100, one token
type, a random next-sentence label.  Parameters (traffic/<mix>.json):
`seq_len`, `micro_batch` (rows per chip), `mask_share`."""

import numpy as np

from benchmarks.harness import numpy_seed


def tokens_per_step(traffic: dict, chips: int) -> int:
    return traffic["micro_batch"] * chips * traffic["seq_len"]


def batches(traffic: dict, *, seed: int, chips: int, config: dict, family):
    rng = np.random.RandomState(numpy_seed(seed))
    rows, seq = traffic["micro_batch"] * chips, traffic["seq_len"]
    vocab = family.prompt_vocab(config)
    mask_id = config["assumed"]["mask_token_id"]
    while True:
        ids = rng.randint(0, vocab, size=(rows, seq)).astype(np.int32)
        labels = np.full((rows, seq), -100, np.int32)
        mask = rng.rand(rows, seq) < traffic["mask_share"]
        labels[mask] = ids[mask]
        ids[mask] = mask_id
        yield {"input_ids": ids, "mlm_labels": labels,
               "token_type_ids": np.zeros((rows, seq), np.int32),
               "nsp_labels": rng.randint(0, 2, size=(rows,)).astype(np.int32)}
