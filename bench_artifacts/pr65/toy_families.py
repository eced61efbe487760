"""For every served family's toy model (the tests' own `_model()` and
`_serve()`): the cache's `describe()`, `nbytes()`, the table's layout,
each refusal's exception and text, and the `COUNTERS` snapshot of one
seeded run — as JSON, to compare a parent checkout's with a change's.

    JAX_PLATFORMS=cpu PYTHONPATH=<checkout> python \
        <checkout>/bench_artifacts/pr65/toy_families.py <checkout> out.json

Reads nothing of the cache's constructor: it goes through `ServeEngine`.
"""

import importlib
import json
import os
import sys
import types

import numpy as np

root = os.path.abspath(sys.argv[1])
sys.path.insert(0, root)
sys.path.insert(0, os.path.join(root, "tests"))

import jax  # noqa: E402

from deepspeed_tpu.monitor.counters import COUNTERS  # noqa: E402
from deepspeed_tpu.serving import ServeConfig, ServeEngine  # noqa: E402

FAMILIES = {           # family -> (tests module, _model keywords)
    "evabyte": ("test_evabyte", None),
    "deepseek_v2": ("test_deepseek_v2", {}),
    "command_a_plus": ("test_cohere2_moe", {}),
    "granite": ("test_granite_hybrid", {}),
    "glm": ("test_glm_moe_dsa", {}),
    "qwen3_next": ("test_qwen3_next", {}),
    "nemotron_h": ("test_nemotron_h", {}),
    "lfm2": ("test_lfm2_moe", {}),
}


def _gpt():
    from deepspeed_tpu.models import GPT, gpt2_config

    model = GPT(gpt2_config("nano", num_layers=2, num_heads=4, d_model=32,
                            vocab_size=64, max_seq_len=64))
    serve = lambda **kw: ServeConfig(**dict(dict(
        block_size=4, num_blocks=40, max_batch=4, prefill_chunk=8,
        max_seq_len=64), **kw))
    return model, model.init(jax.random.PRNGKey(1)), serve, 64


def _family(name):
    if name == "gpt2":
        return _gpt()
    module, kw = FAMILIES[name]
    mod = importlib.import_module(module)
    if kw is None:                      # EvaByte's tests keep a fixture
        model = mod.EvaByte(mod._config())
        params = jax.jit(model.init)(jax.random.PRNGKey(0))
    else:
        model, params = mod._model(**kw)
    return model, params, mod._serve, model.config.vocab_size


def _refusal(fn):
    try:
        fn()
    except Exception as e:  # noqa: BLE001 — the text is what is compared
        return [type(e).__name__, str(e)]
    return None


def probe(name):
    model, params, serve, vocab = _family(name)
    mesh = types.SimpleNamespace(size=4, axis_size=lambda a: 1)
    paged = name == "gpt2"
    out = {"refuses": {
        "prefix_cache": _refusal(lambda: ServeEngine(
            model, params, serve(prefix_cache=True))) if not paged else None,
        "mesh": _refusal(lambda: ServeEngine(
            model, params, serve(prefix_cache=paged), mesh_info=mesh))
        if not paged else None}}
    COUNTERS.reset()
    eng = ServeEngine(model, params, serve(prefix_cache=paged))
    out["refuses"]["sessions"] = _refusal(
        lambda: eng.submit([1, 2, 3], 2, session_id="s"))
    if out["refuses"]["sessions"] is None:      # it was taken: run it out
        eng.run()
        COUNTERS.reset()
    kv = eng.kv
    out["describe"] = kv.describe()
    out["nbytes"] = kv.nbytes()
    out["state_nbytes"] = kv.state_nbytes()
    out["index_nbytes"] = kv.index_nbytes()
    out["bytes_per_block"] = kv.bytes_per_block()
    out["capacity_blocks"] = kv.capacity_blocks
    out["token_capacity"] = kv.token_capacity
    out["blocks_needed"] = [kv.blocks_needed(n) for n in (1, 7, 33, 64)]
    out["table"] = [kv.table_width, kv.window_blocks, kv.ring_blocks,
                    int(eng._slots.host["tables"].shape[1])]
    out["entries"] = [[list(a.shape) + [str(a.dtype)]
                       for a in jax.tree_util.tree_leaves(e)]
                      for e in kv.caches]
    rs = np.random.RandomState(7)
    lens = (5, 19, 3, 41, 12, 9)
    reqs = [eng.submit(rs.randint(0, vocab, (n,)).tolist(), 6 + i,
                       seed=i, temperature=0.7 * (i % 2))
            for i, n in enumerate(lens)]
    eng.run()
    out["tokens"] = [r.out for r in reqs]
    out["free_blocks"] = kv.free_blocks
    out["counters"] = {k: list(v) for k, v in
                       sorted(COUNTERS.snapshot().items())
                       if not k.endswith("_ms")}
    return out


if __name__ == "__main__":
    names = ["gpt2"] + list(FAMILIES)
    result = {name: probe(name) for name in names}
    with open(sys.argv[2], "w") as f:
        json.dump(result, f, indent=1, sort_keys=True)
    print("wrote", sys.argv[2], {n: len(r["counters"])
                                 for n, r in result.items()})
