"""The serving cache's plan (`serving/kv_cache.py::cache_plan`), family
by family, over the toy model of each family's own tests: the groups
partition the layers, the cache's bytes are the groups', and what the
cache says of itself, what it refuses and how a request's table is laid
out are what the cache of twenty-one constructor arguments said,
refused and laid out (`cache_plan_parent.json`, written at PR 65 from
the parent commit by `bench_artifacts/pr65/toy_families.py`)."""

import importlib
import json
import os
import types

import pytest

import jax

from deepspeed_tpu.models import GPT, gpt2_config
from deepspeed_tpu.serving import PagedKVCache, ServeConfig, ServeEngine
from deepspeed_tpu.serving.kv_cache import KINDS, cache_plan

with open(os.path.join(os.path.dirname(__file__),
                       "cache_plan_parent.json")) as f:
    PARENT = json.load(f)

FAMILIES = {            # family -> (its tests' module, the model's class)
    "gpt2": None,
    "evabyte": ("test_evabyte", "EvaByte"),
    "deepseek_v2": ("test_deepseek_v2", "DeepSeekV2"),
    "command_a_plus": ("test_cohere2_moe", "Cohere2Moe"),
    "granite": ("test_granite_hybrid", "GraniteHybrid"),
    "glm": ("test_glm_moe_dsa", "GlmMoeDsa"),
    "qwen3_next": ("test_qwen3_next", "Qwen3Next"),
    "nemotron_h": ("test_nemotron_h", "NemotronH"),
    "lfm2": ("test_lfm2_moe", "Lfm2Moe"),
}
EACH = pytest.mark.parametrize("family", list(FAMILIES))


def _family(name):
    """(model, its tests' ServeConfig maker) — no weights: a plan reads
    the spec and two configs."""
    if name == "gpt2":
        model = GPT(gpt2_config("nano", num_layers=2, num_heads=4,
                                d_model=32, vocab_size=64, max_seq_len=64))
        return model, lambda **kw: ServeConfig(**dict(dict(
            block_size=4, num_blocks=40, max_batch=4, prefill_chunk=8,
            max_seq_len=64), **kw))
    module, cls = FAMILIES[name]
    mod = importlib.import_module(module)
    return getattr(mod, cls)(mod._config()), mod._serve


def _plan_and_cache(name):
    model, serve = _family(name)
    c = serve(prefix_cache=name == "gpt2")
    plan = cache_plan(model.layer_spec(), model.config, c)
    return plan, PagedKVCache(plan, c.num_blocks,
                              dtype=model.config.param_dtype,
                              prefix_cache=c.prefix_cache)


@EACH
def test_the_groups_partition_the_layers(family):
    model, serve = _family(family)
    plan = cache_plan(model.layer_spec(), model.config, serve())
    layers = [i for g in plan.groups for i in g.layers]
    assert sorted(layers) == list(range(model.config.num_layers))
    assert all(g.layers and g.kind in KINDS for g in plan.groups)
    assert plan.num_layers == model.config.num_layers
    for g in plan.groups:       # a run of the table, or no blocks at all
        assert (g.run == "") == (g.keeps in ("slots", "nothing"))
        assert (g.run == "ring") == (g.keeps == "ring")


@EACH
def test_the_caches_bytes_are_the_groups(family):
    plan, kv = _plan_and_cache(family)
    want = PARENT[family]
    assert kv.nbytes() == sum(kv.group_nbytes(g)[0] for g in plan.groups)
    assert kv.nbytes() == want["nbytes"]
    assert kv.state_nbytes() == want["state_nbytes"]
    assert kv.index_nbytes() == want["index_nbytes"]
    assert kv.bytes_per_block() == want["bytes_per_block"]
    assert [[list(a.shape) + [str(a.dtype)]
             for a in jax.tree_util.tree_leaves(e)]
            for e in kv.caches] == want["entries"]


@EACH
def test_the_cache_describes_itself_as_it_did(family):
    assert _plan_and_cache(family)[1].describe() == PARENT[family]["describe"]


@EACH
def test_the_table_is_laid_out_as_the_schedule_had_it(family):
    """`table_width`, the window's entries inside it and the ring's
    behind it: the three numbers the engine hands the schedule, and the
    width of a slot's row of tables."""
    plan, kv = _plan_and_cache(family)
    want = PARENT[family]
    assert [plan.table_width, plan.window_blocks, plan.ring_blocks,
            plan.table_width + plan.ring_blocks] == want["table"]
    assert [kv.table_width, kv.window_blocks, kv.ring_blocks] == \
        want["table"][:3]
    assert kv.token_capacity == want["token_capacity"]
    assert kv.capacity_blocks == want["capacity_blocks"]
    assert [kv.blocks_needed(n) for n in (1, 7, 33, 64)] == \
        want["blocks_needed"]
    assert kv.alloc("a", kv.blocks_needed(7)).shape == (want["table"][3],)


@EACH
def test_what_is_not_offered_is_refused_in_the_same_words(family):
    """The prefix cache and a mesh at construction — before the weights
    are looked at — and sessions by the first group that has a reason."""
    model, serve = _family(family)
    want = PARENT[family]["refuses"]
    mesh = types.SimpleNamespace(size=4, axis_size=lambda axis: 1)
    for what, build in (
            ("prefix_cache", lambda: ServeEngine(
                model, None, serve(prefix_cache=True))),
            ("mesh", lambda: ServeEngine(
                model, None, serve(prefix_cache=False), mesh_info=mesh))):
        if want[what] is not None:
            with pytest.raises(NotImplementedError) as e:
                build()
            assert [type(e.value).__name__, str(e.value)] == want[what]
    plan = cache_plan(model.layer_spec(), model.config, serve())
    said = next((g.no_sessions for g in plan.groups if g.no_sessions), None)
    assert said == (want["sessions"] and want["sessions"][1])
    if family in ("gpt2", "evabyte"):     # nothing to say of a mesh
        assert not any(g.no_mesh for g in plan.groups)
