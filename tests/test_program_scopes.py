"""The serving programs name their own stages (PR 59).

`monitor/tracing.py::program_scopes` reads a compiled program's text into
{instruction: scope path}; `ServeEngine.attach_tracing` hands a recorder
that map for every program the engine runs, and nothing where there is no
recorder; every operation of the eight served families' programs lies
under exactly one of the six stages; and a scope is metadata and nothing
else: the compiled text without its `metadata={...}` is the text compiled
with `jax.named_scope` doing nothing, for the serving programs and for
the fused training step of the GPT and BERT toys (whose only change is
the registry's `kernel.` / `oracle.` wrap).
"""

import contextlib
import json
import os
import re
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.monitor import tracing
from deepspeed_tpu.serving import ServeConfig, ServeEngine
from deepspeed_tpu.serving.programs import STAGES

_METADATA = re.compile(r',? ?metadata=\{(?:[^{}"]|"(?:[^"\\]|\\.)*")*\}')
# the tables `stack_frame_id` points into: metadata's other half
_TABLES = re.compile(r"^(?:FileNames|FunctionNames|FileLocations|"
                     r"StackFrames)\n(?:\d+ .*\n)*\n?", re.M)
_PARAMETER = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = .*? parameter\(\d+\)",
                        re.M)


def _stage(path: str) -> str:
    return tracing.stage_of(path, STAGES)


def _stripped(text: str) -> str:
    """The program without what a scope may touch: `metadata={...}`, the
    tables of source locations it points into, and the LABELS of
    parameters — XLA names a branch computation's parameter after the
    scope its conditional was written under (`%sample.2` for
    `%jit_decode_.2`); every other instruction keeps its name."""
    text = _TABLES.sub("", _METADATA.sub("", text))
    labels = set(_PARAMETER.findall(text))
    return re.sub(r"%?([\w.\-]+)", lambda m: "%parameter"
                  if m.group(1) in labels else m.group(0), text)


# -- (a) the map of a program's text ----------------------------------------

HAND = '''HloModule jit_decode, is_scheduled=true

%fused_computation (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  ROOT %mul.3 = f32[8]{0} multiply(%param_0, %param_0), metadata={op_name="jit(decode)/ffn/moe_experts/oracle.touched_experts/mul" stack_frame_id=4}
}

%region_0.2 (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0), metadata={op_name="reduce_sum"}
  %b = f32[] parameter(1), metadata={op_name="reduce_sum"}
  ROOT %reduce_sum.5 = f32[] add(%a, %b), metadata={op_name="reduce_sum" stack_frame_id=9}
}

%body (arg: (s32[], f32[8])) -> (s32[], f32[8]) {
  %arg = (s32[], f32[8]{0}) parameter(0)
  %get-tuple-element.1 = f32[8]{0} get-tuple-element(%arg), index=1
  %dot.7 = f32[8]{0} dot(%get-tuple-element.1, %get-tuple-element.1), metadata={op_name="jit(decode)/attn/swa_attend/oracle.grouped_attention/while/body/dot_general" stack_frame_id=2}
  ROOT %tuple.1 = (s32[], f32[8]{0}) tuple(%get-tuple-element.1, %dot.7)
}

ENTRY %main.9 (tokens.1: s32[8], w.1: f32[8]) -> (f32[8], f32[8]) {
  %tokens.1 = s32[8]{0} parameter(0), metadata={op_name="tokens"}
  %w.1 = f32[8]{0} parameter(1), metadata={op_name="params['w']"}
  %copy-start.21 = (f32[8]{0:S(1)}, f32[8]{0}, u32[]{:S(2)}) copy-start(%w.1)
  %slice-start.3 = ((f32[8]{0}), f32[4]{0:S(1)}, s32[]{:S(2)}, /*index=3*/s32[]) slice-start(%w.1), slice={[0:4]}
  %while.4 = (s32[], f32[8]{0}) while(%tokens.1), condition=%cond, body=%body, metadata={op_name="jit(decode)/attn/swa_attend/oracle.grouped_attention/while" stack_frame_id=2}
  %slice-done.3 = f32[4]{0:S(1)} slice-done(%slice-start.3)
  %custom-call.5 = f32[8]{0:S(1)} custom-call(%slice-done.3, %slice-done.3), custom_call_target="ConcatBitcast"
  %custom-call.2 = f32[8]{0} custom-call(%while.4, %custom-call.5), custom_call_target="tpu_custom_call", backend_config={"kernel_name": "walk"}, metadata={op_name="jit(decode)/attn/full_attend/kernel.grouped_attention/jit(_walk)/pallas_call" stack_frame_id=3}
  %copy-done.21 = f32[8]{0:S(1)} copy-done(%copy-start.21)
  %fusion.8 = f32[8]{0} fusion(%custom-call.2, %copy-done.21), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(decode)/ffn/moe_experts/oracle.touched_experts/mul" stack_frame_id=4}
  %add.6 = f32[8]{0} add(%fusion.8, %fusion.8), metadata={op_name="jit(decode)/add;jit(decode)/ffn/moe_shared/mul" stack_frame_id=5}
  %copy.9 = f32[8]{0} copy(%fusion.8)
  %copy.10 = f32[8]{0} copy(%w.1)
  ROOT %tuple.2 = (f32[8]{0}, f32[8]{0}, f32[8]{0}) tuple(%add.6, %copy.9, %copy.10)
}
'''


def test_program_scopes_of_a_text_written_by_hand():
    """Nested scopes, a `while` and its body, a fusion and the
    instruction inside it, a Mosaic call behind an inner `jit`, an
    instruction under no scope (`add.6`, merged with another whose name
    follows its own behind a ";"); the compiler's own instructions, which
    have no `op_name`, counted with the first instruction that consumes
    what they made (a prefetch of a weight: `copy-start.21` /
    `copy-done.21` for the FFN's fusion, `slice-start.3` /
    `slice-done.3` / the `ConcatBitcast` for the kernel; a tuple shape
    with its `/*index=3*/` is read past), else with what they consumed
    (`copy.9` behind the root); and what is left out: parameters (their
    `op_name` is the argument's name), a reduction's body (a bare
    primitive: no path), tuples, and a copy that nothing with a path
    touches (`copy.10`)."""
    scopes = tracing.program_scopes(HAND)
    kernel = "attn/full_attend/kernel.grouped_attention"
    experts = "ffn/moe_experts/oracle.touched_experts"
    assert scopes == {
        "mul.3": experts,
        "dot.7": "attn/swa_attend/oracle.grouped_attention/while/body",
        "while.4": "attn/swa_attend/oracle.grouped_attention",
        "custom-call.2": kernel,
        "fusion.8": experts,
        "add.6": "",
        "copy-start.21": experts + "/xla.copy-start",
        "copy-done.21": experts + "/xla.copy-done",
        "slice-start.3": kernel + "/xla.slice-start",
        "slice-done.3": kernel + "/xla.slice-done",
        "custom-call.5": kernel + "/xla.custom-call",
        "copy.9": experts + "/xla.copy",
    }
    assert tracing.program_name(HAND) == "jit_decode"
    assert [_stage(p) for p in (
        "attn/full_attend", "ffn", "", "while/body", "attention")] == \
        ["attn", "ffn", "", "", ""]
    packed = tracing.pack_scopes(scopes)
    assert packed["paths"] == sorted(set(scopes.values()))
    assert all(isinstance(i, int) for i in packed["instructions"].values())
    assert tracing.unpack_scopes(packed) == scopes
    events = [{"name": "decode_step", "args": {}},
              {"name": "program_scopes",
               "args": dict(packed, program="jit_decode")}]
    assert tracing.scope_maps(events) == {"jit_decode": scopes}


def test_program_scopes_of_a_compiled_toy_program():
    """What this jax writes: nested scopes, an inner `jit`, a `while`
    (its own instruction and its body's), a conditional, and no
    parameter among the instructions."""

    @jax.jit
    def inner(x):
        return jnp.sin(x) @ x

    @jax.jit
    def toy(x, n):
        with jax.named_scope("attn"):
            with jax.named_scope("core"):
                y = inner(x)
                y = jax.lax.fori_loop(0, n, lambda i, c: c @ x + 1.0, y)
        with jax.named_scope("sample"):
            z = jax.lax.cond(jnp.any(y > 0), lambda: jnp.sum(y, axis=0),
                             lambda: y[0])
        return z + x[0]

    text = toy.lower(jnp.ones((16, 16)), 3).compile().as_text()
    assert tracing.program_name(text) == "jit_toy"
    scopes = tracing.program_scopes(text)
    paths = set(scopes.values())
    assert {"attn/core", "attn/core/while/body", "sample", ""} <= paths
    assert {_stage(p) for p in paths} == {"attn", "sample", ""}
    assert not any("jit(" in p for p in paths)
    whiles = [k for k in scopes if k.startswith("while")]
    assert whiles and all(scopes[k] == "attn/core" for k in whiles)
    names = set(re.findall(r"^\s*(?:ROOT )?%?([\w.\-]+) = ", text, re.M))
    assert set(scopes) <= names
    assert not any(re.search(rf"%?{re.escape(k)} = \S+ parameter\(", text)
                   for k in scopes)


# -- (b) every operation under one stage; a scope is metadata only ----------


def _gpt(**kw):
    from deepspeed_tpu.models import GPT, gpt2_config

    return GPT(gpt2_config("nano", num_layers=2, num_heads=4, d_model=64,
                           vocab_size=256, max_seq_len=128,
                           shard_activations=False)), dict(
        block_size=16, num_blocks=33, max_batch=4, prefill_chunk=32,
        max_seq_len=128, **kw)


def _evabyte():
    from deepspeed_tpu.models import EvaByte, EvaByteConfig

    return EvaByte(EvaByteConfig(
        max_seq_len=256, num_layers=2, num_heads=4, d_model=64, d_ff=176,
        window_size=32, chunk_size=4, attn_out_std=0.3)), dict(
        block_size=4, num_blocks=96, max_batch=3, prefill_chunk=8,
        max_seq_len=256, prefix_cache=False)


def _deepseek():
    from deepspeed_tpu.models import DeepSeekV2, DeepSeekV2Config
    from deepspeed_tpu.models import deepseek_v2 as dsv2

    return DeepSeekV2(DeepSeekV2Config(
        vocab_size=128, max_seq_len=128, num_layers=3, num_heads=4,
        d_model=64, kv_lora_rank=32, qk_nope_head_dim=16,
        qk_rope_head_dim=16, v_head_dim=16, d_ff=96, first_k_dense=1,
        num_experts=8, top_k=3, num_shared_experts=1, d_expert=48,
        yarn=dsv2.Yarn(40.0, 64, 32.0, 1.0, 0.707, 0.707), init_std=0.2,
        router_std=1.0)), dict(
        block_size=8, num_blocks=40, max_batch=3, prefill_chunk=16,
        max_seq_len=128, prefix_cache=False)


def _command_a():
    from deepspeed_tpu.models.cohere2_moe import (Cohere2Moe,
                                                  Cohere2MoeConfig)

    return Cohere2Moe(Cohere2MoeConfig(
        vocab_size=128, max_seq_len=256, num_layers=4, num_heads=8,
        kv_heads=2, head_dim=16, d_model=64, d_expert=32, num_experts=8,
        top_k=4, num_shared=2, window=32, init_std=0.2)), dict(
        block_size=8, num_blocks=120, max_batch=3, prefill_chunk=16,
        max_seq_len=256, prefix_cache=False)


def _granite():
    from deepspeed_tpu.models.granite_hybrid import (GraniteHybrid,
                                                     GraniteHybridConfig)

    return GraniteHybrid(GraniteHybridConfig(
        vocab_size=97, max_seq_len=64, num_layers=3, period=3,
        attention_at=(1,), d_model=32, d_ffn=64, num_heads=4, kv_heads=2,
        head_dim=8, ssm_heads=4, ssm_head_dim=8, ssm_state=16, ssm_conv=4,
        ssm_chunk=4, init_std=0.2)), dict(
        block_size=4, num_blocks=64, max_batch=3, prefill_chunk=8,
        max_seq_len=64, prefix_cache=False)


def _glm():
    from deepspeed_tpu.models.glm_moe_dsa import GlmMoeDsa, GlmMoeDsaConfig

    return GlmMoeDsa(GlmMoeDsaConfig(
        vocab_size=97, max_seq_len=96, num_layers=3, num_heads=4,
        d_model=32, q_lora_rank=16, kv_lora_rank=16, qk_nope_head_dim=8,
        qk_rope_head_dim=4, v_head_dim=12, index_heads=3, index_head_dim=8,
        index_topk=8, indexer_types=("full", "shared", "full"), d_ff=48,
        first_k_dense=1, num_experts=16, top_k=4, d_expert=24,
        experts_held=4, first_expert=4, init_std=0.3, router_std=0.5,
        bias_std=0.3, query_std=0.6)), dict(
        block_size=4, num_blocks=80, max_batch=3, prefill_chunk=8,
        max_seq_len=96, prefix_cache=False)


def _qwen3_next():
    from deepspeed_tpu.models.qwen3_next import Qwen3Next, Qwen3NextConfig

    return Qwen3Next(Qwen3NextConfig(
        vocab_size=97, max_seq_len=64, num_layers=4, period=4, d_model=32,
        num_heads=4, kv_heads=2, head_dim=16, rotary_dim=8, rope_theta=1e4,
        gdn_key_heads=2, gdn_value_heads=4, gdn_key_dim=8, gdn_value_dim=8,
        gdn_conv=4, gdn_chunk=4, d_expert=16, d_shared=16, num_experts=16,
        top_k=4, init_std=0.2, init_dt=(1e-3, 0.5))), dict(
        block_size=4, num_blocks=64, max_batch=3, prefill_chunk=8,
        max_seq_len=64, prefix_cache=False)


def _nemotron_h():
    from deepspeed_tpu.models.nemotron_h import NemotronH, NemotronHConfig

    return NemotronH(NemotronHConfig(
        vocab_size=97, max_seq_len=64, pattern="MEM*E", d_model=32,
        num_heads=4, kv_heads=2, head_dim=16, ssm_heads=8, ssm_head_dim=4,
        ssm_state=8, ssm_groups=4, ssm_conv=4, ssm_chunk=4, d_expert=16,
        d_shared=24, num_experts=16, top_k=3, init_std=0.2)), dict(
        block_size=4, num_blocks=64, max_batch=3, prefill_chunk=8,
        max_seq_len=64, prefix_cache=False)


def _lfm2_moe():
    from deepspeed_tpu.models.lfm2_moe import Lfm2Moe, Lfm2MoeConfig

    return Lfm2Moe(Lfm2MoeConfig(
        vocab_size=97, max_seq_len=64,
        layer_types=("conv", "conv", "full_attention", "conv"), d_model=32,
        d_ffn=48, dense_layers=2, num_heads=4, kv_heads=2, head_dim=16,
        rope_theta=1e4, d_expert=16, num_experts=16, top_k=3,
        init_std=0.2)), dict(
        block_size=4, num_blocks=64, max_batch=3, prefill_chunk=8,
        max_seq_len=64, prefix_cache=False)


FAMILIES = {"gpt": _gpt, "evabyte": _evabyte, "deepseek_v2": _deepseek,
            "command_a": _command_a, "granite_hybrid": _granite,
            "glm_moe_dsa": _glm, "qwen3_next": _qwen3_next,
            "nemotron_h": _nemotron_h, "lfm2_moe": _lfm2_moe,
            "gpt_drafting": lambda: _gpt(draft_len=2)}
# the scopes each family's programs are known by, beneath their stages
# (PERF.md §3 names the metric that reads each)
EXPECT = {
    "gpt": {"attn/paged_attend/oracle.paged_attention"},
    "gpt_drafting": {"attn/paged_attend/oracle.paged_attention"},
    "evabyte": {"attn/eva_attend/oracle.eva_attention"},
    "deepseek_v2": {"attn/mla_attend", "ffn/moe_route", "ffn/moe_experts",
                    "ffn/moe_shared"},
    "command_a": {"attn/swa_attend/oracle.grouped_attention",
                  "attn/full_attend/oracle.grouped_attention",
                  "ffn/moe_experts"},
    "granite_hybrid": {"attn/full_attend/oracle.grouped_attention"},
    "glm_moe_dsa": {"attn/dsa_index", "attn/dsa_select", "attn/dsa_attend",
                    "ffn/moe_experts"},
    "qwen3_next": {"attn/gated_attend/oracle.grouped_attention",
                   "ffn/moe_route", "ffn/moe_experts", "ffn/moe_shared"},
    "nemotron_h": {"attn/full_attend/oracle.grouped_attention",
                   "ffn/moe_route", "ffn/moe_experts", "ffn/moe_shared"},
    "lfm2_moe": {"attn/full_attend/oracle.grouped_attention",
                 "ffn/moe_route", "ffn/moe_experts"},
}
STATE = {"granite_hybrid": ("state/ssm.scan", "state/ssm.step"),
         "nemotron_h": ("state/ssm.scan", "state/ssm.step"),
         "qwen3_next": ("state/gdn.scan", "state/gdn.step"),
         "lfm2_moe": ("state/conv.scan", "state/conv.step")}
_TEXTS = {}


def _program_texts(family: str) -> dict:
    """{program: compiled text} of a toy engine's programs, at the shapes
    the engine calls them with."""
    model, serve = FAMILIES[family]()
    params = jax.jit(model.init)(jax.random.PRNGKey(0))
    engine = ServeEngine(model, params, ServeConfig(**serve))
    try:
        return {name: program.lower(*args).compile().as_text()
                for name, (program, args) in engine._program_calls().items()}
    finally:
        engine.close()


def _texts(family: str, monkeypatch) -> tuple:
    """(with the scopes, with `jax.named_scope` doing nothing)."""
    if family not in _TEXTS:
        scoped = _program_texts(family)
        jax.clear_caches()
        with monkeypatch.context() as m:
            m.setattr(jax, "named_scope",
                      lambda name: contextlib.nullcontext())
            bare = _program_texts(family)
        jax.clear_caches()
        _TEXTS[family] = scoped, bare
    return _TEXTS[family]


CASES = [(f, p) for f in FAMILIES for p in (
    ("prefill", "verify") if f == "gpt_drafting"
    else ("prefill", "decode", "seat"))
    if not (p == "seat" and f not in ("gpt", "deepseek_v2"))]


@pytest.mark.parametrize("family,program", CASES,
                         ids=[f"{f}-{p}" for f, p in CASES])
def test_every_operation_lies_under_one_stage_and_scopes_are_metadata_only(
        family, program, monkeypatch):
    scoped, bare = _texts(family, monkeypatch)
    assert set(scoped) == set(bare) == (
        {"prefill", "verify"} if family == "gpt_drafting"
        else {"prefill", "decode", "seat"})
    scopes = tracing.program_scopes(scoped[program])
    assert len(scopes) > (50 if program != "seat" else 0)
    outside = {k: v for k, v in scopes.items() if not _stage(v)}
    assert not outside, outside
    stages = {_stage(v) for v in scopes.values()}
    if program == "seat":
        assert stages == {"sample"}
    else:
        has_state = family in STATE
        assert stages == {"embed", "attn", "ffn", "head", "sample"} | (
            {"state"} if has_state else set())
        paths = set(scopes.values())
        # a scope is a prefix of the paths beneath it
        under = lambda want: any(p == want or p.startswith(want + "/")
                                 for p in paths)
        for want in EXPECT[family]:
            assert under(want), (want, sorted(paths))
        if has_state:
            assert under(STATE[family][program == "decode"])
            assert not under(STATE[family][program != "decode"])
    # metadata only: without it, the text is the one compiled with no
    # scope anywhere
    unscoped = tracing.program_scopes(bare[program])
    assert not any(_stage(v) for v in unscoped.values())
    assert _stripped(scoped[program]) == _stripped(bare[program])
    assert scoped[program] != bare[program]


def _fused_step_text(kind: str) -> str:
    """The compiled text of a toy engine's fused training step, lowered
    from the arguments the engine hands it."""
    import deepspeed_tpu
    from deepspeed_tpu.comm import make_mesh

    if kind == "gpt":
        from deepspeed_tpu.models import GPT, gpt2_config

        model = GPT(gpt2_config("nano", num_layers=2, num_heads=4,
                                d_model=64, vocab_size=256, max_seq_len=64,
                                shard_activations=False))
        t = np.random.RandomState(0).randint(0, 256, (2, 65)).astype(
            np.int32)
        batch = (t[:, :-1], t[:, 1:])
    else:
        from deepspeed_tpu.models import Bert, bert_config

        model = Bert(bert_config("bert-tiny", max_seq_len=64))
        rs = np.random.RandomState(0)
        ids = rs.randint(0, 512, (2, 64)).astype(np.int32)
        batch = {"input_ids": ids,
                 "mlm_labels": np.where(rs.rand(2, 64) < 0.15, ids,
                                        -100).astype(np.int32),
                 "token_type_ids": np.zeros((2, 64), np.int32),
                 "nsp_labels": rs.randint(0, 2, (2,)).astype(np.int32)}
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, mpu=make_mesh(devices=jax.devices()[:1]),
        config_params={
            "train_batch_size": 2, "train_micro_batch_size_per_gpu": 2,
            "optimizer": {"type": "Adam", "params": {"lr": 1e-4}},
            "zero_optimization": {"stage": 2}, "mesh": {"data": 1},
            "steps_per_print": 0})
    counted = engine._step_fns["full"]
    step, seen = counted.fn, {}

    def spy(*args):
        seen["args"] = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(
                np.shape(a), jnp.result_type(a),
                sharding=getattr(a, "sharding", None)), args)
        return step(*args)

    counted.fn = spy
    engine.forward(batch)
    engine.backward()
    engine.step()
    return step.lower(*seen["args"]).compile().as_text()


@pytest.mark.parametrize("kind", ["gpt", "bert"])
def test_the_fused_training_step_gains_metadata_only(kind, monkeypatch):
    """The training models have no scope of this PR's; their attention
    goes through `kernels/registry.py::dispatch`, which wraps what it
    calls in `oracle.<op>` here (`kernel.<op>` on the chip)."""
    scoped = _fused_step_text(kind)
    jax.clear_caches()
    with monkeypatch.context() as m:
        m.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
        bare = _fused_step_text(kind)
    jax.clear_caches()
    paths = set(tracing.program_scopes(scoped).values())
    assert any("oracle.flash_attention" in p for p in paths), sorted(paths)
    assert not any("oracle." in p or "kernel." in p
                   for p in tracing.program_scopes(bare).values())
    assert _stripped(scoped) == _stripped(bare)


# -- the shapes the map is compiled from are the shapes a launch hands over --


def _aval(a) -> tuple:
    """What `jit` keys a program on, of an argument or of its
    description."""
    if not isinstance(a, jax.ShapeDtypeStruct):
        a = jax.api_util.shaped_abstractify(a)
    return tuple(a.shape), jnp.dtype(a.dtype), bool(a.weak_type)


class _Spy:
    """A program that keeps what its last call was handed."""

    def __init__(self, fn):
        self.fn, self.seen = fn, None

    def __call__(self, *args):
        self.seen = jax.tree_util.tree_map(_aval, args)
        return self.fn(*args)


@pytest.mark.parametrize("family", list(FAMILIES))
def test_program_calls_are_the_launches_arguments(family):
    """`ServeEngine._program_calls` describes each program's arguments
    from what the engine holds, and `attach_tracing` compiles the map's
    text from that description: it is the module the launches run — and
    its instruction names the ones a device trace prints — only while
    every argument's shape, dtype and weak type are what `_prefill_chunk`,
    the decode (or verify) launch and `seat` really hand over."""
    model, serve = FAMILIES[family]()
    params = jax.jit(model.init)(jax.random.PRNGKey(0))
    engine = ServeEngine(model, params, ServeConfig(**serve))
    try:
        described = {
            name: jax.tree_util.tree_map(_aval, args)
            for name, (_, args) in engine._program_calls().items()}
        assert set(described) == (
            {"prefill", "verify"} if family == "gpt_drafting"
            else {"prefill", "decode", "seat"})
        for name in described:
            engine.programs[name] = _Spy(engine.programs[name])
        # a prompt of two chunks and a tail, some tokens behind it
        prompt = list(range(1, 2 * serve["prefill_chunk"] + 4))
        assert len(engine.generate([prompt], 4)[0]) == 4
        for name, want in described.items():
            assert engine.programs[name].seen == want, name
    finally:
        engine.close()


# -- (d) the engine tells a recorder, and nobody else -----------------------


class _Lowering:
    """A program that counts how often it is lowered."""

    def __init__(self, fn):
        self.fn, self.lowered = fn, 0

    def __call__(self, *args):
        return self.fn(*args)

    def lower(self, *args):
        self.lowered += 1
        return self.fn.lower(*args)


@pytest.mark.parametrize("draft_len", [0, 2])
def test_attach_tracing_lowers_nothing_without_a_tracer(draft_len, tmp_path,
                                                        monkeypatch):
    model, serve = _gpt(draft_len=draft_len)
    params = jax.jit(model.init)(jax.random.PRNGKey(0))
    engine = ServeEngine(model, params, ServeConfig(**serve))
    names = ("prefill", "decode", "seat", "verify")
    for name in names:
        engine.programs[name] = _Lowering(engine.programs[name])
    parsed = []
    monkeypatch.setattr(
        tracing, "program_scopes",
        lambda text, real=tracing.program_scopes: parsed.append(1) or
        real(text))
    prompt = list(range(1, 40))
    want = engine.generate([prompt], 5)
    engine.attach_tracing()
    engine.attach_tracing(slo=tracing.ServingSLO())
    assert engine.generate([prompt], 5) == want
    assert [engine.programs[n].lowered for n in names] == [0, 0, 0, 0]
    assert not parsed

    rec = tracing.TraceRecorder(str(tmp_path))
    engine.attach_tracing(tracer=rec)
    runs = ("prefill", "verify") if draft_len else \
        ("prefill", "decode", "seat")
    assert [engine.programs[n].lowered for n in names] == \
        [int(n in runs) for n in names]
    assert len(parsed) == len(runs)
    events = [e for e in rec.last_events() if e["name"] == "program_scopes"]
    assert [e["args"]["program"] for e in events] == \
        [f"jit_{n}" for n in runs]
    for e in events:
        assert e["ph"] == "i" and e["cat"] == "serve"
        assert e["args"]["seconds"] >= 0
        scopes = tracing.unpack_scopes(e["args"])
        assert scopes and all(_stage(p) for p in scopes.values())
    # the traced engine decodes what the untraced one did, and a step
    # lowers nothing more
    assert engine.generate([prompt], 5) == want
    assert sum(engine.programs[n].lowered for n in names) == len(runs)
    rec.close()
    engine.close()
    # the events reach the recorder's file, whatever its sampling
    segments, _ = tracing.read_trace_file(rec.path)
    assert set(tracing.scope_maps(segments[0][1])) == \
        {f"jit_{n}" for n in runs}


@pytest.mark.parametrize("watchdog_first", [True, False])
def test_program_scopes_events_are_never_sampled_out(tmp_path,
                                                     watchdog_first):
    """... and a watchdog's trip snapshot, which ships the recorder's
    tail as a timeline, leaves the maps out, whichever was attached
    first."""
    from deepspeed_tpu.runtime.resilience import StepWatchdog

    model, serve = _gpt()
    params = jax.jit(model.init)(jax.random.PRNGKey(0))
    engine = ServeEngine(model, params, ServeConfig(**serve))
    rec = tracing.TraceRecorder(str(tmp_path), sample_rate=0.0)
    wd = StepWatchdog(600.0, str(tmp_path / "wd"), rank=0)
    try:
        if watchdog_first:
            engine.attach_watchdog(wd)
        engine.attach_tracing(tracer=rec)
        if not watchdog_first:
            engine.attach_watchdog(wd)
        engine.generate([list(range(1, 20))], 3)
        names = [e["name"] for e in rec.last_events()]
        assert names.count("program_scopes") == 3
        assert "decode_step" not in names and "prefill_chunk" not in names
        tail = [e["name"] for e in wd._flight_recorder_tail()]
        assert tail == [n for n in names if n != "program_scopes"]
    finally:
        wd.stop()
        rec.close()
        engine.close()


# -- the operator's join and the benchmark's reader, on the recorded trace --

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DATA = os.path.join(ROOT, "benchmarks", "tests", "data")


def test_the_tool_and_the_benchmarks_reader_agree_on_the_recorded_trace(
        tmp_path):
    """`monitor/tracing.py::device_scope_times` (what `tools/
    trace_report.py --xplane` prints) and `benchmarks/readers/
    trace_scope_time.py` (what the eleven metrics read) are two joins of
    the same two things and import nothing from each other: on the trace
    recorded on the chip with its `program_scopes` events they give the
    same nanoseconds, stage by stage and kernel by name."""
    sys.path.insert(0, ROOT)
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    from benchmarks import harness, trace_reduce
    from benchmarks.readers import trace_scope_time
    import trace_report

    xplane = os.path.join(DATA, "serve_scoped.xplane.pb.gz")
    with open(os.path.join(DATA, "serve_scoped.scopes.json")) as f:
        events = json.load(f)
    profile = trace_reduce.load(xplane)
    trace = trace_reduce.reduce(profile)
    run = harness.RunResult(end_to_end={}, correct=True, attempted=1,
                            failed=0, notes=[], memory_peak_bytes=0,
                            program_spans=events)
    times = tracing.device_scope_times(profile, events, trace.window)
    assert set(times) == {"jit_prefill", "jit_decode", "jit_seat"}
    assert trace_scope_time.STAGES == STAGES
    for program, got in times.items():
        assert got["runs"] == len(trace.module_durations(program)) > 0
        assert got["run_ns"] == pytest.approx(
            1e9 * sum(trace.module_durations(program)) / got["runs"])
        for match, mine in [(f"^{s}(/|$)", lambda p, s=s:
                             _stage(p) == s)
                            for s in STAGES] + [
                ("^$", lambda p: not _stage(p)),
                (r"(^|/)kernel\.", lambda p: "/kernel." in p),
                (r"(^|/)xla\.", lambda p: "/xla." in p)]:
            tool = sum(ns for p, ns in got["paths"].items() if mine(p))
            read = trace_scope_time.read(cell=None, run=run, trace=trace,
                                         program=program, match=match,
                                         scale=1e9)
            assert read == pytest.approx(tool, abs=1e-6), (program, match)
        # stages and the rest are all of a run's operations
        assert sum(got["paths"].values()) <= got["run_ns"]
        if program != "jit_seat":   # 2 us: one operation and its edges
            assert sum(got["paths"].values()) == pytest.approx(
                got["run_ns"], rel=0.02)
    # the Mosaic call of a decode step is read by its name
    assert any(p.endswith("kernel.paged_attention") and ns > 0
               for p, ns in times["jit_decode"]["paths"].items())
    # the command line, over a run dir that holds the recorder's file
    with open(tmp_path / "trace.rank00000.jsonl", "w") as f:
        f.write(json.dumps({"type": "trace_meta", "rank": 0}) + "\n")
        for e in events:
            f.write(json.dumps(e) + "\n")
    lines = trace_report.scope_report(str(tmp_path), xplane)
    text = "\n".join(lines)
    whole = tracing.device_scope_times(profile, events)
    assert text == "\n".join(tracing.scope_table(whole, STAGES))
    assert re.search(r"^jit_decode: \d+ runs, \d+\.\d+ ms a run", text, re.M)
    for stage in ("embed", "attn", "ffn", "head", "sample"):
        assert re.search(rf"^  {stage} +\d+\.\d+ ms", text, re.M), text
    # every scope beneath a stage that holds a thousandth of a run: the
    # layer's own, the registry's kernel, the compiler's data movement
    decode = text[text.index("jit_decode:"):text.index("jit_seat:")]
    for depth, scope in ((1, "paged_attend"), (2, "kernel.paged_attention"),
                         (2, r"xla\.[\w\-]+")):
        assert re.search(rf"^  {'  ' * depth}{scope} +\d+\.\d+ ms", decode,
                         re.M), decode
    ms = lambda label: float(re.search(
        rf"^ +{label} +(\d+\.\d+) ms", decode, re.M).group(1))
    assert ms("attn") >= ms("paged_attend") >= ms(r"kernel\.paged_attention")
    assert ms(r"kernel\.paged_attention") == pytest.approx(sum(
        ns for p, ns in whole["jit_decode"]["paths"].items()
        if "/kernel.paged_attention" in p) / 1e6, abs=1e-3)
    assert "(no operation)" in decode
