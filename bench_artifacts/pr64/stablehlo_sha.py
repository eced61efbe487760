"""sha256 of the StableHLO that `prefill` and `decode` lower to at each
serving cell's shapes, for a described v5e with the registry seeing a TPU
(no chip needed), of the jaxpr of a training step at each training
cell's widths, of the jaxpr of every family's decode walk
(`kernels/paged.py::_walk`, the kernel's body in it, which carries no
source location) and — new in PR 64 — of every family's prefill walk
(`_prefill_walk`): PR 62's script with `assist` (PR 63's cell) and the
`chunk` lines added.
Run in the parent's checkout and in the change's and compare: PR 64
changes `prefill` of `command-a-plus-d4.serve.mixedlen` — its three
sliding layers' attention is the walk of the window's live blocks modulo
the ring, a tile of the chunk's queries a program — and nothing else:
its `decode`, both programs of `chat`, `longdoc`, `chatgen`, `chatrate`,
`longctx`, `longchat`, `reasoning` and `assist` and both training steps
must read the same on both sides, and so must the jaxpr of every
family's decode walk (the sliding one among them: `_live_run` now shares
`_sliding_run` with the chunk) and of every prefill walk but the
sliding one, which the parent refuses.
A Mosaic kernel's serialized body carries its source locations — the
checkout's path and the line numbers among them — so the body is cut out
of the program's text before it is hashed; the walks' jaxprs hold the
bodies to account.

    JAX_PLATFORMS=cpu PYTHONPATH=<checkout> python stablehlo_sha.py
"""
import hashlib
import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp

jax.config.update("jax_traceback_in_locations_limit", 1)
from deepspeed_tpu.ops import pallas_backend  # noqa: E402

pallas_backend.interpret = lambda: False

from deepspeed_tpu import models  # noqa: E402
from deepspeed_tpu.serving import ServeProgramBuilder, ServeSchedule  # noqa: E402


from jax.experimental import topologies  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

ONE_CHIP = SingleDeviceSharding(topologies.get_topology_desc(
    platform="tpu", topology_name="v5e:2x2").devices[0])


def sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=ONE_CHIP)


def lowered(model, sched, caches, slots, chunk, table):
    progs = ServeProgramBuilder(model, sched).build()
    params = jax.tree_util.tree_map(
        lambda s: sds(s.shape, s.dtype),
        jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    decode = (sds((slots,), jnp.int32), sds((slots,), jnp.int32),
              sds((slots,), jnp.bool_), sds((slots, table), jnp.int32),
              sds((slots,), jnp.float32), sds((slots,), jnp.int32),
              sds((slots,), jnp.uint32))
    prefill = (sds((1, chunk), jnp.int32), sds((), jnp.int32),
               sds((), jnp.int32), sds((table,), jnp.int32),
               sds((), jnp.float32), sds((), jnp.int32), sds((), jnp.uint32))
    for name, args in (("decode", decode), ("prefill", prefill)):
        text = re.sub(r'\\22body\\22: \\22[^\\]*\\22', "BODY",
                      progs[name].lower(params, caches, *args).as_text())
        yield name, hashlib.sha256(text.encode()).hexdigest(), len(text)


def cells():
    from deepspeed_tpu.serving.kv_cache import pool_width

    bf = jnp.bfloat16
    gpt = models.GPT(models.gpt2_config("xl", param_dtype=bf))
    pool = sds((513 * 16, pool_width(25, 64)), bf)
    yield "gpt2-xl.serve.chat", gpt, ServeSchedule(
        max_batch=16, prefill_chunk=256, block_size=16, num_blocks=513,
        table_width=64), [(pool, pool)] * 48, 16, 256, 64
    eva = models.EvaByte(models.EvaByteConfig(
        max_seq_len=16384, num_layers=16, param_dtype=bf))
    pool = sds((1537 * 16, 4096), bf)
    yield "evabyte-d16.serve.longdoc", eva, ServeSchedule(
        max_batch=8, prefill_chunk=1024, block_size=16, num_blocks=1537,
        table_width=192, window_blocks=128), [(pool, pool)] * 16, 8, 1024, 192
    dsv2 = models.DeepSeekV2(models.DeepSeekV2Config(
        num_layers=9, param_dtype=bf))
    yield "deepseek-v2-lite-d9.serve.chatgen", dsv2, ServeSchedule(
        max_batch=32, prefill_chunk=512, block_size=16, num_blocks=8193,
        table_width=256), [(sds((8193 * 16, 640), bf),)] * 9, 32, 512, 256
    if hasattr(models, "Cohere2Moe"):
        c2 = models.Cohere2Moe(models.Cohere2MoeConfig(
            vocab_size=32768, max_seq_len=16384, num_layers=4,
            experts_held=16, param_dtype=bf))
        full = sds((16385 * 16, 1024), bf)
        ring = sds(((16 * 288 + 1) * 16, 1024), bf)
        yield "command-a-plus-d4.serve.mixedlen", c2, ServeSchedule(
            max_batch=16, prefill_chunk=512, block_size=16, num_blocks=16385,
            table_width=1024, ring_blocks=288), \
            [(ring, ring)] * 3 + [(full, full)], 16, 512, 1024 + 288


def glm():
    from benchmarks import harness

    config = harness.load_json("configs", "glm-5.2-d5.json")
    g = harness.plugin("models", "glm_moe_dsa").build(
        config, seq_len=24576, n_dev=1, param_dtype="bfloat16")
    rows = sds((12289 * 16, 640), jnp.bfloat16)
    keys = sds((12289 * 16, 128), jnp.bfloat16)
    return "glm-5.2-d5.serve.longctx", g, ServeSchedule(
        max_batch=8, prefill_chunk=512, block_size=16, num_blocks=12289,
        table_width=1536), [(rows, keys) if kind == "full" else (rows,)
                            for kind in g.config.indexer_types], 8, 512, 1536


def granite():
    g = models.GraniteHybrid(models.GraniteHybridConfig(
        max_seq_len=2048, param_dtype=jnp.bfloat16))
    spec = g.layer_spec()
    rows = sds((8193 * 16, 512), jnp.bfloat16)
    state = (sds((64, 64, 64, 128), jnp.float32),
             sds((64, 3, 4352), jnp.bfloat16))
    caches = [state if spec.mixer_of(i) == "ssm" else (rows, rows)
              for i in range(g.config.num_layers)]
    sched = ServeSchedule(max_batch=64, prefill_chunk=512, block_size=16,
                          num_blocks=8193, table_width=128)
    progs = ServeProgramBuilder(g, sched).build()
    params = jax.tree_util.tree_map(
        lambda s: sds(s.shape, s.dtype),
        jax.eval_shape(g.init, jax.random.PRNGKey(0)))
    decode = (sds((64,), jnp.int32), sds((64,), jnp.int32),
              sds((64,), jnp.bool_), sds((64, 128), jnp.int32),
              sds((64,), jnp.float32), sds((64,), jnp.int32),
              sds((64,), jnp.uint32))
    prefill = (sds((1, 512), jnp.int32), sds((), jnp.int32),
               sds((), jnp.int32), sds((129,), jnp.int32),
               sds((), jnp.float32), sds((), jnp.int32), sds((), jnp.uint32))
    for name, args in (("decode", decode), ("prefill", prefill)):
        text = re.sub(r'\\22body\\22: \\22[^\\]*\\22', "BODY",
                      progs[name].lower(params, caches, *args).as_text())
        yield name, hashlib.sha256(text.encode()).hexdigest(), len(text)


def qwen3():
    from deepspeed_tpu.models.qwen3_next import Qwen3Next, Qwen3NextConfig

    model = Qwen3Next(Qwen3NextConfig(
        vocab_size=18992, max_seq_len=13312, num_layers=12, experts_held=64,
        param_dtype=jnp.bfloat16))
    spec = model.layer_spec()
    rows = sds((39937 * 16, 512), jnp.bfloat16)
    state = (sds((48, 32, 128, 128), jnp.float32),
             sds((48, 3, 8192), jnp.bfloat16))
    return "qwen3-next-80b-a3b-d12.serve.longchat", model, ServeSchedule(
        max_batch=48, prefill_chunk=512, block_size=16, num_blocks=39937,
        table_width=832), [state if spec.mixer_of(i) == "gdn" else (rows, rows)
                           for i in range(12)], 48, 512, 832


def nemotron():
    from deepspeed_tpu.models.nemotron_h import NemotronH, NemotronHConfig

    model = NemotronH(NemotronHConfig(
        vocab_size=16384, max_seq_len=2560, experts_held=16,
        param_dtype=jnp.bfloat16))
    spec = model.layer_spec()
    rows = sds((6401 * 16, 256), jnp.bfloat16)
    state = (sds((40, 64, 64, 128), jnp.float32),
             sds((40, 3, 6144), jnp.bfloat16))
    caches = [{"ssm": state, "attention": (rows, rows), "none": ()}[
        spec.mixer_of(i)] for i in range(model.config.num_layers)]
    sched = ServeSchedule(max_batch=40, prefill_chunk=512, block_size=16,
                          num_blocks=6401, table_width=160)
    progs = ServeProgramBuilder(model, sched).build()
    params = jax.tree_util.tree_map(
        lambda s: sds(s.shape, s.dtype),
        jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    decode = (sds((40,), jnp.int32), sds((40,), jnp.int32),
              sds((40,), jnp.bool_), sds((40, 160), jnp.int32),
              sds((40,), jnp.float32), sds((40,), jnp.int32),
              sds((40,), jnp.uint32))
    prefill = (sds((1, 512), jnp.int32), sds((), jnp.int32),
               sds((), jnp.int32), sds((161,), jnp.int32),
               sds((), jnp.float32), sds((), jnp.int32), sds((), jnp.uint32))
    for name, args in (("decode", decode), ("prefill", prefill)):
        text = re.sub(r'\\22body\\22: \\22[^\\]*\\22', "BODY",
                      progs[name].lower(params, caches, *args).as_text())
        yield name, hashlib.sha256(text.encode()).hexdigest(), len(text)


def lfm2():
    from deepspeed_tpu.models.lfm2_moe import Lfm2Moe, Lfm2MoeConfig

    model = Lfm2Moe(Lfm2MoeConfig(
        vocab_size=8192, max_seq_len=3072, experts_held=8,
        param_dtype=jnp.bfloat16))
    spec = model.layer_spec()
    rows = sds((18433 * 16, 512), jnp.bfloat16)
    kept = (sds((96, 2, 2048), jnp.bfloat16),)
    caches = [{"conv": kept, "attention": (rows, rows)}[spec.mixer_of(i)]
              for i in range(model.config.num_layers)]
    sched = ServeSchedule(max_batch=96, prefill_chunk=512, block_size=16,
                          num_blocks=18433, table_width=192)
    progs = ServeProgramBuilder(model, sched).build()
    params = jax.tree_util.tree_map(
        lambda s: sds(s.shape, s.dtype),
        jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    decode = (sds((96,), jnp.int32), sds((96,), jnp.int32),
              sds((96,), jnp.bool_), sds((96, 192), jnp.int32),
              sds((96,), jnp.float32), sds((96,), jnp.int32),
              sds((96,), jnp.uint32))
    prefill = (sds((1, 512), jnp.int32), sds((), jnp.int32),
               sds((), jnp.int32), sds((193,), jnp.int32),
               sds((), jnp.float32), sds((), jnp.int32), sds((), jnp.uint32))
    for name, args in (("decode", decode), ("prefill", prefill)):
        text = re.sub(r'\\22body\\22: \\22[^\\]*\\22', "BODY",
                      progs[name].lower(params, caches, *args).as_text())
        yield name, hashlib.sha256(text.encode()).hexdigest(), len(text)


def chunks():
    """(family, fn, shapes) of each family's prefill walk at its cell's
    shapes: one request's chunk of 512 queries."""
    from deepspeed_tpu.kernels import paged

    bf, i32 = jnp.bfloat16, jnp.int32

    def call(heads, dim, width, nblocks, lanes):
        return (jax.ShapeDtypeStruct((1, 512, heads, dim), bf),
                jax.ShapeDtypeStruct((nblocks * 16, lanes), bf),
                jax.ShapeDtypeStruct((nblocks * 16, lanes), bf),
                jax.ShapeDtypeStruct((1, width), i32),
                jax.ShapeDtypeStruct((1, 512), i32))

    grouped = lambda kv, **kw: lambda *a: paged.grouped_attention_pallas(
        *a, kv_heads=kv, block_size=16, **kw)
    yield "mixedlen.grouped_full", grouped(8), call(128, 128, 1024, 16385,
                                                    1024)
    yield "longchat.grouped", grouped(2), call(16, 256, 832, 39937, 512)
    yield "reasoning.grouped", grouped(2), call(32, 128, 160, 6401, 256)
    yield "mixedlen.grouped_sliding", lambda *a: grouped(
        8, window=4096, newest=a[-1])(*a[:-1]), \
        call(128, 128, 288, 16 * 288 + 1, 1024) + (
            jax.ShapeDtypeStruct((1,), i32),)


def walks():
    """(family, sha, size) of the jaxpr of each family's decode walk at
    its cell's shapes: the `pallas_call` and the kernel's body in it."""
    from deepspeed_tpu.kernels import eva, paged
    from deepspeed_tpu.serving.kv_cache import pool_width

    bf, i32 = jnp.bfloat16, jnp.int32

    def call(slots, heads, dim, width, nblocks, lanes):
        return (jax.ShapeDtypeStruct((slots, 1, heads, dim), bf),
                jax.ShapeDtypeStruct((nblocks * 16, lanes), bf),
                jax.ShapeDtypeStruct((nblocks * 16, lanes), bf),
                jax.ShapeDtypeStruct((slots, width), i32),
                jax.ShapeDtypeStruct((slots, 1), i32))

    grouped = lambda kv, **kw: lambda *a: paged.grouped_attention_pallas(
        *a, kv_heads=kv, block_size=16, **kw)
    q, pool, _, tables, q_pos = call(32, 16, 576, 256, 8193, 640)
    yield "chat.paged", lambda *a: paged.paged_attention_pallas(
        *a, block_size=16), call(16, 25, 64, 64, 513, pool_width(25, 64))
    yield "longdoc.eva", lambda *a: eva.eva_attention_pallas(
        *a, window=2048, chunk=16, block_size=16), \
        call(8, 32, 128, 192, 1537, 4096)
    yield "chatgen.latent", lambda *a: paged.latent_attention_pallas(
        *a, block_size=16, rank=512, scale=0.1147), (q, pool, tables, q_pos)
    yield "chatrate.grouped", grouped(8, scale=1 / 64), \
        call(64, 32, 64, 128, 8193, 512)
    yield "mixedlen.grouped_full", grouped(8), \
        call(16, 128, 128, 1024, 16385, 1024)
    yield "longchat.grouped", grouped(2), call(48, 16, 256, 832, 39937, 512)
    yield "reasoning.grouped", grouped(2), call(40, 32, 128, 160, 6401, 256)
    ring = call(16, 128, 128, 288, 16 * 288 + 1, 1024)
    yield "mixedlen.grouped_sliding", lambda *a: grouped(
        8, window=4096, newest=a[-1])(*a[:-1]), \
        ring + (jax.ShapeDtypeStruct((16,), i32),)


def training():
    """(cell, sha, size) of the two training cells' step programs at two
    layers of their widths, micro batch and length."""
    import deepspeed_tpu
    from benchmarks import harness
    from deepspeed_tpu.comm import make_mesh

    for cell, family, config, mix in (
            ("gpt2-xl-d24.train.seq1024", "gpt", "gpt2-xl-d24.json",
             "lm.seq1024.micro4.json"),
            ("bert-large.train.seq128", "bert", "bert-large.json",
             "mlm.seq128.micro64.json")):
        w = harness.load_json("workloads", cell + ".json")
        traffic = harness.load_json("traffic", mix)
        config = dict(harness.load_json("configs", config))
        config["n_layer" if family == "gpt" else "num_hidden_layers"] = 2
        fam = harness.plugin("models", family)
        seq, micro = traffic["seq_len"], traffic["micro_batch"]
        model = fam.build(config, seq_len=seq, n_dev=1, **w.get("model", {}))
        mesh = make_mesh(devices=jax.devices()[:1])
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=model, mpu=mesh, config_params=dict(
                w["engine"], train_batch_size=micro,
                train_micro_batch_size_per_gpu=micro, mesh={"data": 1},
                steps_per_print=0),
            model_parameters=jax.jit(model.init)(jax.random.PRNGKey(0)))
        gen = harness.plugin("traffic", traffic["generator"])
        batch = next(gen.batches(traffic, seed=1, chips=1, config=config,
                                 family=fam))
        args = (engine._params, engine._opt_state, engine._scaler_state,
                engine._shard_batch(batch), engine._next_rng(),
                engine._step_lr(), jnp.asarray(1.0, jnp.float32))
        # the engine's mesh is this sandbox's CPU device, for which a
        # Mosaic kernel does not lower: the step's jaxpr instead, the
        # flash kernels' bodies in it
        text = str(jax.make_jaxpr(engine._step_fns["full"].fn)(*args))
        yield cell, hashlib.sha256(text.encode()).hexdigest(), len(text), \
            "pallas_call" in text


for cell, *rest in list(cells()) + [glm(), qwen3()]:
    for name, sha, size in lowered(*rest):
        print(cell, name, sha, size, flush=True)
for name, sha, size in granite():
    print("granite-4.0-h-micro.serve.chatrate", name, sha, size, flush=True)
for name, sha, size in nemotron():
    print("nemotron-3-nano-30b-a3b-e16.serve.reasoning", name, sha, size,
          flush=True)
for name, sha, size in lfm2():
    print("lfm2-24b-a2b-e8.serve.assist", name, sha, size, flush=True)
for kind, calls in (("walk", walks()), ("chunk", chunks())):
    for family, fn, shapes in calls:
        try:
            text = str(jax.make_jaxpr(fn)(*shapes))
        except ValueError as e:   # the parent has no sliding chunk walk
            print(kind, family, "refused:", str(e)[:60], flush=True)
            continue
        print(kind, family, hashlib.sha256(text.encode()).hexdigest(),
              len(text), "pallas_call" in text, flush=True)
for cell, sha, size, kernel in training():
    print(cell, "step_2_layers_jaxpr", sha, size,
          "flash kernel" if kernel else "XLA attention", flush=True)
