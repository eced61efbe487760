"""How close the programs come to the float32 reference at the published
widths, piece by piece (my chip runs, PR 57): one request by hand through
a builder's `prefill` and `step_logits` — a prompt of `--prompt` tokens in
chunks of 512, then `--steps` decode steps — every logits row against
`benchmarks/reference/qwen3_next.py`, with each registry op in turn sent
to its oracle, and the `gdn_step` kernel against `delta_step` alone.

    python3 bench_artifacts/pr57/probe.py --prompt 2048 --steps 48
"""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--prompt", type=int, default=2048)
    ap.add_argument("--steps", type=int, default=48)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--seq", type=int, default=4096)
    ap.add_argument("--seed", type=int, default=2157000101)
    ap.add_argument("--layers", type=int, default=12)
    args = ap.parse_args()

    from benchmarks.harness import load_json, plugin
    from deepspeed_tpu.kernels import kernel_config, registry
    from deepspeed_tpu.kernels.gdn import gdn_step_info
    from deepspeed_tpu.kernels.ssm import live_slots
    from deepspeed_tpu.models import qwen3_next as qn
    from deepspeed_tpu.serving import (PagedKVCache, ServeConfig,
                                       ServeProgramBuilder, ServeSchedule)
    from deepspeed_tpu.serving.kv_cache import cache_plan
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    config = load_json("configs", "qwen3-next-80b-a3b-d12.json")
    if args.layers != 12:
        config = dict(config, num_hidden_layers=args.layers,
                      held=dict(config["held"],
                                layers=list(range(args.layers))))
    family = plugin("models", "qwen3_next")
    ref = plugin("reference", "qwen3_next")
    model = family.build(config, seq_len=args.seq, n_dev=1,
                         param_dtype="bfloat16")
    params = jax.jit(model.init)(jax.random.PRNGKey(args.seed % (2 ** 31)))
    cfg, spec = model.config, model.layer_spec()

    # the kernel alone
    k = jax.random.split(jax.random.PRNGKey(1), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    B, H, d = 6, 32, 128
    live = jnp.asarray([1, 0, 1, 1, 0, 1])
    m = live.astype(jnp.float32)[:, None]
    a = (unit(jax.random.normal(k[0], (B, H, d))) * d ** -0.5,
         unit(jax.random.normal(k[1], (B, H, d))),
         jax.random.normal(k[2], (B, H, d)),
         -jax.random.uniform(k[3], (B, H)) * m,
         jax.random.uniform(k[4], (B, H)) * m,
         jax.random.normal(k[5], (B, H, d, d)))
    ids, n = live_slots(live)
    info = gdn_step_info(a[5])
    print("# gdn_step resolves to",
          registry.resolve_impl("gdn_step", info=info), flush=True)
    o_k, s_k = jax.jit(lambda *x: registry.dispatch(
        "gdn_step", *x, info=info))(*a, ids, n)
    o_r, s_r = jax.jit(qn.delta_step)(*a)
    on = np.asarray(live, bool)
    print(json.dumps({"kernel_alone": {
        "o_max_diff": float(np.abs(np.asarray(o_k - o_r))[on].max()),
        "state_max_diff": float(np.abs(np.asarray(s_k - s_r))[on].max()),
        "dead_state_equal": bool(np.array_equal(
            np.asarray(s_k)[~on], np.asarray(a[5])[~on])),
        "o_std": float(np.asarray(o_r)[on].std())}}), flush=True)

    bs, chunk = 16, 512
    W = args.seq // bs
    nblocks = args.slots * W + 1
    sched = ServeSchedule(max_batch=args.slots, prefill_chunk=chunk,
                          block_size=bs, num_blocks=nblocks, table_width=W)
    rng = np.random.RandomState(args.seed % (2 ** 31))
    prompt = rng.randint(0, cfg.vocab_size, (args.prompt,)).tolist()
    slot = 1

    def drive(ops):
        with kernel_config(ops=ops):
            builder = ServeProgramBuilder(model, sched)
            progs = builder.build()
            step = jax.jit(builder.step_logits, donate_argnums=(1,))
            kv = PagedKVCache(
                cache_plan(spec, cfg, ServeConfig(
                    block_size=bs, max_batch=args.slots, prefill_chunk=chunk,
                    max_seq_len=args.seq)),
                nblocks, dtype=jnp.bfloat16, prefix_cache=False)
            table = kv.alloc("r", -(-(len(prompt) + args.steps) // bs))
            table = np.pad(table, (0, W - len(table)))
            rows, caches = [], kv.caches
            zero = (np.float32(0), np.int32(0), np.uint32(0))
            for pos in range(0, len(prompt), chunk):
                part = prompt[pos:pos + chunk]
                toks = np.zeros((1, chunk), np.int32)
                toks[0, :len(part)] = part
                tok, lg, caches = progs["prefill"](
                    params, caches, jnp.asarray(toks), np.int32(pos),
                    np.int32(len(part)),
                    jnp.asarray(np.append(table, slot).astype(np.int32)),
                    *zero)
            rows.append(np.asarray(lg))
            out = [int(tok)]
            active = np.arange(args.slots) == slot
            tables = np.zeros((args.slots, W), np.int32)
            tables[slot] = table
            for p in range(len(prompt), len(prompt) + args.steps):
                lg, caches, _ = step(
                    params, caches,
                    jnp.full((args.slots,), out[-1], jnp.int32),
                    jnp.full((args.slots,), p, jnp.int32),
                    jnp.asarray(active), jnp.asarray(tables))
                rows.append(np.asarray(lg[slot]))
                out.append(int(np.argmax(lg[slot])))
            del caches, kv
            return np.stack(rows), out

    kw = ref.for_config(config)
    base_rows, base_out = None, None
    for name, ops in (("all kernels", {}),
                      ("gdn_step oracle", {"gdn_step": "jnp"}),
                      ("grouped_attention oracle",
                       {"grouped_attention": "jnp"}),
                      ("touched_experts oracle",
                       {"touched_experts": "jnp"})):
        rows, out = drive(ops)
        seq = np.zeros((1, args.seq), np.int32)
        full = prompt + out[:-1]
        seq[0, :len(full)] = full
        lg = np.asarray(ref.logits(params, jnp.asarray(seq), **kw))[0]
        want = lg[len(prompt) - 1:len(prompt) + args.steps]
        diff = np.abs(rows - want)
        chosen = np.asarray(out)
        gap = want.max(-1) - want[np.arange(len(chosen)), chosen]
        print(json.dumps({
            "ops": name, "logits_std": float(want.std()),
            "prefill_row_max_diff": float(diff[0].max()),
            "prefill_row_mean_diff": float(diff[0].mean()),
            "decode_mean_diff_first8": float(diff[1:9].mean()),
            "decode_mean_diff_last8": float(diff[-8:].mean()),
            "decode_max_diff": float(diff[1:].max()),
            "top1_agreement": float((gap == 0).mean()),
            "worst_gap": float(gap.max()),
            "top2_gap_median": float(np.median(
                np.sort(want, -1)[:, -1] - np.sort(want, -1)[:, -2])),
        }), flush=True)
        del lg


if __name__ == "__main__":
    main()
