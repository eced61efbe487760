"""Runs the new cell with one mechanism of the SYSTEM broken at a time
(the reference untouched) and prints what the cell's own check says:
the cell's runner, check and limits as the workload file gives them.
A builder's script (PR 63), run on the chip:

    python3 bench_artifacts/pr63/sabotage.py --seconds 20 [--only a,b]

The programs are traced when the runner builds its engine, inside the
patches; `jax.clear_caches()` between runs.
"""
import contextlib, gc, json, os, sys
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
import argparse


@contextlib.contextmanager
def patched(obj, name, new):
    old = getattr(obj, name)
    setattr(obj, name, new(old))
    try:
        yield
    finally:
        setattr(obj, name, old)


def sabotages():
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.kernels import moe_kernels
    from deepspeed_tpu.models import cohere2_moe as c2
    from deepspeed_tpu.models import evabyte
    from deepspeed_tpu.models import lfm2_moe as lfm
    from deepspeed_tpu.models import qwen3_next as qn
    from deepspeed_tpu.moe import dropless
    from deepspeed_tpu.serving import layers
    from deepspeed_tpu.serving.kv_cache import PagedKVCache

    def low(x):
        return x.astype(jnp.float8_e4m3fn).astype(x.dtype)

    def fp8_inputs(orig):
        return lambda x, w, *a, **kw: orig(low(x), w, *a, **kw)

    def fp8_rows(orig):         # project_gated -> (q, k, v, gate)
        def call(*a, **kw):
            q, k, v, gate = orig(*a, **kw)
            return low(q), low(k), low(v), gate
        return call

    def fp8_hidden(orig):       # what an expert's `down` multiplies
        return lambda dot, experts: low(orig(dot, experts))

    def no_gate(orig):          # (i) g = u: B * u left out
        def conv_mix(spec, p, h, rows, n_valid, live=None):
            T, K = h.shape[1], spec.conv_taps
            _, gate_out, u = jnp.split(lfm.matmul32(h, p["in"]), 3, axis=-1)
            seq = jnp.concatenate(
                [rows, u.astype(rows.dtype)], axis=1).astype(jnp.float32)
            w = p["conv_w"].astype(jnp.float32)
            c = sum(seq[:, j:j + T] * w[:, j] for j in range(K))
            rows = jax.vmap(lambda s, n: jax.lax.dynamic_slice_in_dim(
                s, n, K - 1, axis=0))(seq, n_valid).astype(rows.dtype)
            return lfm.matmul32(gate_out * c, p["out"]), rows
        return conv_mix

    def taps_reversed(orig):    # (ii)
        return lambda spec, p, *a, **kw: orig(
            spec, dict(p, conv_w=p["conv_w"][:, ::-1]), *a, **kw)

    def bias_weighs(orig):      # (iii) the choosing bias in the weights
        def route(h, router, top_k, scoring="softmax", renormalize=False,
                  select_bias=None, scale=1.0, renorm_eps=0.0):
            w, idx = orig(h, router, top_k, scoring, renormalize,
                          select_bias, scale, renorm_eps)
            if select_bias is None:
                return w, idx
            s = jax.nn.sigmoid(jnp.dot(
                h.astype(jnp.float32), router.astype(jnp.float32),
                precision=jax.lax.Precision.HIGHEST)) + select_bias
            w = jnp.take_along_axis(s, idx, axis=-1)
            return w / (jnp.sum(w, -1, keepdims=True) + renorm_eps) \
                * scale, idx
        return route

    def not_normed(orig):       # (iv) q and k as projected
        return lambda x, p, eps: x.astype(jnp.float32)

    def interleaved(orig):      # (v) pairs 2i, 2i + 1
        return lambda x, positions, theta: c2.rope_interleaved(
            x, positions, theta)

    def rows_dropped(orig):     # (vi) a prefill chunk starts from zeros
        def conv_mix(spec, p, h, rows, n_valid, live=None):
            if h.shape[1] > 1:
                rows = jnp.zeros_like(rows)
            return orig(spec, p, h, rows, n_valid, live)
        return conv_mix

    def all_held(orig):
        return lambda w, idx, first, count: (
            w, idx % count, jnp.ones(idx.shape, bool))

    return {
        "none": [],
        "k_activations_and_rows_at_fp8_e4m3": [
            (m, "matmul32", fp8_inputs)
            for m in (c2, layers, evabyte, lfm, qn)
        ] + [(dropless, "_dot32", fp8_inputs),
             (dropless, "experts_grouped", fp8_inputs),
             (dropless, "experts_slabs", fp8_inputs),
             (dropless, "experts_touched_only", fp8_inputs),
             (dropless, "expert_hidden", fp8_hidden),
             (moe_kernels, "expert_hidden", fp8_hidden),
             (qn, "project_gated", fp8_rows)],
        "i_gate_b_times_u_left_out": [(lfm, "conv_mix", no_gate)],
        "ii_taps_reversed": [(lfm, "conv_mix", taps_reversed)],
        "iii_choosing_bias_in_the_weights": [(c2, "route", bias_weighs)],
        "iv_q_and_k_not_normed": [(qn, "rms_norm_plain", not_normed)],
        "v_pairs_2i_2i_plus_1_rotated": [(qn, "rope", interleaved)],
        "vi_kept_rows_dropped_between_chunks": [(lfm, "conv_mix",
                                                 rows_dropped)],
        "vii_seated_slot_keeps_its_last_tenants_rows": [
            (PagedKVCache, "reset_state", lambda o: (
                lambda self, slot: None))],
        "viii_elsewhere_computed_by_e_mod_8": [(c2, "held_assignments",
                                                all_held)],
    }


def main():
    import jax

    from benchmarks import run
    from benchmarks.harness import plugin

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="lfm2-24b-a2b-e8.serve.assist")
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=2163000133)
    ap.add_argument("--only", default="")
    ap.add_argument("--rate", type=float, default=0.0)
    args = ap.parse_args(None, argparse.Namespace(trace=0))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        benchmark = json.load(f)
    cell = run.build_cell(args, benchmark)
    if args.rate:
        cell.traffic = dict(cell.traffic, rate_rps=args.rate)
    runner = plugin("runners", cell.workload["runner"])
    table = sabotages()
    for name in (args.only.split(",") if args.only else table):
        jax.clear_caches()
        with contextlib.ExitStack() as stack:
            for obj, attr, new in table[name]:
                stack.enter_context(patched(obj, attr, new))
            try:
                result = runner.run(cell)
                print(json.dumps({
                    "sabotage": name, "seed": args.seed,
                    "rate_rps": cell.traffic["rate_rps"],
                    "requests": result.notes[0]["requests"],
                    "finished": result.notes[0]["finished"],
                    "seconds": args.seconds, "correct": result.correct,
                    "tokens_per_s":
                    result.end_to_end["serve_tokens_per_s"],
                    "check": result.notes[-1]}), flush=True)
                del result
            except Exception as e:  # noqa: BLE001 - report and go on
                print(json.dumps({"sabotage": name, "error": repr(e)}),
                      flush=True)
        gc.collect()


if __name__ == "__main__":
    main()
