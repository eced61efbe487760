"""Runs the new cell with one mechanism of the SYSTEM broken at a time
(the reference untouched) and prints what the cell's own check says:
the cell's runner, check and limits as the workload file gives them.
A builder's script (PR 44), run on the chip:

    python3 bench_artifacts/pr44/sabotage.py --seconds 25 [--only a,b]
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
    from deepspeed_tpu.models import cohere2_moe as c2
    from deepspeed_tpu.models import evabyte
    from deepspeed_tpu.moe import dropless
    from deepspeed_tpu.models.layer_spec import LayerSpec
    from deepspeed_tpu.serving import kv_cache, layers, programs

    def heads_mod(orig):
        def attend(q, k, v, mask):
            B, T, H, Dh = q.shape
            KV = k.shape[2]
            q2 = q.reshape(B, T, H // KV, KV, Dh).transpose(0, 1, 3, 2, 4)
            out = orig(q2.reshape(B, T, H, Dh), k, v, mask)
            return out.reshape(B, T, KV, H // KV, Dh).transpose(
                0, 1, 3, 2, 4).reshape(B, T, H * Dh)
        return attend

    def all_held(orig):
        import jax.numpy as jnp
        return lambda w, idx, first, count: (
            w, idx % count, jnp.ones(idx.shape, bool))

    def fp8_inputs(orig):
        import jax.numpy as jnp

        def low(x):
            return x.astype(jnp.float8_e4m3fn).astype(jnp.bfloat16)
        return lambda x, w, *a, **kw: orig(low(x), w, *a, **kw)

    return {
        "h_product_inputs_rounded_to_fp8_e4m3": [
            (c2, "matmul32", fp8_inputs), (layers, "matmul32", fp8_inputs),
            (evabyte, "matmul32", fp8_inputs),
            (dropless, "_dot32", fp8_inputs),
            (dropless, "experts_grouped", fp8_inputs)],
        "none": [],
        "a_window_mask_dropped": [(layers, "_visible", lambda o: (
            lambda at, q_pos, window: o(at, q_pos, 0)))],
        "b_full_layer_rotated": [(LayerSpec, "rotates", lambda o: (
            lambda self, layer: True))],
        "b2_sliding_layer_not_rotated": [(LayerSpec, "rotates", lambda o: (
            lambda self, layer: o(self, layer) and layer != 1))],
        "c_head_n_reads_kv_n_mod_8": [(c2, "attend_grouped", heads_mod)],
        "d_ring_without_the_chunks_margin": [
            (kv_cache, "ring_blocks_for", lambda o: (
                lambda window, chunk, bs: window // bs)),
            (programs.ServeProgramBuilder, "_check_grouped", lambda o: (
                lambda self, s: None))],
        "e_weights_not_renormalised": [(c2, "route", lambda o: (
            lambda h, r, k, scoring, renormalize: o(h, r, k, scoring,
                                                    False)))],
        "f_shared_summed": [(c2, "silu_gated_ffn", lambda o: (
            lambda p, h: o(p, h) * 4.0))],
        "g_elsewhere_computed_by_e_mod_16": [(c2, "held_assignments",
                                              all_held)],
    }


def main():
    from benchmarks import run
    from benchmarks.harness import plugin

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="command-a-plus-d4.serve.mixedlen")
    ap.add_argument("--seconds", type=float, default=25.0)
    ap.add_argument("--seed", type=int, default=2190000133)
    ap.add_argument("--only", default="")
    args = ap.parse_args(None, argparse.Namespace(trace=0))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        benchmark = json.load(f)
    cell = run.build_cell(args, benchmark)
    runner = plugin("runners", cell.workload["runner"])
    table = sabotages()
    for name in (args.only.split(",") if args.only else table):
        with contextlib.ExitStack() as stack:
            for obj, attr, new in table[name]:
                stack.enter_context(patched(obj, attr, new))
            try:
                result = runner.run(cell)
                print(json.dumps({"sabotage": name, "correct": result.correct,
                                  "failed": result.failed,
                                  "attempted": result.attempted,
                                  "check": result.notes[-1],
                                  "itl_p95": result.end_to_end["serve_itl_p95_ms"]}),
                      flush=True)
                del result
            except Exception as e:  # noqa: BLE001
                print(json.dumps({"sabotage": name, "error": repr(e)[:400]}),
                      flush=True)
        gc.collect()


if __name__ == "__main__":
    main()
