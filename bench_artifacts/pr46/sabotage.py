"""Runs the new cell with one mechanism of the SYSTEM broken at a time
(the reference untouched) and prints what the cell's own check says: the
cell's runner, check and limits as the workload file gives them.  A
builder's script (PR 46), run on the chip:

    python3 bench_artifacts/pr46/sabotage.py --seconds 25 [--only a,b]
"""
import argparse, contextlib, gc, json, os, sys
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


@contextlib.contextmanager
def patched(obj, name, new):
    old = getattr(obj, name)
    setattr(obj, name, new(old))
    try:
        yield
    finally:
        setattr(obj, name, old)


def sabotages():
    import jax.numpy as jnp

    from deepspeed_tpu.models import cohere2_moe, evabyte, granite_hybrid
    from deepspeed_tpu.serving import layers
    from deepspeed_tpu.serving.kv_cache import PagedKVCache

    def fp8_inputs(orig):
        def low(x):
            return x.astype(jnp.float8_e4m3fn).astype(jnp.bfloat16)
        return lambda x, w, *a, **kw: orig(low(x), w, *a, **kw)

    def in_prefill(before=None, after=None):
        """`granite_hybrid.ssm_mix` (which serving/layers.py loads through
        models/layer_spec.py STATE_MIXERS since PR 63, when a block is
        built) for a prefill chunk (T > 1) with
        `before(state, conv, n_valid, T)` applied to what it is handed
        and `after(state, conv)` to what it hands on."""
        def wrap(orig):
            def mix(spec, p, h, state, conv, n_valid, live=None):
                chunk = h.shape[1] > 1
                if chunk and before:
                    state, conv, n_valid = before(state, conv, n_valid,
                                                  h.shape[1])
                out, state, conv = orig(spec, p, h, state, conv, n_valid,
                                        live)
                if chunk and after:
                    state, conv = after(state, conv)
                return out, state, conv
            return mix
        return wrap

    return {
        "h_product_inputs_rounded_to_fp8_e4m3": [
            (granite_hybrid, "matmul32", fp8_inputs),
            (layers, "matmul32", fp8_inputs),
            (cohere2_moe, "matmul32", fp8_inputs),
            (evabyte, "matmul32", fp8_inputs)],
        "none": [],
        # a chunk starts from zeros: lost between one chunk and the next
        "a1_state_not_carried_from_chunk_to_chunk": [
            (granite_hybrid, "ssm_mix", in_prefill(
                before=lambda s, c, n, T: (jnp.zeros_like(s), c, n)))],
        # a chunk hands zeros on: lost before the next chunk AND before
        # the first decode step
        "a2_state_not_carried_past_any_chunk": [
            (granite_hybrid, "ssm_mix", in_prefill(
                after=lambda s, c: (jnp.zeros_like(s), c)))],
        "b1_conv_rows_dropped_from_chunk_to_chunk": [
            (granite_hybrid, "ssm_mix", in_prefill(
                before=lambda s, c, n, T: (s, jnp.zeros_like(c), n)))],
        "b2_conv_rows_dropped_past_any_chunk": [
            (granite_hybrid, "ssm_mix", in_prefill(
                after=lambda s, c: (s, jnp.zeros_like(c))))],
        "c_padded_tail_moves_state_and_rows": [
            (granite_hybrid, "ssm_mix", in_prefill(
                before=lambda s, c, n, T: (s, c, jnp.full_like(n, T))))],
        "d_seated_slot_keeps_its_last_tenants_state": [
            (PagedKVCache, "reset_state", lambda o: (
                lambda self, slot: None))],
        "e_attention_scale_one_eighth": [
            (cohere2_moe, "attend_grouped", lambda o: (
                lambda q, k, v, mask, scale=None: o(q, k, v, mask)))],
        "f_branches_added_without_the_0.22": [
            (layers, "_scaled", lambda o: (
                lambda x, by: x if by == 0.22 else o(x, by)))],
    }


def main():
    from benchmarks import run
    from benchmarks.harness import plugin

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload",
                    default="granite-4.0-h-micro.serve.chatrate")
    ap.add_argument("--seconds", type=float, default=25.0)
    ap.add_argument("--seed", type=int, default=2190000246)
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
                print(json.dumps({
                    "sabotage": name, "correct": result.correct,
                    "failed": result.failed, "attempted": result.attempted,
                    "check": result.notes[-1],
                    "compiles_in_window":
                    result.notes[0]["compiles_in_window"],
                    "itl_p95": result.end_to_end["serve_itl_p95_ms"]}),
                    flush=True)
                del result
            except Exception as e:  # noqa: BLE001
                print(json.dumps({"sabotage": name, "error": repr(e)[:400]}),
                      flush=True)
        gc.collect()


if __name__ == "__main__":
    main()
