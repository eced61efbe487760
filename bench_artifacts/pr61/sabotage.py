"""Runs the new cell with one mechanism of the SYSTEM broken at a time
(the reference untouched) and prints what the cell's own check says:
the cell's runner, check and limits as the workload file gives them.
A builder's script (PR 61), run on the chip:

    python3 bench_artifacts/pr61/sabotage.py --seconds 20 [--only a,b]

Where a sabotage changes the recurrence itself the decode step's is sent
to its oracle (`kernel_config(ops={"ssm_step": "jnp"})`), which calls
the patched `by_group`.  The kernels' own jitted calls keep their traces:
`jax.clear_caches()` between runs.
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
    from deepspeed_tpu.models import granite_hybrid as gh
    from deepspeed_tpu.models import nemotron_h as nh
    from deepspeed_tpu.moe import dropless
    from deepspeed_tpu.serving import layers
    from deepspeed_tpu.serving.kv_cache import PagedKVCache

    def low(x):
        return x.astype(jnp.float8_e4m3fn).astype(x.dtype)

    def fp8_inputs(orig):
        return lambda x, w, *a, **kw: orig(low(x), w, *a, **kw)

    def fp8_second(orig):       # dense_expert(p, x)
        return lambda p, x: orig(p, low(x))

    def fp8_rows(orig):
        def call(*a, **kw):
            q, k, v = orig(*a, **kw)
            return low(q), low(k), low(v)
        return call

    def fp8_hidden(orig):       # what an expert's `down` multiplies
        return lambda dot, experts: low(orig(dot, experts))

    def from_zeros(orig):       # every scan chunk starts from zeros
        def scan(x, Bm, Cm, dt, A, state, chunk):
            ys = []
            for at in range(0, x.shape[1], chunk):
                cut = lambda t: t[:, at:at + chunk]
                y, state = orig(cut(x), cut(Bm), cut(Cm), cut(dt), A,
                                jnp.zeros_like(state), chunk)
                ys.append(y)
            return jnp.concatenate(ys, axis=1), state
        return scan

    def group_zero(orig):       # every head reads group 0's B and C
        def by_group(fn, groups):
            grouped = orig(fn, groups)

            def call(x, Bm, Cm, *rest):
                first = lambda t: jnp.broadcast_to(
                    jnp.take(t, jnp.array([0]), axis=-2), t.shape)
                return grouped(x, first(Bm), first(Cm), *rest)
            return call
        return by_group

    def silu_for_relu2(orig):
        def hidden(dot, experts):
            if "gate" in experts:
                return orig(dot, experts)
            return jax.nn.silu(dot(experts["up"]))
        return hidden

    def norm_over_all(orig):    # the gated norm over all 4,096
        return lambda spec, g, p: orig(spec._replace(ssm_groups=1), g, p)

    def bias_weighs(orig):      # the choosing bias let into the weights
        def route(h, router, top_k, scoring="softmax", renormalize=False,
                  select_bias=None, scale=1.0):
            w, idx = orig(h, router, top_k, scoring, renormalize,
                          select_bias, scale)
            if select_bias is None:
                return w, idx
            s = jax.nn.sigmoid(jnp.dot(
                h.astype(jnp.float32), router.astype(jnp.float32),
                precision=jax.lax.Precision.HIGHEST)) + select_bias
            w = jnp.take_along_axis(s, idx, axis=-1)
            return w / jnp.sum(w, -1, keepdims=True) * scale, idx
        return route

    def all_held(orig):
        return lambda w, idx, first, count: (
            w, idx % count, jnp.ones(idx.shape, bool))

    hidden = [(dropless, "expert_hidden", silu_for_relu2),
              (moe_kernels, "expert_hidden", silu_for_relu2)]
    return {
        "none": [],
        "k_activations_and_rows_at_fp8_e4m3": [
            (m, "matmul32", fp8_inputs)
            for m in (c2, layers, evabyte, gh, nh)
        ] + [(dropless, "_dot32", fp8_inputs),
             (dropless, "dense_expert", fp8_second),
             (dropless, "experts_grouped", fp8_inputs),
             (dropless, "experts_slabs", fp8_inputs),
             (dropless, "experts_touched_only", fp8_inputs),
             (dropless, "expert_hidden", fp8_hidden),
             (moe_kernels, "expert_hidden", fp8_hidden),
             (c2, "project_grouped", fp8_rows)],
        "a_state_thrown_away_between_scan_chunks": [
            (gh, "ssm_scan", from_zeros)],
        "b_seated_slot_keeps_its_last_tenants_state": [
            (PagedKVCache, "reset_state", lambda o: (
                lambda self, slot: None))],
        "c_group_zero_b_and_c_for_every_head": [(gh, "by_group", group_zero)],
        "d_silu_for_relu_squared": hidden,
        "e_gated_norm_over_all_4096": [(gh, "gated_norm", norm_over_all)],
        "f_choosing_bias_in_the_weights": [(c2, "route", bias_weighs)],
        "g_elsewhere_computed_by_e_mod_16": [(c2, "held_assignments",
                                              all_held)],
    }


# sabotages of the recurrence itself: the decode step's by the oracle
ORACLE = ("c_",)


def main():
    import jax

    from benchmarks import run
    from benchmarks.harness import plugin
    from deepspeed_tpu.kernels import kernel_config

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload",
                    default="nemotron-3-nano-30b-a3b-e16.serve.reasoning")
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=2161000133)
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
            if name.startswith(ORACLE):
                stack.enter_context(kernel_config(ops={"ssm_step": "jnp"}))
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
