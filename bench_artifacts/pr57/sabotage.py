"""Runs the new cell with one mechanism of the SYSTEM broken at a time
(the reference untouched) and prints what the cell's own check says:
the cell's runner, check and limits as the workload file gives them.
A builder's script (PR 57), run on the chip:

    python3 bench_artifacts/pr57/sabotage.py --seconds 20 [--only a,b]

Where a sabotage changes the rule itself the decode step's recurrence is
sent to its oracle (`kernel_config(ops={"gdn_step": "jnp"})`), which
calls the patched `delta_step`.
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

    from deepspeed_tpu.models import cohere2_moe as c2
    from deepspeed_tpu.models import evabyte
    from deepspeed_tpu.models import qwen3_next as qn
    from deepspeed_tpu.moe import dropless
    from deepspeed_tpu.serving import layers
    from deepspeed_tpu.serving.kv_cache import PagedKVCache

    def low(x):
        return x.astype(jnp.float8_e4m3fn).astype(x.dtype)

    def fp8_inputs(orig):
        return lambda x, w, *a, **kw: orig(low(x), w, *a, **kw)

    def fp8_rows(orig):
        def call(*a, **kw):
            q, k, v, gate = orig(*a, **kw)
            return low(q), low(k), low(v), gate
        return call

    def fp8_rule(orig):         # q^, k^ and v as they enter the rule
        return lambda q, k, v, *a, **kw: orig(low(q), low(k), low(v),
                                              *a, **kw)

    def rule(change):
        """`delta_step` / `delta_scan` with (g, beta) changed."""
        def wrap(orig):
            def call(q, k, v, g, beta, *rest):
                return orig(q, k, v, *change(g, beta), *rest)
            return call
        return wrap

    def no_delta_step(orig):    # u = beta v: plain linear attention
        def step(q, k, v, g, beta, state):
            state = state * jnp.exp(g)[:, :, None, None] + \
                k[..., None] * (beta[..., None] * v)[:, :, None, :]
            return jnp.sum(state * q[..., None], axis=-2), state
        return step

    def no_delta_scan(orig):    # the same rule, token by token
        step = no_delta_step(None)

        def scan(q, k, v, g, beta, state, chunk):
            def one(state, t):
                o, state = step(*t, state)
                return state, o
            t = tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta))
            state, o = jax.lax.scan(one, state, t)
            return jnp.moveaxis(o, 0, 1), state
        return scan

    def from_zeros(orig):       # every prefill chunk scans from zeros
        return lambda q, k, v, g, beta, state, chunk: orig(
            q, k, v, g, beta, jnp.zeros_like(state), chunk)

    def spec_with(**kw):
        def wrap(orig):
            return lambda self: orig(self)._replace(**kw)
        return wrap

    def all_held(orig):
        return lambda w, idx, first, count: (
            w, idx % count, jnp.ones(idx.shape, bool))

    def no_gate(orig):
        def call(*a, **kw):
            return orig(*a, **kw)[:3] + (None,)
        return call

    both = lambda wrap: [(qn, "delta_step", wrap), (qn, "delta_scan", wrap)]
    spec = (qn.Qwen3Next, "layer_spec")
    return {
        "none": [],
        "k_products_rows_and_rule_inputs_at_fp8_e4m3": [
            (m, "matmul32", fp8_inputs) for m in (c2, layers, evabyte, qn)
        ] + [(dropless, "_dot32", fp8_inputs),
             (dropless, "experts_grouped", fp8_inputs),
             (dropless, "experts_touched_only", fp8_inputs),
             (qn, "project_gated", fp8_rows)] + both(fp8_rule),
        "a_delta_term_dropped_plain_linear_attention": [
            (qn, "delta_step", no_delta_step),
            (qn, "delta_scan", no_delta_scan)],
        "b_beta_is_one": both(rule(lambda g, beta: (
            g, jnp.where(beta > 0, 1.0, 0.0)))),
        "c_g_is_zero_no_decay": both(rule(lambda g, beta: (0.0 * g, beta))),
        "d_qk_l2_norm_dropped": [(qn, "_l2norm", lambda o: (lambda x: x))],
        "e_state_not_carried_across_prefill_chunks": [
            (qn, "delta_scan", from_zeros)],
        "f_seated_slot_keeps_its_last_tenants_state": [
            (PagedKVCache, "reset_state", lambda o: (
                lambda self, slot: None))],
        "g_output_gate_dropped": [(qn, "project_gated", no_gate)],
        "h_rotary_over_all_256": [spec + (spec_with(rotary_dim=0),)],
        "i_shared_experts_gate_dropped": [spec + (spec_with(shared="sum"),)],
        "j_elsewhere_computed_by_e_mod_64": [(c2, "held_assignments",
                                              all_held)],
    }


# sabotages of the rule itself: the decode step's recurrence by the oracle
ORACLE = ("k_", "a_", "b_", "c_")


def main():
    from benchmarks import run
    from benchmarks.harness import plugin
    from deepspeed_tpu.kernels import kernel_config

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload",
                    default="qwen3-next-80b-a3b-d12.serve.longchat")
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=2157000133)
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
        with contextlib.ExitStack() as stack:
            for obj, attr, new in table[name]:
                stack.enter_context(patched(obj, attr, new))
            if name.startswith(ORACLE):
                stack.enter_context(kernel_config(ops={"gdn_step": "jnp"}))
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
