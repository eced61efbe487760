"""Runs the new cell with one mechanism of the SYSTEM broken at a time
(the reference untouched) and prints what the cell's own check says:
the cell's runner, check and limits as the workload file gives them.
A builder's script (PR 54), run on the chip:

    python3 bench_artifacts/pr54/sabotage.py --seconds 20 [--only a,b]
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
    from deepspeed_tpu.models import deepseek_v2, evabyte, glm_moe_dsa
    from deepspeed_tpu.moe import dropless
    from deepspeed_tpu.serving import layers, sparse

    def low(x):
        return x.astype(jnp.float8_e4m3fn).astype(jnp.bfloat16)

    def fp8_inputs(orig):
        return lambda x, w, *a, **kw: orig(low(x), w, *a, **kw)

    def fp8_outputs(n):
        def wrap(orig):
            def call(*a, **kw):
                out = orig(*a, **kw)
                return tuple(low(o) for o in out[:n]) + tuple(out[n:])
            return call
        return wrap

    def topk_of(k):
        def wrap(orig):
            return lambda self: orig(self)._replace(index_topk=k)
        return wrap

    def no_relu(orig):
        def scores(q, w, keys):
            dots = jnp.einsum("bthd,bkd->bthk", q, keys,
                              preferred_element_type=jnp.float32)
            return jnp.einsum("bthk,bth->btk", dots, w)
        return scores

    def another_selection(orig):
        def attend(spec, cfg, p, h, kv, addr, s, layer, sel, write):
            out, kv2, sel2 = orig(spec, cfg, p, h, kv, addr, s, layer, sel,
                                  write)
            if layer == 0:      # hand on the LOWEST-scored rows instead
                with patched(sparse, "index_scores", lambda o: (
                        lambda q, w, keys: -o(q, w, keys))):
                    _, _, sel2 = orig(spec, cfg, p, h, kv, addr, s, layer,
                                      sel, write)
            return out, kv2, sel2
        return attend

    def bias_weighs(orig):
        def route(h, router, top_k, scoring="softmax", renormalize=False,
                  select_bias=None, scale=1.0):
            s = jax.nn.sigmoid(jnp.dot(
                h.astype(jnp.float32), router.astype(jnp.float32),
                precision=jax.lax.Precision.HIGHEST)) + select_bias
            w, idx = jax.lax.top_k(s, top_k)
            return w / w.sum(-1, keepdims=True) * scale, \
                idx.astype(jnp.int32)
        return route

    def all_held(orig):
        return lambda w, idx, first, count: (
            w, idx % count, jnp.ones(idx.shape, bool))

    spec = (glm_moe_dsa.GlmMoeDsa, "layer_spec")
    return {
        "none": [],
        "h_products_and_rows_at_fp8_e4m3": [
            (m, "matmul32", fp8_inputs)
            for m in (c2, layers, evabyte, deepseek_v2, glm_moe_dsa, sparse)
        ] + [(dropless, "_dot32", fp8_inputs),
             (dropless, "experts_grouped", fp8_inputs),
             (dropless, "experts_touched_only", fp8_inputs),
             (sparse, "latent_project", fp8_outputs(3)),
             (sparse, "index_project", fp8_outputs(2))],
        "a_indexer_bypassed_every_row_attended": [spec + (topk_of(1 << 30),)],
        "b_shared_layers_given_another_selection": [
            (sparse, "sparse_latent_attend", another_selection)],
        "c_relu_of_the_index_score_dropped": [
            (sparse, "index_scores", no_relu)],
        "d_1024_rows_chosen_for_2048": [spec + (topk_of(1024),)],
        "e_selection_bias_let_into_the_weights": [(c2, "route", bias_weighs)],
        "f_factor_2_5_dropped": [(c2, "route", lambda o: (
            lambda *a, scale=1.0, **kw: o(*a, scale=1.0, **kw)))],
        "g_elsewhere_computed_by_e_mod_16": [(c2, "held_assignments",
                                              all_held)],
    }


def main():
    from benchmarks import run
    from benchmarks.harness import plugin

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="glm-5.2-d5.serve.longctx")
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=2254000133)
    ap.add_argument("--only", default="")
    ap.add_argument("--init", default="",
                    help="k=v,... over the configuration's assumed.init")
    ap.add_argument("--rate", type=float, default=0.0)
    args = ap.parse_args(None, argparse.Namespace(trace=0))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        benchmark = json.load(f)
    cell = run.build_cell(args, benchmark)
    if args.init:
        init = dict(cell.config["assumed"]["init"], **{
            k: float(v) for k, v in (kv.split("=")
                                     for kv in args.init.split(","))})
        cell.config = dict(cell.config, assumed=dict(
            cell.config["assumed"], init=init))
    if args.rate:
        cell.traffic = dict(cell.traffic, rate_rps=args.rate)
    runner = plugin("runners", cell.workload["runner"])
    table = sabotages()
    for name in (args.only.split(",") if args.only else table):
        with contextlib.ExitStack() as stack:
            for obj, attr, new in table[name]:
                stack.enter_context(patched(obj, attr, new))
            try:
                result = runner.run(cell)
                print(json.dumps({
                    "sabotage": name, "seed": args.seed,
                    "init": cell.config["assumed"]["init"],
                    "rate_rps": cell.traffic["rate_rps"],
                    "requests": result.notes[0]["requests"],
                    "finished": result.notes[0]["finished"],
                    "seconds": args.seconds, "correct": result.correct,
                    "failed": result.failed, "attempted": result.attempted,
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
