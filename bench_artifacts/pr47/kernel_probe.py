"""PR 47's micro-benchmark (chip only): ONE state-space layer's
recurrence of a decode step at the cell's shapes — state
`[64, 64, 64, 128]` float32 — at 19, 37, 56 and 64 live slots, scattered
and contiguous, three ways: today's fusion (`ssm_step`, every slot's
state read and written), the live list walked in plain XLA
(`lax.fori_loop` + `dynamic_update_slice`), and the `ssm_step_live`
kernel (at several head tiles, and with parts of its body taken out or
swapped to see what bounds it; call 2 ran it over a static grid of 64
places too, `kernel` in `kernel_probe_call2.jsonl`, beside the grid of
the live count it has now, `kernel_dynamic_grid` there).  A reading is the device's time for ONE recurrence:
a jitted chain of 9, each fed the state of the one before, less a chain
of 1, over 8 (medians of 20 calls after 3 warm ones; the host's launch
and read-back cancel).  Prints one JSON line a reading, with the GB the
way must stream and its share of 819 GB/s.

    chiprun -- python bench_artifacts/pr47/kernel_probe.py
"""
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, ".")
from deepspeed_tpu.kernels import ssm  # noqa: E402
from deepspeed_tpu.models.granite_hybrid import ssm_step  # noqa: E402

assert jax.default_backend() == "tpu", jax.default_backend()
PEAK = 819e9
B, H, P, N = 64, 64, 64, 128
SLOT = H * P * N * 4


def _median_ms(fn, *args, n=20):
    for _ in range(3):
        jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(n):
        t = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t)
    return float(np.median(ts)) * 1e3


def timed(fn, state, *rest):
    """Device ms of one `fn(*rest, state) -> (y, state)`: chains of 9
    and of 1 over the state, every y summed so that none is dead."""
    def chain(reps):
        def run(state, *rest):
            def body(_, c):
                state, acc = c
                y, state = fn(*rest[:5], state, *rest[5:])
                return state, acc + y
            return jax.lax.fori_loop(
                0, reps, body, (state, jnp.zeros((B, H, P), jnp.float32)))
        return jax.jit(run)
    return (_median_ms(chain(9), state, *rest)
            - _median_ms(chain(1), state, *rest)) / 8


def fori_form(x, Bm, Cm, dt, A, state, ids, n):
    """The live list walked in plain XLA: a slot's state sliced out,
    stepped and put back in the loop."""
    def body(j, c):
        state, y = c
        b = ids[j]
        one = lambda a: jax.lax.dynamic_slice_in_dim(a, b, 1)
        yb, sb = ssm_step(one(x), one(Bm), one(Cm), one(dt), A, one(state))
        return (jax.lax.dynamic_update_slice_in_dim(state, sb, b, 0),
                jax.lax.dynamic_update_slice_in_dim(y, yb, b, 0))
    state, y = jax.lax.fori_loop(
        0, n, body, (state, jnp.zeros(x.shape, jnp.float32)))
    return y, state


# the kernel's body with parts taken out: what bounds it?
def _copy_body(ids_ref, n_ref, rows_ref, bc_ref, s_ref, so_ref, y_ref):
    so_ref[...] = s_ref[...]
    y_ref[...] = rows_ref[0]


def _no_reduce_body(ids_ref, n_ref, rows_ref, bc_ref, s_ref, so_ref, y_ref):
    Bm = bc_ref[0:1, :]
    dtx, a = rows_ref[0].T, rows_ref[1].T
    for r in range(s_ref.shape[0]):
        so_ref[r] = s_ref[r] * a[:, r:r + 1] + dtx[:, r:r + 1] * Bm
    y_ref[...] = rows_ref[0]


def _lane_reduce_body(ids_ref, n_ref, rows_ref, bc_ref, s_ref, so_ref, y_ref):
    """Call 1's way to y, for the record: a reduction along the lanes a
    row."""
    Bm, Cm = bc_ref[0:1, :], bc_ref[1:2, :]
    dtx, a = rows_ref[0].T, rows_ref[1].T
    cols = []
    for r in range(s_ref.shape[0]):
        s = s_ref[r] * a[:, r:r + 1] + dtx[:, r:r + 1] * Bm
        so_ref[r] = s
        cols.append(jnp.sum(s * Cm, axis=-1, keepdims=True))
    y_ref[...] = jnp.concatenate(cols, axis=1).T


def kernel_way(body=None, budget=None):
    def fn(x, Bm, Cm, dt, A, state, ids, n):
        keep = ssm._step_kernel, ssm._STATE_BLOCK_BYTES
        if body is not None:
            ssm._step_kernel = body
        if budget is not None:
            ssm._STATE_BLOCK_BYTES = budget
        try:
            y, state = ssm._step_live.__wrapped__(
                jnp.exp(dt * A), dt[:, :, None] * x, Bm, Cm, state, ids,
                n.reshape(1), interpret=False)
        finally:
            ssm._step_kernel, ssm._STATE_BLOCK_BYTES = keep
        return y, state
    return fn


def scene(live, scattered, seed=47):
    k = jax.random.split(jax.random.PRNGKey(seed), 7)
    x = jax.random.normal(k[0], (B, H, P))
    Bm = jax.random.normal(k[1], (B, N))
    Cm = jax.random.normal(k[2], (B, N))
    A = -jax.random.uniform(k[4], (H,), minval=1.0, maxval=16.0)
    state = jax.random.normal(k[5], (B, H, P, N))
    if scattered:
        on = np.zeros(B, bool)
        on[np.asarray(jax.random.permutation(k[6], B))[:live]] = True
    else:
        on = np.arange(B) < live
    dt = jax.random.uniform(k[3], (B, H), minval=0.001, maxval=0.1) * \
        jnp.asarray(on, jnp.float32)[:, None]
    ids, n = ssm.live_slots(jnp.asarray(on))
    return (x, Bm, Cm, dt, A), state, (ids, n), on


def main():
    out = []

    def report(way, live, scattered, ms, slots_streamed, **more):
        gb = 2 * slots_streamed * SLOT / 1e9
        line = {"way": way, "live": live,
                "layout": "scattered" if scattered else "contiguous",
                "ms": round(ms, 4), "slots_streamed": slots_streamed,
                "GB": round(gb, 4),
                "bw_share_pct": round(gb * 1e9 / (ms * 1e-3) / PEAK * 100, 1),
                **more}
        print(json.dumps(line), flush=True)
        out.append(line)

    oracle = lambda x, Bm, Cm, dt, A, state, ids, n: ssm_step(
        x, Bm, Cm, dt, A, state)
    for live in (19, 37, 56, 64):
        for scattered in ((True, False) if live < B else (False,)):
            small, state, lst, on = scene(live, scattered)
            args = (*small, *lst)
            want_y, want_s = jax.jit(ssm_step)(*small, state)
            got_y, got_s = jax.jit(ssm.ssm_step_pallas)(*small, state, *lst)
            err = {
                "state_max_abs_err": float(jnp.abs(got_s - want_s).max()),
                "live_y_max_abs_err_over_max": float(
                    jnp.abs((got_y - want_y)[on]).max()
                    / jnp.abs(want_y).max()),
                "dead_state_bit_equal": bool(
                    (np.asarray(got_s)[~on] == np.asarray(state)[~on]).all()),
                "dead_y_zero": bool((np.asarray(got_y)[~on] == 0).all())}
            del want_y, want_s, got_y, got_s
            report("fusion", live, scattered, timed(oracle, state, *args), B)
            report("fori_loop", live, scattered,
                   timed(fori_form, state, *args), live)
            report("kernel", live, scattered,
                   timed(kernel_way(), state, *args), live,
                   head_tile=ssm.head_tile(H, P, N), **err)
            if scattered or live == B:
                for th in (32, 16):
                    report("kernel", live, scattered, timed(
                        kernel_way(budget=4 * th * P * N * 4), state, *args),
                        live, head_tile=th)
                report("kernel_copy_only", live, scattered, timed(
                    kernel_way(_copy_body), state, *args), live)
                report("kernel_no_reduce", live, scattered, timed(
                    kernel_way(_no_reduce_body), state, *args), live)
                report("kernel_lane_reduce", live, scattered, timed(
                    kernel_way(_lane_reduce_body), state, *args), live)
    # no slot live: a grid of no step
    small, state, lst, on = scene(0, False)
    _, got = jax.jit(kernel_way())(*small, state, *lst)
    report("kernel", 0, False, timed(kernel_way(), state, *small, *lst), 0,
           dead_state_bit_equal=bool((got == state).all()))
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/pr47_kernel_probe.jsonl", "w") as f:
        for line in out:
            f.write(json.dumps(line) + "\n")


if __name__ == "__main__":
    main()
