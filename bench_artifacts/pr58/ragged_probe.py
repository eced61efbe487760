"""`lax.ragged_dot` alone, on the chip, at the shapes of a prefill chunk's
routed product (`moe/dropless.py::experts_grouped`): what the rows of no
group cost and what the many small groups cost.  One "layer" is the
gate, up and down products with the SiLU between; a timed program chains
12 of them (4 sets of weights, each used three times: nothing stays in
VMEM from one layer to the next), 10 programs are enqueued and the last
waited for.

    chiprun -- python bench_artifacts/pr58/ragged_probe.py

Each line: the case, rows given to the call, rows that belong to a
group, ms a layer, the layer's weights in MB and the share of 819 GB/s
at which they were streamed.
"""
import json
import os
import time

import numpy as np

import jax
import jax.numpy as jnp

LAYERS, SETS, HBM = 12, 4, 819e9

# (name, experts held, D, F, rows given, rows held)
CASES = [
    ("longchat 5120 given 640 held", 64, 2048, 512, 5120, 640),
    ("longchat 1280 given 640 held", 64, 2048, 512, 1280, 640),
    ("longchat 640 given 640 held", 64, 2048, 512, 640, 640),
    ("longchat 1280 given 1280 held", 64, 2048, 512, 1280, 1280),
    ("chatgen 3072 given 3072 held", 64, 2048, 1408, 3072, 3072),
    ("mixedlen 4096 given 512 held", 16, 4096, 4096, 4096, 512),
    ("mixedlen 1024 given 512 held", 16, 4096, 4096, 1024, 512),
]


def layer(x, sizes, gate, up, down):
    dot = lambda a, w: jax.lax.ragged_dot(
        a, w, sizes, preferred_element_type=jnp.float32)
    h = jax.nn.silu(dot(x, gate)) * dot(x, up)
    return dot(h.astype(x.dtype), down)


@jax.jit
def chain(x, sizes, weights):
    for i in range(LAYERS):
        gate, up, down = weights[i % SETS]
        # keep the values bounded from layer to layer
        x = jnp.tanh(layer(x, sizes, gate, up, down)).astype(x.dtype)
    return x


def timed(fn, *args, n=10):
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n * 1e3


def main():
    assert jax.default_backend() == "tpu", jax.default_backend()
    rng = np.random.RandomState(58)
    lines = []
    for name, E, D, F, given, held in CASES:
        key = jax.random.PRNGKey(given * 7 + held)
        weights = []
        for s in range(SETS):
            k1, k2, k3, key = jax.random.split(key, 4)
            weights.append((
                jax.random.normal(k1, (E, D, F), jnp.bfloat16) * D ** -0.5,
                jax.random.normal(k2, (E, D, F), jnp.bfloat16) * D ** -0.5,
                jax.random.normal(k3, (E, F, D), jnp.bfloat16) * F ** -0.5))
        sizes = jnp.asarray(rng.multinomial(held, np.ones(E) / E), jnp.int32)
        x = jnp.asarray(rng.randn(given, D), jnp.bfloat16)
        ms = timed(chain, x, sizes, weights) / LAYERS
        mb = 3 * E * D * F * 2 / 1e6
        line = {"probe": "ragged_dot", "case": name, "experts": E,
                "model_dim": D, "expert_dim": F, "rows_given": given,
                "rows_held": held, "layer_ms": ms, "weights_mb": mb,
                "stream_share_pct": mb * 1e6 / HBM / (ms * 1e-3) * 100,
                "device": jax.devices()[0].device_kind}
        print(json.dumps(line), flush=True)
        lines.append(line)
        del weights
    os.makedirs("chiprun_out/pr58", exist_ok=True)
    with open("chiprun_out/pr58/ragged_probe.jsonl", "a") as f:
        for line in lines:
            f.write(json.dumps(line) + "\n")


if __name__ == "__main__":
    main()
