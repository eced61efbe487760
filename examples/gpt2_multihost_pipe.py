"""Multi-host pipeline parallelism: one physical stage per process,
activations/grads crossing process boundaries through p2p.Channel
collectives (the NCCL-p2p analogue; reference pipe/p2p.py:31-75).

Run as N cooperating processes (this script self-launches them on one
machine for the demo; on a real pod each host runs one process under
`jax.distributed`):

    JAX_PLATFORMS=cpu python examples/gpt2_multihost_pipe.py --procs 2

Or exercise the identical channel executor single-process on the
virtual mesh:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
        python examples/gpt2_multihost_pipe.py --single
"""

from __future__ import annotations

import argparse
import os
import socket
import subprocess
import sys

from common import print_curve, token_batches  # noqa: E402

V, D = 128, 32
MICRO, M = 4, 4


def build_module(num_stages):
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.runtime.pipe.module import (LayerSpec,
                                                   PipelineModule,
                                                   TiedLayerSpec)

    class Embed:
        def init(self, rng):
            return {"w": jax.random.normal(rng, (V, D)) * 0.05}

        def apply(self, p, x, rng=None, train=True):
            return p["w"][x]

    class Block:
        def __init__(self, ff):
            self.ff = ff

        def init(self, rng):
            k1, k2 = jax.random.split(rng)
            return {"a": jax.random.normal(k1, (D, self.ff)) * 0.05,
                    "b": jax.random.normal(k2, (self.ff, D)) * 0.05}

        def apply(self, p, x, rng=None, train=True):
            return x + jnp.tanh(x @ p["a"]) @ p["b"]

    def head(layer, p, x):
        return x @ p["w"].T

    def ce(logits, labels):
        lp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
        return -jnp.mean(jnp.take_along_axis(lp, labels[..., None], -1))

    return PipelineModule(
        [TiedLayerSpec("emb", Embed)]
        + [LayerSpec(Block, ff) for ff in (48, 64, 48)]
        + [TiedLayerSpec("emb", Embed, forward_fn=head)],
        num_stages=num_stages, loss_fn=ce)


def config(use_channels=False):
    c = {"train_batch_size": MICRO * M,
         "train_micro_batch_size_per_gpu": MICRO,
         "gradient_accumulation_steps": M,
         "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
         "gradient_clipping": 1.0,
         "mesh": {"data": 1, "pipe": -1},
         "steps_per_print": 0}
    if use_channels:
        c["pipeline"] = {"use_p2p_channels": True}
    return c


def worker(proc_id, nprocs, coord, steps):
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.distributed.initialize(coordinator_address=coord,
                               num_processes=nprocs, process_id=proc_id)
    import deepspeed_tpu

    engine, *_ = deepspeed_tpu.initialize(
        model=build_module(nprocs), dist_init_required=False,
        config_params=config())
    assert engine._mh, "multi-host pipe mode inactive"
    losses = []
    for step in range(steps):
        batches = list(token_batches(M, MICRO, 12, V, seed=step))
        losses.append(float(engine.train_batch(iter(batches))))
    if proc_id == 0:
        print_curve(f"mh-pipe (stage {proc_id}/{nprocs})", losses)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--procs", type=int, default=2)
    ap.add_argument("--steps", type=int, default=5)
    ap.add_argument("--single", action="store_true",
                    help="channel executor on local device groups")
    ap.add_argument("--_worker", type=int, default=None)
    ap.add_argument("--_coord", default=None)
    args = ap.parse_args()

    if args._worker is not None:
        worker(args._worker, args.procs, args._coord, args.steps)
        return

    if args.single:
        import deepspeed_tpu

        engine, *_ = deepspeed_tpu.initialize(
            model=build_module(2), config_params=config(use_channels=True))
        assert engine._mh
        losses = []
        for step in range(args.steps):
            batches = list(token_batches(M, MICRO, 12, V, seed=step))
            losses.append(float(engine.train_batch(iter(batches))))
        print_curve("mh-pipe channels (single-process)", losses)
        return

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    coord = f"127.0.0.1:{s.getsockname()[1]}"
    s.close()
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    procs = [subprocess.Popen(
        [sys.executable, os.path.abspath(__file__),
         "--procs", str(args.procs), "--steps", str(args.steps),
         "--_worker", str(i), "--_coord", coord], env=env)
        for i in range(args.procs)]
    try:
        # a dead worker leaves the others blocked in collectives — bound
        # the wait and kill the stragglers so the demo can't hang
        rc = [p.wait(timeout=600) for p in procs]
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    assert all(r == 0 for r in rc), rc
    print("all processes done")


if __name__ == "__main__":
    main()
