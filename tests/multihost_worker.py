"""Worker script for test_multihost.py: one of N jax.distributed
processes, each backing 4 virtual CPU devices, training the same dp=8
engine and writing its own checkpoint shard pieces (no cross-host
gather)."""

import os
import sys


def main():
    proc_id = int(sys.argv[1])
    nprocs = int(sys.argv[2])
    coord = sys.argv[3]
    ckpt_dir = sys.argv[4]

    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.distributed.initialize(coordinator_address=coord,
                               num_processes=nprocs, process_id=proc_id)

    import numpy as np

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), ".."))
    import deepspeed_tpu
    from simple_model import SimpleModel

    assert jax.process_count() == nprocs
    assert jax.device_count() == 4 * nprocs

    engine, *_ = deepspeed_tpu.initialize(
        model=SimpleModel(hidden_dim=64),
        dist_init_required=False,  # already initialized above
        config_params={
            "train_batch_size": 8 * nprocs,
            "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
            "zero_optimization": {"stage": 2},
            "mesh": {"data": 4 * nprocs},
            "steps_per_print": 0,
        })
    rng = np.random.RandomState(0)  # same data on all hosts (global batch)
    for step in range(3):
        x = rng.randn(8 * nprocs, 64).astype(np.float32)
        y = (x @ np.ones((64, 4), np.float32) * 0.1)
        loss = engine.forward((x, y))
        engine.backward()
        engine.step()
    engine.save_checkpoint(ckpt_dir, tag="mh")
    # every process reports the final loss; the parent asserts agreement
    print(f"MHOK proc={proc_id} loss={float(loss):.6f} "
          f"params0={float(np.asarray(jax.tree_util.tree_leaves(engine.params)[0]).sum()):.6f}",
          flush=True)


if __name__ == "__main__":
    main()
