"""Worker for the 2-process bucketed-wire slow-lane parity test
(test_grad_bucketing.py): each jax.distributed process backs 4 virtual
CPU devices; the SAME data stream trains an implicit-wire engine, a
bucketed-wire engine, and a HIERARCHICAL bucketed engine (data_outer=2:
one outer group per process, so the inter-group hop rides the real
gloo/TCP boundary while intra-group collectives stay in-process), so
the cross-process collectives carry real serialized bytes.  Every
process prints the final losses + a param checksum per wire; the parent
asserts all wires agree and all processes agree with each other."""

import os
import sys


def main():
    proc_id = int(sys.argv[1])
    nprocs = int(sys.argv[2])
    coord = sys.argv[3]

    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.distributed.initialize(coordinator_address=coord,
                               num_processes=nprocs, process_id=proc_id)

    import numpy as np

    here = os.path.dirname(os.path.abspath(__file__))
    sys.path.insert(0, here)
    sys.path.insert(0, os.path.join(here, ".."))
    import deepspeed_tpu
    from simple_model import SimpleModel

    assert jax.process_count() == nprocs

    def run(comm):
        cfg = {
            "train_batch_size": 8 * nprocs,
            "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
            "zero_optimization": {"stage": 2},
            "mesh": {"data": 4 * nprocs},
            "steps_per_print": 0,
        }
        if comm is not None:
            cfg["comm"] = comm
        engine, *_ = deepspeed_tpu.initialize(
            model=SimpleModel(hidden_dim=64), dist_init_required=False,
            config_params=cfg)
        rng = np.random.RandomState(0)  # same global batch on all hosts
        loss = None
        for _ in range(3):
            x = rng.randn(8 * nprocs, 64).astype(np.float32)
            y = x @ np.ones((64, 4), np.float32) * 0.1
            loss = engine.forward((x, y))
            engine.backward()
            engine.step()
        # in-jit checksum to a replicated scalar: post-step leaves may be
        # dp-sharded across processes (non-addressable host-side)
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec

        psum = float(jax.jit(
            lambda t: sum(jnp.abs(l).sum()
                          for l in jax.tree_util.tree_leaves(t)),
            out_shardings=NamedSharding(engine.mesh_info.mesh,
                                        PartitionSpec()))(engine.params))
        return float(loss), psum, engine

    implicit_loss, implicit_psum, _ = run(None)
    bucketed_loss, bucketed_psum, engine = run(
        {"gradient_reduction": "bucketed", "reduce_bucket_size": 1024})
    assert engine.bucket_plan is not None, \
        "bucketed wire did not engage on the 2-process lane"
    # hierarchical lane: "auto" must map processes to outer groups
    # (outer=nprocs, inner=4 local devices) on this topology
    hier_loss, hier_psum, hier_engine = run(
        {"gradient_reduction": "bucketed", "reduce_bucket_size": 1024,
         "hierarchy": "auto"})
    assert hier_engine.mesh_info.hierarchical, \
        "hierarchy=auto did not factor the data axis across processes"
    assert hier_engine.mesh_info.data_outer_size == nprocs
    hplan = hier_engine.bucket_plan
    assert hplan is not None and hplan.hierarchical
    assert hplan.wire_bytes_inter_per_reduction * 4 <= \
        engine.bucket_plan.wire_bytes_per_reduction + 4 * 16 * \
        hplan.n_buckets, "inter bytes did not drop by the inner factor"
    # overlapped lanes over the REAL socket exchange: the hierarchical
    # pair (outer=nprocs=2: a 2-element outer reduce is commutative)
    # and the flat int8 pair (gather wires share the serial sum
    # expression) must be BITWISE the serial runs
    hov_loss, hov_psum, hov_engine = run(
        {"gradient_reduction": "bucketed", "reduce_bucket_size": 1024,
         "hierarchy": "auto", "overlap": "on"})
    assert "grads" in hov_engine._step_fns, \
        "comm.overlap did not engage on the 2-process lane"
    assert hov_loss == hier_loss and hov_psum == hier_psum, \
        ("overlapped hier lane diverged from serial",
         hov_loss, hier_loss, hov_psum, hier_psum)
    hov_engine.close_overlap()
    i8_loss, i8_psum, _ = run(
        {"gradient_reduction": "bucketed", "reduce_bucket_size": 1024,
         "wire_dtype": "int8"})
    i8o_loss, i8o_psum, i8o_engine = run(
        {"gradient_reduction": "bucketed", "reduce_bucket_size": 1024,
         "wire_dtype": "int8", "overlap": "on"})
    assert i8o_loss == i8_loss and i8o_psum == i8_psum, \
        ("overlapped int8 lane diverged from serial",
         i8o_loss, i8_loss, i8o_psum, i8_psum)
    i8o_engine.close_overlap()
    print(f"GWOK proc={proc_id} "
          f"implicit={implicit_loss:.6f}/{implicit_psum:.6f} "
          f"bucketed={bucketed_loss:.6f}/{bucketed_psum:.6f} "
          f"hier={hier_loss:.6f}/{hier_psum:.6f} "
          f"overlap_bitwise=1 "
          f"buckets={engine.bucket_plan.n_buckets}", flush=True)


if __name__ == "__main__":
    main()
