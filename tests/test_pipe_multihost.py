"""Multi-host heterogeneous pipeline: 2 jax.distributed processes (one
physical stage each, 2 CPU devices per stage for within-stage dp) train a
TiedLayerSpec pipeline through p2p.Channel collectives; per-step losses
must agree across processes and match a single-process run of the same
model/data. Reference capability: deepspeed/runtime/pipe/p2p.py:31-75
(NCCL p2p between pipeline ranks across nodes).

The single-process channel executor (pipeline.use_p2p_channels) is
covered by the fast tests below; the 2-process run is slow-marked."""

import os
import socket
import subprocess
import sys

import numpy as np
import pytest


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _single_process_losses(steps, use_channels, interleave=1,
                           num_stages=2):
    import deepspeed_tpu
    from pipe_parity_common import M, build_module, config, data

    engine, *_ = deepspeed_tpu.initialize(
        model=build_module(num_stages=num_stages, interleave=interleave),
        config_params=config(use_channels))
    assert engine._staged
    assert engine._mh == use_channels
    losses = [float(engine.train_batch(iter(data(100 + i, M))))
              for i in range(steps)]
    ev = float(engine.eval_batch(iter(data(999, M))))
    return losses, ev


def test_channel_executor_matches_single_controller():
    """The p2p-channel executor (the exact multi-host code path, run
    single-process) trains identically to the proven single-controller
    1F1B executor."""
    ref_l, ref_e = _single_process_losses(3, use_channels=False)
    ch_l, ch_e = _single_process_losses(3, use_channels=True)
    np.testing.assert_allclose(ch_l, ref_l, rtol=1e-4)
    np.testing.assert_allclose(ch_e, ref_e, rtol=1e-4)


@pytest.mark.slow
def test_channel_executor_interleaved():
    """Interleaved virtual stages through the channel executor: chunk
    wrap-around channels (stage P-1 chunk c -> stage 0 chunk c+1)."""
    ref_l, _ = _single_process_losses(2, use_channels=False, interleave=2)
    ch_l, _ = _single_process_losses(2, use_channels=True, interleave=2)
    np.testing.assert_allclose(ch_l, ref_l, rtol=1e-4)


@pytest.mark.slow
def test_four_process_pipeline_parity():
    """4 jax.distributed processes x 4 stages (an earlier review's gap:
    the channel executor was proven at exactly 2 processes): tied
    embedding spans the full pipeline depth, every process walks the
    same canonical order, all four report identical losses matching the
    single-process oracle, and the 4-way checkpoint round-trips."""
    steps = 2
    nprocs = 4
    coord = f"127.0.0.1:{_free_port()}"
    worker = os.path.join(os.path.dirname(__file__),
                          "multihost_pipe_worker.py")
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    import shutil
    import tempfile

    ckdir = tempfile.mkdtemp(prefix="mhpipe4_ck_")
    procs = [
        subprocess.Popen(
            [sys.executable, worker, str(i), str(nprocs), coord,
             str(steps), ckdir],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            env=env)
        for i in range(nprocs)
    ]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=1800)
            outs.append(out)
            assert p.returncode == 0, out[-3000:]
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()

    curves = []
    for out in outs:
        assert "MHPIPE done" in out, out[-2000:]
        assert "CKPT_OK" in out, out[-2000:]
        losses = [float(ln.split("loss=")[1])
                  for ln in out.splitlines() if "loss=" in ln]
        evals = [float(ln.split("eval=")[1])
                 for ln in out.splitlines() if "eval=" in ln]
        assert len(losses) == steps and len(evals) == 1, out[-2000:]
        curves.append(losses + evals)
    for c in curves[1:]:
        np.testing.assert_allclose(c, curves[0], rtol=1e-6)

    # the 4-way-written checkpoint loads into a single-host 4-stage
    # engine with optimizer state
    import deepspeed_tpu
    from pipe_parity_common import M, build_module, config, data

    back, *_ = deepspeed_tpu.initialize(
        model=build_module(num_stages=nprocs), config_params=config())
    d, _ = back.load_checkpoint(ckdir, tag="mh")
    assert d is not None and back.global_steps == steps
    assert np.isfinite(float(back.train_batch(iter(data(888, M)))))
    shutil.rmtree(ckdir, ignore_errors=True)

    # parity vs the single-process 4-stage oracle
    ref_l, ref_e = _single_process_losses(steps, use_channels=False,
                                          num_stages=nprocs)
    np.testing.assert_allclose(curves[0][:steps], ref_l, rtol=1e-3)
    np.testing.assert_allclose(curves[0][steps], ref_e, rtol=1e-3)


@pytest.mark.slow
def test_two_process_pipeline_parity():
    steps = 3
    nprocs = 2
    coord = f"127.0.0.1:{_free_port()}"
    worker = os.path.join(os.path.dirname(__file__),
                          "multihost_pipe_worker.py")
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    import shutil
    import tempfile

    ckdir = tempfile.mkdtemp(prefix="mhpipe_ck_")
    # a single-host-written checkpoint for the workers' cross-direction
    # load check (written on the local 8-device mesh before they start)
    shdir = tempfile.mkdtemp(prefix="mhpipe_sh_")
    import deepspeed_tpu
    from pipe_parity_common import M, build_module, config, data

    sh_engine, *_ = deepspeed_tpu.initialize(
        model=build_module(num_stages=nprocs),
        config_params=config())
    sh_engine.train_batch(iter(data(100, M)))
    sh_engine.save_checkpoint(shdir, tag="sh")

    procs = [
        subprocess.Popen(
            [sys.executable, worker, str(i), str(nprocs), coord,
             str(steps), ckdir, shdir],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            env=env)
        for i in range(nprocs)
    ]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=900)
            outs.append(out)
            assert p.returncode == 0, out[-3000:]
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()

    # both processes completed and report identical losses; the
    # cross-process checkpoint roundtrip resumed with loss parity
    curves = []
    for out in outs:
        assert "MHPIPE done" in out, out[-2000:]
        assert "CKPT_OK" in out, out[-2000:]
        assert "SH_OK" in out, out[-2000:]
        losses = [float(ln.split("loss=")[1])
                  for ln in out.splitlines() if "loss=" in ln]
        evals = [float(ln.split("eval=")[1])
                 for ln in out.splitlines() if "eval=" in ln]
        assert len(losses) == steps and len(evals) == 1, out[-2000:]
        curves.append(losses + evals)
    np.testing.assert_allclose(curves[0], curves[1], rtol=1e-6)

    # cross-direction loss agreement: both workers continued identically
    # from the single-host checkpoint
    lx = {ln.split("lx=")[1].split()[0]
          for out in outs for ln in out.splitlines() if "lx=" in ln}
    assert len(lx) == 1, lx

    # and the mh-written checkpoint loads back into a single-host engine
    # WITH optimizer state (the reassembled per-chunk layout)
    back, *_ = deepspeed_tpu.initialize(
        model=build_module(num_stages=nprocs),
        config_params=config())
    d, _ = back.load_checkpoint(ckdir, tag="mh")
    assert d is not None and back.global_steps == steps
    for rt in back._runtimes():
        assert int(np.asarray(rt.opt_state["step"])) == steps
    assert np.isfinite(float(back.train_batch(iter(data(888, M)))))
    # cleanup on success (kept on failure for post-mortem)
    shutil.rmtree(ckdir, ignore_errors=True)
    shutil.rmtree(shdir, ignore_errors=True)

    # and the multi-host curve matches the single-process oracle
    # (2 devices per process over 2 processes vs 8 local devices — use
    # the same per-stage device count by building the oracle fresh here)
    ref_l, ref_e = _single_process_losses(steps, use_channels=False)
    np.testing.assert_allclose(curves[0][:steps], ref_l, rtol=1e-3)
    np.testing.assert_allclose(curves[0][steps], ref_e, rtol=1e-3)


@pytest.mark.slow
def test_four_process_compiled_matches_interpreted():
    """The compiled flat-program executor (the default) and the
    interpreted per-event oracle (`pipeline.debug_schedule: true`)
    train equivalently on the real 4-process x 4-stage channel pipeline
    — the multi-rank closure of the single-process parity pins in
    tests/test_pipe_compiler.py.  The two engines run inside ONE process
    group (the worker trains both).  BIT-identity is pinned by the
    single-process channel tests; across real ranks the transport's
    reduction order is not bit-stable call-to-call on a contended host
    (~1e-4 rel drift between IDENTICAL consecutive batches), so this
    asserts tight closeness, which still fails on any structural
    divergence between the executors."""
    steps = 2
    nprocs = 4
    coord = f"127.0.0.1:{_free_port()}"
    worker = os.path.join(os.path.dirname(__file__),
                          "multihost_pipe_worker.py")
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["DSTPU_TEST_COMPARE_DEBUG"] = "1"
    import shutil
    import tempfile

    ckdir = tempfile.mkdtemp(prefix="mhpipe4_ds_")
    procs = [
        subprocess.Popen(
            [sys.executable, worker, str(i), str(nprocs), coord,
             str(steps), ckdir],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True, env=env)
        for i in range(nprocs)
    ]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=1800)
            outs.append(out)
            assert p.returncode == 0, out[-3000:]
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        shutil.rmtree(ckdir, ignore_errors=True)
    for out in outs:
        compiled = [float(ln.split("loss=")[1]) for ln in out.splitlines()
                    if "loss=" in ln and "dbg" not in ln]
        interp = [float(ln.split("dloss=")[1]) for ln in out.splitlines()
                  if "dloss=" in ln]
        assert len(compiled) == steps and len(interp) == steps, out[-2000:]
        np.testing.assert_allclose(compiled, interp, rtol=1e-3)
