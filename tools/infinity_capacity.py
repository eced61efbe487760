"""ZeRO-Infinity capacity report: max params/chip (BASELINE.json axis).

For a GPT config, prints total parameters, the device-resident working
set under streaming (embed + head resident, 2 blocks double-buffered,
saved block inputs), and host bytes (fp32 masters + Adam moments), then
the implied max model size for a given HBM/host budget. With --step it
also runs one real streamed step to prove the config executes.

Usage:
  python tools/infinity_capacity.py --size xl --seq 1024 --micro 8
  python tools/infinity_capacity.py --size xl --hbm-gb 16 --host-gb 256
"""

from __future__ import annotations

import argparse
import os
import sys

_ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
sys.path.insert(0, _ROOT)

import jax  # noqa: E402


def report(size, seq, micro, hbm_gb, host_gb, run_step=False,
           nvme_path=None):
    from deepspeed_tpu.models import GPT, gpt2_config

    cfg = gpt2_config(size, max_seq_len=seq)
    model = GPT(cfg)
    n = model.num_params()
    d, L, V = cfg.d_model, cfg.num_layers, cfg.vocab_size
    block_params = 12 * d * d + 13 * d  # qkv/proj/fc1/fc2 + ln/biases
    embed_params = V * d + seq * d
    wire = 2  # bf16 bytes
    resident = (embed_params + d * 2) * wire          # embed + head ln
    stream = 2 * block_params * wire                  # double buffer
    acts = (L + 1) * micro * seq * d * wire           # saved block inputs
    ce = micro * seq // max(1, micro * seq // 2048) * V * 4  # one CE chunk
    device = resident + stream + acts + ce
    host = n * 4 * 3  # fp32 masters + m + v
    print(f"gpt2-{size}: {n/1e9:.3f}B params, {L} layers, d={d}, seq={seq},"
          f" micro={micro}")
    print(f"  device working set : {device/2**30:.2f} GiB "
          f"(embed+head {resident/2**30:.2f}, 2-block stream "
          f"{stream/2**30:.3f}, activations {acts/2**30:.2f}, CE chunk "
          f"{ce/2**30:.2f})")
    print(f"  host masters+Adam  : {host/2**30:.2f} GiB")
    print(f"  resident-engine HBM would need ~{n*(4+4+8)/2**30:.1f} GiB "
          f"(fp32 master+grad+moments) + activations")
    # implied capacity: params bounded by host RAM at 12 B/param; device
    # side bounded by activations+embed only (blocks stream)
    host_cap = host_gb * 2**30 / 12
    print(f"  max params/chip    : ~{host_cap/1e9:.0f}B with {host_gb} GiB "
          f"host RAM (12 B/param host-side; device holds "
          f"{device/2**30:.2f} GiB << {hbm_gb} GiB HBM)")
    biggest_group = max(block_params, embed_params) * 4
    print(f"  with --nvme        : host RAM holds ~2 groups "
          f"({2 * biggest_group/2**30:.2f} GiB) + grad sink "
          f"({n*4/2**30:.2f} GiB); masters+moments page to SSD — "
          f"capacity is NVMe-bounded, not RAM-bounded")
    if run_step:
        import resource

        import numpy as np

        import deepspeed_tpu

        dev = ({"device": "nvme", "nvme_path": nvme_path}
               if nvme_path else {"device": "cpu"})
        engine, *_ = deepspeed_tpu.initialize(model=model, config_params={
            "train_batch_size": micro,
            "optimizer": {"type": "Adam", "params": {"lr": 1e-4}},
            "zero_optimization": {"stage": 3, "offload_param": dev},
            "bf16": {"enabled": True},
            "mesh": {"data": 1},
            "steps_per_print": 0})
        rng = np.random.RandomState(0)
        tok = rng.randint(0, cfg.vocab_size, (micro, seq + 1)).astype("i4")
        loss = engine.forward((tok[:, :-1], tok[:, 1:]))
        engine.backward()
        engine.step()
        rss = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024
        where = "NVMe-paged masters" if nvme_path else "RAM masters"
        print(f"  one streamed step  : loss={float(loss):.3f} OK "
              f"({where}); peak RSS {rss/2**30:.2f} GiB vs "
              f"{host/2**30:.2f} GiB masters+moments")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--size", default="xl")
    ap.add_argument("--seq", type=int, default=1024)
    ap.add_argument("--micro", type=int, default=8)
    ap.add_argument("--hbm-gb", type=float, default=16)
    ap.add_argument("--host-gb", type=float, default=256)
    ap.add_argument("--step", action="store_true")
    ap.add_argument("--nvme", default=None,
                    help="page fp32 masters+moments to this SSD path "
                         "(capacity becomes NVMe-bounded)")
    args = ap.parse_args()
    report(args.size, args.seq, args.micro, args.hbm_gb, args.host_gb,
           run_step=args.step, nvme_path=args.nvme)


if __name__ == "__main__":
    main()
