"""The claimed cell's own run with the kernel's two VMEM constants set
from the command line (a builder's experiment, PR 47: does the block a
grid step takes, or the VMEM the kernel asks for, move the step in situ
— where XLA's own prefetches share the chip with it — as it does not in
the micro-benchmark?).

    python bench_artifacts/pr47/vmem_probe.py <block MiB> <rest MiB> \
        --workload granite-4.0-h-micro.serve.chatrate --seed 1 --seconds 50 --trace 1
"""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import run  # noqa: E402
from deepspeed_tpu.kernels import ssm  # noqa: E402

ssm._STATE_BLOCK_BYTES = int(sys.argv[1]) << 20
ssm._STATE_REST = int(sys.argv[2]) << 20
sys.exit(run.main(sys.argv[3:]))
