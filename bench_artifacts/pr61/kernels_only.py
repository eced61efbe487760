"""chip_smoke.py's device and kernels phases alone (PR 61, a builder's
script): every kernel `auto` picks at the cells' shapes against its
oracle, the grouped `ssm_step` (8 groups) and the two-matrix touched and
slab products at width 1,856 among them.

    python3 bench_artifacts/pr61/kernels_only.py
"""
import os, sys
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
import chip_smoke
from deepspeed_tpu.utils.compile_cache import enable_compile_cache

cache = enable_compile_cache()
chip_smoke.emit(chip_smoke.device_phase(1, cache))
out = chip_smoke.kernels_phase()
chip_smoke.emit(out)
