"""chip_smoke.py's device and kernels phases alone (PR 64, a builder's
script; PR 62's): every kernel `auto` picks at the cells' shapes against
its oracle, a sliding layer's prefill chunk at mixedlen's shapes
(`sliding_prefill_bf16_H128_KV8_Dh128_ring288_at<start>`) among them.

    python3 bench_artifacts/pr64/kernels_only.py
"""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
import chip_smoke  # noqa: E402
from deepspeed_tpu.utils.compile_cache import enable_compile_cache  # noqa: E402

cache = enable_compile_cache()
chip_smoke.emit(chip_smoke.device_phase(1, cache))
chip_smoke.emit(chip_smoke.kernels_phase())
