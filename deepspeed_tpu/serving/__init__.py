"""deepspeed_tpu.serving — the continuous-batching inference engine.

The headline serving scenario (ROADMAP item 1): a paged, mesh-sharded
KV cache with block-level prefix caching and, for models that keep
exact keys only for an open window, summary rows under the same
allocator (`kv_cache.py`), in-flight admission with chunked prefill
(`scheduler.py`), compiled prefill/decode programs built
StepBuilder-style from the model's layer spec (`programs.py`,
`layers.py`), the engine + worker loop with pinned sessions
(`engine.py`), and a multi-replica fleet router (`router.py`).  Benchmarked by
`tools/serve_bench.py`; tutorial at docs/tutorials/serving.md.
"""

from .engine import ServeConfig, ServeEngine, ServeWorker, SessionPin
from .kv_cache import (KV_QUANT_WIRES, TRASH_BLOCK, PagedKVCache,
                       kv_block_bytes, resolve_kv_dtype)
from .programs import (KV_MODES, ServeProgramBuilder, ServeSchedule,
                       dequantize_params, quantize_params, sample_token)
from .router import FleetRouter, build_fleet
from .scheduler import (ADMISSION_POLICIES, ERROR, FINISHED, PREFILL,
                        RUNNING, WAITING, Request, Scheduler)

__all__ = [
    "ServeConfig", "ServeEngine", "ServeWorker", "SessionPin",
    "PagedKVCache", "TRASH_BLOCK", "KV_QUANT_WIRES", "KV_MODES",
    "kv_block_bytes", "resolve_kv_dtype", "ServeProgramBuilder",
    "ServeSchedule", "sample_token", "quantize_params",
    "dequantize_params", "Request", "Scheduler", "ADMISSION_POLICIES",
    "WAITING", "PREFILL", "RUNNING", "FINISHED", "ERROR", "FleetRouter",
    "build_fleet",
]
