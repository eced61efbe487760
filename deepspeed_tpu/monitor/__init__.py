"""deepspeed_tpu.monitor — structured run telemetry.

One subsystem unifying the observability shims (utils/timer,
utils/tensorboard, profiling/flops_profiler) into a single pipeline:

* `RunMonitor` — per-rank schema-versioned JSONL event stream + manifest
  + end-of-run summaries, TensorBoard as one sink beside it, multi-host
  heartbeats with rank-0 straggler detection.
* `Span` / `TraceWindow` — async-dispatch-aware timing (close on a
  block_until_ready marker) and the config-driven `jax.profiler.trace`
  capture window.
* `COUNTERS` — process-global comm/dispatch counters threaded through
  the p2p channels, the compiled pipeline executor, the collective
  wrappers, and the hostwire.
* `report` — renders any run's JSONL back into a markdown table
  (CLI: tools/run_report.py).
"""

from .config import MONITOR, DeepSpeedMonitorConfig  # noqa: F401
from .counters import (COUNTERS, US_IN_BYTES_COUNTERS,  # noqa: F401
                       CounterRegistry, tree_bytes)
from .monitor import (SCHEMA_VERSION, RunMonitor,  # noqa: F401
                      device_memory_stats)
from .spans import Span, SpanSet, TraceWindow  # noqa: F401
from .tracing import (TRACE_SCHEMA_VERSION, ServingSLO,  # noqa: F401
                      TraceRecorder, percentile_nearest_rank,
                      read_trace_file)
