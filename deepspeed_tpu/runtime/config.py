"""DeepSpeedConfig — JSON config parsing + validation.

Schema-compatible with the reference (deepspeed/runtime/config.py:536):
user configs written for DeepSpeed parse unchanged. The batch triple
(train_batch_size = micro_batch * gradient_accumulation_steps * dp_world)
solver mirrors reference config.py:681-752. TPU additions: a "mesh"
section selecting parallel axis sizes.
"""

import json

from ..elasticity import (
    ElasticityConfigError,
    compute_elastic_config,
    elasticity_enabled,
    ensure_immutable_elastic_config,
)
from ..elasticity import constants as ec
from ..monitor.config import DeepSpeedMonitorConfig
from ..profiling.config import DeepSpeedFlopsProfilerConfig
from ..utils.logging import logger
from . import constants as c
from .activation_checkpointing.config import DeepSpeedActivationCheckpointingConfig
from .config_utils import (
    DeepSpeedConfigObject,
    dict_raise_error_on_duplicate_keys,
    get_scalar_param,
)
from .zero.config import DeepSpeedZeroConfig


class DeepSpeedConfigError(Exception):
    pass


TORCH_DTYPES = {
    "fp16": "float16", "float16": "float16", "half": "float16",
    "bf16": "bfloat16", "bfloat16": "bfloat16",
    "fp32": "float32", "float32": "float32", "float": "float32",
}


class DeepSpeedConfigWriter(DeepSpeedConfigObject):
    pass


def parse_comm_hierarchy(value):
    """Normalize the `comm.hierarchy` knob to "none" | "auto" | int
    (the explicit outer factor).  Shared by the config validator and the
    engine's mesh construction (which runs before full config parsing)."""
    if value is None:
        value = c.COMM_HIERARCHY_DEFAULT
    if isinstance(value, dict):
        unknown = set(value) - {"outer"}
        if unknown:
            raise ValueError(
                f"comm.hierarchy: unknown key(s) {sorted(unknown)}; "
                "expected {'outer': <int>}")
        value = value.get("outer", 1)
    if isinstance(value, str):
        mode = value.lower()
        if mode in ("none", "flat", "off"):
            return "none"
        if mode == "auto":
            return "auto"
        raise ValueError(
            "comm.hierarchy must be 'none', 'auto', an int outer factor, "
            f"or {{'outer': <int>}}, got {value!r}")
    if isinstance(value, bool) or not isinstance(value, int):
        raise ValueError(
            "comm.hierarchy must be 'none', 'auto', an int outer factor, "
            f"or {{'outer': <int>}}, got {value!r}")
    if value < 1:
        raise ValueError(
            f"comm.hierarchy outer factor must be >= 1, got {value}")
    return "none" if value == 1 else value


def check_hierarchy_divides(outer: int, dp_size: int) -> None:
    """An explicit outer factor must factor the dp size exactly — raise
    a shape-level ValueError naming the axis sizes instead of letting a
    jitted reshape/scatter trace into an opaque shape error."""
    if dp_size % outer != 0:
        raise ValueError(
            f"comm.hierarchy: data_outer={outer} does not divide the "
            f"data-parallel axis size {dp_size} (data_inner would be "
            f"{dp_size / outer:g}); pick an outer factor from the "
            f"divisors of {dp_size}")


def parse_comm_overlap(value):
    """Normalize the `comm.overlap` knob to "none" | "auto" | "on".
    Booleans are accepted (the reference `overlap_comm` style): true
    means "on" (demand overlap; unservable configs fall back with a
    warning), false means "none"."""
    if value is None:
        value = c.COMM_OVERLAP_DEFAULT
    if isinstance(value, bool):
        return "on" if value else "none"
    if isinstance(value, str):
        mode = value.lower()
        if mode in ("none", "off", "false"):
            return "none"
        if mode == "auto":
            return "auto"
        if mode in ("on", "true"):
            return "on"
    raise ValueError(
        f"comm.{c.COMM_OVERLAP} must be one of {c.COMM_OVERLAP_MODES} "
        f"(or a bool), got {value!r}")


class DeepSpeedCommConfig(DeepSpeedConfigObject):
    """Gradient-reduction wire selection (runtime/comm/bucketing.py).

    "comm": {
      "gradient_reduction": "implicit" | "bucketed",
      "wire_dtype": "fp32" | "bf16" | "split" | "int8" | "int4",
      "reduce_bucket_size": <elements>,  # default: zero_optimization's knob
      "hierarchy": "none" | "auto" | <outer> | {"outer": <outer>},
      "wire_dtype_inner": ...,           # per-level overrides (hierarchy)
      "wire_dtype_outer": ...,
      "quant_block_size": <elements per fp16 scale>   # int8/int4 wires
    }

    `implicit` (default) leaves DP reduction to XLA's psum at the
    loss-mean boundary — right on ICI, where XLA overlaps the per-leaf
    psums with the backward.  `bucketed` concatenates grads into the
    BucketPlan's fused buckets, one collective per bucket — measured 2x+
    faster on the two-process CPU/TCP lane; not measured on a TPU.
    The reference's top-level `fp32_allreduce` key forces wire_dtype to
    fp32 (the engine's `allreduce_always_fp32()` reflects the result).

    `hierarchy` factors the data axis for the two-level wire (ZeRO++
    arXiv:2306.10209 recipe): intra-group reduce-scatter, inter-group
    collective on the 1/inner shard, intra-group all-gather.  Per-level
    wire dtypes let the slow hop compress (bf16/split, or the blockwise
    int8/int4 quantized gathers — qgZ, comm/quant.py) while the fast
    hop stays exact.  The inner level is scatter-structured and cannot
    carry the gather-structured wires: a "split" request there lowers
    to fp32 with a log line (legacy behaviour), an EXPLICIT
    "wire_dtype_inner": "int8"/"int4" raises — a psum_scatter has no
    way to carry the per-block scales, and silently dropping a
    requested quantization would misreport the wire.
    """

    def __init__(self, param_dict, zero_config, world_size=None):
        super().__init__()
        d = param_dict.get(c.COMM) or {}
        self.gradient_reduction = str(get_scalar_param(
            d, c.COMM_GRADIENT_REDUCTION,
            c.COMM_GRADIENT_REDUCTION_DEFAULT)).lower()
        if self.gradient_reduction not in c.COMM_GRADIENT_REDUCTION_MODES:
            raise ValueError(
                f"comm.gradient_reduction must be one of "
                f"{c.COMM_GRADIENT_REDUCTION_MODES}, "
                f"got {self.gradient_reduction!r}")
        self.fp32_allreduce = bool(get_scalar_param(
            param_dict, c.FP32_ALLREDUCE, c.FP32_ALLREDUCE_DEFAULT))
        from .comm.bucketing import GATHER_WIRES, WIRE_MODES
        from .comm.quant import QUANT_WIRES, validate_block_size

        def wire_param(key, default):
            w = get_scalar_param(d, key, default)
            if w is None:
                return None
            w = str(w).lower()
            if w not in WIRE_MODES:
                # name the offending level AND the full valid set here —
                # a typo'd dtype must never fall through to a jit-time
                # failure inside the traced step program
                raise ValueError(f"comm.{key} must be one of {WIRE_MODES}, "
                                 f"got {w!r}")
            return "fp32" if self.fp32_allreduce else w

        self.wire_dtype = wire_param(c.COMM_WIRE_DTYPE,
                                     c.COMM_WIRE_DTYPE_DEFAULT)
        self.hierarchy = parse_comm_hierarchy(
            get_scalar_param(d, c.COMM_HIERARCHY, c.COMM_HIERARCHY_DEFAULT))
        if isinstance(self.hierarchy, int) and world_size is not None:
            check_hierarchy_divides(self.hierarchy, int(world_size))
        # per-level overrides default to the single-level wire; the
        # inner level can't carry the gather-structured split wire
        # (BucketPlan would re-materialize the full bucket), so it
        # falls back to exact fp32 there — the fast hop staying exact
        # is the recommended placement anyway (comm_tuning.md)
        inner_override = wire_param(c.COMM_WIRE_DTYPE_INNER, None)
        self.wire_dtype_inner = inner_override or self.wire_dtype
        self.wire_dtype_outer = wire_param(c.COMM_WIRE_DTYPE_OUTER, None) \
            or self.wire_dtype
        if inner_override in QUANT_WIRES:
            # an explicitly requested quantized inner wire cannot be
            # honored (the scatter level has nowhere to put the
            # per-block scales) and silently lowering it would
            # misreport the compression — reject, naming the level
            raise ValueError(
                f"comm.{c.COMM_WIRE_DTYPE_INNER} = {inner_override!r}: "
                "the int8/int4 wires are gather-structured (per-block "
                "scales cannot ride a psum_scatter) and cannot run the "
                "intra-group scatter level; use fp32 or bf16 for "
                f"{c.COMM_WIRE_DTYPE_INNER} and put the quantized wire "
                f"on {c.COMM_WIRE_DTYPE_OUTER}")
        if self.wire_dtype_inner in GATHER_WIRES:
            if inner_override is not None:
                # warn only on an EXPLICIT inner-split request; when it
                # is merely inherited from wire_dtype the flat path may
                # still run the split wire unchanged (hierarchy "auto"
                # can resolve flat), and on a factored mesh the engine's
                # BucketPlan log shows the effective per-level wires
                logger.warning(
                    "comm: the split wire is gather-structured and cannot "
                    "run the intra-group scatter level; wire_dtype_inner "
                    "lowers to fp32")
            self.wire_dtype_inner = "fp32"
        self.overlap = parse_comm_overlap(
            get_scalar_param(d, c.COMM_OVERLAP, c.COMM_OVERLAP_DEFAULT))

        def overlap_int(key, default, minimum=1):
            v = get_scalar_param(d, key, default)
            try:
                iv = int(v)
            except (TypeError, ValueError):
                raise ValueError(
                    f"comm.{key} must be an integer >= {minimum}, "
                    f"got {v!r}")
            if iv < minimum:
                raise ValueError(
                    f"comm.{key} must be >= {minimum}, got {iv}")
            return iv

        # the ticket deadline must fire BEFORE the hang watchdog does —
        # a named exchange timeout beats an anonymous stack snapshot
        # (StepWatchdog deadline guidance, docs/tutorials/resilience.md)
        self.overlap_timeout_ms = overlap_int(
            c.COMM_OVERLAP_TIMEOUT_MS, c.COMM_OVERLAP_TIMEOUT_MS_DEFAULT)
        self.overlap_reconnect_attempts = overlap_int(
            c.COMM_OVERLAP_RECONNECT_ATTEMPTS,
            c.COMM_OVERLAP_RECONNECT_ATTEMPTS_DEFAULT, minimum=0)
        self.overlap_reconnect_window_ms = overlap_int(
            c.COMM_OVERLAP_RECONNECT_WINDOW_MS,
            c.COMM_OVERLAP_RECONNECT_WINDOW_MS_DEFAULT)
        self.overlap_keepalive_ms = overlap_int(
            c.COMM_OVERLAP_KEEPALIVE_MS,
            c.COMM_OVERLAP_KEEPALIVE_MS_DEFAULT)
        self.reduce_bucket_size = int(get_scalar_param(
            d, c.COMM_REDUCE_BUCKET_SIZE, zero_config.reduce_bucket_size))
        block = get_scalar_param(d, c.COMM_QUANT_BLOCK_SIZE,
                                 c.COMM_QUANT_BLOCK_SIZE_DEFAULT)
        try:
            self.quant_block_size = validate_block_size(block)
        except ValueError as e:
            raise ValueError(f"comm.{c.COMM_QUANT_BLOCK_SIZE}: {e}")
        # MoE token movement: sorted dispatch + the explicit expert
        # all-to-all wire (moe/dispatch.py).  Parsed eagerly so a bad
        # sub-key fails at config time; the engine installs the result
        # process-globally at initialize().
        from ..moe.dispatch import parse_moe_config

        self.moe = parse_moe_config(d.get(c.COMM_MOE),
                                    default_block=self.quant_block_size)


class DeepSpeedDataPipelineConfig(DeepSpeedConfigObject):
    """Async input pipeline (runtime/dataloader.py PrefetchLoader +
    engine._DeviceFeed).

    "data_pipeline": {
      "enabled": true,          # master switch (default ON)
      "prefetch_depth": 2,      # bounded host queue, in batches
      "num_workers": 1,         # parallel collate threads
      "device_prefetch": true   # device_put batch N+1 during step N
    }

    Defaults ON: background collate + device double-buffering hide the
    host-side gap between step dispatches.  Correctness is unchanged —
    batch order is deterministic and the loss sequence is byte-identical
    with the pipeline off (tests/test_data_pipeline.py pins it across
    all three jitted step paths).  `prefetch_depth: 0` disables host
    prefetch while keeping device double-buffering, and vice versa.
    """

    def __init__(self, param_dict):
        super().__init__()
        d = param_dict.get(c.DATA_PIPELINE) or {}
        known = {c.DATA_PIPELINE_ENABLED, c.DATA_PIPELINE_PREFETCH_DEPTH,
                 c.DATA_PIPELINE_NUM_WORKERS, c.DATA_PIPELINE_DEVICE_PREFETCH}
        unknown = set(d) - known
        if unknown:
            raise ValueError(
                f"data_pipeline: unknown key(s) {sorted(unknown)}; "
                f"expected a subset of {sorted(known)}")
        self.enabled = bool(get_scalar_param(
            d, c.DATA_PIPELINE_ENABLED, c.DATA_PIPELINE_ENABLED_DEFAULT))
        depth = get_scalar_param(d, c.DATA_PIPELINE_PREFETCH_DEPTH,
                                 c.DATA_PIPELINE_PREFETCH_DEPTH_DEFAULT)
        workers = get_scalar_param(d, c.DATA_PIPELINE_NUM_WORKERS,
                                   c.DATA_PIPELINE_NUM_WORKERS_DEFAULT)
        for name, val, lo in ((c.DATA_PIPELINE_PREFETCH_DEPTH, depth, 0),
                              (c.DATA_PIPELINE_NUM_WORKERS, workers, 1)):
            if isinstance(val, bool) or not isinstance(val, int) or val < lo:
                raise ValueError(
                    f"data_pipeline.{name} must be an int >= {lo}, "
                    f"got {val!r}")
        self.prefetch_depth = int(depth)
        self.num_workers = int(workers)
        self.device_prefetch = bool(get_scalar_param(
            d, c.DATA_PIPELINE_DEVICE_PREFETCH,
            c.DATA_PIPELINE_DEVICE_PREFETCH_DEFAULT))

    @property
    def host_prefetch(self) -> bool:
        """True when the background-thread host loop should engage."""
        return self.enabled and self.prefetch_depth > 0

    @property
    def device_feed(self) -> bool:
        """True when the engine should double-buffer batches on device."""
        return self.enabled and self.device_prefetch


class DeepSpeedFaultsConfig(DeepSpeedConfigObject):
    """Chaos-ready runtime (runtime/resilience.py).

    "faults": {
      "seed": 0,
      "enabled": true,          # injection gate; default: rules present
      "rules": [{"site": ..., "kind": "raise"|"delay_ms"|"corrupt"|
                 "hang"|"kill", ...schedule...}],
      "retry": {"max_attempts": 4, "base_delay_ms": 50,
                "max_delay_ms": 2000, "jitter": 0.25},
      "watchdog": {"enabled": false, "deadline_s": 600, "poll_s": 1.0,
                   "first_beat_mult": 4.0,  # pre-first-beat grace
                   "snapshot_dir": null}   # default: the monitor run dir
    }

    `rules` drive deterministic fault injection (every rule is validated
    here — a typo'd site key or kind fails at config time, never inside
    a training step); `retry` and `watchdog` are HARDENING knobs that
    apply whether or not injection is enabled.  The engine installs the
    plan/policy process-globally at initialize() and arms the watchdog
    beside the run monitor."""

    def __init__(self, param_dict):
        super().__init__()
        from .resilience import FaultPlan, RetryPolicy

        d = param_dict.get(c.FAULTS) or {}
        known = {c.FAULTS_ENABLED, c.FAULTS_SEED, c.FAULTS_RULES,
                 c.FAULTS_RETRY, c.FAULTS_WATCHDOG}
        unknown = set(d) - known
        if unknown:
            raise ValueError(
                f"faults: unknown key(s) {sorted(unknown)}; expected a "
                f"subset of {sorted(known)}")
        self.seed = int(get_scalar_param(d, c.FAULTS_SEED,
                                         c.FAULTS_SEED_DEFAULT))
        rules = d.get(c.FAULTS_RULES) or []
        if not isinstance(rules, list):
            raise ValueError(
                f"faults.{c.FAULTS_RULES} must be a list of rule objects, "
                f"got {type(rules).__name__}")
        enabled = d.get(c.FAULTS_ENABLED)
        try:
            # parse eagerly: rule validation errors belong to config time
            self.plan = FaultPlan.from_config(
                rules, seed=self.seed,
                enabled=None if enabled is None else bool(enabled))
        except ValueError as e:
            raise ValueError(f"faults.{c.FAULTS_RULES}: {e}")
        self.enabled = self.plan.enabled

        r = d.get(c.FAULTS_RETRY) or {}
        known_r = {c.FAULTS_RETRY_MAX_ATTEMPTS, c.FAULTS_RETRY_BASE_DELAY_MS,
                   c.FAULTS_RETRY_MAX_DELAY_MS, c.FAULTS_RETRY_JITTER}
        unknown = set(r) - known_r
        if unknown:
            raise ValueError(
                f"faults.{c.FAULTS_RETRY}: unknown key(s) {sorted(unknown)}; "
                f"expected a subset of {sorted(known_r)}")
        try:
            self.retry_policy = RetryPolicy(
                max_attempts=get_scalar_param(
                    r, c.FAULTS_RETRY_MAX_ATTEMPTS,
                    c.FAULTS_RETRY_MAX_ATTEMPTS_DEFAULT),
                base_delay_ms=get_scalar_param(
                    r, c.FAULTS_RETRY_BASE_DELAY_MS,
                    c.FAULTS_RETRY_BASE_DELAY_MS_DEFAULT),
                max_delay_ms=get_scalar_param(
                    r, c.FAULTS_RETRY_MAX_DELAY_MS,
                    c.FAULTS_RETRY_MAX_DELAY_MS_DEFAULT),
                jitter=get_scalar_param(r, c.FAULTS_RETRY_JITTER,
                                        c.FAULTS_RETRY_JITTER_DEFAULT))
        except ValueError as e:
            raise ValueError(f"faults.{c.FAULTS_RETRY}: {e}")

        w = d.get(c.FAULTS_WATCHDOG) or {}
        known_w = {c.FAULTS_WATCHDOG_ENABLED, c.FAULTS_WATCHDOG_DEADLINE_S,
                   c.FAULTS_WATCHDOG_POLL_S, c.FAULTS_WATCHDOG_SNAPSHOT_DIR,
                   c.FAULTS_WATCHDOG_FIRST_BEAT_MULT}
        unknown = set(w) - known_w
        if unknown:
            raise ValueError(
                f"faults.{c.FAULTS_WATCHDOG}: unknown key(s) "
                f"{sorted(unknown)}; expected a subset of {sorted(known_w)}")
        self.watchdog_enabled = bool(get_scalar_param(
            w, c.FAULTS_WATCHDOG_ENABLED, c.FAULTS_WATCHDOG_ENABLED_DEFAULT))
        self.watchdog_deadline_s = float(get_scalar_param(
            w, c.FAULTS_WATCHDOG_DEADLINE_S,
            c.FAULTS_WATCHDOG_DEADLINE_S_DEFAULT))
        self.watchdog_poll_s = float(get_scalar_param(
            w, c.FAULTS_WATCHDOG_POLL_S, c.FAULTS_WATCHDOG_POLL_S_DEFAULT))
        self.watchdog_snapshot_dir = get_scalar_param(
            w, c.FAULTS_WATCHDOG_SNAPSHOT_DIR, None)
        # grace multiplier on the deadline before the FIRST beat: covers
        # first-step compile — including an elastic restart's recompile
        # at the new mesh shape (StepWatchdog docstring).  An explicit
        # null selects the legacy mode: not armed at all until beat 1.
        fbm = (w[c.FAULTS_WATCHDOG_FIRST_BEAT_MULT]
               if c.FAULTS_WATCHDOG_FIRST_BEAT_MULT in w
               else c.FAULTS_WATCHDOG_FIRST_BEAT_MULT_DEFAULT)
        try:
            self.watchdog_first_beat_mult = (None if fbm is None
                                             else float(fbm))
        except (TypeError, ValueError):
            raise ValueError(
                f"faults.watchdog.{c.FAULTS_WATCHDOG_FIRST_BEAT_MULT} "
                f"must be a number >= 1 or null (null: never armed "
                f"before the first beat), got {fbm!r}")
        if self.watchdog_first_beat_mult is not None and \
                self.watchdog_first_beat_mult < 1.0:
            raise ValueError(
                f"faults.watchdog.{c.FAULTS_WATCHDOG_FIRST_BEAT_MULT} "
                f"must be >= 1 (a sub-1 multiplier would make the "
                f"compile window stricter than steady state), got "
                f"{self.watchdog_first_beat_mult}")
        if self.watchdog_enabled and self.watchdog_deadline_s <= 0:
            raise ValueError(
                f"faults.watchdog.{c.FAULTS_WATCHDOG_DEADLINE_S} must be "
                f"> 0, got {self.watchdog_deadline_s}")
        if self.watchdog_enabled and self.watchdog_poll_s <= 0:
            # poll_s 0 would busy-spin the daemon thread on a core
            raise ValueError(
                f"faults.watchdog.{c.FAULTS_WATCHDOG_POLL_S} must be "
                f"> 0, got {self.watchdog_poll_s}")


class DeepSpeedAutotuneConfig(DeepSpeedConfigObject):
    """The self-tuning runtime (runtime/autotune/).

    "autotune": {"enabled": false, "probe_steps": 2, "probe_warmup": 1,
                 "budget_s": null, "cache_path": null, "ledger_path":
                 null, "apply_winner": true, "min_improvement": 0.03,
                 "wire_dtypes": ["fp32","bf16","int8"],
                 "bucket_sizes": [], "include_overlap": true,
                 "online": {"enabled": false, "window": 5,
                            "baseline_steps": 5, "threshold": 1.5,
                            "exposed_threshold_ms": 0.0,
                            "cooldown_steps": 20, "check_every": 1,
                            "radius": 1, "safe_only": true}}

    `enabled` arms the runtime (engine.autotune_search() probes the
    legal candidate space, winner-cached by (model shape, mesh, fabric)
    fingerprint); `online.enabled` additionally watches every step
    boundary for sustained regression and live-retunes a bounded knob
    neighborhood.  Every knob is validated HERE so a typo fails at
    config time, not inside a probe."""

    def __init__(self, param_dict):
        super().__init__()
        d = param_dict.get(c.AUTOTUNE) or {}
        known = {c.AUTOTUNE_ENABLED, c.AUTOTUNE_PROBE_STEPS,
                 c.AUTOTUNE_PROBE_WARMUP, c.AUTOTUNE_BUDGET_S,
                 c.AUTOTUNE_CACHE_PATH, c.AUTOTUNE_LEDGER_PATH,
                 c.AUTOTUNE_APPLY_WINNER, c.AUTOTUNE_MIN_IMPROVEMENT,
                 c.AUTOTUNE_WIRE_DTYPES, c.AUTOTUNE_BUCKET_SIZES,
                 c.AUTOTUNE_INCLUDE_OVERLAP, c.AUTOTUNE_ONLINE}
        unknown = set(d) - known
        if unknown:
            raise ValueError(
                f"autotune: unknown key(s) {sorted(unknown)}; expected a "
                f"subset of {sorted(known)}")
        self.enabled = bool(get_scalar_param(
            d, c.AUTOTUNE_ENABLED, c.AUTOTUNE_ENABLED_DEFAULT))

        def pos_int(key, default, minimum=1):
            v = get_scalar_param(d, key, default)
            if isinstance(v, bool) or not isinstance(v, int) or v < minimum:
                raise ValueError(
                    f"autotune.{key} must be an int >= {minimum}, got {v!r}")
            return int(v)

        self.probe_steps = pos_int(c.AUTOTUNE_PROBE_STEPS,
                                   c.AUTOTUNE_PROBE_STEPS_DEFAULT)
        self.probe_warmup = pos_int(c.AUTOTUNE_PROBE_WARMUP,
                                    c.AUTOTUNE_PROBE_WARMUP_DEFAULT,
                                    minimum=0)
        budget = get_scalar_param(d, c.AUTOTUNE_BUDGET_S,
                                  c.AUTOTUNE_BUDGET_S_DEFAULT)
        if budget is not None:
            try:
                budget = float(budget)
            except (TypeError, ValueError):
                raise ValueError(
                    f"autotune.{c.AUTOTUNE_BUDGET_S} must be a positive "
                    f"number of seconds or null, got {budget!r}")
            if budget <= 0:
                raise ValueError(
                    f"autotune.{c.AUTOTUNE_BUDGET_S} must be > 0, "
                    f"got {budget}")
        self.budget_s = budget
        for key, attr in ((c.AUTOTUNE_CACHE_PATH, "cache_path"),
                          (c.AUTOTUNE_LEDGER_PATH, "ledger_path")):
            v = get_scalar_param(d, key, None)
            if v is not None and not isinstance(v, str):
                raise ValueError(
                    f"autotune.{key} must be a path string or null, "
                    f"got {v!r}")
            setattr(self, attr, v)
        self.apply_winner = bool(get_scalar_param(
            d, c.AUTOTUNE_APPLY_WINNER, c.AUTOTUNE_APPLY_WINNER_DEFAULT))
        mi = get_scalar_param(d, c.AUTOTUNE_MIN_IMPROVEMENT,
                              c.AUTOTUNE_MIN_IMPROVEMENT_DEFAULT)
        try:
            mi = float(mi)
        except (TypeError, ValueError):
            raise ValueError(
                f"autotune.{c.AUTOTUNE_MIN_IMPROVEMENT} must be a "
                f"fraction in [0, 1), got {mi!r}")
        if not 0.0 <= mi < 1.0:
            raise ValueError(
                f"autotune.{c.AUTOTUNE_MIN_IMPROVEMENT} must be a "
                f"fraction in [0, 1), got {mi}")
        self.min_improvement = mi
        from .comm.bucketing import WIRE_MODES

        wires = d.get(c.AUTOTUNE_WIRE_DTYPES,
                      list(c.AUTOTUNE_WIRE_DTYPES_DEFAULT))
        if not isinstance(wires, (list, tuple)) or not wires or \
                any(str(w).lower() not in WIRE_MODES for w in wires):
            raise ValueError(
                f"autotune.{c.AUTOTUNE_WIRE_DTYPES} must be a non-empty "
                f"list drawn from {WIRE_MODES}, got {wires!r}")
        self.wire_dtypes = tuple(str(w).lower() for w in wires)
        buckets = d.get(c.AUTOTUNE_BUCKET_SIZES,
                        list(c.AUTOTUNE_BUCKET_SIZES_DEFAULT))
        if not isinstance(buckets, (list, tuple)) or any(
                isinstance(b, bool) or not isinstance(b, int) or b < 1
                for b in buckets):
            raise ValueError(
                f"autotune.{c.AUTOTUNE_BUCKET_SIZES} must be a list of "
                f"positive element counts, got {buckets!r}")
        self.bucket_sizes = tuple(int(b) for b in buckets)
        self.include_overlap = bool(get_scalar_param(
            d, c.AUTOTUNE_INCLUDE_OVERLAP,
            c.AUTOTUNE_INCLUDE_OVERLAP_DEFAULT))

        o = d.get(c.AUTOTUNE_ONLINE) or {}
        known_o = {c.AUTOTUNE_ONLINE_ENABLED, c.AUTOTUNE_ONLINE_WINDOW,
                   c.AUTOTUNE_ONLINE_BASELINE_STEPS,
                   c.AUTOTUNE_ONLINE_THRESHOLD,
                   c.AUTOTUNE_ONLINE_EXPOSED_THRESHOLD_MS,
                   c.AUTOTUNE_ONLINE_COOLDOWN_STEPS,
                   c.AUTOTUNE_ONLINE_CHECK_EVERY, c.AUTOTUNE_ONLINE_RADIUS,
                   c.AUTOTUNE_ONLINE_SAFE_ONLY}
        unknown = set(o) - known_o
        if unknown:
            raise ValueError(
                f"autotune.{c.AUTOTUNE_ONLINE}: unknown key(s) "
                f"{sorted(unknown)}; expected a subset of {sorted(known_o)}")

        def online_int(key, default, minimum=1):
            v = get_scalar_param(o, key, default)
            if isinstance(v, bool) or not isinstance(v, int) or v < minimum:
                raise ValueError(
                    f"autotune.online.{key} must be an int >= {minimum}, "
                    f"got {v!r}")
            return int(v)

        self.online_enabled = bool(get_scalar_param(
            o, c.AUTOTUNE_ONLINE_ENABLED, c.AUTOTUNE_ONLINE_ENABLED_DEFAULT))
        self.online_window = online_int(c.AUTOTUNE_ONLINE_WINDOW,
                                        c.AUTOTUNE_ONLINE_WINDOW_DEFAULT)
        self.online_baseline_steps = online_int(
            c.AUTOTUNE_ONLINE_BASELINE_STEPS,
            c.AUTOTUNE_ONLINE_BASELINE_STEPS_DEFAULT)
        thr = get_scalar_param(o, c.AUTOTUNE_ONLINE_THRESHOLD,
                               c.AUTOTUNE_ONLINE_THRESHOLD_DEFAULT)
        try:
            thr = float(thr)
        except (TypeError, ValueError):
            raise ValueError(
                f"autotune.online.{c.AUTOTUNE_ONLINE_THRESHOLD} must be a "
                f"ratio > 1.0, got {thr!r}")
        if thr <= 1.0:
            raise ValueError(
                f"autotune.online.{c.AUTOTUNE_ONLINE_THRESHOLD} must be "
                f"> 1.0 (a ratio over the step-time baseline), got {thr}")
        self.online_threshold = thr
        exp = get_scalar_param(o, c.AUTOTUNE_ONLINE_EXPOSED_THRESHOLD_MS,
                               c.AUTOTUNE_ONLINE_EXPOSED_THRESHOLD_MS_DEFAULT)
        try:
            exp = float(exp)
        except (TypeError, ValueError):
            raise ValueError(
                f"autotune.online.{c.AUTOTUNE_ONLINE_EXPOSED_THRESHOLD_MS} "
                f"must be a millisecond count >= 0 (0 disables), got {exp!r}")
        if exp < 0:
            raise ValueError(
                f"autotune.online.{c.AUTOTUNE_ONLINE_EXPOSED_THRESHOLD_MS} "
                f"must be >= 0 (0 disables the exposed trigger), got {exp}")
        self.online_exposed_threshold_ms = exp
        self.online_cooldown_steps = online_int(
            c.AUTOTUNE_ONLINE_COOLDOWN_STEPS,
            c.AUTOTUNE_ONLINE_COOLDOWN_STEPS_DEFAULT, minimum=0)
        self.online_check_every = online_int(
            c.AUTOTUNE_ONLINE_CHECK_EVERY,
            c.AUTOTUNE_ONLINE_CHECK_EVERY_DEFAULT)
        self.online_radius = online_int(c.AUTOTUNE_ONLINE_RADIUS,
                                        c.AUTOTUNE_ONLINE_RADIUS_DEFAULT)
        self.online_safe_only = bool(get_scalar_param(
            o, c.AUTOTUNE_ONLINE_SAFE_ONLY,
            c.AUTOTUNE_ONLINE_SAFE_ONLY_DEFAULT))


# accepted serving.kv_dtype spellings; must stay a superset of what
# serving.kv_cache.resolve_kv_dtype() resolves (kept local so the
# training-side config never imports the jax-heavy serving package)
SERVING_KV_DTYPES = ("bf16", "bfloat16", "fp16", "float16", "fp32",
                     "float32", "int8", "int4")


class DeepSpeedServingConfig(DeepSpeedConfigObject):
    """Inference-side knobs (deepspeed_tpu.serving).

    "serving": {"kv_dtype": null,
                "speculative": {"enabled": false, "draft_len": 4,
                                "ngram": 3},
                "prefix_cache": {"enabled": true, "min_match_blocks": 1,
                                 "session_ttl_s": 120.0},
                "fleet": {"replicas": 1, "queue_limit": 64,
                          "session_affinity": true}}

    `kv_dtype` selects the paged KV cache's storage mode: null stores
    at the param dtype; "bf16"/"fp16"/"fp32" store dense at that dtype;
    "int8"/"int4" store per-(row, head) quantized payload + fp16 scale
    pairs (runtime/comm/quant.py row kernels).  `speculative.enabled`
    arms self-speculative n-gram decoding: `draft_len` candidate tokens
    drafted host-side per verify step by an `ngram`-suffix match over
    the request's own context (no extra model).  `prefix_cache` governs
    block-level KV sharing (serving/kv_cache.py chain hashes) and the
    pinned-session residency window; `fleet` sizes the multi-replica
    router (serving/router.py).  Every knob is validated HERE so a typo
    fails at config time, not mid-serve; the autotuner's "serve" scope
    re-validates its candidate fragments through this class so the
    search space can never propose an illegal config."""

    def __init__(self, param_dict):
        super().__init__()
        d = param_dict.get(c.SERVING) or {}
        known = {c.SERVING_KV_DTYPE, c.SERVING_SPECULATIVE,
                 c.SERVING_PREFIX_CACHE, c.SERVING_FLEET}
        unknown = set(d) - known
        if unknown:
            raise ValueError(
                f"serving: unknown key(s) {sorted(unknown)}; expected a "
                f"subset of {sorted(known)}")
        kv = get_scalar_param(d, c.SERVING_KV_DTYPE,
                              c.SERVING_KV_DTYPE_DEFAULT)
        if kv is not None:
            if not isinstance(kv, str) or \
                    kv.lower() not in SERVING_KV_DTYPES:
                raise ValueError(
                    f"serving.{c.SERVING_KV_DTYPE} must be null or one of "
                    f"{SERVING_KV_DTYPES}, got {kv!r}")
            kv = kv.lower()
        self.kv_dtype = kv

        s = d.get(c.SERVING_SPECULATIVE) or {}
        known_s = {c.SERVING_SPEC_ENABLED, c.SERVING_SPEC_DRAFT_LEN,
                   c.SERVING_SPEC_NGRAM}
        unknown = set(s) - known_s
        if unknown:
            raise ValueError(
                f"serving.{c.SERVING_SPECULATIVE}: unknown key(s) "
                f"{sorted(unknown)}; expected a subset of {sorted(known_s)}")
        self.spec_enabled = bool(get_scalar_param(
            s, c.SERVING_SPEC_ENABLED, c.SERVING_SPEC_ENABLED_DEFAULT))

        def spec_int(key, default, minimum=1):
            v = get_scalar_param(s, key, default)
            if isinstance(v, bool) or not isinstance(v, int) or v < minimum:
                raise ValueError(
                    f"serving.speculative.{key} must be an int >= "
                    f"{minimum}, got {v!r}")
            return int(v)

        self.spec_draft_len = spec_int(c.SERVING_SPEC_DRAFT_LEN,
                                       c.SERVING_SPEC_DRAFT_LEN_DEFAULT)
        self.spec_ngram = spec_int(c.SERVING_SPEC_NGRAM,
                                   c.SERVING_SPEC_NGRAM_DEFAULT)

        p = d.get(c.SERVING_PREFIX_CACHE) or {}
        known_p = {c.SERVING_PREFIX_ENABLED,
                   c.SERVING_PREFIX_MIN_MATCH_BLOCKS,
                   c.SERVING_PREFIX_SESSION_TTL_S}
        unknown = set(p) - known_p
        if unknown:
            raise ValueError(
                f"serving.{c.SERVING_PREFIX_CACHE}: unknown key(s) "
                f"{sorted(unknown)}; expected a subset of {sorted(known_p)}")
        self.prefix_enabled = bool(get_scalar_param(
            p, c.SERVING_PREFIX_ENABLED, c.SERVING_PREFIX_ENABLED_DEFAULT))
        mm = get_scalar_param(p, c.SERVING_PREFIX_MIN_MATCH_BLOCKS,
                              c.SERVING_PREFIX_MIN_MATCH_BLOCKS_DEFAULT)
        if isinstance(mm, bool) or not isinstance(mm, int) or mm < 1:
            raise ValueError(
                f"serving.prefix_cache.{c.SERVING_PREFIX_MIN_MATCH_BLOCKS} "
                f"must be an int >= 1, got {mm!r}")
        self.prefix_min_match_blocks = int(mm)
        ttl = get_scalar_param(p, c.SERVING_PREFIX_SESSION_TTL_S,
                               c.SERVING_PREFIX_SESSION_TTL_S_DEFAULT)
        try:
            ttl = float(ttl)
        except (TypeError, ValueError):
            ttl = -1.0
        if ttl <= 0:
            raise ValueError(
                f"serving.prefix_cache.{c.SERVING_PREFIX_SESSION_TTL_S} "
                f"must be a second count > 0, got "
                f"{p.get(c.SERVING_PREFIX_SESSION_TTL_S)!r}")
        self.session_ttl_s = ttl

        f = d.get(c.SERVING_FLEET) or {}
        known_f = {c.SERVING_FLEET_REPLICAS, c.SERVING_FLEET_QUEUE_LIMIT,
                   c.SERVING_FLEET_SESSION_AFFINITY}
        unknown = set(f) - known_f
        if unknown:
            raise ValueError(
                f"serving.{c.SERVING_FLEET}: unknown key(s) "
                f"{sorted(unknown)}; expected a subset of {sorted(known_f)}")

        def fleet_int(key, default):
            v = get_scalar_param(f, key, default)
            if isinstance(v, bool) or not isinstance(v, int) or v < 1:
                raise ValueError(
                    f"serving.fleet.{key} must be an int >= 1, got {v!r}")
            return int(v)

        self.fleet_replicas = fleet_int(c.SERVING_FLEET_REPLICAS,
                                        c.SERVING_FLEET_REPLICAS_DEFAULT)
        self.fleet_queue_limit = fleet_int(
            c.SERVING_FLEET_QUEUE_LIMIT, c.SERVING_FLEET_QUEUE_LIMIT_DEFAULT)
        self.fleet_session_affinity = bool(get_scalar_param(
            f, c.SERVING_FLEET_SESSION_AFFINITY,
            c.SERVING_FLEET_SESSION_AFFINITY_DEFAULT))

    def to_serve_kwargs(self):
        """The ServeConfig fragment this block selects: feed as
        `ServeConfig(**cfg.serving_config.to_serve_kwargs(), ...)`.
        Disabled speculation maps to draft_len=0 (the engine's plain
        decode path), not a missing key, so the serve-scope autotuner
        can diff candidate fragments field-for-field."""
        return {
            "kv_dtype": self.kv_dtype,
            "draft_len": self.spec_draft_len if self.spec_enabled else 0,
            "spec_ngram": self.spec_ngram,
            "prefix_cache": self.prefix_enabled,
            "prefix_min_match_blocks": self.prefix_min_match_blocks,
            "session_ttl_s": self.session_ttl_s,
        }

    def to_fleet_kwargs(self):
        """The FleetRouter sizing this block selects: feed as
        `FleetRouter(build_fleet(..., replicas=k['replicas']),
        queue_limit=k['queue_limit'], ...)`."""
        return {
            "replicas": self.fleet_replicas,
            "queue_limit": self.fleet_queue_limit,
            "session_affinity": self.fleet_session_affinity,
        }


def get_fp16_enabled(param_dict):
    return get_scalar_param(param_dict.get(c.FP16, {}), c.FP16_ENABLED,
                            c.FP16_ENABLED_DEFAULT)


def get_precision(param_dict):
    """Return the compute dtype name. Two spellings are accepted: the
    EleutherAI fork's fp16 section with "type": "bfloat16" (reference
    runtime/constants.py:127-161, engine.py:613-620), and the top-level
    `{"bf16": {"enabled": true}}` section of later DeepSpeed versions —
    the latter was previously IGNORED (silently training in fp32)."""
    bf16 = param_dict.get("bf16", param_dict.get("bfloat16", {})) or {}
    if get_scalar_param(bf16, c.FP16_ENABLED, False):
        if get_fp16_enabled(param_dict):
            raise DeepSpeedConfigError(
                "bf16 and fp16 cannot both be enabled")
        return "bfloat16"
    if not get_fp16_enabled(param_dict):
        return "float32"
    raw = get_scalar_param(param_dict.get(c.FP16, {}), c.FP16_TYPE,
                           c.FP16_TYPE_DEFAULT)
    if raw not in TORCH_DTYPES:
        raise DeepSpeedConfigError(
            f"fp16.type must be one of {sorted(set(TORCH_DTYPES))}, got {raw!r}")
    return TORCH_DTYPES[raw]


class DeepSpeedConfig(DeepSpeedConfigObject):
    def __init__(self, json_file_or_dict, mpu=None, param_dict=None,
                 world_size=None):
        super().__init__()
        if param_dict is not None:
            self._param_dict = param_dict
        elif isinstance(json_file_or_dict, dict):
            self._param_dict = json_file_or_dict
        elif isinstance(json_file_or_dict, str):
            try:
                with open(json_file_or_dict) as f:
                    self._param_dict = json.load(
                        f, object_pairs_hook=dict_raise_error_on_duplicate_keys)
            except FileNotFoundError:
                raise DeepSpeedConfigError(
                    f"DeepSpeed config file not found: {json_file_or_dict}")
        else:
            raise DeepSpeedConfigError(
                "config must be a dict or a path to a json file, got "
                f"{type(json_file_or_dict)}")

        # world size for the batch triple: dp size (reference uses dist world
        # / mp size; here it's device_count / (model*pipe*seq axes))
        if world_size is not None:
            self.world_size = int(world_size)
        elif mpu is not None:
            self.world_size = int(mpu.get_data_parallel_world_size())
        else:
            self.world_size = self._infer_dp_world_size()

        # Elasticity resolves the batch triple before parsing it
        # (reference runtime/config.py:537-614).
        self.elasticity_enabled = elasticity_enabled(self._param_dict)
        if self.elasticity_enabled:
            elastic_dict = self._param_dict[ec.ELASTICITY]
            ensure_immutable_elastic_config(elastic_dict)
            final_batch_size, valid_gpus, micro_batch = compute_elastic_config(
                self._param_dict, world_size=self.world_size)
            self.elastic_valid_world_sizes = valid_gpus
            ignore = elastic_dict.get(ec.IGNORE_NON_ELASTIC_BATCH_INFO,
                                      ec.IGNORE_NON_ELASTIC_BATCH_INFO_DEFAULT)
            batch_keys = (c.TRAIN_BATCH_SIZE, c.TRAIN_MICRO_BATCH_SIZE_PER_GPU,
                          c.GRADIENT_ACCUMULATION_STEPS)
            if not ignore and any(k in self._param_dict for k in batch_keys):
                raise ElasticityConfigError(
                    f"batch size keys {batch_keys} must not be set when "
                    f"elasticity is enabled (set "
                    f"'{ec.IGNORE_NON_ELASTIC_BATCH_INFO}': true to override)")
            self._param_dict = dict(self._param_dict)
            self._param_dict[c.TRAIN_BATCH_SIZE] = final_batch_size
            self._param_dict[c.TRAIN_MICRO_BATCH_SIZE_PER_GPU] = micro_batch
            self._param_dict[c.GRADIENT_ACCUMULATION_STEPS] = (
                final_batch_size // (micro_batch * self.world_size))

        self._initialize_params(self._param_dict)
        self._configure_train_batch_size()
        self._do_sanity_check()

    def _infer_dp_world_size(self):
        mesh_dict = self._param_dict.get(c.MESH) or {}
        try:
            import jax

            n = jax.device_count()
        except Exception:
            n = 1
        non_dp = 1
        for axis in ("model", "pipe", "seq"):
            non_dp *= max(1, int(mesh_dict.get(axis, 1)))
        dp = mesh_dict.get("data", -1)
        if dp in (-1, None):
            dp = max(1, n // non_dp)
        return int(dp)

    # -- parsing ----------------------------------------------------------

    def _initialize_params(self, pd):
        if "kernels" in pd:
            raise DeepSpeedConfigError(
                "unknown config block 'kernels': which kernel a call runs "
                "is decided per call by deepspeed_tpu/kernels/registry.py "
                "(a model config's attn_impl, or DS_KERNEL_<OP>=0 in the "
                "environment, overrides it)")
        self.train_batch_size = get_scalar_param(pd, c.TRAIN_BATCH_SIZE,
                                                 c.TRAIN_BATCH_SIZE_DEFAULT)
        self.train_micro_batch_size_per_gpu = get_scalar_param(
            pd, c.TRAIN_MICRO_BATCH_SIZE_PER_GPU,
            c.TRAIN_MICRO_BATCH_SIZE_PER_GPU_DEFAULT)
        self.gradient_accumulation_steps = get_scalar_param(
            pd, c.GRADIENT_ACCUMULATION_STEPS,
            c.GRADIENT_ACCUMULATION_STEPS_DEFAULT)
        self.steps_per_print = get_scalar_param(pd, c.STEPS_PER_PRINT,
                                                c.STEPS_PER_PRINT_DEFAULT)
        self.dump_state = get_scalar_param(pd, c.DUMP_STATE, c.DUMP_STATE_DEFAULT)
        self.disable_allgather = get_scalar_param(pd, c.DISABLE_ALLGATHER,
                                                  c.DISABLE_ALLGATHER_DEFAULT)

        self.gradient_clipping = get_scalar_param(pd, c.GRADIENT_CLIPPING,
                                                  c.GRADIENT_CLIPPING_DEFAULT)
        self.sparse_gradients_enabled = get_scalar_param(
            pd, c.SPARSE_GRADIENTS, c.SPARSE_GRADIENTS_DEFAULT)
        self.prescale_gradients = get_scalar_param(pd, c.PRESCALE_GRADIENTS,
                                                   c.PRESCALE_GRADIENTS_DEFAULT)
        self.gradient_predivide_factor = get_scalar_param(
            pd, c.GRADIENT_PREDIVIDE_FACTOR, c.GRADIENT_PREDIVIDE_FACTOR_DEFAULT)

        self.zero_config = DeepSpeedZeroConfig(pd)
        self.zero_optimization_stage = self.zero_config.stage
        self.zero_enabled = self.zero_optimization_stage > 0

        # gradient-reduction wire (runtime/comm/bucketing.py)
        self.comm_config = DeepSpeedCommConfig(pd, self.zero_config,
                                               world_size=self.world_size)

        # async input pipeline (runtime/dataloader.py PrefetchLoader +
        # engine._DeviceFeed) — default ON
        self.data_pipeline_config = DeepSpeedDataPipelineConfig(pd)

        # chaos-ready runtime: fault injection + retry + watchdog
        # (runtime/resilience.py)
        self.faults_config = DeepSpeedFaultsConfig(pd)

        # the self-tuning runtime (runtime/autotune/): fingerprinted
        # config search + the online retune loop
        self.autotune_config = DeepSpeedAutotuneConfig(pd)

        # inference-side knobs (deepspeed_tpu.serving): KV cache storage
        # dtype + self-speculative decoding — the autotuner's "serve"
        # scope searches this block
        self.serving_config = DeepSpeedServingConfig(pd)

        # pipeline: use_p2p_channels forces the multi-host channel
        # executor even single-process (the driver's virtual-multichip
        # dryrun runs the real cross-process code path this way)
        self.pipe_use_p2p_channels = bool(
            (pd.get("pipeline") or {}).get("use_p2p_channels", False))
        # debug_schedule selects the per-event interpreted schedule walk
        # (the parity oracle / bring-up executor) instead of the default
        # precompiled flat program (runtime/pipe/compiler.py)
        self.pipe_debug_schedule = bool(
            (pd.get("pipeline") or {}).get("debug_schedule", False))

        self.activation_checkpointing_config = \
            DeepSpeedActivationCheckpointingConfig(pd)
        self.flops_profiler_config = DeepSpeedFlopsProfilerConfig(pd)

        # precision
        self.fp16_enabled = get_fp16_enabled(pd)
        self.precision = get_precision(pd)
        fp16_dict = pd.get(c.FP16, {})
        self.loss_scale = get_scalar_param(fp16_dict, c.FP16_LOSS_SCALE,
                                           c.FP16_LOSS_SCALE_DEFAULT)
        self.initial_scale_power = get_scalar_param(
            fp16_dict, c.FP16_INITIAL_SCALE_POWER,
            c.FP16_INITIAL_SCALE_POWER_DEFAULT)
        self.loss_scale_window = get_scalar_param(
            fp16_dict, c.FP16_LOSS_SCALE_WINDOW, c.FP16_LOSS_SCALE_WINDOW_DEFAULT)
        self.hysteresis = get_scalar_param(fp16_dict, c.FP16_HYSTERESIS,
                                           c.FP16_HYSTERESIS_DEFAULT)
        self.min_loss_scale = get_scalar_param(fp16_dict, c.FP16_MIN_LOSS_SCALE,
                                               c.FP16_MIN_LOSS_SCALE_DEFAULT)
        self.amp_enabled = get_scalar_param(pd.get(c.AMP, {}), c.AMP_ENABLED,
                                            c.AMP_ENABLED_DEFAULT)
        self.amp_params = {k: v for k, v in pd.get(c.AMP, {}).items()
                           if k != c.AMP_ENABLED}

        # optimizer / scheduler
        opt_dict = pd.get(c.OPTIMIZER, None)
        self.optimizer_name = (opt_dict.get(c.TYPE).lower()
                               if opt_dict and opt_dict.get(c.TYPE) else None)
        self.optimizer_params = (opt_dict.get(c.OPTIMIZER_PARAMS, {})
                                 if opt_dict else None)
        self.optimizer_legacy_fusion = (get_scalar_param(
            opt_dict, c.LEGACY_FUSION, c.LEGACY_FUSION_DEFAULT)
            if opt_dict else c.LEGACY_FUSION_DEFAULT)
        self.zero_allow_untested_optimizer = get_scalar_param(
            pd, c.ZERO_ALLOW_UNTESTED_OPTIMIZER,
            c.ZERO_ALLOW_UNTESTED_OPTIMIZER_DEFAULT)

        sched_dict = pd.get(c.SCHEDULER, None)
        self.scheduler_name = sched_dict.get(c.TYPE) if sched_dict else None
        self.scheduler_params = (sched_dict.get(c.SCHEDULER_PARAMS, {})
                                 if sched_dict else None)

        # observability
        self.wall_clock_breakdown = get_scalar_param(
            pd, c.WALL_CLOCK_BREAKDOWN, c.WALL_CLOCK_BREAKDOWN_DEFAULT)
        self.memory_breakdown = get_scalar_param(pd, c.MEMORY_BREAKDOWN,
                                                 c.MEMORY_BREAKDOWN_DEFAULT)
        # structured run telemetry (monitor/): JSONL event stream,
        # profiler capture window, heartbeats — TensorBoard is one sink
        self.monitor_config = DeepSpeedMonitorConfig(pd)
        tb = pd.get(c.TENSORBOARD, {})
        self.tensorboard_enabled = get_scalar_param(tb, c.TENSORBOARD_ENABLED,
                                                    c.TENSORBOARD_ENABLED_DEFAULT)
        self.tensorboard_output_path = get_scalar_param(
            tb, c.TENSORBOARD_OUTPUT_PATH, c.TENSORBOARD_OUTPUT_PATH_DEFAULT)
        self.tensorboard_job_name = get_scalar_param(
            tb, c.TENSORBOARD_JOB_NAME, c.TENSORBOARD_JOB_NAME_DEFAULT)

        # progressive layer drop
        pld = pd.get(c.PROGRESSIVE_LAYER_DROP, {})
        self.pld_enabled = get_scalar_param(pld, c.PLD_ENABLED, c.PLD_ENABLED_DEFAULT)
        self.pld_params = ({c.PLD_THETA: get_scalar_param(pld, c.PLD_THETA,
                                                          c.PLD_THETA_DEFAULT),
                            c.PLD_GAMMA: get_scalar_param(pld, c.PLD_GAMMA,
                                                          c.PLD_GAMMA_DEFAULT)}
                           if self.pld_enabled else False)

        ckpt = pd.get(c.CHECKPOINT, {})
        self.checkpoint_tag_validation_mode = str(get_scalar_param(
            ckpt, c.CHECKPOINT_TAG_VALIDATION,
            c.CHECKPOINT_TAG_VALIDATION_DEFAULT)).lower()
        self.checkpoint_tag_validation_enabled = \
            self.checkpoint_tag_validation_mode != "ignore"
        self.checkpoint_tag_validation_fail = \
            self.checkpoint_tag_validation_mode == "fail"
        # TPU addition: overlap checkpoint serialization with training
        # (serialize+write+commit land on background threads; the commit
        # marker and 'latest' update last — runtime/checkpointing.py)
        self.checkpoint_async_save = bool(get_scalar_param(
            ckpt, c.CHECKPOINT_ASYNC_SAVE, c.CHECKPOINT_ASYNC_SAVE_DEFAULT))
        self.checkpoint_commit_timeout_ms = int(get_scalar_param(
            ckpt, c.CHECKPOINT_COMMIT_TIMEOUT_MS,
            c.CHECKPOINT_COMMIT_TIMEOUT_MS_DEFAULT))
        if self.checkpoint_commit_timeout_ms <= 0:
            raise ValueError(
                f"checkpoint.{c.CHECKPOINT_COMMIT_TIMEOUT_MS} must be a "
                f"positive millisecond count, got "
                f"{self.checkpoint_commit_timeout_ms}")
        # SIGTERM = save-if-possible (elasticity/supervisor.py): a set
        # preempt_save_dir arms the engine's signal handler — emergency
        # checkpoint at the next step boundary, then a clean exit
        preempt = get_scalar_param(ckpt, c.CHECKPOINT_PREEMPT_SAVE_DIR,
                                   c.CHECKPOINT_PREEMPT_SAVE_DIR_DEFAULT)
        if preempt is not None and not isinstance(preempt, str):
            raise ValueError(
                f"checkpoint.{c.CHECKPOINT_PREEMPT_SAVE_DIR} must be a "
                f"directory path string or null, got {preempt!r}")
        self.checkpoint_preempt_save_dir = preempt

        self.sparse_attention = pd.get(c.SPARSE_ATTENTION, None)
        self.vocabulary_size = get_scalar_param(pd, c.VOCABULARY_SIZE,
                                                c.VOCABULARY_SIZE_DEFAULT)

        # TPU additions
        self.mesh_shape = pd.get(c.MESH, c.MESH_DEFAULT)

    # -- batch triple (reference config.py:681-752) -----------------------

    def _configure_train_batch_size(self):
        self._set_batch_related_parameters()
        self._batch_assertion()

    def _set_batch_related_parameters(self):
        train_batch = self.train_batch_size
        micro_batch = self.train_micro_batch_size_per_gpu
        grad_acc = self.gradient_accumulation_steps
        dp = self.world_size

        if all(x is not None for x in (train_batch, micro_batch, grad_acc)):
            pass
        elif train_batch is not None and micro_batch is not None:
            grad_acc = train_batch // micro_batch
            grad_acc //= dp
            self.gradient_accumulation_steps = grad_acc
        elif train_batch is not None and grad_acc is not None:
            micro_batch = train_batch // dp
            micro_batch //= grad_acc
            self.train_micro_batch_size_per_gpu = micro_batch
        elif micro_batch is not None and grad_acc is not None:
            self.train_batch_size = micro_batch * grad_acc * dp
        elif train_batch is not None:
            self.gradient_accumulation_steps = 1
            self.train_micro_batch_size_per_gpu = train_batch // dp
        elif micro_batch is not None:
            self.train_batch_size = micro_batch * dp
            self.gradient_accumulation_steps = 1
        else:
            raise DeepSpeedConfigError(
                "Either train_batch_size or train_micro_batch_size_per_gpu "
                "needs to be provided")

    def _batch_assertion(self):
        train_batch = self.train_batch_size
        micro_batch = self.train_micro_batch_size_per_gpu
        grad_acc = self.gradient_accumulation_steps
        dp = self.world_size
        if not (train_batch > 0 and micro_batch > 0 and grad_acc > 0):
            raise DeepSpeedConfigError(
                f"batch sizes must be positive: train_batch_size={train_batch}, "
                f"micro_batch={micro_batch}, grad_acc={grad_acc}")
        if train_batch != micro_batch * grad_acc * dp:
            raise DeepSpeedConfigError(
                f"Check batch related parameters: train_batch_size={train_batch} "
                f"is not equal to micro_batch_per_gpu({micro_batch}) * "
                f"gradient_acc_steps({grad_acc}) * world_size({dp})")

    # -- sanity (reference config.py _do_sanity_check) --------------------

    def _do_sanity_check(self):
        if self.optimizer_name is not None and self.zero_enabled:
            if (self.optimizer_name not in c.DEEPSPEED_OPTIMIZERS and
                    not self.zero_allow_untested_optimizer):
                logger.warning(
                    f"optimizer '{self.optimizer_name}' is untested with ZeRO; "
                    f"set '{c.ZERO_ALLOW_UNTESTED_OPTIMIZER}': true to silence")
        if self.zero_config.stage == 2 and not self.fp16_enabled:
            # reference requires fp16 for ZeRO>0; bf16/fp32 work fine on TPU,
            # keep a log line for parity awareness only
            logger.debug("ZeRO-2 without reduced precision (allowed on TPU)")

    def print(self, name="DeepSpeedConfig"):
        logger.info(f"{name}:")
        for k in sorted(self.__dict__):
            if not k.startswith("_"):
                logger.info(f"  {k} = {self.__dict__[k]}")
