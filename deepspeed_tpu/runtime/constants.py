"""JSON config keys + defaults.

The JSON schema is API surface shared with the reference
(/root/reference/deepspeed/runtime/constants.py) so user configs port
unchanged. TPU-specific additions are marked; CUDA-only knobs are accepted
and treated as no-ops by the engine.
"""

#############################################
# Routes (reference constants.py ROUTE_*)
#############################################
ROUTE_TRAIN = "train"
ROUTE_EVAL = "eval"
ROUTE_PREDICT = "predict"
ROUTE_ENCODE = "encode"

#############################################
# Batch size triple
#############################################
TRAIN_BATCH_SIZE = "train_batch_size"
TRAIN_BATCH_SIZE_DEFAULT = None
TRAIN_MICRO_BATCH_SIZE_PER_GPU = "train_micro_batch_size_per_gpu"
TRAIN_MICRO_BATCH_SIZE_PER_GPU_DEFAULT = None
GRADIENT_ACCUMULATION_STEPS = "gradient_accumulation_steps"
GRADIENT_ACCUMULATION_STEPS_DEFAULT = None

#############################################
# Optimizer / scheduler
#############################################
OPTIMIZER = "optimizer"
OPTIMIZER_TYPE_DEFAULT = None
OPTIMIZER_PARAMS = "params"
TYPE = "type"
LEGACY_FUSION = "legacy_fusion"
LEGACY_FUSION_DEFAULT = False
SCHEDULER = "scheduler"
SCHEDULER_TYPE_DEFAULT = None
SCHEDULER_PARAMS = "params"
MAX_GRAD_NORM = "max_grad_norm"

ADAM_OPTIMIZER = "adam"
LAMB_OPTIMIZER = "lamb"
ONEBIT_ADAM_OPTIMIZER = "onebitadam"
ONEBIT_LAMB_OPTIMIZER = "onebitlamb"
DEEPSPEED_OPTIMIZERS = [ADAM_OPTIMIZER, LAMB_OPTIMIZER, ONEBIT_ADAM_OPTIMIZER,
                        ONEBIT_LAMB_OPTIMIZER]
# optimizer params key (reference fp16/onebit + adam configs)
ADAM_W_MODE = "adam_w_mode"
ADAM_W_MODE_DEFAULT = True

ZERO_ALLOW_UNTESTED_OPTIMIZER = "zero_allow_untested_optimizer"
ZERO_ALLOW_UNTESTED_OPTIMIZER_DEFAULT = False

#############################################
# Misc engine knobs
#############################################
STEPS_PER_PRINT = "steps_per_print"
STEPS_PER_PRINT_DEFAULT = 10
DISABLE_ALLGATHER = "disable_allgather"
DISABLE_ALLGATHER_DEFAULT = False
DUMP_STATE = "dump_state"
DUMP_STATE_DEFAULT = False
VOCABULARY_SIZE = "vocabulary_size"
VOCABULARY_SIZE_DEFAULT = None
WALL_CLOCK_BREAKDOWN = "wall_clock_breakdown"
WALL_CLOCK_BREAKDOWN_DEFAULT = False
MEMORY_BREAKDOWN = "memory_breakdown"
MEMORY_BREAKDOWN_DEFAULT = False

#############################################
# Gradient handling
#############################################
GRADIENT_CLIPPING = "gradient_clipping"
GRADIENT_CLIPPING_DEFAULT = 0.0
SPARSE_GRADIENTS = "sparse_gradients"
SPARSE_GRADIENTS_DEFAULT = False
PRESCALE_GRADIENTS = "prescale_gradients"
PRESCALE_GRADIENTS_DEFAULT = False
GRADIENT_PREDIVIDE_FACTOR = "gradient_predivide_factor"
GRADIENT_PREDIVIDE_FACTOR_DEFAULT = 1.0

#############################################
# Gradient-reduction wire (TPU-specific addition; see
# runtime/comm/bucketing.py and docs/tutorials/comm_tuning.md).
# FP32_ALLREDUCE is the reference key (engine fp32_allreduce option):
# when true the wire dtype is forced to fp32 regardless of COMM_WIRE_DTYPE.
#############################################
COMM = "comm"
COMM_GRADIENT_REDUCTION = "gradient_reduction"
COMM_GRADIENT_REDUCTION_DEFAULT = "implicit"  # or "bucketed"
COMM_GRADIENT_REDUCTION_MODES = ("implicit", "bucketed")
COMM_WIRE_DTYPE = "wire_dtype"
COMM_WIRE_DTYPE_DEFAULT = "fp32"  # fp32 | bf16 | split | int8 | int4
COMM_REDUCE_BUCKET_SIZE = "reduce_bucket_size"  # elements; falls back to
                                                # zero_optimization's knob
# Two-level (intra/inter fabric) reduction over a factored data axis:
#   "hierarchy": "none" | "auto" | <outer int> | {"outer": <int>}
# "auto" derives one outer group per jax process; an explicit outer must
# divide the dp size.  Only meaningful with gradient_reduction=bucketed.
COMM_HIERARCHY = "hierarchy"
COMM_HIERARCHY_DEFAULT = "none"
# Per-level wire overrides (default: wire_dtype for both levels; the
# inner level is scatter-structured, so the gather-structured wires
# (split/int8/int4) cannot run there — an explicit quantized inner
# request is a ValueError, an inherited one lowers to fp32).
COMM_WIRE_DTYPE_INNER = "wire_dtype_inner"
COMM_WIRE_DTYPE_OUTER = "wire_dtype_outer"
# Blockwise quantization granularity for the int8/int4 wires and the
# qwZ parameter gather: elements per fp16 scale (positive even int).
COMM_QUANT_BLOCK_SIZE = "quant_block_size"
COMM_QUANT_BLOCK_SIZE_DEFAULT = 256
# Comm/compute overlap (runtime/comm/overlap.py + step_builder.py):
#   "none"  serial wire (default)
#   "auto"  overlap where the engine can serve it (bucketed wire at
#           stage<3, qwZ gather at stage 3), logged fallback otherwise
#   true / "on"  demand overlap; unservable configs (onebit, Infinity,
#           offload, pipe-parallel stages, no overlappable wire) fall
#           back to the serial path with a WARNING — never silently
COMM_OVERLAP = "overlap"
COMM_OVERLAP_DEFAULT = "none"
COMM_OVERLAP_MODES = ("none", "auto", "on")
# How long a step may block on one in-flight exchange before the wait
# fails (ExchangeTicket deadline).  Size BELOW the StepWatchdog
# deadline (faults.watchdog.deadline_s, default 600 s): the ticket
# timeout is the named, actionable failure — the watchdog's stack
# snapshot is the backstop for hangs nobody sized a deadline for.
COMM_OVERLAP_TIMEOUT_MS = "overlap_timeout_ms"
COMM_OVERLAP_TIMEOUT_MS_DEFAULT = 300_000
# Self-healing budget for a dropped exchange connection: dial attempts
# with bounded exponential backoff (0 = never reconnect, go straight
# to the KV fallback + coordinated demotion), and the TOTAL time
# budget on both sides — the dialer's whole redial loop and the
# accepting side's wait for the peer's re-dial are each bounded by the
# window, so keep it below overlap_timeout_ms: a blackholed peer must
# reach the KV fallback before an in-flight ticket deadline fires.
COMM_OVERLAP_RECONNECT_ATTEMPTS = "overlap_reconnect_attempts"
COMM_OVERLAP_RECONNECT_ATTEMPTS_DEFAULT = 8
COMM_OVERLAP_RECONNECT_WINDOW_MS = "overlap_reconnect_window_ms"
COMM_OVERLAP_RECONNECT_WINDOW_MS_DEFAULT = 60_000
# Sender-worker keepalive cadence: a dead connection surfaces within
# ~one interval even between submits (idle wires otherwise only learn
# about a dead peer at the next data frame).
COMM_OVERLAP_KEEPALIVE_MS = "overlap_keepalive_ms"
COMM_OVERLAP_KEEPALIVE_MS_DEFAULT = 5_000
FP32_ALLREDUCE = "fp32_allreduce"
FP32_ALLREDUCE_DEFAULT = False
# MoE token movement (moe/dispatch.py; validated by parse_moe_config —
# every key is rejected at config time naming the key + valid set):
#   "moe": {
#     "dispatch": "dense" | "sorted",   # default dense (the seed path);
#                                       # defaults to sorted when an a2a
#                                       # wire dtype is requested
#     "a2a_wire_dtype": null | "fp32" | "bf16" | "int8" | "int4",
#                      # null = exchange left implicit to XLA; a dtype
#                      # selects the EXPLICIT shard_map all-to-all wire
#     "a2a_wire_dtype_inner": ...,      # per-level overrides on a
#     "a2a_wire_dtype_outer": ...,      # factored (hierarchical) mesh
#     "placement": "auto" | "data" | "inner",
#                      # "inner" pins experts to data_inner (replicated
#                      # across outer groups): the exchange never leaves
#                      # the fast fabric.  "auto" = inner when factored.
#     "dropless": false,                # second-pass overflow bucket
#     "overflow_factor": 0.25,          # bucket = ceil(f * k * tokens)
#     "quant_block_size": <even int>,   # default: comm.quant_block_size
#     "overlap": "none" | "auto" | "on",  # accepted; falls back LOGGED
#     "counters": true                  # moe.* callback counters
#   }
COMM_MOE = "moe"

#############################################
# Async input pipeline (TPU-specific addition; see runtime/dataloader.py
# PrefetchLoader, engine._DeviceFeed and docs/tutorials/data_pipeline.md).
# Default ON: host collate runs on background thread(s) and batch N+1's
# H2D transfer overlaps step N's compute.  The batch stream and loss
# curve are byte-identical with the pipeline off (pinned in
# tests/test_data_pipeline.py).
#############################################
DATA_PIPELINE = "data_pipeline"
DATA_PIPELINE_ENABLED = "enabled"
DATA_PIPELINE_ENABLED_DEFAULT = True
DATA_PIPELINE_PREFETCH_DEPTH = "prefetch_depth"   # bounded-queue batches
DATA_PIPELINE_PREFETCH_DEPTH_DEFAULT = 2
DATA_PIPELINE_NUM_WORKERS = "num_workers"         # parallel collate threads
DATA_PIPELINE_NUM_WORKERS_DEFAULT = 1
DATA_PIPELINE_DEVICE_PREFETCH = "device_prefetch"  # double-buffer H2D
DATA_PIPELINE_DEVICE_PREFETCH_DEFAULT = True

#############################################
# Chaos-ready runtime (TPU-specific addition; see runtime/resilience.py
# and docs/tutorials/resilience.md).  `rules` drive deterministic fault
# INJECTION (gated on `enabled`, default on iff rules are present);
# `retry` tunes the transient-fault backoff applied to hostwire KV
# traffic and checkpoint file IO; `watchdog` arms the in-process hang
# detector that snapshots + escalates to the elasticity supervisor.
#############################################
FAULTS = "faults"
FAULTS_ENABLED = "enabled"
FAULTS_SEED = "seed"
FAULTS_SEED_DEFAULT = 0
FAULTS_RULES = "rules"
FAULTS_RETRY = "retry"
FAULTS_RETRY_MAX_ATTEMPTS = "max_attempts"
FAULTS_RETRY_MAX_ATTEMPTS_DEFAULT = 4
FAULTS_RETRY_BASE_DELAY_MS = "base_delay_ms"
FAULTS_RETRY_BASE_DELAY_MS_DEFAULT = 50.0
FAULTS_RETRY_MAX_DELAY_MS = "max_delay_ms"
FAULTS_RETRY_MAX_DELAY_MS_DEFAULT = 2000.0
FAULTS_RETRY_JITTER = "jitter"
FAULTS_RETRY_JITTER_DEFAULT = 0.25
FAULTS_WATCHDOG = "watchdog"
FAULTS_WATCHDOG_ENABLED = "enabled"
FAULTS_WATCHDOG_ENABLED_DEFAULT = False
FAULTS_WATCHDOG_DEADLINE_S = "deadline_s"
FAULTS_WATCHDOG_DEADLINE_S_DEFAULT = 600.0
FAULTS_WATCHDOG_POLL_S = "poll_s"
FAULTS_WATCHDOG_POLL_S_DEFAULT = 1.0
FAULTS_WATCHDOG_SNAPSHOT_DIR = "snapshot_dir"
FAULTS_WATCHDOG_FIRST_BEAT_MULT = "first_beat_mult"
# grace multiplier on the deadline BEFORE the first step-boundary beat:
# an elastic shrink/grow restart pays a full recompile at the new mesh
# shape, which legitimately lands between construction and beat 1
FAULTS_WATCHDOG_FIRST_BEAT_MULT_DEFAULT = 4.0

#############################################
# Precision: fp16 section doubles as the precision section via "type"
# (EleutherAI fork: PRECISION, runtime/constants.py:127-161)
#############################################
FP16 = "fp16"
FP16_ENABLED = "enabled"
FP16_ENABLED_DEFAULT = False
FP16_TYPE = "type"
FP16_TYPE_DEFAULT = "fp16"
PRECISION_TYPES = ("fp16", "float16", "half", "bf16", "bfloat16", "fp32",
                   "float32", "float")
FP16_LOSS_SCALE = "loss_scale"
FP16_LOSS_SCALE_DEFAULT = 0
FP16_INITIAL_SCALE_POWER = "initial_scale_power"
FP16_INITIAL_SCALE_POWER_DEFAULT = 32
FP16_LOSS_SCALE_WINDOW = "loss_scale_window"
FP16_LOSS_SCALE_WINDOW_DEFAULT = 1000
FP16_HYSTERESIS = "hysteresis"
FP16_HYSTERESIS_DEFAULT = 2
FP16_MIN_LOSS_SCALE = "min_loss_scale"
FP16_MIN_LOSS_SCALE_DEFAULT = 1

AMP = "amp"
AMP_ENABLED = "enabled"
AMP_ENABLED_DEFAULT = False

#############################################
# TensorBoard
#############################################
TENSORBOARD = "tensorboard"
TENSORBOARD_ENABLED = "enabled"
TENSORBOARD_ENABLED_DEFAULT = False
TENSORBOARD_OUTPUT_PATH = "output_path"
TENSORBOARD_OUTPUT_PATH_DEFAULT = ""
TENSORBOARD_JOB_NAME = "job_name"
TENSORBOARD_JOB_NAME_DEFAULT = "DeepSpeedJobName"

#############################################
# Progressive layer drop
#############################################
PROGRESSIVE_LAYER_DROP = "progressive_layer_drop"
PLD_ENABLED = "enabled"
PLD_ENABLED_DEFAULT = False
PLD_THETA = "theta"
PLD_THETA_DEFAULT = 1.0
PLD_GAMMA = "gamma"
PLD_GAMMA_DEFAULT = 0.001

#############################################
# Checkpoint
#############################################
CHECKPOINT = "checkpoint"
CHECKPOINT_TAG_VALIDATION = "tag_validation"
CHECKPOINT_TAG_VALIDATION_MODES = ("ignore", "warn", "fail")
CHECKPOINT_TAG_VALIDATION_DEFAULT = "warn"
CHECKPOINT_ASYNC_SAVE = "async_save"
CHECKPOINT_ASYNC_SAVE_DEFAULT = False
CHECKPOINT_COMMIT_TIMEOUT_MS = "commit_timeout_ms"
CHECKPOINT_COMMIT_TIMEOUT_MS_DEFAULT = 300_000
# Preemption safety: when set, the engine installs a SIGTERM handler
# honoring the supervisor's "SIGTERM = save-if-possible" contract — an
# emergency checkpoint is saved into this directory at the next step
# boundary, committed through the two-phase barrier, and the process
# exits cleanly so the relaunch resumes from the preemption point.
CHECKPOINT_PREEMPT_SAVE_DIR = "preempt_save_dir"
CHECKPOINT_PREEMPT_SAVE_DIR_DEFAULT = None

#############################################
# Sparse attention
#############################################
SPARSE_ATTENTION = "sparse_attention"
SPARSE_DENSE_MODE = "dense"
SPARSE_FIXED_MODE = "fixed"
SPARSE_VARIABLE_MODE = "variable"
SPARSE_BIGBIRD_MODE = "bigbird"
SPARSE_BSLONGFORMER_MODE = "bslongformer"
SPARSE_MODE = "mode"
SPARSE_MODE_DEFAULT = SPARSE_FIXED_MODE
SPARSE_BLOCK = "block"
SPARSE_BLOCK_DEFAULT = 16
SPARSE_DIFFERENT_LAYOUT_PER_HEAD = "different_layout_per_head"
SPARSE_DIFFERENT_LAYOUT_PER_HEAD_DEFAULT = False
SPARSE_NUM_LOCAL_BLOCKS = "num_local_blocks"
SPARSE_NUM_LOCAL_BLOCKS_DEFAULT = 4
SPARSE_NUM_GLOBAL_BLOCKS = "num_global_blocks"
SPARSE_NUM_GLOBAL_BLOCKS_DEFAULT = 1
SPARSE_ATTENTION_TYPE = "attention"
SPARSE_ATTENTION_TYPE_DEFAULT = "bidirectional"
SPARSE_HORIZONTAL_GLOBAL_ATTENTION = "horizontal_global_attention"
SPARSE_HORIZONTAL_GLOBAL_ATTENTION_DEFAULT = False
SPARSE_NUM_DIFFERENT_GLOBAL_PATTERNS = "num_different_global_patterns"
SPARSE_NUM_DIFFERENT_GLOBAL_PATTERNS_DEFAULT = 1
SPARSE_NUM_RANDOM_BLOCKS = "num_random_blocks"
SPARSE_NUM_RANDOM_BLOCKS_DEFAULT = 0
SPARSE_LOCAL_WINDOW_BLOCKS = "local_window_blocks"
SPARSE_LOCAL_WINDOW_BLOCKS_DEFAULT = [4]
SPARSE_GLOBAL_BLOCK_INDICES = "global_block_indices"
SPARSE_GLOBAL_BLOCK_INDICES_DEFAULT = [0]
SPARSE_GLOBAL_BLOCK_END_INDICES = "global_block_end_indices"
SPARSE_GLOBAL_BLOCK_END_INDICES_DEFAULT = None
SPARSE_NUM_SLIDING_WINDOW_BLOCKS = "num_sliding_window_blocks"
SPARSE_NUM_SLIDING_WINDOW_BLOCKS_DEFAULT = 3

#############################################
# Autotune: the self-tuning runtime (runtime/autotune/)
#
# "autotune": {
#   "enabled": false,          # arm the runtime (search on demand)
#   "probe_steps": 2,          # timed engine steps per candidate probe
#   "probe_warmup": 1,         # compile/warm steps before timing
#   "budget_s": null,          # wall budget across one search (null =
#                              # unbounded; exhausted => skipped probes,
#                              # and a degraded probe set is never cached)
#   "cache_path": null,        # fingerprint-keyed winner cache JSON
#   "ledger_path": null,       # default: <monitor run dir>/autotune.jsonl
#   "apply_winner": true,      # swap the engine onto the search winner
#   "min_improvement": 0.03,   # swap only if winner ms/step beats the
#                              # incumbent by this fraction
#   "wire_dtypes": [...],      # candidate wire dtypes
#   "bucket_sizes": [],        # extra reduce_bucket_size candidates
#   "include_overlap": true,   # include comm.overlap flips
#   "online": {                # the live retune loop
#     "enabled": false, "window": 5, "baseline_steps": 5,
#     "threshold": 1.5,        # sustained ms/step ratio over baseline
#     "exposed_threshold_ms": 0.0,  # exposed-wire creep trigger (0=off)
#     "cooldown_steps": 20,    # no re-trigger right after a retune
#     "check_every": 1,        # rank-consensus cadence (boundaries)
#     "radius": 1,             # knob-distance of the re-probe set
#     "safe_only": true        # online swaps keep bitwise loss parity
#   }
# }
#############################################
AUTOTUNE = "autotune"
AUTOTUNE_ENABLED = "enabled"
AUTOTUNE_ENABLED_DEFAULT = False
AUTOTUNE_PROBE_STEPS = "probe_steps"
AUTOTUNE_PROBE_STEPS_DEFAULT = 2
AUTOTUNE_PROBE_WARMUP = "probe_warmup"
AUTOTUNE_PROBE_WARMUP_DEFAULT = 1
AUTOTUNE_BUDGET_S = "budget_s"
AUTOTUNE_BUDGET_S_DEFAULT = None
AUTOTUNE_CACHE_PATH = "cache_path"
AUTOTUNE_CACHE_PATH_DEFAULT = None
AUTOTUNE_LEDGER_PATH = "ledger_path"
AUTOTUNE_LEDGER_PATH_DEFAULT = None
AUTOTUNE_APPLY_WINNER = "apply_winner"
AUTOTUNE_APPLY_WINNER_DEFAULT = True
AUTOTUNE_MIN_IMPROVEMENT = "min_improvement"
AUTOTUNE_MIN_IMPROVEMENT_DEFAULT = 0.03
AUTOTUNE_WIRE_DTYPES = "wire_dtypes"
AUTOTUNE_WIRE_DTYPES_DEFAULT = ("fp32", "bf16", "int8")
AUTOTUNE_BUCKET_SIZES = "bucket_sizes"
AUTOTUNE_BUCKET_SIZES_DEFAULT = ()
AUTOTUNE_INCLUDE_OVERLAP = "include_overlap"
AUTOTUNE_INCLUDE_OVERLAP_DEFAULT = True
AUTOTUNE_ONLINE = "online"
AUTOTUNE_ONLINE_ENABLED = "enabled"
AUTOTUNE_ONLINE_ENABLED_DEFAULT = False
AUTOTUNE_ONLINE_WINDOW = "window"
AUTOTUNE_ONLINE_WINDOW_DEFAULT = 5
AUTOTUNE_ONLINE_BASELINE_STEPS = "baseline_steps"
AUTOTUNE_ONLINE_BASELINE_STEPS_DEFAULT = 5
AUTOTUNE_ONLINE_THRESHOLD = "threshold"
AUTOTUNE_ONLINE_THRESHOLD_DEFAULT = 1.5
AUTOTUNE_ONLINE_EXPOSED_THRESHOLD_MS = "exposed_threshold_ms"
AUTOTUNE_ONLINE_EXPOSED_THRESHOLD_MS_DEFAULT = 0.0
AUTOTUNE_ONLINE_COOLDOWN_STEPS = "cooldown_steps"
AUTOTUNE_ONLINE_COOLDOWN_STEPS_DEFAULT = 20
AUTOTUNE_ONLINE_CHECK_EVERY = "check_every"
AUTOTUNE_ONLINE_CHECK_EVERY_DEFAULT = 1
AUTOTUNE_ONLINE_RADIUS = "radius"
AUTOTUNE_ONLINE_RADIUS_DEFAULT = 1
AUTOTUNE_ONLINE_SAFE_ONLY = "safe_only"
AUTOTUNE_ONLINE_SAFE_ONLY_DEFAULT = True

#############################################
# Serving (deepspeed_tpu.serving) — inference-side knobs the autotuner's
# "serve" scope searches over. No reference analogue (the reference
# inference engine arrived in later versions).
# "serving": {
#   "kv_dtype": null,          # null = param dtype | "bf16"|"int8"|"int4"
#   "speculative": {
#     "enabled": false,        # arm self-speculative n-gram decoding
#     "draft_len": 4,          # candidate tokens per verify step
#     "ngram": 3               # suffix-match length of the host drafter
#   },
#   "prefix_cache": {
#     "enabled": true,         # block-level prefix sharing + sessions
#     "min_match_blocks": 1,   # shortest chain worth aliasing
#     "session_ttl_s": 120.0   # pinned-session residency window
#   },
#   "fleet": {
#     "replicas": 1,           # in-process ServeEngine replicas
#     "queue_limit": 64,       # per-replica waiting-queue cap
#     "session_affinity": true # pinned sessions land on their replica
#   }
# }
#############################################
SERVING = "serving"
SERVING_KV_DTYPE = "kv_dtype"
SERVING_KV_DTYPE_DEFAULT = None
SERVING_SPECULATIVE = "speculative"
SERVING_SPEC_ENABLED = "enabled"
SERVING_SPEC_ENABLED_DEFAULT = False
SERVING_SPEC_DRAFT_LEN = "draft_len"
SERVING_SPEC_DRAFT_LEN_DEFAULT = 4
SERVING_SPEC_NGRAM = "ngram"
SERVING_SPEC_NGRAM_DEFAULT = 3
SERVING_PREFIX_CACHE = "prefix_cache"
SERVING_PREFIX_ENABLED = "enabled"
SERVING_PREFIX_ENABLED_DEFAULT = True
SERVING_PREFIX_MIN_MATCH_BLOCKS = "min_match_blocks"
SERVING_PREFIX_MIN_MATCH_BLOCKS_DEFAULT = 1
SERVING_PREFIX_SESSION_TTL_S = "session_ttl_s"
SERVING_PREFIX_SESSION_TTL_S_DEFAULT = 120.0
SERVING_FLEET = "fleet"
SERVING_FLEET_REPLICAS = "replicas"
SERVING_FLEET_REPLICAS_DEFAULT = 1
SERVING_FLEET_QUEUE_LIMIT = "queue_limit"
SERVING_FLEET_QUEUE_LIMIT_DEFAULT = 64
SERVING_FLEET_SESSION_AFFINITY = "session_affinity"
SERVING_FLEET_SESSION_AFFINITY_DEFAULT = True

#############################################
# TPU-specific additions (no reference analogue)
#############################################
MESH = "mesh"  # {"data": -1, "model": 1, "pipe": 1, "seq": 1}
MESH_DEFAULT = None
REMAT = "rematerialization"  # {"enabled": bool, "policy": "dots"|"nothing"|"everything"}
