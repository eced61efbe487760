"""Process-global comm/dispatch counters.

The reference attributes step time by reading NCCL byte counts out of
band; here every hot-path comm primitive increments a named counter
(calls + bytes) as it dispatches, and the telemetry layer reads *deltas*
per step (`RunMonitor.step_start` snapshots, `step_end` diffs).  The
increment is two integer adds on a plain dict entry — cheap enough to
stay unconditional, so the counters are always truthful whether or not
a monitor is attached.

Instrumented sites:

* `runtime/pipe/p2p.py` — `Channel.transfer` (interpreted walk),
  `ChannelPlan.__call__` (fused compiled-executor transfer),
  `GlobalScalars.sum`: per-dispatch send/recv bytes.
* `runtime/pipe/compiler.py` — the single-controller xfer closures
  (`pipe.xfer_act` / `pipe.xfer_grad` device_put reshards).
* `comm/dist.py` — the in-jit collective wrappers.  Those run under
  `jit`/`shard_map` tracing, so each record is a *traced* occurrence
  (once per compiled program), not a per-execution count; the name
  prefix `dist.` marks that distinction.
* `runtime/comm/hostwire.py` — KV-wire payload bytes per allgather.
* `runtime/comm/bucketing.py` — `bucket.*` per-bucket collective payloads
  (traced occurrences, like `dist.*`; hierarchical plans tag them
  `bucket.intra.*` / `bucket.inter.*` per level); the engine
  additionally records per-dispatch `grad_wire.reduce` totals from the
  BucketPlan's static accounting, which tests pin against the plan
  exactly — plus, for hierarchical plans, the per-fabric split
  `grad_wire.intra` (fast-fabric scatter/gather legs) and
  `grad_wire.inter` (the slow-fabric hop on the 1/inner-size shard).
* the input pipeline (`input.*`, rendered by monitor/report.py as its
  own "Input pipeline" section rather than the comm table):
  `input.host_wait_ms` — wall time the engine's Python thread spent
  blocked pulling a batch from the host iterator (bytes slot carries
  integer MICROSECONDS; the report divides back to ms), recorded by
  `runtime/dataloader.timed_next` on every engine-side pull so
  prefetch-on/off lanes are directly comparable;
  `input.h2d_bytes` — batch bytes actually `device_put` by
  `engine._shard_batch`/`_shard_batch_stacked` (already-placed arrays
  are skipped and not counted); `input.queue_depth` — PrefetchLoader
  queue occupancy sampled at each pop (mean = bytes/calls);
  `input.replicated_batches` — batches whose dim 0 didn't divide the
  data axis and were replicated (dp x compute for that batch; the
  dataloader's wraparound tail padding exists to keep this at zero).
* checkpointing (`ckpt.*`, rendered by monitor/report.py as a
  "Checkpointing" section, like `input.*` kept out of the comm table):
  `ckpt.stall_ms` — wall time the TRAINING thread spent blocked inside
  `save_checkpoint_state` (bytes slot carries integer MICROSECONDS;
  with async_save this is the host snapshot only, without it the full
  serialize+write+commit); `ckpt.bytes` — serialized bytes per
  COMMITTED tag (added by the commit job, so an interrupted save never
  counts); `ckpt.pending` — background writer-queue depth sampled at
  each save (mean = bytes/calls, like input.queue_depth);
  `ckpt.skipped_tags` — uncommitted/corrupt tags read_latest_tag
  skipped back over while resolving a resume point.
* the chaos runtime (`fault.*` / `watchdog.*`, runtime/resilience.py,
  rendered by monitor/report.py as the "Resilience" section):
  `fault.injected` — FaultPlan injections fired; `fault.retried` —
  retry_transient attempts after a transient failure;
  `fault.recovered_ms` — wall time ops spent recovering before
  eventually succeeding (bytes slot carries integer MICROSECONDS);
  `watchdog.trips` — StepWatchdog deadline trips (each one also dumps
  a diagnostic snapshot + supervisor escalation file);
  `input.worker_respawns` — dead prefetch workers replaced by the
  consumer (counted under input.* but rendered with Resilience).
* the serving engine (`serve.*` / `kv.*`, deepspeed_tpu/serving/,
  rendered by monitor/report.py as the "Serving" section and excluded
  from the comm byte table): `serve.requests` — requests completed
  naturally (bytes = generated tokens); `serve.tokens` — tokens
  decoded (prefill first tokens included); `serve.decode_steps` —
  decode dispatches (bytes = active slots, so bytes/calls is the mean
  batch occupancy continuous batching exists to maximize);
  `serve.decode_ahead` — calls = decode steps launched, bytes = those
  launched while the step before was still unread;
  `serve.decode_ahead.dropped` — lane-steps computed for a request
  that had already ended (an `eos_token` found one step late);
  `serve.sample.greedy_steps` — calls = decode steps launched, bytes =
  those in which no live slot had a temperature above 0, so that the
  program's sampling tail was the argmax alone;
  `serve.prefill_chunks` — chunked-prefill dispatches (bytes = prompt
  tokens); `serve.ttft_ms` — time-to-first-token (integer MICROSECONDS
  in the bytes slot, the ckpt.stall_ms convention; one call per first
  token); `serve.shed` — in-flight requests shed after a wedged decode
  step (watchdog escalation, state 'error'); `kv.blocks_in_use` —
  paged-KV occupancy sampled once per engine step (mean =
  bytes/calls); `kv.evictions` — KV blocks FORCIBLY reclaimed from
  shed/errored requests (natural completion frees blocks without
  counting here — a healthy run keeps this at zero).  Speculative
  decoding (rendered as the section's "Speculative decoding" rows):
  `serve.draft_tokens` — draft candidates proposed to the verify
  program (calls); `serve.accepted_tokens`
  — drafts accepted AND emitted (calls; a draft accepted by verify but
  cut by max_new/EOS does not count — the counter is the exact number
  of extra tokens speculation bought, so accepted/decode_steps is the
  bonus tokens-per-step and accepted/draft is the acceptance rate);
  `kv.dequant_ms` — µs-in-bytes (the ckpt.stall_ms convention): wall
  time of the serial loop's `verify` dispatches, launch to tokens read,
  against a QUANTIZED kv cache; the loop that runs ahead records none (XLA
  fuses the row dequant into the attention gather, so the cost is only
  isolable by A/B against a dense lane — serve_bench does exactly
  that); zero when kv_dtype is dense.  Prefix caching + sessions
  (rendered as the section's "Prefix cache" rows): `kv.prefix_hits` —
  admissions that aliased at least one cached block (bytes = blocks
  aliased instead of recomputed); `kv.prefix_hit_tokens` — prompt
  tokens whose prefill was SKIPPED because their KV rows were already
  resident (bytes; counted for both hash-matched and session-pinned
  admissions — the numerator of the cache hit rate);
  `kv.cow_copies` — copy-on-write block privatizations when a
  full-prompt hit must recompute its final token into a LIVE-shared
  block (bytes = device bytes copied); `kv.session_pins` — session
  pin events at request finish (bytes = blocks held resident);
  `kv.prefix_evictions` — refcount-0 cached blocks reclaimed LRU-first
  by the allocator under pool pressure (distinct from `kv.evictions`,
  which counts FORCED frees of errored requests' live blocks).
  Summarised windows (a served model with "eva" attention:
  exact rows for the open window, one summary row a chunk behind it):
  `kv.summary_rows` — summary rows written (calls = program calls that
  completed a chunk; bytes = rows); `kv.window_closes` — windows
  closed mid-request at a step boundary (bytes = exact blocks returned
  to the free list); `serve.eva.rows_read` — calls = queries decoded,
  bytes = cache rows they read (the window up to the query plus the
  visible summary rows); `serve.eva.context_tokens` — bytes = cached
  length of the same queries, so rows_read / context_tokens is the
  share of full attention's reads that is left;
  `serve.eva.rows_walked` — calls = the same queries, bytes = pool
  rows their attention fetches (the blocks the rows read lie in where
  the EVA kernel runs, every entry of the table where the jnp oracle
  does), so rows_read / rows_walked is the share of a step's reads
  that it needs.
  Latent rows and routed experts (a served model with "latent"
  attention and a "routed_experts" FFN): `serve.mla.rows_read` — calls
  = queries decoded, bytes = latent rows they attend (one a cached
  token, shared by all heads: every cached row of the request);
  `serve.mla.rows_walked` — the same calls, bytes = latent rows the
  step's attention fetches for them in a layer (the cached length
  rounded up to a block where the decode call is the walk of live
  blocks, the table's whole width where it gathers);
  `serve.moe.assignments` — calls = routed-layer calls,
  bytes = token-expert pairs computed (tokens x top_k, nothing
  dropped); `serve.moe.experts_touched` — calls = decode steps x
  routed layers, bytes = experts with at least one active slot's
  token (counted in the program, read back with the step's tokens);
  `serve.moe.experts_streamed` — the same calls, bytes = experts whose
  weights the step's routed product read: the touched ones where it
  follows the touched list or sorts by expert, every expert held where
  it masks (`moe/dropless.py::routed_way`, asked once at build) — an
  expert streamed is the matrices of its form, `expert_matrices` x
  [D, F] values: three of a SiLU-gated expert, two of a relu2 one;
  `serve.moe.prefill_rows_multiplied` — calls = prefill chunks x routed
  layers, bytes = assignment rows the chunks' routed products
  multiplied with an expert's matrices (slabs walked x a slab's rows
  where the product walks compact slabs of the rows held, tokens x
  top_k where it groups every assignment; counted in the program,
  returned behind each chunk's sample and read with the request's
  first token).
  Grouped rows over two groups of layers (a served model with
  "grouped" attention and sliding layers): `serve.window.rows_read` —
  calls = queries decoded, bytes = rows one attends in ONE sliding
  layer (min(cached, window)); `serve.attn.rows_read` — the same summed
  over all the layers (every cached row in a full one);
  `serve.attn.rows_walked` — calls = slots decoded, bytes = pool rows
  the step's attention FETCHES for them over the same layers (a slot's
  cached length rounded up to a block in a layer whose decode call
  resolves to the walk of live blocks, the table's — or the ring's —
  whole width in a layer that gathers; `kernels/registry.py`, asked
  once for each kind of layer at build);
  `serve.attn.prefill_rows_walked` — calls = prefill chunks launched,
  bytes = pool rows the chunk's attention FETCHES over the same layers
  (its last position + 1 rounded up to a block, the table's width at
  most, in a layer whose prefill call resolves to the walk of the
  request's live blocks — in a sliding layer from the block that holds
  the chunk's first query's lower bound, the ring at most; its run's
  whole width in a layer that gathers; asked once for each kind of
  layer at build), all four from
  positions on the host; `kv.ring_wraps` — calls = requests that ended
  with more rows than a ring holds, bytes = blocks the ring saved them
  in the window group.  Behind a share of the experts
  `serve.moe.experts_touched` and `serve.moe.experts_streamed` count
  among the experts held and `serve.moe.assignments` is not emitted
  (only the program knows how many of a call's assignments it held).
  A state beside rows (a served model some of whose layers mix tokens
  by a state-space recurrence and keep a fixed state a slot, no rows),
  all from what the host knows: `serve.ssm.state_bytes` — calls =
  decode steps, bytes = state (the float32 state and the convolution's
  kept inputs, every such layer) the step's program reads and writes as
  it is built, which the engine asks `kernels/registry.py` once: where
  the recurrence is the `ssm_step` kernel, the running slots' float32
  state twice and every slot's kept inputs twice; where it is the
  oracle, every slot's of both, twice; `serve.ssm.slots_live` — calls =
  decode steps, bytes = running slots x layers with a state (times a
  layer's bytes a slot: what the live slots need);
  `serve.ssm.state_resets` — calls = slots zeroed on the
  device as a request is seated (serving/kv_cache.py `reset_state`) —
  and `serve.gdn.state_bytes`, `serve.gdn.slots_live`,
  `serve.gdn.state_resets`, the same three name for
  name, where the layers with a state are gated delta-rule mixers
  (models/qwen3_next.py; the kernel asked about is `gdn_step`), which
  emit no `serve.ssm.*`; and `serve.conv.state_bytes`,
  `serve.conv.slots_live`, `serve.conv.state_resets`, name for name
  again, where they are gated short convolutions (models/lfm2_moe.py:
  what a slot keeps is the convolution's last `taps - 1` inputs and
  nothing else; no kernel is asked about, so `state_bytes` is every
  slot's rows twice, whatever is running) — the family is the kind's
  `counters` in models/layer_spec.py `STATE_MIXERS`;
  `serve.attn.rows_read`, `serve.attn.rows_walked` and
  `serve.attn.prefill_rows_walked` as above over the attention layers
  alone.
  Paged attention (the GPT family): `serve.paged.rows_walked`
  — calls = slots decoded, bytes = pool rows their attention reads (a
  slot's live blocks where the paged kernel runs, the table's whole
  width where the jnp oracle does).
  Fleet routing (`router.*`, serving/router.py, rendered as the
  "Fleet router" rows; excluded from the comm byte table like the
  rest of the serving families): `router.dispatches` — requests
  dispatched to a replica (bytes += the chosen replica's
  `kv.blocks_in_use` at dispatch, so bytes/calls is the mean load a
  dispatch landed on); `router.spills` — dispatches deflected from
  the least-loaded pick because its queue was full;
  `router.shed` — requests refused at the front door with every
  replica queue saturated (returned in state 'error', never
  enqueued).
* the MoE wire (`moe.*`, moe/dispatch.py sorted dispatch + explicit
  expert all-to-all; rendered by monitor/report.py as the "MoE wire"
  section, excluded from the comm byte table).  Recorded per EXECUTION
  via async `jax.debug.callback` from inside the traced program — one
  callback per LOCAL mesh rank per event (the 8-device virtual test
  mesh fires 8 per a2a hop; a real deployment sums its local devices),
  never bumped by AOT lowering or flops analysis; read after
  `jax.effects_barrier()` for exact totals:
  `moe.a2a_bytes` — wire bytes per a2a hop (all local ranks; a
  training dispatch runs 4 traversals: forward dispatch+combine and
  the mirrored backward), pinned byte-exact against
  `dispatch.A2APlan` in tier-1; `moe.a2a_inter` — the subset crossing
  the slow fabric (`data_outer` hops; ZERO under inner placement —
  the number the hierarchy-aware placement exists to minimize);
  `moe.a2a_exposed_ms` — µs-in-bytes (the ckpt.stall_ms convention):
  a2a wall time on the critical path, measured by the
  `tools/moe_a2a_bench.py` wire-on/wire-off lanes (the in-program a2a
  is consumed by the very next expert matmul, so today ALL of it is
  exposed — this is what a future chunked overlap would hide);
  `moe.dropped_tokens` — assignments past expert capacity (bytes;
  calls = dispatches), zero in dropless mode while the overflow
  bucket holds; `moe.capacity_frac` — ppm-in-bytes occupancy of the
  [E, C] expert buckets per dispatch (mean utilisation % =
  bytes / calls / 1e4).
* the self-tuning runtime (`autotune.*`, runtime/autotune/; rendered
  by monitor/report.py as the "Autotune" section beside the
  `autotune.jsonl` ledger, excluded from the comm byte table):
  `autotune.probes` — candidate probes run (bytes = probe wall time in
  integer MICROSECONDS, the ckpt.stall_ms convention; probe dispatches
  go through the raw `.fn` programs so they never bump the
  `grad_wire.*` per-dispatch counters); `autotune.cache_hits` — winner
  cache hits (a hit applies with ZERO probes); `autotune.rejected` —
  candidate compositions pruned by the config validators before any
  probe; `autotune.retunes` — online retunes triggered by sustained
  regression (step-time or exposed-wire creep); `autotune.swaps` —
  live config swaps applied through the StepBuilder rebuild (search
  winners, cached winners and online retune winners all count here).
* the Pallas kernel registry (`kernel.*`, deepspeed_tpu/kernels;
  rendered by monitor/report.py as the "Kernels" section, excluded
  from the comm byte table): `kernel.dispatches` — registry
  resolutions that took an op's Pallas path (counted at TRACE time,
  once per jit trace, not per step); `kernel.fallbacks` — resolutions
  that ran the jnp oracle instead (incompatible fabric, declined
  shape, or an explicit jnp pin);
  `kernel.flash.blocks.<bq>x<bk>.walk<rows>` — the tile schedule the
  training flash kernels (ops/transformer/flash_attention.py, never
  through the registry) ran for a shape: score tile and resident
  K/V rows (counted at TRACE time, per traced call).
* the training step schedule (runtime/engine.py):
  `engine.overflow_flag.waits` — calls = hot-path settles
  (`_resolve_pending_overflow(keep_newest=True)`) that found an
  overflow flag older than the newest not yet produced and waited for
  it; bytes slot = integer MICROSECONDS waited (the
  `input.host_wait_ms` convention).  Reads 0 calls where the loop
  reads anything of step k-1 after it dispatches step k; a loop that
  reads nothing is held here, two steps ahead of the device, and
  counts once a step — that wait costs the device nothing.
* trace/SLO telemetry (`trace.*` / `slo.*`, monitor/tracing.py;
  rendered by monitor/report.py as the "Tracing" rows of the Serving
  SLO section, excluded from the comm byte table): `trace.events` —
  span events flushed to the rank-local trace file (bytes = JSONL
  bytes written, bounded by `max_file_bytes`); `trace.dropped` —
  events the byte cap rejected (the ring buffer still holds them for
  the watchdog flight recorder); `slo.windows` — periodic `slo`
  monitor events emitted by the ServingSLO sliding window.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import numpy as np


def tree_bytes(tree: Any) -> int:
    """Total byte size of a pytree of arrays / ShapeDtypeStructs /
    tracers (anything with .shape and .dtype). Best-effort: leaves
    without a static shape contribute 0 — a counter must never raise
    into the hot path."""
    import jax

    total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        try:
            shape = getattr(leaf, "shape", None)
            dtype = getattr(leaf, "dtype", None)
            if shape is None or dtype is None:
                continue
            total += int(np.prod(shape, dtype=np.int64)) * \
                np.dtype(dtype).itemsize
        except Exception:
            continue
    return total


class CounterRegistry:
    """Named (calls, bytes) accumulators with snapshot/delta reads."""

    __slots__ = ("_c",)

    def __init__(self):
        self._c: Dict[str, list] = {}

    def add(self, name: str, nbytes: int = 0, calls: int = 1) -> None:
        e = self._c.get(name)
        if e is None:
            self._c[name] = [calls, nbytes]
        else:
            e[0] += calls
            e[1] += nbytes

    def snapshot(self) -> Dict[str, tuple]:
        return {k: (v[0], v[1]) for k, v in self._c.items()}

    def delta_since(self, snap: Optional[Dict[str, tuple]]) -> Dict[str, dict]:
        snap = snap or {}
        out = {}
        for k, v in self._c.items():
            c0, b0 = snap.get(k, (0, 0))
            dc, db = v[0] - c0, v[1] - b0
            if dc or db:
                out[k] = {"calls": dc, "bytes": db}
        return out

    def totals(self) -> Dict[str, dict]:
        return {k: {"calls": v[0], "bytes": v[1]} for k, v in self._c.items()}

    def reset(self) -> None:
        self._c.clear()


# THE process-global registry every instrumented site writes to.
COUNTERS = CounterRegistry()

# Counters whose bytes slot carries integer MICROSECONDS (the
# ckpt.stall_ms convention) instead of real bytes.  The counter/doc
# lint test (tests/test_tracing.py) cross-checks this registry against
# docs/tutorials/monitoring.md so every µs-in-bytes counter stays
# flagged as such wherever it is documented.
US_IN_BYTES_COUNTERS = frozenset((
    "input.host_wait_ms",
    "ckpt.stall_ms",
    "fault.recovered_ms",
    "grad_wire.exposed_ms",
    "serve.ttft_ms",
    "kv.dequant_ms",
    "moe.a2a_exposed_ms",
    "autotune.probes",
    "engine.overflow_flag.waits",
))
