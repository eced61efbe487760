"""The continuous-batching serving engine.

One `ServeEngine` owns: a `PagedKVCache` (block pool + free list), a
`Scheduler` (admission + slots), and the jitted {prefill, decode}
program pair from `ServeProgramBuilder`.  It serves any model that
hands over a layer spec (`model.layer_spec()`, models/layer_spec.py):
the GPT family, EvaByte, DeepSeek-V2, Command A+ and Granite 4.0-H
today.  `step()` is the whole serving
loop body — admit, prefill one chunk round, decode one token for every
running slot — and everything else (the bench's Poisson arrival thread,
`generate()`'s synchronous loop, a `ServeWorker` daemon) just drives
`step()`.

The loop runs one decode step ahead of what it has read.  One
iteration, in order: (1) shed if asked, admit; (2) launch a prefill
chunk for a request still in its prompt — a prompt's last chunk samples
the first token, `seat` puts it into the slot's entry of the token
vector on the device, and the request is running; (3) launch decode
step i for every running request whose answer is not yet wholly
computed: each slot's last token is step i-1's sample as it lies on the
device (or `seat`'s), positions were advanced by step i-1 itself, and
`active`, `tables`, `temperatures`, `top_ks`, `seeds` are the arrays
step i-1 was handed unless a request joined, left, crossed a block or
closed a window since — then a COPY of the engine's own rows is
uploaded, so nothing a launched program reads is ever rewritten
(`_SlotState`); (4) only now the one blocking read: step i-1's tokens,
and the host's half of that step — append to `req.out`, stamp, finish,
free — while step i runs; (5) the first token of this iteration's
prompt-ending chunk, read as soon as the chunk has run, with step i
queued behind it.  Decided AHEAD, from positions alone and in launch
order: a request whose `max_new_tokens` the launched step completes is
not in the next step; a window the launched step fills is closed and
its blocks may be taken by whatever is launched next (the device runs
programs in launch order, so a freed block is only written by a program
queued after the one that last read it).  Found ONE STEP LATE, because
only the tokens say it: with `eos_token` set, a request that ends at
step i has already ridden step i+1 — that lane's token is dropped
(`serve.decode_ahead.dropped`), its one extra row lies beyond the rows
of its answer in a block it still held at the launch, and finishing
(prefix registration, session pin, blocks back) happens when the host
reads step i.  Outputs are those of a loop that reads every step before
it launches the next, token for token.  A step after which nothing is
left to launch is read at once; `run()`, `generate()`, a shed, a dead
worker and `close()` read (or wait out) what is in flight before they
report.  `draft_len > 0` keeps the serial loop — drafting reads the
tokens — and that, with whether a request set `eos_token`, is all the
loop adapts to: no option.  Counters: `serve.decode_ahead` (calls =
decode steps launched, bytes = those launched while the step before was
still unread) and `serve.decode_ahead.dropped` (calls = lane-steps
computed for a request that had already ended).

What the engine's thread is doing, phase by phase (`monitor.tracing.
phase`: a profiler annotation always, the same interval in the attached
recorder where the engine step is sampled; disjoint, in this order in an
iteration): `serve.idle` (the worker, nothing submitted and nothing
unread), `serve.admit` (1), `serve.prefill.launch` (2; inside it, where
layers keep a state a slot, `serve.state.reset` before a request's
first chunk), `serve.decode.launch` with the uploads of (3) inside it as
`serve.decode.upload`, `serve.read` (the host blocked on a launched
program's tokens) and `serve.bookkeep` (the host's half of (4) and (5)
once they are there); the serial loop adds `serve.draft`.

Resilience contract (the PR-8 machinery, applied to serving):

* `fault_point` sites `serve.step` / `serve.admit` / `serve.prefill` /
  `serve.decode` make the engine chaos-testable like every other layer.
* `attach_watchdog(wd)` registers the serving worker thread as a
  StepWatchdog thread group and beats the watchdog at every step
  boundary; wiring the watchdog's `on_trip` to `request_shed()` closes
  the loop: a wedged decode step trips the deadline, the trip handler
  flags the engine, and the moment the engine thread is live again it
  SHEDS the in-flight batch — those requests finish in state "error"
  with their KV blocks reclaimed (`kv.evictions`), waiting requests are
  admitted and complete normally.  Shedding the stuck work instead of
  hanging the fleet is the serving analogue of the supervisor's
  SIGTERM-first restart.

Counters (monitor/counters.py "Serving" section): `serve.requests`
(completed; bytes = generated tokens), `serve.tokens`,
`serve.decode_steps` (bytes = active slots -> mean batch occupancy),
`serve.prefill_chunks` (bytes = prompt tokens prefetched),
`serve.ttft_ms` (µs in the bytes slot, the ckpt.stall_ms convention),
`serve.shed`, plus `kv.blocks_in_use` / `kv.evictions` from the cache.
Speculative decoding adds `serve.draft_tokens` (candidates proposed),
`serve.accepted_tokens` (drafts accepted AND emitted — the
acceptance-rate numerator; accepted/decode_steps is the extra
tokens/step speculation bought), and `kv.dequant_ms` (µs-in-bytes:
wall time of the serial loop's `verify` dispatches, launch to tokens
read, against a QUANTIZED cache; the loop that runs ahead records none
— launch to read spans the step before there).

Prefix caching + pinned sessions (PR 19): admission aliases the
request's already-cached full prompt blocks (serving/kv_cache.py chain
hashes) so prefill starts at the first non-cached position, and a
request submitted with a `session_id` keeps its blocks resident after
finishing (`SessionPin`, TTL + pressure-released) so the conversation's
next turn re-prefills only its new tokens.  Both are table-entry
aliasing — the programs are untouched, which is what keeps greedy
output bitwise-identical with the cache on or off.  Counters:
`kv.prefix_hits`, `kv.prefix_hit_tokens`, `kv.cow_copies`,
`kv.session_pins`, `kv.prefix_evictions`.

What the cache keeps, family by family, is the cache plan's
(serving/kv_cache.py `cache_plan`: a list of layer groups); the engine
builds the schedule and the cache from it, refuses in a group's own
words what is not offered for it (`prefix_cache=True`, a mesh of more
than one device, sessions; the program builder refuses `draft_len > 0`,
quantized weights and int8/int4 rows), and at step boundaries does the
same three things for every plan: before a call it takes the blocks the
call will write where a run's blocks are not handed out at admission
(`kv.extend`: a window's exact and summary blocks, a ring's), before a
request's first prefill chunk it zeroes its slot's arrays where layers
keep arrays by slot (`kv.reset_state`, phase `serve.state.reset`:
launched behind whatever step still decodes for the slot's last tenant;
`prefill` learns the slot from one more entry behind the request's
table, and a decode step hands a slot that is not running its arrays
back as it found them), and after a call it closes a window the call
filled (`kv.close_window`: the exact blocks go back mid-request, the
summary rows become visible — the programs read that off the position;
host span `eva.window_close`).  No program of its own, so nothing
compiles after warm-up.  What a step and a chunk read is counted kind by
kind (`kv_cache.KINDS`), from positions on the host, with "walks" — a
call fetches the live blocks, or the running slots' state, and not
everything — being what the kernel registry answers for the programs'
shapes, asked once at build:

* paged: `serve.paged.rows_walked` (calls = slots decoded, bytes = the
  pool rows attention reads for them: a slot's live blocks where the
  paged kernel runs, the table's whole width where the jnp oracle does).
* eva (exact rows for an open window, a summary row a chunk behind it):
  `kv.summary_rows`, `kv.window_closes`, `serve.eva.rows_read`,
  `serve.eva.context_tokens`, `serve.eva.rows_walked` (bytes = the rows
  attention fetches for the queries `rows_read` counts: the live window
  and summary blocks where it walks, the table's whole width where not).
* latent (one row a token for all heads; the programs pick expanded or
  absorbed products from the call's query count): `serve.mla.rows_read`
  (calls = queries decoded, bytes = latent rows they attend: every
  cached row), `serve.mla.rows_walked` (the same calls, bytes = latent
  rows the step FETCHES: a slot's cached length rounded up to a block
  where its decode call is the walk — kernels/paged.py, one K/V head a
  row and one operand —, the table's whole width where it gathers; a
  prefill chunk, and every backend but the TPU, gathers).
* sparse (latent rows of which a learned selection is attended: the
  "full" layers keep an index key a token, the "shared" ones take the
  choice over; `serve.mla.*` is not emitted): `serve.sparse.keys_scored`
  (calls = queries decoded x "full" layers, bytes = index keys they
  score: every cached one), `serve.sparse.rows_selected` (calls = queries
  decoded x layers, bytes = rows the chosen sets hold: min(cached,
  index_topk) a layer), `serve.sparse.rows_fetched` (the same calls,
  bytes = latent rows the decode program gathers for them: `index_topk`
  a slot a layer, chosen or not), `serve.sparse.selections_shared`
  (calls = layer-calls that attended a selection made by an earlier
  layer of the same call: the "shared" layers of every step and chunk).
* grouped (rows of `kv_heads` keys and values; sliding layers in a ring
  of `window + prefill_chunk` rows a request, sized for `max_batch`
  requests so that it never runs dry — no option — or, where the ring
  would be no shorter than `max_seq_len` or layers with a state stand
  beside them, in the table under a mask): `serve.window.rows_read`
  (calls = queries decoded, bytes = rows one of them attends in ONE
  sliding layer: min(cached, window)), `serve.attn.rows_read` (the same
  summed over all the layers), `serve.attn.rows_walked` (calls = slots
  decoded, bytes = the pool rows the layers fetch for that: in a group
  that walks, a slot's cached length rounded up to a block — less, in a
  sliding layer, the blocks below the one its window begins in — and its
  run's whole width — the table's, or the ring's — in a group that
  gathers), `serve.attn.prefill_rows_walked` (calls = prefill chunks
  launched, bytes = the pool rows the layers fetch for a chunk: its last
  position + 1 rounded up to a block — its padded tail's, the table's
  width at most — less, in a sliding layer, the blocks below the one its
  FIRST query's window begins in, its run at most; the whole width of
  its run in a group that gathers), `kv.ring_wraps` (calls = requests
  that ended with more rows than a ring, bytes = the blocks the ring
  saved each).
* ssm | gdn | conv (models/layer_spec.py `STATE_MIXERS`: a float32 state
  and the convolution's last inputs a SLOT — the convolution kind those
  inputs alone —, held for all `max_batch` slots whatever is seated, so
  the state and not the rows sizes `max_batch`; grouped attention in the
  other layers): `serve.ssm.state_bytes` (calls = decode steps, bytes =
  what the step's program reads and writes: where the recurrence is the
  kind's kernel, the float32 state of the RUNNING slots twice and every
  slot's convolution inputs twice; where it is the oracle, or the kind
  has no kernel, every slot's of both, twice), `serve.ssm.slots_live`
  (bytes = running slots x layers with a state), `serve.ssm.state_resets`
  (calls = slots zeroed) — under `serve.gdn.*` or `serve.conv.*`, name
  for name, as the kind's entry of `STATE_MIXERS` says.

Behind routed FFNs, whatever the cache: `serve.moe.assignments` (calls =
routed-layer calls, bytes = token-expert pairs they computed: tokens x
top_k, nothing dropped; not emitted behind a share of the experts, where
only the program knows how many of a call's assignments it held),
`serve.moe.experts_touched` (calls = decode steps x routed layers, bytes
= experts — of those held — with at least one active slot's token,
counted in the program and read back with the step's tokens, one step
after the launch), `serve.moe.experts_streamed` (the same calls, bytes =
experts whose weights the step's routed product read: the touched ones
where it follows the touched list — a TPU — or sorts by expert, every
one held where it masks; `moe/dropless.py::routed_way`, asked once at
build) and `serve.moe.prefill_rows_multiplied` (calls = prefill chunks x
routed layers, bytes = assignment rows the chunks' routed products
multiplied with an expert's matrices: the slabs walked times a slab's
rows where the product walks compact slabs — a TPU —, tokens x top_k
where it groups every assignment; counted in the program,
`moe/dropless.py::rows_multiplied`, returned behind each chunk's sample
and read when the request's first token is, never by a read of its own).

Speculative decoding (`draft_len > 0`): each decode step becomes a
verify step — a host-side n-gram drafter proposes up to `draft_len`
candidates per slot from the request's own emitted tokens, the batched
`verify` program scores all draft_len+1 positions through the paged
cache in one dispatch, and the engine emits the longest matching
prefix plus the target's own next token.  Because verify samples with
the same position-keyed RNG rule as decode, output is token-identical
to the non-speculative engine at matched kv_dtype (and to `generate()`
at dense KV) — speculation changes WHEN tokens arrive, never WHICH.
"""

from __future__ import annotations

import collections
import dataclasses
import threading
import time
from typing import Any, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from ..monitor.counters import COUNTERS
from ..monitor.tracing import phase
from ..runtime.resilience import fault_point
from ..utils.logging import logger
from .kv_cache import (KINDS, TRASH_BLOCK, Counted, PagedKVCache, cache_plan,
                       resolve_kv_dtype)
from .programs import ServeProgramBuilder, ServeSchedule
from .scheduler import (ADMISSION_POLICIES, ERROR, FINISHED, RUNNING,
                        Request, Scheduler)


@dataclasses.dataclass
class ServeConfig:
    """Serving knobs (validated at construction; see
    docs/tutorials/serving.md for sizing guidance)."""

    block_size: int = 16              # tokens per KV block
    num_blocks: int = 64              # pool size INCLUDING the trash block
    max_batch: int = 8                # decode slots
    prefill_chunk: int = 32           # prompt tokens per prefill call
    max_seq_len: Optional[int] = None  # per-request cap; default model's
    admission: str = "continuous"     # "continuous" | "static"
    max_prefill_chunks_per_step: int = 1
    quantized_weights: Any = False    # False | "int8" | "int4"
    kv_dtype: Any = None              # None (param dtype) | "bf16" |
    #                                   "int8" | "int4" | dtype-like
    draft_len: int = 0                # speculative candidates per step
    spec_ngram: int = 3               # suffix n-gram the drafter matches
    prefix_cache: bool = True         # block-level prefix sharing
    prefix_min_match_blocks: int = 1  # shortest chain worth aliasing
    session_ttl_s: float = 120.0      # pinned-session residency window

    def __post_init__(self):
        for name in ("block_size", "max_batch", "prefill_chunk",
                     "max_prefill_chunks_per_step"):
            if int(getattr(self, name)) < 1:
                raise ValueError(
                    f"serving {name} must be >= 1, got "
                    f"{getattr(self, name)}")
        if int(self.num_blocks) < 2:
            raise ValueError(
                f"serving num_blocks must be >= 2 (block 0 is reserved), "
                f"got {self.num_blocks}")
        if self.admission not in ADMISSION_POLICIES:
            raise ValueError(
                f"serving admission must be one of {ADMISSION_POLICIES}, "
                f"got {self.admission!r}")
        q = self.quantized_weights
        if q not in (False, None, "int8", "int4"):
            raise ValueError(
                f"serving quantized_weights must be False, 'int8' or "
                f"'int4', got {q!r}")
        if self.kv_dtype is not None:
            resolve_kv_dtype(self.kv_dtype)  # raises on typos, loudly
        if int(self.draft_len) < 0:
            raise ValueError(
                f"serving draft_len must be >= 0, got {self.draft_len}")
        if int(self.spec_ngram) < 1:
            raise ValueError(
                f"serving spec_ngram must be >= 1, got {self.spec_ngram}")
        if int(self.prefix_min_match_blocks) < 1:
            raise ValueError(
                f"serving prefix_min_match_blocks must be >= 1, got "
                f"{self.prefix_min_match_blocks}")
        if float(self.session_ttl_s) <= 0:
            raise ValueError(
                f"serving session_ttl_s must be > 0, got "
                f"{self.session_ttl_s}")

    @property
    def quant_mode(self) -> str:
        return self.quantized_weights if self.quantized_weights else "none"


@dataclasses.dataclass
class SessionPin:
    """One resident session: a finished request's KV blocks held by an
    extra reference so the next turn re-prefills only its new tokens.
    `tokens` is the full history (prompt + output) the pin's blocks
    encode; `cached_len` the rows actually written (the final emitted
    token's K/V never is — its row is recomputed by the next turn's
    prefill)."""

    sid: Any
    owner: Any                        # the kv allocator's owner key
    tokens: List[int]
    cached_len: int
    blocks: int
    expires: float


@dataclasses.dataclass
class _First:
    """A prompt's first token: sampled by `prefill`, not yet read."""

    req: Request
    tok: Any                          # device scalar (behind routed
    #                                   FFNs [2]: the rows multiplied too)


@dataclasses.dataclass
class _Step:
    """A decode step: launched, its tokens not yet read."""

    toks: Any                         # device [R] (+ 1 behind routed FFNs)
    lanes: List[Tuple[Request, int]]  # (request, slot) as launched
    tus0: int                         # the tracer's clock at the launch
    index: int                        # engine.steps at the launch


class _SlotState:
    """The packed decode-batch state, one row a slot, under the names
    `decode` takes it by.  `host` is the engine's own copy, which it
    rewrites at will; a program is only ever handed `on_device()`: an
    upload of a COPY of each row set that changed since its last upload
    (on the CPU `jnp.asarray` may alias the host array, and on any
    backend a transfer may still be under way when the call returns),
    else the array the step before was handed.  So nothing a launched
    program reads is rewritten, and a step that no request joins or
    leaves uploads nothing."""

    def __init__(self, slots: int, table_width: int):
        self.host = {
            "positions": np.zeros((slots,), np.int32),
            "active": np.zeros((slots,), bool),
            "tables": np.full((slots, table_width), TRASH_BLOCK, np.int32),
            "temperatures": np.zeros((slots,), np.float32),
            "top_ks": np.zeros((slots,), np.int32),
            "seeds": np.zeros((slots,), np.uint32)}
        self._dev = {}
        self._stale = set(self.host)

    def set(self, slot: int, **rows) -> None:
        for name, row in rows.items():
            self.host[name][slot] = row
        self._stale.update(rows)

    def on_device(self) -> tuple:
        for name in self._stale:
            self._dev[name] = jnp.asarray(self.host[name].copy())
        self._stale.clear()
        return tuple(self._dev[name] for name in self.host)

    def advanced(self, slots: List[int], positions) -> None:
        """A launched step moved `slots` on by one; `positions` is what
        it returned for the step after it."""
        self.host["positions"][slots] += 1
        self._dev["positions"] = positions


class ServeEngine:
    """Continuous-batching decode engine over a mesh-sharded paged KV
    cache.  Single engine thread drives `step()`; `submit()` is safe
    from any thread."""

    def __init__(self, model, params, config: Optional[ServeConfig]
                 = None, mesh_info=None, programs: Optional[dict] = None,
                 clock=time.monotonic):
        self.model = model
        self.config = config or ServeConfig()
        self.clock = clock
        cfg = model.config
        c = self.config
        spec = model.layer_spec()
        self.max_seq_len = int(c.max_seq_len or cfg.max_seq_len)
        if self.max_seq_len > cfg.max_seq_len:
            raise ValueError(
                f"serving max_seq_len {self.max_seq_len} exceeds the "
                f"model's positional table ({cfg.max_seq_len})")
        if mesh_info is None:
            from ..comm.mesh import peek_mesh

            mesh_info = peek_mesh()
        self.mesh_info = mesh_info
        # what each layer keeps, and the table that addresses it: the
        # schedule and the cache are built from it, and what is not offered
        # for a group is refused in the group's own words
        self.plan = plan = cache_plan(spec, cfg, c)
        for g in plan.groups:
            if c.prefix_cache and g.no_prefix_cache:
                raise NotImplementedError(g.no_prefix_cache)
            if g.no_mesh and mesh_info is not None and mesh_info.size > 1:
                raise NotImplementedError(g.no_mesh.format(n=mesh_info.size))
        # routed-FFN layers, for serve.moe.*
        routed = spec.routed_layers(cfg.num_layers)
        self._routed_layers = len(routed)
        self._top_k = spec.top_k
        self._held_share = spec.held is not None
        # what a decode step's routed product streams of a layer's
        # experts, for serve.moe.experts_streamed: None where it follows
        # the touched list, the experts held where every one is read
        self._streams_all = None
        if self._routed_layers:
            from ..moe.dropless import (expert_matrices, routed_way,
                                        routed_words)

            experts = params["blocks"][routed[0]]["mlp"]["experts"]
            rows = c.max_batch * (int(c.draft_len) + 1)
            if routed_way(rows, spec.top_k, experts,
                          cfg.num_experts) == "masked":
                self._streams_all = experts["up"].shape[0]
            words = lambda n: routed_words(n, spec.top_k, experts,
                                           cfg.num_experts)
            logger.info(
                f"routed FFN in {len(routed)} layer(s), "
                f"{experts['up'].shape[0]} of {cfg.num_experts} experts of "
                f"{expert_matrices(experts)} matrices held: a decode step's "
                f"{words(rows)}; a prefill chunk's {words(c.prefill_chunk)}")
        kv_dtype = cfg.param_dtype if c.kv_dtype is None else c.kv_dtype
        kv_mode = resolve_kv_dtype(kv_dtype)[0]
        schedule = ServeSchedule(
            max_batch=c.max_batch, prefill_chunk=c.prefill_chunk,
            block_size=c.block_size, num_blocks=c.num_blocks,
            table_width=plan.table_width, quantized=c.quant_mode,
            kv_dtype=kv_mode, draft_len=int(c.draft_len),
            window_blocks=plan.window_blocks, ring_blocks=plan.ring_blocks)
        if programs is None:
            # the builder refuses what a family's programs cannot do
            # before the pool is laid out
            programs = ServeProgramBuilder(model, schedule).build()
        elif programs["schedule"].program_key() != schedule.program_key():
            raise ValueError(
                f"prebuilt programs were compiled for "
                f"{programs['schedule'].describe()!r} but this engine "
                f"needs {schedule.describe()!r}")
        # the chain-hash salt: anything that changes K/V block CONTENT
        # for the same token ids must key the prefix cache (the kv
        # storage mode is folded in by the cache itself)
        prefix_salt = (f"{cfg.num_layers}|{cfg.num_heads}|{cfg.head_dim}|"
                       f"{cfg.vocab_size}|{cfg.max_seq_len}|{c.quant_mode}")
        self.kv = PagedKVCache(
            plan, c.num_blocks, dtype=kv_dtype, mesh_info=mesh_info,
            prefix_cache=c.prefix_cache,
            min_match_blocks=c.prefix_min_match_blocks,
            prefix_salt=prefix_salt)
        self.scheduler = Scheduler(self.kv, c.max_batch,
                                   admission=c.admission, clock=clock,
                                   draft_len=int(c.draft_len))
        # resident sessions (sid -> SessionPin), insertion-ordered so
        # pressure release walks oldest-pinned first
        self._sessions: "dict[Any, SessionPin]" = {}
        if c.prefix_cache:
            self.scheduler.session_lookup = self._session_lookup
            self.scheduler.session_consumed = self._session_consumed
        self.programs = programs
        # kind by kind, what a step's and a chunk's counting reads: the
        # plan's groups of the kind, their bytes, and whether the call over
        # a group walks what is live or reads everything — what the
        # registry answers for the programs' shapes, asked once here (a
        # decode step's `max_batch` slots; a prefill chunk's one request,
        # only where the kind counts chunks)
        from ..kernels import registry

        def walks(kind, g, q_len, batch):
            asked = kind.asks and kind.asks(
                g, spec, cfg, schedule, self.kv.caches[g.layers[0]], q_len,
                batch)
            return bool(asked) and registry.resolve_impl(
                asked[0], info=asked[1]) == "pallas"

        self._counted = {}              # kind -> Counted
        for name in dict.fromkeys(g.kind for g in plan.groups):
            kind = KINDS[name]
            groups = tuple(g for g in plan.groups if g.kind == name)
            self._counted[name] = Counted(
                plan, groups,
                tuple((walks(kind, g, int(c.draft_len) + 1, c.max_batch),
                       walks(kind, g, c.prefill_chunk, 1)
                       if kind.chunk else None) for g in groups),
                tuple(self.kv.group_nbytes(g) for g in groups))
        self.params = programs["prepare_params"](
            self._place_params(params))
        logger.info(f"serving engine up: {schedule.describe()}; "
                    f"{self.kv.describe()}")
        # packed decode-batch state (one row per slot).  Each slot's
        # last token lies on the device where decode runs ahead of what
        # the host has read, on the host where drafting reads it
        self._slots = _SlotState(c.max_batch,
                                 plan.table_width + plan.ring_blocks)
        self._serial = int(c.draft_len) > 0
        self._tokens = (np.zeros if self._serial else jnp.zeros)(
            (c.max_batch,), np.int32)
        # launched and not yet read, in launch order
        self._unread: "collections.deque" = collections.deque()
        self.steps = 0
        self.peak_blocks_in_use = 0
        self.peak_resident = 0        # max concurrent block-holding reqs
        self._shed_reason: Optional[str] = None
        self._watchdog = None
        self._worker: Optional["ServeWorker"] = None
        self._wake = threading.Event()
        self._tracer = None               # monitor.tracing.TraceRecorder
        self._slo = None                  # monitor.tracing.ServingSLO

    # -- placement ----------------------------------------------------

    def _place_params(self, params):
        """Best-effort TP placement: when a mesh with model > 1 is in
        scope, put each leaf at its GPT param_spec so the programs run
        Megatron-sharded; otherwise leave leaves where they are."""
        info = self.mesh_info
        if info is None:
            return params
        from ..comm.mesh import MODEL_AXIS

        if info.axis_size(MODEL_AXIS) <= 1:
            return params
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        try:
            return jax.tree_util.tree_map(
                lambda leaf, spec: jax.device_put(
                    leaf, NamedSharding(info.mesh, spec)),
                params, self.model.param_specs,
                is_leaf=lambda x: hasattr(x, "ndim"))
        except Exception as e:
            logger.warning(
                f"serving TP param placement failed ({e}); weights stay "
                f"replicated")
            return params

    # -- submission ---------------------------------------------------

    def submit(self, prompt: Sequence[int], max_new_tokens: int,
               temperature: float = 0.0, top_k: int = 0, seed: int = 0,
               eos_token: Optional[int] = None,
               session_id: Optional[Any] = None) -> Request:
        prompt = [int(t) for t in prompt]
        if not prompt:
            raise ValueError("prompt must be non-empty")
        if int(max_new_tokens) < 1:
            raise ValueError(
                f"max_new_tokens must be >= 1, got {max_new_tokens}")
        if len(prompt) + int(max_new_tokens) > self.max_seq_len:
            raise ValueError(
                f"prompt {len(prompt)} + max_new {max_new_tokens} "
                f"exceeds the engine's max_seq_len {self.max_seq_len}")
        if int(top_k) < 0 or float(temperature) < 0.0:
            raise ValueError(
                f"top_k must be >= 0 and temperature >= 0, got "
                f"{top_k}, {temperature}")
        for g in self.plan.groups:
            if session_id is not None and g.no_sessions:
                raise NotImplementedError(g.no_sessions)
        req = Request(prompt=prompt, max_new_tokens=int(max_new_tokens),
                      temperature=float(temperature), top_k=int(top_k),
                      seed=int(seed), eos_token=eos_token,
                      session_id=session_id)
        self.scheduler.submit(req)
        self._wake.set()
        return req

    # -- pinned sessions ----------------------------------------------

    @property
    def resident_sessions(self) -> int:
        return len(self._sessions)

    def session_active(self, sid) -> bool:
        """True while routing `sid` here still wins: a resident pin,
        or a live request carrying the session (whose natural finish
        will re-pin it).  Safe from any thread — the fleet router's
        affinity-staleness probe."""
        if sid in self._sessions:
            return True
        return self.scheduler.has_session(sid)

    def _session_lookup(self, req: Request):
        """Scheduler hook: the pin `req` can adopt, or None.  A pin is
        only served when its history is a PREFIX of the new prompt —
        anything else (edited history, expired TTL) releases the pin
        and falls back to chain-hash matching, which still catches the
        registered full blocks."""
        s = self._sessions.get(req.session_id)
        if s is None:
            return None
        n = len(s.tokens)
        if (s.expires <= self.clock() or n > len(req.prompt)
                or req.prompt[:n] != s.tokens):
            self.release_session(s.sid)
            return None
        return s

    def _session_consumed(self, req: Request, pin: SessionPin) -> None:
        """Scheduler hook: the pin's blocks now belong to `req`."""
        self._sessions.pop(pin.sid, None)

    def _pin_session(self, req: Request) -> None:
        """Keep a naturally-finished session request's blocks resident
        (one extra reference each) so turn k+1 re-prefills only its new
        tokens.  Called BEFORE scheduler.finish drops the request's own
        references — net effect: the blocks stay held by the pin."""
        sid = req.session_id
        old = self._sessions.pop(sid, None)
        if old is not None:
            self.kv.free(old.owner)
        owner = ("session", sid, req.rid)
        n = self.kv.pin(owner, req.rid)
        if not n:
            return
        self._sessions[sid] = SessionPin(
            sid=sid, owner=owner, tokens=req.prompt + req.out,
            cached_len=req.cached_len, blocks=n,
            expires=self.clock() + float(self.config.session_ttl_s))
        COUNTERS.add("kv.session_pins", nbytes=n)

    def release_session(self, sid) -> bool:
        """Drop a session's pin (its registered blocks stay matchable
        from the prefix LRU until evicted).  Returns True if held."""
        s = self._sessions.pop(sid, None)
        if s is None:
            return False
        self.kv.free(s.owner)
        return True

    def _expire_sessions(self) -> None:
        now = self.clock()
        for sid in [sid for sid, s in self._sessions.items()
                    if s.expires <= now]:
            self.release_session(sid)

    def _session_pressure_release(self) -> None:
        """KV-pressure valve: while admission is starving the queue
        head with a decode slot free (so the shortfall is blocks, not
        slots), release pinned sessions oldest-first and retry — a
        waiting request always outranks a resident session."""
        sch = self.scheduler
        while (sch.n_waiting and self._sessions
               and any(s is None for s in sch.slots)
               and not (sch.admission == "static"
                        and any(s is not None for s in sch.slots))):
            oldest = next(iter(self._sessions))
            self.release_session(oldest)
            if sch.admit():
                break

    # -- tracing / SLO telemetry --------------------------------------

    def attach_tracing(self, tracer=None, slo=None) -> None:
        """Attach a `monitor.tracing.TraceRecorder` and/or a
        `monitor.tracing.ServingSLO` aggregator.  The tracer records
        the per-request lifecycle (`queue_wait` at admission,
        `prefill_chunk` spans, a `first_token` instant, per-step
        `decode_step`/`verify_step` spans with batch occupancy and
        draft accept counts, `finish`/`shed` instants — all cat
        "serve") and the `serve.*` phases of every iteration;
        request-scoped events are sampled per rid, step spans and
        phases per engine step, so a loaded engine stays within the
        recorder's byte budget.  A `decode_step` runs from the step's
        launch to the end of its book-keeping one iteration later
        (under the next launch); its `rids` are the requests its
        tokens went to and `stamp_us` the instant they were stamped
        (`Request.token_times`), on the recorder's clock, as on
        `first_token`.  The SLO aggregator is fed UNSAMPLED
        (TTFT, tokens, queue depth, accept rate, sheds) and ticked at
        every step boundary so its windows never have sampling holes.
        When a watchdog is attached (before or after this call) the
        tracer's tail doubles as its trip-snapshot flight recorder.

        A tracer is also told, once and here, which instruction of each
        compiled program was written under which scope: one instant
        `program_scopes` a program (`_record_program_scopes`), never
        sampled out, for whoever reads a device trace of the run beside
        the recorder's file.  That compiles each program once more (a
        load where the persistent compile cache holds it); without a
        tracer nothing is lowered, compiled or parsed."""
        self._tracer = tracer
        self._slo = slo
        self.scheduler.tracer = tracer
        if slo is not None and getattr(slo, "tracer", None) is None:
            slo.tracer = tracer
        if tracer is not None and self._watchdog is not None:
            self._watchdog.set_flight_recorder(self._flight_tail)
        if tracer is not None:
            self._record_program_scopes(tracer)

    def _flight_tail(self) -> list:
        """The tracer's newest events as a watchdog's trip snapshot
        ships them: what the wedged step was doing, without the
        programs' maps (tens to hundreds of KB each, and no timeline)."""
        return [e for e in self._tracer.last_events()
                if e.get("name") != "program_scopes"]

    def _program_calls(self) -> dict:
        """{name: (jitted program, its arguments as the engine hands
        them over)} for the programs this engine runs — `prefill`, and
        `decode` and `seat`, or `verify` where it drafts — from what it
        holds: the weights and the cache as they lie, the slot state's
        rows, the schedule's sizes.  A description, so it can drift
        from what `_prefill_chunk`, the decode launch and `seat` pass:
        tests/test_program_scopes.py holds every argument's shape,
        dtype and weak type to a real call's, family by family."""
        c = self.config
        of = jax.ShapeDtypeStruct
        scalar = lambda dtype: of((), dtype)
        host = self._slots.host
        rows = {name: of(a.shape, a.dtype) for name, a in host.items()}
        tokens = of((c.max_batch,), jnp.int32)
        held = (self.params, self.kv.caches)
        # a request's table as `prefill` takes it: behind the entries of
        # layers with a state, the slot
        table = of((host["tables"].shape[1] + bool(self.kv.by_slot),),
                   jnp.int32)
        calls = {"prefill": (self.programs["prefill"], held + (
            of((1, c.prefill_chunk), jnp.int32), scalar(jnp.int32),
            scalar(jnp.int32), table, scalar(jnp.float32),
            scalar(jnp.int32), scalar(jnp.uint32)))}
        if self._serial:
            k = int(c.draft_len)
            calls["verify"] = (self.programs["verify"], held + (
                of((c.max_batch, k + 1), jnp.int32), rows["positions"],
                of((c.max_batch,), jnp.int32), rows["active"],
                rows["tables"], rows["temperatures"], rows["top_ks"],
                rows["seeds"]))
            return calls
        calls["decode"] = (self.programs["decode"],
                           held + (tokens,) + tuple(rows.values()))
        # `prefill`'s sample as it lies: two entries behind routed FFNs
        calls["seat"] = (self.programs["seat"], (
            tokens, scalar(jnp.int32),
            of((2,) if self._routed_layers else (), jnp.int32)))
        return calls

    def _record_program_scopes(self, tracer) -> None:
        """One `program_scopes` instant a program: `program`, the name
        the device trace's `XLA Modules` line gives its runs
        (`jit_decode`); `paths` and `instructions`, every instruction of
        the compiled module that was written under a scope as an index
        into the table of scope paths (monitor/tracing.py
        `program_scopes`, `pack_scopes`); `seconds`, what lowering,
        compiling (or loading) and parsing it took."""
        from ..monitor import tracing

        for name, (program, args) in self._program_calls().items():
            t0 = time.perf_counter()
            text = program.lower(*args).compile().as_text()
            packed = tracing.pack_scopes(tracing.program_scopes(text))
            tracer.instant(
                "program_scopes", "serve",
                program=tracing.program_name(text) or f"jit_{name}",
                seconds=round(time.perf_counter() - t0, 3), **packed)

    def _req_tracer(self, req: Request):
        """The tracer, iff this request's rid is sampled in."""
        tr = self._tracer
        if tr is not None and tr.sampled(f"rid:{req.rid}"):
            return tr
        return None

    # -- shedding (watchdog escalation target) ------------------------

    def request_shed(self, reason: str = "watchdog trip") -> None:
        """Flag the in-flight batch for shedding; safe from any thread
        (the watchdog's on_trip handler).  Consumed at the next point
        the engine thread is live — the requests wedged in the stuck
        step finish in state 'error', everything waiting proceeds."""
        self._shed_reason = str(reason)

    def _check_shed(self) -> bool:
        reason = self._shed_reason
        if reason is None:
            return False
        self._shed_reason = None
        # what is in flight is read first: once a victim's blocks are
        # handed back, no program launched for it is still to run
        self._settle()
        victims = self.scheduler.occupied()
        for req in victims:
            self._retire(req, ERROR, error=reason)
        if victims:
            COUNTERS.add("serve.shed", calls=len(victims))
            if self._slo is not None:
                self._slo.observe_shed(len(victims))
            if self._tracer is not None:
                self._tracer.instant("shed", "serve", n=len(victims),
                                     reason=reason)
            logger.error(
                f"serving: SHED {len(victims)} in-flight request(s) "
                f"({reason}); {self.kv.blocks_in_use} blocks still held, "
                f"{self.scheduler.n_waiting} waiting proceed")
        return bool(victims)

    def _retire(self, req: Request, state: str,
                error: Optional[str] = None) -> None:
        """Terminal transition of a request that holds a slot: slot and
        blocks go back now, and no later step decodes for the slot."""
        slot = req.slot
        self.kv.count_wraps(req.cached_len)
        self.scheduler.finish(req, state, error=error)
        if slot is not None:
            self._slots.set(slot, active=False, tables=TRASH_BLOCK)

    # -- the serving loop body ----------------------------------------

    def step(self) -> bool:
        """One engine iteration: admit -> prefill chunk round -> launch
        the next decode step -> read what was launched before it.
        Returns True when any work was done (callers idle otherwise)."""
        fault_point("serve.step")
        self._check_shed()
        if self._watchdog is not None:
            self._watchdog.beat(self.steps)
        fault_point("serve.admit")
        tr = self._step_tracer()
        with phase("serve.admit", tr):
            self._expire_sessions()
            self.scheduler.admit()
            self._session_pressure_release()
        if self._slo is not None:
            # depth AFTER admission = backlog the cache/slots could not
            # absorb this step, the saturation signal SLO windows want
            self._slo.observe_queue_depth(self.scheduler.n_waiting)
        did = bool(self._unread)
        for req in self.scheduler.prefilling()[
                :self.config.max_prefill_chunks_per_step]:
            fault_point("serve.prefill")
            if self._check_shed():
                return True
            with phase("serve.prefill.launch", tr):
                self._prefill_chunk(req)
            if self._serial:    # drafting reads the first token
                self._settle()
            did = True
        ahead = None
        lanes = self._lanes()
        if lanes:
            fault_point("serve.decode")
            if self._check_shed():
                return True
            if self._serial:
                self._verify_step(lanes)
            else:
                with phase("serve.decode.launch", tr):
                    ahead = self._launch_decode(lanes)
            did = True
        # the one blocking read: the step before, while `ahead` runs
        self._settle(keep=ahead)
        if ahead is not None and not self._lanes() and not \
                self.scheduler.prefilling() and not self.scheduler.n_waiting:
            # nothing is left to launch behind it: wait for it now
            self._settle()
        if did:
            self.steps += 1
            self.kv.sample_occupancy()
            self.peak_blocks_in_use = max(self.peak_blocks_in_use,
                                          self.kv.blocks_in_use)
            self.peak_resident = max(self.peak_resident,
                                     len(self.scheduler.occupied()))
            if self._slo is not None:
                self._slo.tick()
        return did

    def _lanes(self) -> List[Request]:
        """The running requests the next decode step decodes for: not
        those whose last token a launched step already computes."""
        active = self._slots.host["active"]
        return [r for r in self.scheduler.running() if active[r.slot]]

    def has_work(self) -> bool:
        return (self.scheduler.has_work() or bool(self._unread)
                or self._shed_reason is not None)

    def run(self) -> None:
        """Drive step() until every submitted request is terminal and
        nothing launched is unread."""
        while self.has_work():
            self.step()

    def generate(self, prompts: Sequence[Sequence[int]],
                 max_new_tokens: int, temperature: float = 0.0,
                 top_k: int = 0, seeds: Optional[Sequence[int]] = None,
                 eos_token: Optional[int] = None) -> List[List[int]]:
        """Synchronous convenience: submit all, run to completion,
        return the token lists (raises if any request errored)."""
        reqs = [self.submit(p, max_new_tokens, temperature=temperature,
                            top_k=top_k,
                            seed=(seeds[i] if seeds is not None else 0),
                            eos_token=eos_token)
                for i, p in enumerate(prompts)]
        self.run()
        for r in reqs:
            if r.state == ERROR:
                raise RuntimeError(f"request {r.rid} failed: {r.error}")
        return [r.out for r in reqs]

    # -- phases --------------------------------------------------------

    def _prefill_chunk(self, req: Request) -> None:
        C = self.config.prefill_chunk
        chunk = req.prompt[req.prefill_pos:req.prefill_pos + C]
        n_valid = len(chunk)
        pos0 = req.prefill_pos
        tr = self._req_tracer(req)
        tus0 = tr.now_us() if tr is not None else 0
        tokens = np.zeros((1, C), np.int32)
        tokens[0, :n_valid] = chunk
        self._take_blocks(req, pos0, pos0 + n_valid)
        table = req.table
        if self.kv.by_slot:
            if pos0 == 0:   # seated: the slot's last tenant's arrays go
                with phase("serve.state.reset", self._step_tracer()):
                    self.kv.reset_state(req.slot)
            # behind the table's entries: where the request's slot lies
            table = np.append(table, np.int32(req.slot))
        tok, _logits, caches = self.programs["prefill"](
            self.params, self.kv.caches, jnp.asarray(tokens),
            np.int32(req.prefill_pos), np.int32(n_valid),
            jnp.asarray(table), np.float32(req.temperature),
            np.int32(req.top_k), np.uint32(req.seed))
        self.kv.caches = caches
        req.prefill_pos += n_valid
        req.cached_len = req.prefill_pos
        self._close_full_window(req)
        COUNTERS.add("serve.prefill_chunks", nbytes=n_valid)
        for kind, counted in self._counted.items():
            if KINDS[kind].chunk:
                # the chunk's last position is its padded tail's
                KINDS[kind].chunk(counted, np.array([pos0 + C]), C)
        self._count_assignments(n_valid)
        if self._routed_layers and req.prefill_pos < len(req.prompt):
            # behind the sample nobody reads: the rows the chunk's routed
            # products multiplied, read when the last chunk's sample is
            tok.copy_to_host_async()
            req.chunk_counts.append(tok)
        if tr is not None:
            # cached/computed: the prefix-cache outcome per request —
            # how many prompt tokens this request never prefilled
            tr.add_complete("prefill_chunk", "serve", ts_us=tus0,
                            dur_us=tr.now_us() - tus0, rid=req.rid,
                            pos=pos0, n=n_valid,
                            cached=req.prefix_cached_tokens,
                            computed=(len(req.prompt)
                                      - req.prefix_cached_tokens))
        if req.prefill_pos < len(req.prompt):
            return
        # final chunk committed: publish the prompt's full blocks under
        # their chain hashes.  Pin-adopted requests carry NO hashes
        # (scheduler._try_alloc): their prefill attended over
        # decode-written rows, so nothing they wrote is safe to serve
        # to third parties.  `start` skips the already-registered
        # matched prefix.
        if req.block_hashes:
            start = -(-req.prefix_cached_tokens // self.kv.block_size)
            self.kv.register_prefix(req.rid, req.block_hashes, start)
        # the program sampled the request's FIRST token: it joins the
        # decode batch from the device, and is read behind the step
        # launched next (at once where drafting needs it on the host)
        req.state = RUNNING
        rides = req.max_new_tokens > 1
        self._slots.set(
            req.slot, active=rides, tables=req.table,
            # the first decode step writes this token's K/V at position P
            positions=len(req.prompt), temperatures=req.temperature,
            top_ks=req.top_k, seeds=np.uint32(req.seed))
        self._unread.append(_First(req, tok))
        if rides and not self._serial:
            self._tokens = self.programs["seat"](
                self._tokens, np.int32(req.slot), tok)

    def _launch_decode(self, lanes: List[Request]) -> _Step:
        """Launch one decode step for `lanes` from the state as it lies
        on the device — each slot's last token is the step before's
        sample, or `seat`'s — and decide from positions alone, in
        launch order, what the step after it will see: who has had its
        last token computed, which window this step fills."""
        tr = self._tracer
        step = _Step(toks=None, lanes=[(r, r.slot) for r in lanes],
                     tus0=tr.now_us() if tr is not None else 0,
                     index=self.steps)
        state = self._slots
        positions = state.host["positions"]
        slots = [r.slot for r in lanes]
        for req in lanes:
            p = int(positions[req.slot])
            if self._take_blocks(req, p, p + 1):
                state.set(req.slot, tables=req.table)
        self._count_step(lanes, 1)
        COUNTERS.add("serve.decode_ahead", nbytes=int(
            any(isinstance(u, _Step) for u in self._unread)))
        # the predicate `decode` picks its sampling tail by, from the
        # rows it is handed
        COUNTERS.add("serve.sample.greedy_steps", nbytes=int(not np.any(
            state.host["active"] & (state.host["temperatures"] > 0))))
        with phase("serve.decode.upload", self._step_tracer()):
            rows = state.on_device()
        step.toks, self.kv.caches, (self._tokens, moved) = \
            self.programs["decode"](self.params, self.kv.caches,
                                    self._tokens, *rows)
        state.advanced(slots, moved)
        for req in lanes:
            req.cached_len += 1
            if req.cached_len >= len(req.prompt) + req.max_new_tokens - 1:
                state.set(req.slot, active=False)
            else:
                self._close_full_window(req)
        self._unread.append(step)
        return step

    def _settle(self, keep: Optional[_Step] = None) -> None:
        """Read, in launch order, everything launched and not yet read
        — up to `keep`, the step just launched, which stays in flight."""
        while self._unread and self._unread[0] is not keep:
            item = self._unread.popleft()
            if isinstance(item, _First):
                self._read_first(item)
            else:
                self._read_step(item)

    def _read_first(self, item: _First) -> None:
        req = item.req
        if req.done:                    # shed before its token was read
            return
        phases = self._step_tracer()
        with phase("serve.read", phases):
            if self._routed_layers:
                # every chunk's [sample, rows multiplied], the last last
                chunks = [np.asarray(t) for t in (*req.chunk_counts,
                                                  item.tok)]
                first = int(chunks[-1][0])
            else:
                first = int(item.tok)
        with phase("serve.bookkeep", phases):
            if self._routed_layers:
                COUNTERS.add(
                    "serve.moe.prefill_rows_multiplied",
                    calls=len(chunks) * self._routed_layers,
                    nbytes=sum(int(c[1]) for c in chunks))
                req.chunk_counts = []
            tr = self._req_tracer(req)
            now = self.clock()
            stamp_us = tr.now_us() if tr is not None else 0
            req.t_first_token = now
            req.token_times.append(now)
            req.out.append(first)
            COUNTERS.add("serve.tokens")
            COUNTERS.add("serve.ttft_ms", nbytes=int(req.ttft_s * 1e6))
            if self._slo is not None:
                self._slo.observe_ttft(req.ttft_s)
            if tr is not None:
                tr.instant("first_token", "serve", rid=req.rid,
                           ttft_ms=round(req.ttft_s * 1e3, 3),
                           stamp_us=stamp_us)
            if self._serial:
                self._tokens[req.slot] = first
            if self._is_finished(req, first):
                self._finish(req)

    def _read_step(self, step: _Step) -> None:
        """The host's half of a decode step, while the step after it
        runs: append, stamp, finish, free.  A request that ended at the
        step before (its `eos_token`, found one step late) rode this
        one too: that lane's token is dropped."""
        phases = self._step_tracer()
        with phase("serve.read", phases):
            toks = np.asarray(step.toks)
        with phase("serve.bookkeep", phases):
            tr = self._tracer
            if tr is not None and not tr.sampled(f"step:{step.index}"):
                tr = None
            now = self.clock()
            stamp_us = tr.now_us() if tr is not None else 0
            COUNTERS.add("serve.decode_steps", nbytes=len(step.lanes))
            if self._routed_layers:
                # behind the slots' tokens: the experts the step touched
                self._count_assignments(len(step.lanes))
                touched = int(toks[self.config.max_batch])
                COUNTERS.add("serve.moe.experts_touched",
                             calls=self._routed_layers, nbytes=touched)
                COUNTERS.add("serve.moe.experts_streamed",
                             calls=self._routed_layers,
                             nbytes=touched if self._streams_all is None
                             else self._streams_all * self._routed_layers)
            rids = []
            for req, slot in step.lanes:
                if req.done:
                    COUNTERS.add("serve.decode_ahead.dropped")
                    continue
                tok = int(toks[slot])
                req.out.append(tok)
                req.token_times.append(now)
                rids.append(req.rid)
                COUNTERS.add("serve.tokens")
                if self._is_finished(req, tok):
                    self._finish(req)
            if self._slo is not None:
                self._slo.observe_tokens(len(rids))
            if tr is not None:
                tr.add_complete("decode_step", "serve", ts_us=step.tus0,
                                dur_us=tr.now_us() - step.tus0,
                                step=step.index, batch=len(step.lanes),
                                rids=rids, stamp_us=stamp_us)

    def _count_assignments(self, n_tokens: int) -> None:
        """Token-expert pairs a call over `n_tokens` tokens computes in
        its routed layers: every one the router makes.  Of a share of
        the experts only the program knows how many it held: nothing
        is counted."""
        if self._routed_layers and not self._held_share:
            COUNTERS.add(
                "serve.moe.assignments", calls=self._routed_layers,
                nbytes=n_tokens * self._top_k * self._routed_layers)

    def _count_step(self, lanes: List[Request], n_queries: int) -> None:
        """Count, kind by kind of the plan's groups, what a step reads
        whose lanes reach their position + `n_queries` rows each."""
        held = self._slots.host["positions"][
            [r.slot for r in lanes]].astype(np.int64) + n_queries
        for kind, counted in self._counted.items():
            if KINDS[kind].step:
                KINDS[kind].step(counted, held)

    # -- host book-keeping at step boundaries ----------------------------

    def _take_blocks(self, req: Request, start: int, stop: int) -> bool:
        """Before a program writes positions [start, stop): take the
        blocks they need of the runs that are not handed out at
        admission.  -> whether the request's table changed."""
        table = self.kv.extend(req.rid, start, stop)
        if table is not None:
            req.table = table
        return table is not None

    def _close_full_window(self, req: Request) -> None:
        """After a program that writes up to `req.cached_len` was
        launched: if that fills the request's window, give its exact
        blocks back to the free list; its summary rows stay and the
        next query, in the next window, sees them.  Whatever takes the
        blocks next is launched behind the program that last read them.
        The slot's row of the decode state keeps them until the
        request's next step takes its table anew, as every step does."""
        if not self.kv.window_full(req.cached_len):
            return
        tr = self._req_tracer(req)
        tus0 = tr.now_us() if tr is not None else 0
        back = self.kv.close_window(req.rid)
        if tr is not None:
            tr.add_complete("eva.window_close", "serve", ts_us=tus0,
                            dur_us=tr.now_us() - tus0, rid=req.rid,
                            blocks=back, cached=req.cached_len)

    def _step_tracer(self):
        """The tracer, iff this engine step's index is sampled in
        (decode/verify spans are per-step, not per-request)."""
        tr = self._tracer
        if tr is not None and tr.sampled(f"step:{self.steps}"):
            return tr
        return None

    def _record_dequant(self, t0: float) -> None:
        """`kv.dequant_ms` (µs-in-bytes): wall time of the serial
        loop's `verify` dispatches against a QUANTIZED cache — the
        in-program dequantize is XLA-fused into the attention gather,
        so the honest measurement is the whole dispatch, launch to
        tokens read; A/B against the dense-kv lane of the same bench
        isolates the dequant cost.  The loop that runs ahead records
        none: there launch to read spans the step before too."""
        if self.kv.quant_wire:
            COUNTERS.add("kv.dequant_ms",
                         nbytes=int((time.perf_counter() - t0) * 1e6))

    # -- speculative decoding -----------------------------------------

    def _propose_draft(self, req: Request) -> List[int]:
        """Self-speculative n-gram draft, host-side, no extra model:
        find the most recent EARLIER occurrence of the request's last
        `spec_ngram` tokens in its own prompt + output and propose the
        continuation that followed it (falling back to repeating the
        last token).  Clamped so drafts never run past max_new_tokens
        or the request's ALLOCATED cache rows — the verify program
        writes candidate K/V at positions P+1..P+k, and every one of
        those rows must be backed by a real block."""
        c = self.config
        P = int(self._slots.host["positions"][req.slot])
        alloc_rows = len(self.kv.blocks_of(req.rid)) * self.kv.block_size
        k = min(int(c.draft_len),
                req.max_new_tokens - len(req.out) - 1,
                alloc_rows - 1 - P)
        if k <= 0:
            return []
        ctx = req.prompt + req.out
        n = min(int(c.spec_ngram), len(ctx))
        suffix = ctx[-n:]
        # Prefer the LATEST earlier occurrence whose continuation is a
        # full k tokens.  Once greedy output settles into a short cycle
        # (the common repetitive-suffix case), the nearest match sits
        # only cycle-length before the tail, so its continuation is
        # truncated by end-of-context and the draft collapses to ~1
        # token even at 100% acceptance.  An earlier full-window match
        # carries the same cycle with k tokens of runway.  If every
        # match is tail-truncated, keep the longest continuation seen.
        best: List[int] = []
        for j in range(len(ctx) - n - 1, -1, -1):
            if ctx[j:j + n] == suffix:
                d = ctx[j + n:j + n + k]
                if len(d) >= k:
                    return [int(t) for t in d]
                if len(d) > len(best):
                    best = [int(t) for t in d]
        if best:
            return best
        return [int(ctx[-1])] * k

    def _verify_step(self, running: List[Request]) -> None:
        """One speculative step for every running slot: propose up to
        draft_len candidates, score all draft_len+1 positions in ONE
        batched target forward, accept the longest matching prefix and
        emit the target's own sample as the bonus/correction token.

        Greedy pinning: verify samples every position with the same
        position-keyed RNG rule as sequential decode, so the emitted
        stream is token-identical to the non-speculative engine (and,
        at dense KV, to `generate()`) no matter how many drafts hit.
        Rollback is a host-side rewind: rejected rows' K/V stay stale
        in the cache but their positions are >= the rewound front, so
        they are re-written (same scatter rows) before any later
        query's causal mask can attend them — no scatter undo."""
        R = self.config.max_batch
        k = int(self.config.draft_len)
        state = self._slots.host
        tr = self._step_tracer()
        tus0 = tr.now_us() if tr is not None else 0
        with phase("serve.draft", tr):
            drafts = np.zeros((R, k), np.int32)
            n_draft = np.zeros((R,), np.int32)
            for req in running:
                d = self._propose_draft(req)
                n_draft[req.slot] = len(d)
                if d:
                    drafts[req.slot, :len(d)] = d
                    COUNTERS.add("serve.draft_tokens", calls=len(d))
        with phase("serve.decode.launch", tr):
            tokens = np.concatenate([self._tokens[:, None], drafts], axis=1)
            self._count_step(running, k + 1)
            t0 = time.perf_counter()
            toks, caches = self.programs["verify"](
                self.params, self.kv.caches, jnp.asarray(tokens),
                jnp.asarray(state["positions"]), jnp.asarray(n_draft),
                *(jnp.asarray(state[name]) for name in (
                    "active", "tables", "temperatures", "top_ks", "seeds")))
            self.kv.caches = caches
        with phase("serve.read", tr):
            toks = np.asarray(toks)                 # [R, draft_len + 1]
        with phase("serve.bookkeep", tr):
            self._record_dequant(t0)
            self._emit_verified(running, toks, drafts, n_draft, tr, tus0)

    def _emit_verified(self, running: List[Request], toks, drafts, n_draft,
                       tr, tus0: int) -> None:
        """The host's half of a verify step: per slot, accept while
        draft i matches the target's sample for the same position; the
        first sample past the matching prefix is the bonus (all
        accepted) or the correction (a draft rejected)."""
        state = self._slots.host
        now = self.clock()
        COUNTERS.add("serve.decode_steps", nbytes=len(running))
        tot_emitted = 0
        tot_accepted = 0
        for req in running:
            slot = req.slot
            nd = int(n_draft[slot])
            m = 0
            while m < nd and int(drafts[slot, m]) == int(toks[slot, m]):
                m += 1
            emitted = 0
            finished = False
            for i in range(m + 1):
                tok = int(toks[slot, i])
                req.out.append(tok)
                req.token_times.append(now)
                req.cached_len += 1
                emitted += 1
                COUNTERS.add("serve.tokens")
                if self._is_finished(req, tok):
                    finished = True
                    break
            if emitted > 1:
                # emitted - 1 DRAFT tokens were accepted and used (the
                # final emitted token is always the target's own)
                COUNTERS.add("serve.accepted_tokens", calls=emitted - 1)
                tot_accepted += emitted - 1
            tot_emitted += emitted
            if finished:
                self._finish(req)
            else:
                self._tokens[slot] = int(toks[slot, emitted - 1])
                state["positions"][slot] += emitted
        if self._slo is not None:
            self._slo.observe_tokens(tot_emitted)
            self._slo.observe_accept(tot_accepted, int(n_draft.sum()))
        if tr is not None:
            tr.add_complete("verify_step", "serve", ts_us=tus0,
                            dur_us=tr.now_us() - tus0, step=self.steps,
                            batch=len(running),
                            drafted=int(n_draft.sum()),
                            accepted=tot_accepted)

    def _is_finished(self, req: Request, last_tok: int) -> bool:
        if req.eos_token is not None and last_tok == req.eos_token:
            return True
        return len(req.out) >= req.max_new_tokens

    def _finish(self, req: Request) -> None:
        COUNTERS.add("serve.requests", nbytes=len(req.out))
        tr = self._req_tracer(req)
        if tr is not None:
            tr.instant("finish", "serve", rid=req.rid,
                       tokens=len(req.out))
        # the rows that hold the answer: where the end was found one step
        # late, a launched step wrote one more, and it lies beyond them
        req.cached_len = len(req.prompt) + len(req.out) - 1
        if req.session_id is not None and self.config.prefix_cache:
            self._pin_session(req)
        self._retire(req, FINISHED)

    # -- watchdog / worker integration ---------------------------------

    def attach_watchdog(self, watchdog) -> None:
        """Register with a runtime.resilience.StepWatchdog: the engine
        beats it at every step boundary and its serving worker thread
        (when one is attached) reports as the 'serving' thread group in
        trip snapshots.  Wire the watchdog's `on_trip` to
        `request_shed` to get shed-instead-of-hang behavior.

        Idle semantics: a ServeWorker beats the watchdog from its idle
        loop too (no traffic != wedged).  When driving step() yourself
        without a worker, either keep calling step()/beating during
        quiet periods or only arm the watchdog while work is in
        flight."""
        self._watchdog = watchdog
        if self._tracer is not None:
            watchdog.set_flight_recorder(self._flight_tail)
        watchdog.register_threads(
            "serving",
            lambda: [t for t in (self._worker,)
                     if t is not None and t.is_alive()])

    def abort(self, error: str) -> None:
        """Make every request that is not terminal end in state 'error'
        — waiting, prefilling, running — with its blocks handed back,
        after what is in flight for them has run and been read.  Where
        that read fails too (the device, under a loop that died of it)
        the failure is logged and what was in flight is dropped."""
        try:
            self._settle()
        except Exception as e:  # noqa: BLE001 — must still release
            logger.error(f"serving: reading what was in flight failed "
                         f"({type(e).__name__}: {e}); dropped")
            self._unread.clear()
        self.scheduler.abort_waiting(error)
        for req in self.scheduler.occupied():
            self._retire(req, ERROR, error=error)

    def close(self) -> None:
        """Release sessions, stop the worker, read what is in flight and
        end every request that is not terminal in state 'error'."""
        for sid in list(self._sessions):
            self.release_session(sid)
        try:
            if self._worker is not None:
                self._worker.stop()
                self._worker = None
        finally:
            self.abort("engine closed")
            if self._watchdog is not None:
                self._watchdog.unregister_threads("serving")
                self._watchdog = None


class ServeWorker(threading.Thread):
    """Daemon thread driving engine.step() while work is pending —
    what the bench (and a real frontend) runs so submission and
    decoding overlap.  Exceptions terminate every in-flight and
    waiting request loudly (state 'error'), never silently."""

    def __init__(self, engine: ServeEngine, idle_wait_s: float = 0.002):
        super().__init__(name="dstpu-serve-worker", daemon=True)
        self.engine = engine
        self.idle_wait_s = float(idle_wait_s)
        self._halt = threading.Event()
        self.error: Optional[BaseException] = None
        engine._worker = self

    def run(self) -> None:
        eng = self.engine
        try:
            while not self._halt.is_set():
                if eng.has_work():
                    eng.step()
                else:
                    # idle is not wedged: keep beating the watchdog so
                    # a quiet traffic period never trips it.  A truly
                    # wedged step blocks THIS thread inside step(), so
                    # the idle beat can never mask a real hang.
                    with phase("serve.idle", eng._step_tracer()):
                        if eng._watchdog is not None:
                            eng._watchdog.beat(eng.steps)
                        eng._wake.wait(self.idle_wait_s)
                        eng._wake.clear()
        except BaseException as e:  # noqa: BLE001 — reported, not hidden
            self.error = e
            logger.error(f"serving worker died: {type(e).__name__}: {e}")
            eng.abort(f"worker died: {e}")

    def stop(self, timeout: float = 10.0) -> None:
        self._halt.set()
        self.engine._wake.set()
        self.join(timeout=timeout)
        if self.error is not None:
            raise RuntimeError(
                f"serving worker failed: {self.error}") from self.error
