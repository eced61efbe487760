"""Candidate generation over the validated comm/zero config space.

The generator composes knob mutations mechanically (cartesian product
over gradient reduction, per-level wire dtypes, hierarchy factors,
overlap, bucket size, quant block) and then runs EVERY composition
through `config.DeepSpeedCommConfig` — the same validator a user config
passes at initialize().  Whatever the validator rejects (an int8 inner
wire on the scatter level, a non-dividing hierarchy factor, a typo'd
dtype) is pruned before a single probe runs, and counted, so the search
space can never drift from what the engine actually accepts.

Candidate scopes:

  live    a StepBuilder program rebuild on a RUNNING engine can serve
          it (wire dtypes, bucket size, overlap on/off, implicit vs
          bucketed).  The PR-10 mid-run demotion path is the existence
          proof that live rebuilds are safe and bitwise.
  engine  needs a fresh engine build — the data-axis factorization IS
          the mesh layout every array placement derives from
          (engine.allreduce_gradients documents the same boundary), so
          hierarchy mutations only probe through an engine factory
          (tools/autotune_bench.py) and never online.
  serve   inference-side knobs (KV cache storage dtype, speculative
          draft length, prefix-cache enable / min match blocks /
          session TTL) for a ServeEngine.  The `comm` field carries a
          "serving"-block fragment instead, validated through the REAL
          `DeepSpeedServingConfig` by `generate_serve_candidates`; every
          serve candidate needs a fresh ServeEngine (the KV pool layout
          and the verify program are compile-time), so tools/serve_bench
          is the probe harness, never the online loop.

`safe_numerics`: True when swapping to the candidate preserves the
repo's bitwise loss contract on this fabric — every wire level fp32
(implicit psum == bucketed fold == overlap combine, elementwise, pinned
since PR 3/9; bucket size only re-partitions the same elementwise
fold).  Compressed wires (bf16/split/int8/int4) change rounding and are
probe-only by default for the ONLINE retune loop, which pins loss
parity across its swaps.
"""

from __future__ import annotations

from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

# knob fields a 1-knob neighborhood distance is measured over
_KNOB_FIELDS = ("gradient_reduction", "wire_dtype", "wire_dtype_inner",
                "wire_dtype_outer", "hierarchy", "overlap",
                "reduce_bucket_size", "quant_block_size")

# the serve scope's knob fields (Candidate.comm carries a "serving"
# fragment there; see generate_serve_candidates)
_SERVE_KNOB_FIELDS = ("kv_dtype", "draft_len", "prefix_cache",
                      "min_match_blocks", "session_ttl_s")


class Candidate(NamedTuple):
    """One point in the legal config space."""

    name: str
    comm: Dict            # "comm"-block fragment the engine applies
    #                       ("serving" fragment when scope == "serve")
    stage: int = 0        # ZeRO stage the legality check ran against
    scope: str = "live"   # "live" | "engine" | "serve"
    safe_numerics: bool = True

    def knobs(self) -> Dict:
        """Comparable knob view (absent keys normalized) — the
        neighborhood distance and ledger entries read this."""
        c = self.comm
        if self.scope == "serve":
            spec = c.get("speculative") or {}
            pfx = c.get("prefix_cache") or {}
            return {
                "kv_dtype": c.get("kv_dtype") or "dense",
                "draft_len": (int(spec.get("draft_len", 0))
                              if spec.get("enabled") else 0),
                "prefix_cache": bool(pfx.get("enabled", True)),
                "min_match_blocks": int(pfx.get("min_match_blocks", 1)),
                "session_ttl_s": float(pfx.get("session_ttl_s", 120.0)),
            }
        hier = c.get("hierarchy", "none")
        if isinstance(hier, dict):
            hier = hier.get("outer", 1)
        return {
            "gradient_reduction": c.get("gradient_reduction", "implicit"),
            "wire_dtype": c.get("wire_dtype", "fp32"),
            "wire_dtype_inner": c.get("wire_dtype_inner"),
            "wire_dtype_outer": c.get("wire_dtype_outer"),
            "hierarchy": hier,
            "overlap": c.get("overlap", "none"),
            "reduce_bucket_size": c.get("reduce_bucket_size"),
            "quant_block_size": c.get("quant_block_size"),
        }

    def describe(self) -> str:
        k = self.knobs()
        if self.scope == "serve":
            parts = [f"kv {k['kv_dtype']}"]
            if k["draft_len"]:
                parts.append(f"spec draft {k['draft_len']}")
            if not k["prefix_cache"]:
                parts.append("prefix off")
            elif k["min_match_blocks"] != 1:
                parts.append(f"prefix match>={k['min_match_blocks']}")
            return f"{self.name}: " + ", ".join(parts)
        parts = [k["gradient_reduction"]]
        if k["gradient_reduction"] == "bucketed":
            if k["hierarchy"] not in ("none", 1):
                parts.append(f"hier outer={k['hierarchy']} "
                             f"{k['wire_dtype_inner'] or k['wire_dtype']}/"
                             f"{k['wire_dtype_outer'] or k['wire_dtype']}")
            else:
                parts.append(f"wire {k['wire_dtype']}")
            if k["reduce_bucket_size"]:
                parts.append(f"bucket {k['reduce_bucket_size']}")
        if k["overlap"] not in ("none", None):
            parts.append("overlap")
        return f"{self.name}: " + ", ".join(parts)


# knobs where None means "inherit the incumbent's value" (probe.
# apply_candidate setdefaults them) — a wildcard, not a difference
_OPTIONAL_KNOBS = ("wire_dtype_inner", "wire_dtype_outer",
                   "reduce_bucket_size", "quant_block_size")


def _scope_family(c: Candidate) -> str:
    """Knob-space family: "serve" candidates live in their own space;
    "live"/"engine" share the train-side comm space."""
    return "serve" if c.scope == "serve" else "train"


def knob_distance(a: Candidate, b: Candidate) -> int:
    """How many knob fields differ between two candidates.  Optional
    knobs compare as equal when either side leaves them unspecified
    (None = inherit)."""
    if _scope_family(a) != _scope_family(b):
        # candidates from different scope families live in disjoint
        # spaces — farther apart than any same-family pair can be
        return len(_KNOB_FIELDS) + len(_SERVE_KNOB_FIELDS)
    ka, kb = a.knobs(), b.knobs()
    if a.scope == "serve":
        return sum(1 for f in _SERVE_KNOB_FIELDS if ka[f] != kb[f])
    dist = 0
    for f in _KNOB_FIELDS:
        if f in _OPTIONAL_KNOBS and (ka[f] is None or kb[f] is None):
            continue
        if ka[f] != kb[f]:
            dist += 1
    return dist


def neighborhood(current: Candidate, candidates: Sequence[Candidate],
                 radius: int = 1) -> List[Candidate]:
    """The bounded re-probe set the online retune loop walks: every
    candidate within `radius` knob mutations of `current` (current
    itself excluded — the retuner re-probes it separately as the
    baseline)."""
    return [c for c in candidates
            if c.name != current.name
            and knob_distance(current, c) <= radius]


def _is_legal(comm: Dict, stage: int, dp: Optional[int]) -> bool:
    """Run one composed comm block through the REAL config validator —
    the pruning the tentpole exists for.  Anything DeepSpeedCommConfig
    raises on at parse time is illegal here too."""
    from ..config import DeepSpeedCommConfig
    from ..zero.config import DeepSpeedZeroConfig

    zc = DeepSpeedZeroConfig({"zero_optimization": {"stage": stage}})
    try:
        DeepSpeedCommConfig({"comm": dict(comm)}, zc, world_size=dp)
    except ValueError:
        return False
    return True


def _name(reduction: str, wire: str, inner: Optional[str],
          outer_dtype: Optional[str], hier, overlap: bool,
          bucket: Optional[int], block: Optional[int]) -> str:
    if reduction == "implicit":
        return "implicit" + ("_overlap" if overlap else "")
    parts = []
    if hier in ("none", None, 1):
        parts.append(f"flat_{wire}")
    else:
        parts.append(f"hier{hier}_{inner or 'fp32'}_"
                     f"{outer_dtype or wire}")
    if bucket:
        parts.append(f"b{bucket}")
    if block:
        parts.append(f"q{block}")
    if overlap:
        parts.append("overlap")
    return "_".join(parts)


def _safe(wires: Sequence[Optional[str]]) -> bool:
    return all(w in (None, "fp32") for w in wires)


def generate_candidates(
        dp: int,
        stage: int = 0,
        current_outer: int = 1,
        wire_dtypes: Sequence[str] = ("fp32", "bf16", "int8"),
        inner_dtypes: Sequence[Optional[str]] = (None,),
        outers: Optional[Sequence[int]] = None,
        overlap: Sequence[bool] = (False, True),
        include_implicit: bool = True,
        bucket_sizes: Sequence[int] = (),
        quant_blocks: Sequence[int] = (),
) -> Tuple[List[Candidate], int]:
    """Enumerate the legal candidate set for a dp-wide data axis.

    Returns (candidates, n_rejected) where n_rejected counts the
    compositions the config validators pruned (the `autotune.rejected`
    counter).  `outers=None` derives every proper divisor of `dp`;
    hierarchy factors other than `current_outer` come out scope
    "engine" (the factorization is the mesh layout — live rebuilds
    cannot change it).  Structural no-ops are skipped rather than
    rejected: overlap over the implicit wire would fall back with a
    log, not probe anything new."""
    if outers is None:
        outers = [d for d in range(2, dp) if dp % d == 0]
    hierarchies: List = ["none"] + [o for o in outers if o > 1]

    seen = set()
    out: List[Candidate] = []
    rejected = 0

    def add(reduction, wire, inner, outer_dtype, hier, ov, bucket, block):
        nonlocal rejected
        comm: Dict = {"gradient_reduction": reduction}
        if reduction == "bucketed":
            comm["wire_dtype"] = wire
            if hier != "none":
                comm["hierarchy"] = {"outer": int(hier)}
                if inner is not None:
                    comm["wire_dtype_inner"] = inner
                if outer_dtype is not None:
                    comm["wire_dtype_outer"] = outer_dtype
            if bucket is not None:
                comm["reduce_bucket_size"] = int(bucket)
            if block is not None:
                comm["quant_block_size"] = int(block)
        comm["overlap"] = "on" if ov else "none"
        name = _name(reduction, wire, inner, outer_dtype, hier, ov,
                     bucket, block)
        if name in seen:
            return
        seen.add(name)
        if not _is_legal(comm, stage, dp):
            rejected += 1
            return
        hier_outer = 1 if hier == "none" else int(hier)
        scope = "live" if hier_outer == int(current_outer) else "engine"
        out.append(Candidate(
            name=name, comm=comm, stage=stage, scope=scope,
            safe_numerics=_safe((wire, inner, outer_dtype))))

    if include_implicit:
        # the naive default: one psum per leaf, nothing overlapped —
        # the config every search is expected to beat (or honestly
        # confirm on fabrics where XLA's in-program psum wins)
        add("implicit", "fp32", None, None, "none", False, None, None)

    buckets: List[Optional[int]] = [None] + [int(b) for b in bucket_sizes]
    blocks: List[Optional[int]] = [None] + [int(q) for q in quant_blocks]
    for wire in wire_dtypes:
        for hier in hierarchies:
            inner_set = inner_dtypes if hier != "none" else (None,)
            outer_set = ([wire] if hier != "none" else [None])
            for inner in inner_set:
                for outer_dtype in outer_set:
                    # on hierarchical candidates the SLOW hop carries
                    # the compression and the fast hop defaults exact —
                    # wire_dtype itself stays fp32 there so the flat
                    # fallback (if hierarchy disengages) is the safe one
                    flat_wire = "fp32" if hier != "none" else wire
                    for ov in overlap:
                        for bucket in buckets:
                            for block in blocks:
                                if block is not None and not any(
                                        w in ("int8", "int4") for w in
                                        (flat_wire, inner, outer_dtype)):
                                    continue  # block only moves quant wires
                                add("bucketed", flat_wire, inner,
                                    outer_dtype, hier, ov, bucket, block)
    return out, rejected


def _serve_fragment(kv_dtype, draft_len: int, prefix_cache: bool = True,
                    min_match_blocks: int = 1,
                    session_ttl_s: float = 120.0) -> Dict:
    """The "serving"-block fragment a serve-scope knob point maps to —
    the exact dict a user would write under "serving" in their config,
    so validating it validates the real surface."""
    frag: Dict = {"kv_dtype": kv_dtype}
    if draft_len > 0:
        frag["speculative"] = {"enabled": True,
                               "draft_len": int(draft_len)}
    else:
        frag["speculative"] = {"enabled": False}
    frag["prefix_cache"] = {"enabled": bool(prefix_cache),
                            "min_match_blocks": int(min_match_blocks),
                            "session_ttl_s": float(session_ttl_s)}
    return frag


def generate_serve_candidates(
        head_dim: int,
        kv_dtypes: Sequence[Optional[str]] = (None, "bf16", "int8",
                                              "int4"),
        draft_lens: Sequence[int] = (0, 2, 4),
        prefix_modes: Sequence[bool] = (True, False),
        min_matches: Sequence[int] = (1,),
        session_ttls: Sequence[float] = (120.0,),
) -> Tuple[List[Candidate], int]:
    """Enumerate the serve-scope candidate set: the cartesian product
    of KV storage modes, speculative draft lengths, and prefix-cache
    knobs (enabled, min match blocks, session TTL), each composition
    run through the REAL `DeepSpeedServingConfig` validator (same
    pruning contract as the comm space: a typo'd dtype or a negative
    draft_len is rejected and counted, never probed).  `head_dim` gates
    int4 — the packed nibble payload needs an even head_dim, so int4
    points are pruned (and counted rejected) on odd-head_dim models,
    mirroring PagedKVCache's own constructor check.  Disabled prefix
    points collapse min_match/ttl to their defaults (the knobs are
    inert with the cache off — enumerating them would duplicate).

    `safe_numerics` is True only for kv_dtype None/"fp32" (bit-exact
    vs `generate()`); draft_len alone never flips it — speculation is
    token-identical at matched kv_dtype by construction, it changes
    WHEN tokens arrive, never WHICH — and the prefix cache never flips
    it either: aliased blocks are bitwise-identical to recompute by
    the exactness contract (docs/tutorials/serving.md)."""
    from ..config import DeepSpeedServingConfig

    out: List[Candidate] = []
    rejected = 0

    def pfx_points():
        for on in prefix_modes:
            if not on:
                yield (False, 1, 120.0)
                continue
            for mm in min_matches:
                for ttl in session_ttls:
                    yield (True, int(mm), float(ttl))

    for kv in kv_dtypes:
        for draft in draft_lens:
            for on, mm, ttl in pfx_points():
                if kv == "int4" and int(head_dim) % 2 != 0:
                    rejected += 1
                    continue
                frag = _serve_fragment(kv, int(draft), on, mm, ttl)
                try:
                    DeepSpeedServingConfig({"serving": frag})
                except ValueError:
                    rejected += 1
                    continue
                name = f"serve_{kv or 'dense'}_d{int(draft)}"
                if not on:
                    name += "_nopfx"
                else:
                    if mm != 1:
                        name += f"_m{mm}"
                    if ttl != 120.0:
                        name += f"_ttl{int(ttl)}"
                out.append(Candidate(
                    name=name, comm=frag, scope="serve",
                    safe_numerics=kv in (None, "fp32", "float32")))
    return out, rejected


def current_serve_candidate(engine) -> Candidate:
    """The serve candidate describing a live ServeEngine's config —
    the baseline a serve-scope sweep measures lanes against."""
    c = engine.config
    kv = engine.kv.quant_wire  # "int8"/"int4" or None (dense)
    if kv is None and c.kv_dtype is not None:
        kv = str(c.kv_dtype)
    frag = _serve_fragment(kv, int(c.draft_len), bool(c.prefix_cache),
                           int(c.prefix_min_match_blocks),
                           float(c.session_ttl_s))
    name = f"serve_{kv or 'dense'}_d{int(c.draft_len)}"
    if not c.prefix_cache:
        name += "_nopfx"
    else:
        if int(c.prefix_min_match_blocks) != 1:
            name += f"_m{int(c.prefix_min_match_blocks)}"
        if float(c.session_ttl_s) != 120.0:
            name += f"_ttl{int(c.session_ttl_s)}"
    return Candidate(
        name=name, comm=frag, scope="serve",
        safe_numerics=kv in (None, "fp32", "float32"))


def current_candidate(engine) -> Candidate:
    """The candidate describing an engine's CURRENT effective config —
    the baseline the online retuner re-probes and measures swaps
    against."""
    cc = engine._config.comm_config
    plan = engine.bucket_plan
    outer = engine.mesh_info.data_outer_size
    hier = "none" if outer <= 1 else outer
    comm: Dict = {"gradient_reduction":
                  "bucketed" if plan is not None else "implicit"}
    wires: List[Optional[str]] = []
    if plan is not None:
        comm["wire_dtype"] = cc.wire_dtype
        wires.append(cc.wire_dtype)
        comm["reduce_bucket_size"] = plan.bucket_elems
        if hier != "none":
            comm["hierarchy"] = {"outer": outer}
            comm["wire_dtype_inner"] = cc.wire_dtype_inner
            comm["wire_dtype_outer"] = cc.wire_dtype_outer
            wires = [cc.wire_dtype_inner, cc.wire_dtype_outer]
    ov = engine._overlap_mode is not None
    comm["overlap"] = "on" if ov else "none"
    name = _name(comm["gradient_reduction"], comm.get("wire_dtype", "fp32"),
                 comm.get("wire_dtype_inner"), comm.get("wire_dtype_outer"),
                 hier, ov, None, None)
    return Candidate(name=name, comm=comm,
                     stage=engine._config.zero_optimization_stage,
                     scope="live", safe_numerics=_safe(wires))
