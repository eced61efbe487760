"""(model shape, mesh, fabric) fingerprints for the winner cache.

ZeRO++ and the Frontier low-bandwidth study both show the winning
wire/partitioning config is a function of the FABRIC — so a cached
winner is only trustworthy for the exact (model shape, mesh layout,
fabric) it was probed on.  The fingerprint captures all three; the
cache treats it as an opaque equality key and `fingerprint_diff` names
what changed so a stale hit re-probes LOUDLY, never silently."""

from __future__ import annotations

import hashlib
import json
from typing import Any, Dict, List


def make_fingerprint(**sections) -> Dict[str, Any]:
    """Assemble a fingerprint from named sections (plain JSON values).
    A stable digest is attached for log lines and filenames; equality
    checks compare the full dict, not the digest."""
    fp = {k: sections[k] for k in sorted(sections)}
    blob = json.dumps(fp, sort_keys=True, default=str).encode()
    fp["digest"] = hashlib.md5(blob).hexdigest()[:16]
    return fp


def fingerprint_diff(a: Dict[str, Any], b: Dict[str, Any]) -> List[str]:
    """Dotted paths that differ between two fingerprints (digest
    excluded) — the 'what changed' a stale-cache log line names."""
    diffs: List[str] = []

    def walk(x, y, path):
        if isinstance(x, dict) and isinstance(y, dict):
            for k in sorted(set(x) | set(y)):
                if k == "digest" and not path:
                    continue
                walk(x.get(k), y.get(k), path + [str(k)])
        elif x != y:
            diffs.append(".".join(path) or "<root>")

    walk(a or {}, b or {}, [])
    return diffs


def fabric_section() -> Dict[str, Any]:
    """The fabric half of a fingerprint: backend, device kind, device
    count."""
    import jax

    devices = jax.devices()
    return {"backend": jax.default_backend(),
            "device_kind": devices[0].device_kind if devices else "?",
            "devices": len(devices)}


def _model_section(params) -> Dict[str, Any]:
    import jax
    import numpy as np

    leaves = jax.tree_util.tree_leaves(params)
    n_params = 0
    shape_hash = hashlib.md5()
    for leaf in leaves:
        shape = tuple(getattr(leaf, "shape", ()))
        dtype = str(getattr(leaf, "dtype", "?"))
        n_params += int(np.prod(shape, dtype=np.int64)) if shape else 1
        shape_hash.update(f"{shape}:{dtype};".encode())
    return {"n_params": int(n_params), "n_leaves": len(leaves),
            "shapes": shape_hash.hexdigest()[:16]}


def serve_fingerprint(engine) -> Dict[str, Any]:
    """Fingerprint a live ServeEngine for the serve-scope winner cache:
    model shape, serving geometry (pool/block/batch sizing), the KV
    storage + speculation knobs being probed, and the fabric.  Same
    contract as `engine_fingerprint`: a cached serve winner is only
    trustworthy for the exact (model, geometry, fabric) it was lapped
    on — a different block size or device kind re-probes loudly."""
    c = engine.config
    return make_fingerprint(
        model=_model_section(engine.params),
        geometry={"block_size": c.block_size,
                  "num_blocks": c.num_blocks,
                  "max_batch": c.max_batch,
                  "prefill_chunk": c.prefill_chunk,
                  "max_seq_len": engine.max_seq_len,
                  "admission": c.admission},
        serving={"kv_dtype": engine.kv.quant_wire or
                 (str(c.kv_dtype) if c.kv_dtype is not None else "dense"),
                 "draft_len": int(c.draft_len),
                 "spec_ngram": int(c.spec_ngram),
                 "quantized_weights": c.quant_mode,
                 "prefix_cache": bool(c.prefix_cache),
                 "prefix_min_match_blocks": int(c.prefix_min_match_blocks),
                 "session_ttl_s": float(c.session_ttl_s)},
        fabric=fabric_section(),
    )


def engine_fingerprint(engine) -> Dict[str, Any]:
    """Fingerprint a live engine: model shape (leaf shapes/dtypes),
    batch geometry, precision/stage (the dtype config), the mesh layout
    including its data-axis factorization, and the fabric (backend,
    device kind, process topology)."""
    import jax

    mi = engine.mesh_info
    cfg = engine._config
    try:
        processes = jax.process_count()
    except Exception:
        processes = 1
    return make_fingerprint(
        model=_model_section(engine._params),
        batch={"micro": cfg.train_micro_batch_size_per_gpu,
               "gas": cfg.gradient_accumulation_steps,
               "train_batch": cfg.train_batch_size},
        dtypes={"precision": cfg.precision,
                "quantized_weights":
                    getattr(cfg.zero_config, "quantized_weights", None)},
        zero={"stage": cfg.zero_optimization_stage},
        mesh={"data": mi.axis_size("data"),
              "model": mi.axis_size("model"),
              "pipe": mi.axis_size("pipe"),
              "seq": mi.axis_size("seq"),
              "data_outer": mi.data_outer_size,
              "data_inner": mi.data_inner_size},
        fabric=dict(fabric_section(),
                    processes=processes,
                    topology="multi-process" if processes > 1
                             else "single-process"),
    )
