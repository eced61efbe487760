"""The persisted winner cache.

Entries are keyed by fingerprint digest, each `{"fingerprint",
"winner", "trace", "written_unix"}`, so one file serves many (model,
mesh, fabric) combinations.

Invalidation contract (tested): a lookup whose stored fingerprint
differs from the caller's NEVER pins the run — it logs WHAT changed
(`fingerprint_diff`) and reports a miss so the caller re-probes.  An
unreadable/foreign file is a miss too (a corrupt cache must never be
worth more than a probe)."""

from __future__ import annotations

import json
import os
import time
from typing import Any, Dict, List, Optional

from ...utils.logging import logger
from .fingerprint import fingerprint_diff


class WinnerCache:
    def __init__(self, path: Optional[str]):
        self.path = path

    # -- IO ------------------------------------------------------------

    def _read(self) -> Optional[Dict[str, Any]]:
        if not self.path or not os.path.exists(self.path):
            return None
        try:
            with open(self.path) as f:
                data = json.load(f)
            return data if isinstance(data, dict) else None
        except Exception as e:
            logger.warning(
                f"autotune cache {self.path}: unreadable ({type(e).__name__}:"
                f" {e}) — treating as a miss and re-probing")
            return None

    def _write(self, data: Dict[str, Any]) -> None:
        if not self.path:
            return
        try:
            os.makedirs(os.path.dirname(os.path.abspath(self.path)),
                        exist_ok=True)
            tmp = f"{self.path}.tmp.{os.getpid()}"
            with open(tmp, "w") as f:
                json.dump(data, f, indent=2, sort_keys=True, default=str)
            os.replace(tmp, self.path)
        except OSError as e:  # read-only checkout: probing still worked
            logger.warning(f"autotune cache {self.path}: write failed "
                           f"({e}); the winner applies but is not cached")

    # -- lookup/store ----------------------------------------------------

    def lookup(self, fingerprint: Dict[str, Any]) -> Optional[Dict[str, Any]]:
        """The cached winner for this exact fingerprint, or None.  A
        present-but-mismatched entry logs the changed fingerprint
        components and misses — the loud re-probe the invalidation
        tests pin."""
        data = self._read()
        if data is None:
            return None
        digest = fingerprint.get("digest", "")
        entry = (data.get("entries") or {}).get(digest)
        if entry is None:
            # same digest-prefix collisions aside, also scan for a near
            # miss so the log can say WHAT invalidated the closest entry
            entries = list((data.get("entries") or {}).values())
            if entries:
                nearest = min(
                    entries,
                    key=lambda e: len(fingerprint_diff(
                        e.get("fingerprint") or {}, fingerprint)))
                changed = fingerprint_diff(
                    nearest.get("fingerprint") or {}, fingerprint)
                logger.warning(
                    "autotune cache: no winner for this (model, mesh, "
                    f"fabric) fingerprint (nearest entry differs in: "
                    f"{', '.join(changed) or 'structure'}) — probing")
            return None
        if entry.get("fingerprint") != fingerprint:
            changed = fingerprint_diff(entry.get("fingerprint") or {},
                                       fingerprint)
            logger.warning(
                "autotune cache: digest matched but the fingerprint "
                f"differs (changed: {', '.join(changed) or 'structure'}) — "
                "cached winner discarded, re-probing")
            return None
        return entry

    def store(self, fingerprint: Dict[str, Any], winner: Dict[str, Any],
              trace: Optional[List[Dict[str, Any]]] = None) -> None:
        if not self.path:
            return
        data = self._read() or {}
        entries = data.get("entries") or {}
        entries[fingerprint.get("digest", "")] = {
            "fingerprint": fingerprint, "winner": winner,
            "trace": trace or [], "written_unix": time.time()}
        self._write({"schema_version": 1, "entries": entries})
