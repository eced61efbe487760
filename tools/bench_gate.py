"""Shared gate for bench measurement artifacts.

Default mode: exit 0 iff the given bench JSON file's last JSON line
reports a run on real hardware (platform present and not the cpu-smoke
fallback).  For anything that needs to decide whether an artifact is
trustworthy.

`--min-prefix-hit-rate X` mode: exit 0 iff the artifact's last JSON
line carries a prefix-cache hit rate >= X (a `prefix_hit_rate` field,
or `value` when the metric is serve_fleet_bench).  This gate is about
the CLAIM, not the fabric — the prefix cache's hit rate and bitwise
exactness are platform-independent, so the committed CPU fleet
artifact is gateable — hence it skips the hardware check unless
`--require-tpu` is also given.
"""

import argparse
import json
import sys


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("path", nargs="?", default="/tmp/tpu_bench.json")
    ap.add_argument("--min-prefix-hit-rate", type=float, default=None,
                    metavar="X",
                    help="gate on prefix cache hit rate >= X instead "
                    "of on the hardware platform (0 <= X <= 1)")
    ap.add_argument("--require-tpu", action="store_true",
                    help="with --min-prefix-hit-rate: ALSO require "
                    "real hardware")
    args = ap.parse_args()
    try:
        with open(args.path) as f:
            text = f.read()
        try:
            # a committed run artifact: one pretty-printed document
            # wrapping the result (monitor/artifacts.py)
            d = json.loads(text)
            if isinstance(d, dict) and isinstance(d.get("result"), dict):
                d = d["result"]
        except ValueError:
            # a JSONL stream: gate the LAST line
            lines = [l for l in text.splitlines()
                     if l.strip().startswith("{")]
            d = json.loads(lines[-1])
    except Exception as e:  # missing/empty/unparseable artifact
        print(f"gate: no parseable bench line in {args.path}: {e}")
        return 1
    if args.min_prefix_hit_rate is not None:
        rate = d.get("prefix_hit_rate")
        if rate is None and d.get("metric") == "serve_fleet_bench":
            rate = d.get("value")
        if rate is None:
            print("gate: artifact carries no prefix_hit_rate:",
                  d.get("metric"))
            return 1
        if float(rate) < args.min_prefix_hit_rate:
            print(f"gate: prefix hit rate {float(rate):.3f} below floor "
                  f"{args.min_prefix_hit_rate:.3f}")
            return 1
        if args.require_tpu and d.get("platform") in (None, "cpu-smoke"):
            print("gate: bench did not run on TPU:", d.get("platform"))
            return 1
        print(f"gate: valid: {d.get('metric')} hit rate "
              f"{float(rate):.3f} >= {args.min_prefix_hit_rate:.3f}")
        return 0
    if d.get("platform") in (None, "cpu-smoke"):
        print("gate: bench did not run on TPU:", d.get("platform"))
        return 1
    print("gate: valid:", d.get("metric"), d.get("value"), d.get("platform"))
    return 0


if __name__ == "__main__":
    sys.exit(main())
