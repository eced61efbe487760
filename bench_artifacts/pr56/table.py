"""Print a call's `.out` lines (`<side> <cell> seed=<n> trace=<0|1>
{result}`) as a table: one line a run, then the traced runs' device
operations.

    python bench_artifacts/pr56/table.py bench_artifacts/pr56/call_A.out
"""
import json
import sys

KEEP = ("serve_itl_p95_ms", "serve_tokens_per_s", "serve_ttft_p95_ms",
        "setup_s", "decode_step_ms.serve", "token_gap_p50_ms.serve",
        "token_gap_p95_ms.serve", "decode_batch_mean.serve",
        "chunk_gap_share_pct.serve", "ssm_decode_hbm_roofline.serve",
        "swa_moe_decode_hbm_roofline.serve", "decode_hbm_roofline.serve",
        "attn_rows_per_query.serve", "attn_rows_walked_per_query.serve",
        "attn_prefill_rows_walked_per_chunk.serve",
        "paged_rows_walked_per_query.serve",
        "mla_rows_per_query.serve", "mla_rows_walked_per_query.serve",
        "moe_decode_hbm_roofline.serve",
        "moe_experts_touched_per_layer.serve", "prefill_chunk_ms.gap",
        "device_idle_pct.serve", "host_read_blocked_ms.serve",
        "host_decode_launch_ms.serve", "prefill_chunk_ms.serve")

for path in sys.argv[1:]:
    traced = []
    for line in open(path):
        side, cell, seed, trace, js = line.split(" ", 4)
        r = json.loads(js)
        m = {k: v["value"] for k, v in r["metrics"].items()}
        print(side, cell.split(".")[-1], seed, trace,
              "correct" if r["correct"] else "NOT CORRECT",
              f"{r['attempted'] - r['failed']}/{r['attempted']}",
              " ".join(f"{k.replace('.serve', '')}={m[k]:.5g}"
                       for k in KEEP if k in m),
              f"mem={r['device']['memory_peak_bytes'] / 1e9:.3f}GB")
        if trace.strip() == "trace=1":
            traced.append((side, r))
    for side, r in traced:
        print(side, f"busy {r['device']['busy_s']:.3f} of "
              f"{r['device']['window_s']:.3f} s")
        for name, s in r["breakdown"]["device_ops"]:
            print(f"    {s:.3f}  {name}")
        print("    idle:", r["breakdown"]["idle_gaps"])
