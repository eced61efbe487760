"""PERF.md §5's table of PR 59 from the calls' lines: each serving cell's
decode step and prefill chunk by stage (stage_sums.py's join) beside the
metrics the benchmark's own reader printed in the same run.

    python bench_artifacts/pr59/table.py bench_artifacts/pr59/call_A.jsonl
"""
import json
import sys

SHORT = {"gpt2-xl.serve.chat": "chat", "evabyte-d16.serve.longdoc": "longdoc",
         "deepseek-v2-lite-d9.serve.chatgen": "chatgen",
         "command-a-plus-d4.serve.mixedlen": "mixedlen",
         "granite-4.0-h-micro.serve.chatrate": "chatrate",
         "glm-5.2-d5.serve.longctx": "longctx",
         "qwen3-next-80b-a3b-d12.serve.longchat": "longchat"}
STAGES = ("embed", "attn", "state", "ffn", "head", "sample", "(none)")
rows = [json.loads(ln) for p in sys.argv[1:] for ln in open(p)]
print("| cell | program | runs | ms a run | " + " | ".join(STAGES)
      + " | sum / run % | attach s |")
print("|---|---|---|---|" + "---|" * (len(STAGES) + 2))
for r in rows:
    s = r.get("stage_sums")
    if not s:
        continue
    for prog, got in s["programs"].items():
        if prog == "jit_seat" or prog == "jit_seat_counted":
            continue
        ms = got["stage_ms"]
        print(f"| `{SHORT[r['workload']]}` | `{prog}` | {got['runs']} | "
              f"{got['run_ms']:.3f} | "
              + " | ".join(f"{ms.get(k, 0):.3f}" for k in STAGES)
              + f" | {got['sum_over_run_pct']:.2f} | "
              f"{s['attach'][prog]['seconds']:.2f} |")
print()
for r in rows:
    m = (r.get("result") or {}).get("metrics") or {}
    keep = {k: round(v["value"], 4) for k, v in m.items()
            if k.split(".")[0] in (
                "decode_attn_ms", "decode_state_ms", "decode_ffn_ms",
                "decode_head_ms", "moe_experts_ms", "dsa_select_ms",
                "prefill_attn_ms", "prefill_state_ms", "prefill_ffn_ms",
                "scope_unattributed_pct", "decode_step_ms",
                "prefill_chunk_ms", "token_gap_p50_ms", "device_idle_pct")}
    e2e = {k: round(v["value"], 3) for k, v in m.items()
           if k.startswith(("serve_", "setup_s"))}
    print(r["tree"], SHORT.get(r["workload"], r["workload"]), r["seed"],
          "trace", r["trace"], "rc", r["rc"], "correct",
          (r.get("result") or {}).get("correct"), "wall", r["wall_s"],
          json.dumps(keep or e2e))
    s = r.get("stage_sums")
    if s:
        for prog, got in s["programs"].items():
            print("    ", prog, "second:", json.dumps(
                {k: round(v, 3) for k, v in got["second_level_ms"].items()}))
            if "registry_ms" in got:
                print("    ", prog, "registry:", json.dumps(
                    {k: round(v, 4) for k, v in got["registry_ms"].items()}),
                    "moved:", round(got["compiler_moved_ms"], 4))
            print("    ", prog, "unscoped:", json.dumps(
                {k: round(v, 4) for k, v in got["unscoped_ms"].items()}))
