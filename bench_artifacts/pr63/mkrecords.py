"""Builds `benchmarks/records/pr63.jsonl` from the outputs the chip calls
left under `bench_artifacts/pr63/` (one line a run: the result line with
its seed, its label and the notes `PERF.md` quotes; the sweep's and the
sabotage table's lines as they were printed).

    python3 bench_artifacts/pr63/mkrecords.py
"""
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CALLS = {
    "A": "call A (call_A.sh): the parent (f482e20 under .scratch/parent "
         "with this PR's BENCHMARK.json and benchmarks/ laid over it) on "
         "the new cell - it fails at once - the cell once at a guessed 7 "
         "requests/s under placeholder limits, then the sweep that rates "
         "it (benchmarks/sweep.py, 50 s a rate: 5, 7, 9, 11, 13)",
    "B": "call B (call_B.sh): the sweep between 5 and 7 (5.5, 6, 6.5), "
         "then the controls that must fail (sabotage.py, 20 s each, at "
         "4.8 requests/s; W_q and W_k still at the other matrices' scale)",
    "C": "call C (call_C_all.sh), after W_q and W_k were re-drawn "
         "(qk_scale 4) and the cell put at 4.0 requests/s: the unharmed "
         "run and sabotage (iv) again, a traced run and two sets of six, "
         "every run a seed of its own (logit_margin 1.0; the cell on "
         "serve_itl_p95_ms's list alone)",
    "D": "call D (call_D.sh): the other serving cells that share changed "
         "code, parent against the tree from git archive, a pair a cell "
         "sharing its seed, the order alternating",
    "E": "call E (call_E.sh): chip_smoke.py and the cell once, traced, "
         "from the tree git would commit (git archive of the write-tree "
         "in final_tree_of_call_E.txt)",
    "F": "call F (call_F.sh): the cell on serve_tokens_per_s's list too, "
         "logit_margin 2.0: a traced run and two sets of six, every run a "
         "seed of its own - the sets its end-to-end metrics are admitted "
         "by",
}


def main():
    out = []
    for name in sorted(os.listdir(HERE)):
        if not name.endswith(".out"):
            continue
        label = name[:-4]
        call = CALLS.get(label[0], "")
        notes, lines = [], []
        for line in open(os.path.join(HERE, name)):
            line = line.strip()
            if line.startswith("# {"):
                notes.append(json.loads(line[2:]))
            elif line.startswith("{"):
                try:
                    lines.append(json.loads(line))
                except ValueError:
                    pass
        for body in lines:
            if "correct" in body and "metrics" in body:
                head = notes[0] if notes else {}
                out.append({"pr": 63, "call": call, "label": label,
                            "workload": head.get("workload"),
                            "seed": head.get("seed"),
                            "trace": head.get("trace"),
                            "load": notes[1] if len(notes) > 1 else None,
                            "result": body})
            else:
                out.append({"pr": 63, "call": call, "label": label,
                            "line": body})
    path = os.path.join(ROOT, "benchmarks", "records", "pr63.jsonl")
    with open(path, "w") as f:
        for rec in out:
            f.write(json.dumps(rec) + "\n")
    print(len(out), "lines ->", path)


if __name__ == "__main__":
    main()
