"""Builds `benchmarks/records/pr61.jsonl` from the outputs the chip calls
left under `bench_artifacts/pr61/` (one line a run: the result line with
its seed, its label and the notes `PERF.md` quotes; the sweep's and the
sabotage table's lines as they were printed).

    python3 bench_artifacts/pr61/mkrecords.py
"""
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CALLS = {
    "A": "call A (call_A.sh): the parent (90f4eb2 under .scratch/parent "
         "with this PR's BENCHMARK.json and benchmarks/ laid over it) on "
         "the new cell - it fails at once - the kernels phase, then the "
         "cell once at a guessed 1.2 requests/s under placeholder limits",
    "B": "call B (call_B.sh): the sweep that rates the cell, then the "
         "controls that must fail (sabotage.py, 20 s each)",
    "C": "call C (call_C.sh): the final tree from git archive: a traced "
         "run and two sets of six, every run a seed of its own",
    "D": "call D (call_D.sh): the other serving cells, parent against the "
         "final tree, a pair a cell sharing its seed (chatrate and chatgen "
         "in call H; longchat in call M, after the review)",
    "E": "call E: chip_smoke.py from the final tree",
    "F": "call F (call_F.sh): the sweep again and the fp8 control, after "
         "the front matrices went to the kernels turned",
    "G": "call G (call_C.sh): the tree of the first hand-in at 1.44 "
         "requests/s: a traced run and two sets of six",
    "I": "call I (call_I.sh), after the review: the sweep between 1.4 and "
         "1.8, for sweep.py's rule as it is written",
    "J": "call J (call_J.sh), after the review: the final tree at 1.36 "
         "requests/s (0.8 x 1.7): a traced run and two sets of six, every "
         "run a seed of its own",
    "K": "call M (call_M.sh), after the review: the cell with a window of "
         "100 s at its own rate, a traced run and six runs, from the final "
         "tree with the cell appended to serve_tokens_per_s's list for the "
         "reading",
    "N": "call N (call_J.sh), the last: the tree as it is committed (git "
         "archive of the final write-tree, after the words were "
         "rewritten): a traced and an untraced run",
    "L": "call M (call_M.sh), after the review: two more sets of six at "
         "the cell's rate and window, from the same tree, for the "
         "completed tokens/s the cell does not report",
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
                out.append({"pr": 61, "call": call, "label": label,
                            "workload": head.get("workload"),
                            "seed": head.get("seed"),
                            "trace": head.get("trace"),
                            "load": notes[1] if len(notes) > 1 else None,
                            "result": body})
            else:
                out.append({"pr": 61, "call": call, "label": label,
                            "line": body})
    path = os.path.join(ROOT, "benchmarks", "records", "pr61.jsonl")
    with open(path, "w") as f:
        for rec in out:
            f.write(json.dumps(rec) + "\n")
    print(len(out), "lines ->", path)


if __name__ == "__main__":
    main()
