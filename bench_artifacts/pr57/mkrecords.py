"""Builds `benchmarks/records/pr57.jsonl` from the outputs the chip calls
left under `bench_artifacts/pr57/` (one line a run: the result line with
its seed, its call and the notes `PERF.md` quotes; the sweeps' and the
sabotage table's lines as they were printed).

    python3 bench_artifacts/pr57/mkrecords.py
"""
import glob
import json
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CALLS = json.load(open(os.path.join(HERE, "calls.json")))
CELL = "qwen3-next-80b-a3b-d12.serve.longchat"


def plain_run(path):
    """A `benchmarks/run.py` output: `# {...}` notes, then the result."""
    notes, result = [], None
    for line in open(path):
        line = line.strip()
        if line.startswith("# {"):
            notes.append(json.loads(line[2:]))
        elif line.startswith('{"correct"'):
            result = json.loads(line)
    return notes, result


def main():
    out = []
    for name in sorted(os.listdir(HERE)):
        path = os.path.join(HERE, name)
        label = name.split(".")[0]
        call = CALLS.get(label.split("_")[0], "")
        if re.fullmatch(r"[A-Z]\d?_(traced|run\d+|set\d_\d|parent_old_traced)\.out", name):
            notes, result = plain_run(path)
            if result is None:
                continue
            head = notes[0] if notes else {}
            out.append({"pr": 57, "call": call, "label": label,
                        "workload": head.get("workload", CELL),
                        "seed": head.get("seed"), "trace": head.get("trace"),
                        "load": notes[1] if len(notes) > 1 else None,
                        "result": result})
        elif re.fullmatch(r"[A-Z]_(sweep|sabotage|probe|walk\d?)\.out", name) \
                or re.fullmatch(r"[A-Z]\d_walk\.out", name):
            for line in open(path):
                if line.startswith("{"):
                    out.append({"pr": 57, "call": call, "label": label,
                                "workload": CELL, "line": json.loads(line)})
        elif re.fullmatch(r"[A-Z]_(others|final|itl)\.out", name):
            for line in open(path):
                m = re.match(r"(\w+) (\S+) seed=(\d+) (.*)", line.strip())
                if not m or not m.group(4).startswith(("{", "# {")):
                    continue
                body = m.group(4)
                kind = "result" if body.startswith("{") else "note"
                out.append({"pr": 57, "call": call, "label": label,
                            "tree": m.group(1), "workload": m.group(2),
                            "seed": int(m.group(3)),
                            kind: json.loads(body.lstrip("# "))})
        elif name.endswith("_chip_smoke.out"):
            for line in open(path):
                if line.startswith("{"):
                    out.append({"pr": 57, "call": call, "label": label,
                                "chip_smoke": json.loads(line)})
        elif name.endswith("_trace_numbers.out"):
            for line in open(path):
                if line.startswith("{"):
                    out.append({"pr": 57, "call": call, "label": label,
                                "workload": CELL,
                                "trace_numbers": json.loads(line)})
    with open(os.path.join(ROOT, "benchmarks", "records", "pr57.jsonl"),
              "w") as f:
        for rec in out:
            f.write(json.dumps(rec) + "\n")
    print(len(out), "records")


if __name__ == "__main__":
    main()
