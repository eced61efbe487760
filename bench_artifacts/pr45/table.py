"""Print PR 45's chip runs as tables: `python table.py <file.out> ...`
(the lines `call_A.sh` / `call_others.sh` write: side, cell, seed, trace,
then the run's JSON)."""
import json
import sys


def rows(path):
    for line in open(path):
        side, cell, seed, trace, js = line.split(" ", 4)
        yield side, cell, seed[5:], trace[6:], json.loads(js)


for path in sys.argv[1:]:
    print("==", path)
    for side, cell, seed, trace, d in rows(path):
        m = d["metrics"]
        flat = {k: (v["value"] if isinstance(v, dict) else v)
                for k, v in m.items()}
        print(side, cell.split(".")[-1], seed, "trace" + trace,
              "correct" if d["correct"] else "INCORRECT",
              f"{d['attempted']}/{d['failed']}",
              json.dumps({k: (round(v, 4) if isinstance(v, float) else v)
                          for k, v in flat.items()}))
        if trace == "1":
            print("   breakdown:", json.dumps(d.get("breakdown"))[:2500])
