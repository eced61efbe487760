"""What the reasoning cell's ONE deal does to the live batch, reckoned on
the CPU from the traffic file alone (no chip, no model): arrivals and
lengths from `benchmarks/traffic/poisson_lengths.shapes`, a prefill chunk
of 27.4 ms a request, a decode step of 8.5 + 0.26 x live slots ms (14.9
ms at 25 live, call J's traced step), 40 slots never full.

    python3 bench_artifacts/pr61/deal_sim.py [seconds ...]

It is held to what the chip read (PR 61, calls J and M): requests in
flight 25 at the middle and 26 at the end of a 50 s window (read: 25 and
26), 15 and 28 of a 100 s window (14-15 and 28-30), completed tokens/s
1,237 and 1,335 (1,223-1,239 and 1,331-1,337), live slots a step over
every step of the run, the drain's included, 15.5 and 17.9 (the
counters': 14.55 and 17.69).  What the harness does not give and this
does: the mean over the WINDOW's own steps, and over the traced last
5 s."""
import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
from benchmarks.traffic import poisson_lengths as pl  # noqa: E402


def steps_of(traffic, seconds, a=8.5, b=0.26, chunk=0.0274):
    """-> [(time a decode step ended, live slots in it)]."""
    gaps, _, out = pl.shapes(traffic, seconds)
    due, now, nxt, live, steps = np.cumsum(gaps), 0.0, 0, {}, []
    while nxt < len(due) or live:
        while nxt < len(due) and due[nxt] <= now:
            now += chunk
            live[nxt] = int(out[nxt]) - 1   # the chunk gives the first
            nxt += 1
        if not live:
            now = due[nxt]
            continue
        now += (a + b * len(live)) / 1e3
        steps.append((now, len(live)))
        live = {r: n - 1 for r, n in live.items() if n > 1}
    return np.array(steps), due


def main(argv):
    with open(os.path.join(ROOT, "benchmarks/traffic/reasoning.json")) as f:
        traffic = json.load(f)
    for seconds in [float(s) for s in argv] or [50.0, 100.0]:
        s, due = steps_of(traffic, seconds)
        inside = s[:, 0] <= seconds
        last = inside & (s[:, 0] > seconds - 5)
        at = lambda t: int(s[np.searchsorted(s[:, 0], t) - 1, 1])
        print(json.dumps({
            "seconds": seconds, "requests": len(due),
            "in_flight_at_middle": at(seconds / 2),
            "in_flight_at_end": at(seconds),
            "tokens_per_s": round(
                (s[inside, 1].sum() + len(due)) / seconds, 1),
            "drain_s": round(s[-1, 0] - seconds, 1),
            "live_mean_every_step": round(s[:, 1].mean(), 2),
            "live_mean_window_steps": round(s[inside, 1].mean(), 2),
            "live_mean_last_5_s": round(s[last, 1].mean(), 2)}))


if __name__ == "__main__":
    main(sys.argv[1:])
