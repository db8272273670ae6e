"""The host's and the device's planes of a profiler trace checked against
each other by what causality forbids, round by round of the serve loop.

  python3 -m benchmark.clock_check <file.xplane.pb | excerpt.json.gz>

A round's device work is the operations of the first device that start
between its `engine.dispatch` start and its `engine.fetch.wait` end (the
host span that waits for the round's tokens). Two lags follow:

  ready lag   the wait's end less the end of the last of those
              operations: how late the host learns the round is done
  launch lag  the first of those operations' start less the dispatch's
              start: how long the round took to reach the device

Both are causal. The device cannot start a round before the host asked
for it, and the host cannot see it finished before it finished. If the
device plane reads `d` later than the host's clock, every ready lag
reads `d` less and every launch lag `d` more than it is, so
`-min(ready lag) <= d <= min(launch lag)`: a negative ready lag bounds
how far the planes disagree, and in which direction. Nothing here
corrects for it.

  ready_lags       per round: its number, ready and launch lag (ms)
  clock_check      rounds, lag minimums and medians, the rounds that
                   break causality, the bounds on `d`
  per_round_ms     the first device's idle and each `engine.*` span's
                   time, averaged over the traced rounds
  read_ready_lag   a per-layer reader, `harness.READERS`' signature: the
                   median ready lag (ms) of a run's trace; None where the
                   program has no `engine.fetch.wait` span
"""

from __future__ import annotations

import bisect
import json
import statistics
import sys

from . import span_reduce as sr
from . import trace_reduce as tr


def ready_lags(trace: dict) -> list:
    """[{"round", "ready_lag_ms", "launch_lag_ms"}] over the
    `engine.round` spans that hold an `engine.dispatch` and an
    `engine.fetch.wait` and during which the first device ran something;
    the rest are left out."""
    planes = tr.device_planes(trace)
    if not planes:
        return []
    ops = sorted((e[1], e[1] + e[2]) for e in tr.device_ops(planes[0]))
    starts = [s for s, _ in ops]
    spans = sr.program_spans(trace)
    kids = {}
    for i, sp in enumerate(spans):
        kids.setdefault(sp["parent"], []).append(i)
    out = []
    for i, sp in enumerate(spans):
        if sp["name"] != "engine.round":
            continue
        disp = wait = None
        for k in kids.get(i, []):
            if spans[k]["name"] == "engine.dispatch":
                disp = spans[k]
            elif spans[k]["name"] == "engine.fetch":
                wait = next((spans[j] for j in kids.get(k, [])
                             if spans[j]["name"] == "engine.fetch.wait"),
                            None)
        if disp is None or wait is None:
            continue
        lo = bisect.bisect_left(starts, disp["start"])
        hi = bisect.bisect_right(starts, wait["end"])
        if lo >= hi:
            continue
        last_end = max(e for _, e in ops[lo:hi])
        out.append({"round": sp["args"].get("round"),
                    "ready_lag_ms": (wait["end"] - last_end) * 1e-6,
                    "launch_lag_ms": (ops[lo][0] - disp["start"]) * 1e-6})
    return out


def clock_check(trace: dict) -> dict:
    """The lags' minimums and medians over the traced rounds, how many
    rounds break causality (a lag below 0), and the bounds they set on
    how much later the device plane reads than the host's (`skew_ms`:
    [low, high]). None for each where no round could be read."""
    lags = ready_lags(trace)
    ready = [r["ready_lag_ms"] for r in lags]
    launch = [r["launch_lag_ms"] for r in lags]
    if not lags:
        return {"rounds": 0, "ready_lag_ms_min": None,
                "ready_lag_ms_p50": None, "launch_lag_ms_min": None,
                "launch_lag_ms_p50": None, "acausal_rounds": 0,
                "skew_ms": None}
    return {"rounds": len(lags), "ready_lag_ms_min": min(ready),
            "ready_lag_ms_p50": statistics.median(ready),
            "launch_lag_ms_min": min(launch),
            "launch_lag_ms_p50": statistics.median(launch),
            "acausal_rounds": sum(r < 0 or a < 0
                                  for r, a in zip(ready, launch)),
            "skew_ms": [-min(ready), min(launch)]}


def per_round_ms(trace: dict) -> dict:
    """The first device's idle and each `engine.*` span's summed time,
    in ms over the number of `engine.round` spans in the trace."""
    spans = sr.program_spans(trace)
    rounds = sum(sp["name"] == "engine.round" for sp in spans)
    if not rounds:
        return {}
    total = {}
    for sp in spans:
        total[sp["name"]] = total.get(sp["name"], 0.0) \
            + (sp["end"] - sp["start"]) * 1e-6
    out = {"rounds": rounds,
           "device_idle": sr.gap_attribution(trace)["idle_s"] * 1e3
           / rounds}
    out.update({k: v / rounds for k, v in sorted(total.items())})
    return out


def read_ready_lag(ctx, p):
    """Median ready lag (ms) over a run's traced rounds."""
    trace = ctx.get("trace")
    if not trace:
        return None
    return clock_check(trace)["ready_lag_ms_p50"]


def main(argv) -> int:
    path = argv[0]
    trace = tr.load_excerpt(path) if path.endswith(".json.gz") \
        else sr.load(path)
    print(json.dumps({"clock_check": clock_check(trace),
                      "per_round_ms": per_round_ms(trace)}, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
