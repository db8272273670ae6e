"""From a profiler trace (`.xplane.pb`) to numbers: device busy time,
time by source site, exposed collective time, the longest idle gaps and
what the host was doing in them.

The trace is first flattened into plain lists (`load_xplane`), so that
the arithmetic below can be checked on a small recorded excerpt kept in
`tests/data/` (`save_excerpt` wrote it from a real chip trace).

A device is a plane named `/device:TPU:<n>`; its operations are the
events of the line `XLA Ops`. Each operation carries its HLO category and
the source line it was traced from in its stats; `site_of` reduces that
to `<path below megatron_llm_tpu/>:<line>`, or to the category where the
compiler kept no source.
"""

from __future__ import annotations

import gzip
import json
import re

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PREFIX = "/host:"
COLLECTIVE_WORDS = ("all-reduce", "all-gather", "reduce-scatter",
                    "all-to-all", "collective-permute", "collective",
                    "allreduce", "allgather")
_SITE = re.compile(r"([\w./-]+\.py):(\d+)")
KEEP_STATS = ("hlo_category", "source", "tf_op")


def load_xplane(path: str, max_host_events: int = 400_000) -> dict:
    """{"planes": [{"name", "lines": [{"name", "events":
    [[name, start_ns, dur_ns, stats-dict], ...]}]}]}. A device operation
    keeps its short name (`%fusion.12`) and the stats that say what it is
    and where it was traced from; host events keep name and times."""
    from . import xplane

    planes = xplane.read_planes(
        path,
        lambda name: name.startswith((DEVICE_PREFIX, HOST_PREFIX)),
        lambda plane, line: line == OPS_LINE
        or plane.startswith(HOST_PREFIX))
    for plane in planes:
        is_dev = plane["name"].startswith(DEVICE_PREFIX)
        budget = max_host_events
        for line in plane["lines"]:
            if is_dev:
                for ev in line["events"]:
                    ev[0] = ev[0].split(" = ", 1)[0]
                    ev[3] = {k: ev[3][k] for k in KEEP_STATS if ev[3].get(k)}
            else:
                line["events"] = [[e[0], e[1], e[2], {}]
                                  for e in line["events"][:max(budget, 0)]]
                budget -= len(line["events"])
    return {"planes": planes}


def save_excerpt(trace: dict, path: str, t0_ns: float, t1_ns: float):
    """Keep only the events that start inside [t0, t1): a recorded
    excerpt small enough to live beside the tests."""
    out = {"planes": []}
    for plane in trace["planes"]:
        lines = []
        for line in plane["lines"]:
            evs = [e for e in line["events"] if t0_ns <= e[1] < t1_ns]
            if evs:
                lines.append({"name": line["name"], "events": evs})
        out["planes"].append({"name": plane["name"], "lines": lines})
    with gzip.open(path, "wt") as f:
        json.dump(out, f)


def load_excerpt(path: str) -> dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)


def device_planes(trace: dict) -> list:
    return [p for p in trace["planes"] if p["name"].startswith(DEVICE_PREFIX)]


def device_ops(plane: dict) -> list:
    for line in plane["lines"]:
        if line["name"] == OPS_LINE:
            return line["events"]
    return []


def category_of(ev) -> str:
    stats = ev[3]
    cat = stats.get("hlo_category") or stats.get("category") or ""
    return str(cat).strip().lower()


def site_of(ev) -> str:
    """`models/transformer.py:412` for an operation traced from the
    program, the bare category (`data formatting`) where the compiler
    kept no source line."""
    m = _SITE.search(str(ev[3].get("source") or ""))
    if m:
        path = m.group(1)
        cut = path.find("megatron_llm_tpu/")
        if cut >= 0:
            path = path[cut + len("megatron_llm_tpu/"):]
        return f"{path}:{m.group(2)}"
    return category_of(ev) or ev[0]


def is_collective(ev) -> bool:
    text = (category_of(ev) + " " + ev[0]).lower()
    return any(w in text for w in COLLECTIVE_WORDS)


def union_length(intervals) -> float:
    """Total length covered by [start, end) intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def self_times(events) -> list:
    """[(event, self_ns, is_leaf)]: the line nests (a `while` covers the
    operations of its body), so an operation's own time is its duration
    less that of the operations directly inside it. Summed over a line,
    self times give the busy time, with nothing counted twice."""
    evs = sorted(events, key=lambda e: (e[1], -e[2]))
    own = [e[2] for e in evs]
    leaf = [True] * len(evs)
    stack = []
    for i, e in enumerate(evs):
        while stack and evs[stack[-1]][1] + evs[stack[-1]][2] <= e[1] + 1e-6:
            stack.pop()
        if stack:
            own[stack[-1]] -= e[2]
            leaf[stack[-1]] = False
        stack.append(i)
    return [(e, max(o, 0.0), lf) for e, o, lf in zip(evs, own, leaf)]


def span_of(trace: dict):
    """(first start, last end) over every device operation, in ns."""
    lo, hi = None, None
    for plane in device_planes(trace):
        for ev in device_ops(plane):
            lo = ev[1] if lo is None else min(lo, ev[1])
            hi = ev[1] + ev[2] if hi is None else max(hi, ev[1] + ev[2])
    return lo, hi


def busy_seconds(trace: dict) -> list:
    """Per device: seconds in which some operation ran."""
    return [union_length([(e[1], e[1] + e[2]) for e in device_ops(p)]) * 1e-9
            for p in device_planes(trace)]


def _sum_by(trace: dict, key) -> dict:
    """{key(event): seconds of self time}, averaged over the devices."""
    planes = device_planes(trace)
    out = {}
    for plane in planes:
        for ev, own, _ in self_times(device_ops(plane)):
            k = key(ev)
            out[k] = out.get(k, 0.0) + own * 1e-9
    return {k: v / len(planes) for k, v in out.items()}


def time_by_site(trace: dict) -> dict:
    return _sum_by(trace, site_of)


def time_by_category_site(trace: dict) -> dict:
    """{"<category>___<site>": seconds}, the breakdown's naming."""
    def key(ev):
        cat = category_of(ev).replace(" ", "_") or "op"
        site = site_of(ev)
        return cat if site == category_of(ev) else f"{cat}___{site}"
    return _sum_by(trace, key)


def site_seconds(trace: dict, files) -> float:
    """Seconds of the operations whose site lies in one of `files`
    (paths below megatron_llm_tpu/, matched as prefixes of the site)."""
    return sum(sec for site, sec in time_by_site(trace).items()
               if any(site.startswith(f) for f in files))


def exposed_collective_seconds(trace: dict) -> list:
    """Per device: seconds in which a collective ran (or was waited for)
    with no other operation beside it. Only operations with nothing
    inside them count on either side: a `while` runs as long as its body
    and would hide everything."""
    out = []
    for plane in device_planes(trace):
        coll, other = [], []
        for ev, _, leaf in self_times(device_ops(plane)):
            if leaf:
                (coll if is_collective(ev) else other).append(
                    (ev[1], ev[1] + ev[2]))
        both = union_length(coll + other)
        out.append((both - union_length(other)) * 1e-9)
    return out


def idle_gaps(trace: dict, min_ns: float = 20_000.0):
    """Gaps between consecutive operations on the first device, labelled
    by the deepest host event covering the gap's start. Returns
    [[label, seconds], ...], summed per label, longest first, plus one
    row for all the gaps too short to label."""
    planes = device_planes(trace)
    if not planes:
        return []
    spans = sorted((e[1], e[1] + e[2]) for e in device_ops(planes[0]))
    gaps, cur = [], None
    for s, e in spans:
        if cur is not None and s > cur:
            gaps.append((cur, s))
        cur = e if cur is None else max(cur, e)
    host = []
    for plane in trace["planes"]:
        if plane["name"].startswith(HOST_PREFIX):
            for line in plane["lines"]:
                for ev in line["events"]:
                    if ev[2] > 0:
                        host.append((ev[1], ev[1] + ev[2], ev[0]))
    host.sort()
    starts = [h[0] for h in host]
    import bisect

    sums, counts = {}, {}
    short, n_short = 0.0, 0
    for gs, ge in gaps:
        if ge - gs < min_ns:
            short += ge - gs
            n_short += 1
            continue
        # deepest (latest-starting) host event that covers the gap start
        label = "unattributed"
        i = bisect.bisect_right(starts, gs) - 1
        steps = 0
        while i >= 0 and steps < 4000:
            if host[i][1] > gs:
                label = _clean(host[i][2])
                break
            i -= 1
            steps += 1
        sums[label] = sums.get(label, 0.0) + (ge - gs)
        counts[label] = counts.get(label, 0) + 1
    rows = [[f"{k}_x{counts[k]}", v * 1e-9] for k, v in sums.items()]
    rows.sort(key=lambda r: -r[1])
    if n_short:
        rows.append([f"gaps_under_{int(min_ns / 1000)}us_x{n_short}",
                     short * 1e-9])
    return rows


def _clean(name: str) -> str:
    return re.sub(r"[^A-Za-z0-9_.:/()-]+", "_", name)[:80]


def top(rows: dict, n: int = 10) -> list:
    return [[k, v] for k, v in
            sorted(rows.items(), key=lambda kv: -kv[1])[:n]]


def _dump(path: str):
    """A first look by hand: busy time, time by site and by category and
    site, exposed collectives, idle gaps."""
    tr = load_xplane(path)
    lo, hi = span_of(tr)
    print("span_s", (hi - lo) * 1e-9, "busy_s", busy_seconds(tr),
          "exposed_collective_s", exposed_collective_seconds(tr))
    for title, rows in (("by site", time_by_site(tr)),
                        ("by category and site", time_by_category_site(tr))):
        print(title)
        for name, sec in top(rows, 40):
            print(f"  {sec:10.6f}  {name}")
    print("idle gaps")
    for name, sec in idle_gaps(tr)[:15]:
        print(f"  {sec:10.6f}  {name}")


if __name__ == "__main__":
    import sys

    if sys.argv[1] == "dump":
        _dump(sys.argv[2])
    elif sys.argv[1] == "excerpt":
        tr = load_xplane(sys.argv[2])
        lo, _ = span_of(tr)
        save_excerpt(tr, sys.argv[3], lo + float(sys.argv[4]) * 1e6,
                     lo + float(sys.argv[5]) * 1e6)
