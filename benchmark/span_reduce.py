"""From a profiler trace (`.xplane.pb`) to where the time went BY THE
PROGRAM'S OWN NAMES: its host spans (`engine.*`, `train*`: profiler
annotations written by `megatron_llm_tpu/telemetry/trace.py`) and the
named scopes of its device operations (`jax.named_scope`, kept by the
compiler in each operation's `tf_op` stat).

  python3 -m benchmark.span_reduce <file.xplane.pb>        the report, as JSON
  python3 -m benchmark.span_reduce excerpt <file> <out.json.gz> <from_ms> <to_ms>

A traced benchmark run keeps its trace with `BENCH_KEEP_TRACE=<path>`
(`harness.TraceWindow.finish`). The arithmetic is `trace_reduce`'s (self
times, the union of intervals, what a collective is); this file adds the
attribution. A trace of a program without spans or scopes gives empty
answers and raises nothing.

  program_spans     the spans with their parent by nesting, and self time
  gap_attribution   every stretch in which the first device ran nothing,
                    split over the innermost program spans that overlap it
  scope_seconds     device self time by scope path and by phase (forward,
                    remat_forward, backward, optimizer)
  round_kinds       device busy time and wall inside `engine.round` spans,
                    by the kind its `engine.dispatch` child names
  collective_owner  exposed collective seconds by the scope that owns them
"""

from __future__ import annotations

import bisect
import json
import statistics
import sys

from . import trace_reduce as tr

SPAN_PREFIXES = ("engine.", "train.")
SPAN_NAMES = ("train",)  # the step marker (StepTraceAnnotation)
PHASES = ("forward", "remat_forward", "backward", "optimizer")
UNNAMED = "unnamed"
OUTSIDE = "outside_program_span"

# path elements of an operation's name that JAX or the compiler put
# there, not a `jax.named_scope` of the program
_STRUCTURAL = {"while", "body", "cond", "closed_call", "checkpoint",
               "rematted_computation", "remat", "core_call", "pjit",
               "shard_map", "custom_jvp_call", "custom_vjp_call",
               "custom_vjp_call_jaxpr", "custom_lin", "scan", "map"}
_TRANSFORMS = ("jvp", "transpose", "vmap", "pmap", "xmap", "remat",
               "checkpoint", "custom_jvp", "custom_vjp")


def is_program_span(name: str) -> bool:
    return name.startswith(SPAN_PREFIXES) or name in SPAN_NAMES


def load(path: str, max_host_events: int = 400_000) -> dict:
    """`trace_reduce.load_xplane`'s form, with the stats of the program's
    spans kept (their `rid`, `round`, `kind`, `step_num`)."""
    from . import xplane

    planes = xplane.read_planes(
        path,
        lambda name: name.startswith((tr.DEVICE_PREFIX, tr.HOST_PREFIX)),
        lambda plane, line: line == tr.OPS_LINE
        or plane.startswith(tr.HOST_PREFIX))
    for plane in planes:
        is_dev = plane["name"].startswith(tr.DEVICE_PREFIX)
        budget = max_host_events
        for line in plane["lines"]:
            if is_dev:
                for ev in line["events"]:
                    ev[0] = ev[0].split(" = ", 1)[0]
                    ev[3] = {k: ev[3][k] for k in tr.KEEP_STATS
                             if ev[3].get(k)}
                continue
            spans = [e for e in line["events"] if is_program_span(e[0])]
            rest = [[e[0], e[1], e[2], {}] for e in line["events"]
                    if not is_program_span(e[0])][:max(budget, 0)]
            budget -= len(rest)
            line["events"] = sorted(spans + rest, key=lambda e: e[1])
    return {"planes": planes}


# ------------------------------------------------------------ host spans


def program_spans(trace: dict) -> list:
    """[{"name", "line", "start", "end", "args", "parent", "depth",
    "self_ns"}], sorted by line and start. `parent` is the index (into
    this list) of the span that encloses it on its thread's line; self
    time is the duration less that of the spans directly inside."""
    out = []
    for plane in trace["planes"]:
        if not plane["name"].startswith(tr.HOST_PREFIX):
            continue
        for line in plane["lines"]:
            evs = sorted((e for e in line["events"]
                          if is_program_span(e[0]) and e[2] > 0),
                         key=lambda e: (e[1], -e[2]))
            stack = []
            for e in evs:
                start, end = e[1], e[1] + e[2]
                while stack and out[stack[-1]]["end"] <= start + 1e-6:
                    stack.pop()
                span = {"name": e[0], "line": line["name"], "start": start,
                        "end": end, "args": dict(e[3]),
                        "parent": stack[-1] if stack else None,
                        "depth": len(stack), "self_ns": e[2]}
                if stack:
                    out[stack[-1]]["self_ns"] -= e[2]
                stack.append(len(out))
                out.append(span)
    return out


def _innermost_segments(spans: list) -> dict:
    """{line: [(start, end, name)]}: disjoint stretches, each under the
    innermost span that covers it there."""
    by_line = {}
    for i, sp in enumerate(spans):
        by_line.setdefault(sp["line"], []).append(i)
    out = {}
    for line, idxs in by_line.items():
        kids = {}
        for i in idxs:
            kids.setdefault(spans[i]["parent"], []).append(i)
        segs = []

        def walk(i):
            sp = spans[i]
            cur = sp["start"]
            for k in kids.get(i, []):
                if spans[k]["start"] > cur:
                    segs.append((cur, spans[k]["start"], sp["name"]))
                walk(k)
                cur = max(cur, spans[k]["end"])
            if sp["end"] > cur:
                segs.append((cur, sp["end"], sp["name"]))

        for root in kids.get(None, []):
            walk(root)
        segs.sort()
        out[line] = segs
    return out


def _overlaps(segs: list, starts: list, lo: float, hi: float):
    """(name, start, end, length) of each segment's part in [lo, hi)."""
    i = max(bisect.bisect_right(starts, lo) - 1, 0)
    while i < len(segs) and segs[i][0] < hi:
        s, e, name = segs[i]
        cut = min(e, hi) - max(s, lo)
        if cut > 0:
            yield name, max(s, lo), min(e, hi), cut
        i += 1


def capture_bounds(trace: dict):
    """(first start, last end) over every event kept, in ns: what the
    capture covered, as far as the trace itself says."""
    lo = hi = None
    for plane in trace["planes"]:
        for line in plane["lines"]:
            for e in line["events"]:
                lo = e[1] if lo is None else min(lo, e[1])
                hi = e[1] + e[2] if hi is None else max(hi, e[1] + e[2])
    return lo, hi


def device_gaps(trace: dict) -> list:
    """[(start, end, what)] on the first device: the stretch from the
    capture's start to the first operation (`head`), every gap between
    operations (`gap`), the stretch from the last one to the capture's
    end (`tail`)."""
    planes = tr.device_planes(trace)
    if not planes:
        return []
    ops = sorted((e[1], e[1] + e[2]) for e in tr.device_ops(planes[0]))
    lo, hi = capture_bounds(trace)
    if not ops:
        return [(lo, hi, "head")] if lo is not None and hi > lo else []
    out = []
    if ops[0][0] > lo:
        out.append((lo, ops[0][0], "head"))
    cur = ops[0][1]
    for s, e in ops[1:]:
        if s > cur:
            out.append((cur, s, "gap"))
        cur = max(cur, e)
    if hi > cur:
        out.append((cur, hi, "tail"))
    return out


def gap_attribution(trace: dict) -> dict:
    """The idle seconds of the first device, split over the innermost
    program spans that overlap each idle stretch. Where spans of several
    threads overlap one instant, the thread with the most span time takes
    it (the serve loop before a client thread). `idle_s` = the sum of
    `by_span` and `outside_program_span`."""
    spans = program_spans(trace)
    segs = _innermost_segments(spans)
    weight = {line: sum(e - s for s, e, _ in sg) for line, sg in segs.items()}
    lines = sorted(segs, key=lambda ln: -weight[ln])
    starts = {ln: [s for s, _, _ in segs[ln]] for ln in lines}
    by_span, kinds = {}, {"head": 0.0, "gap": 0.0, "tail": 0.0}
    outside, total, n = 0.0, 0.0, 0
    for gs, ge, what in device_gaps(trace):
        n += 1
        total += ge - gs
        kinds[what] += ge - gs
        left = [(gs, ge)]  # what no thread's span has taken yet
        for ln in lines:
            nxt = []
            for lo, hi in left:
                cur = lo
                for name, s, e, cut in _overlaps(segs[ln], starts[ln],
                                                 lo, hi):
                    by_span[name] = by_span.get(name, 0.0) + cut
                    if s > cur:
                        nxt.append((cur, s))
                    cur = e
                if hi > cur:
                    nxt.append((cur, hi))
            left = nxt
        outside += sum(hi - lo for lo, hi in left)
    under = sum(by_span.values())
    return {
        "idle_s": total * 1e-9, "stretches": n,
        "head_s": kinds["head"] * 1e-9, "between_ops_s": kinds["gap"] * 1e-9,
        "tail_s": kinds["tail"] * 1e-9,
        "by_span": {k: v * 1e-9 for k, v in
                    sorted(by_span.items(), key=lambda kv: -kv[1])},
        OUTSIDE: outside * 1e-9,
        "under_span_share": under / total if total else None,
    }


# --------------------------------------------------------- device scopes


def _split(path: str) -> list:
    """Split an operation's name at `/`, not inside parentheses."""
    out, depth, cur = [], 0, []
    for ch in path:
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth = max(depth - 1, 0)
        if ch == "/" and depth == 0:
            out.append("".join(cur))
            cur = []
        else:
            cur.append(ch)
    out.append("".join(cur))
    return out


def scope_of(tf_op) -> tuple:
    """(scope path, phase) of a device operation from its `tf_op` stat,
    e.g. `jit(train_step)/transpose(jvp(layers))/while/body/closed_call/
    checkpoint/rematted_computation/block/mlp/up/dot_general:` ->
    (`layers/block/mlp/up`, `remat_forward`). The last element is the
    primitive; `jit(..)` elements, JAX's transformation wrappers and the
    structure of loops and checkpoints are not scopes. No scope left:
    (`unnamed`, phase)."""
    text = str(tf_op or "").split(":", 1)[0]
    parts = _split(text)[:-1] if text else []
    scopes, transposed, remat = [], False, False
    for tok in parts:
        while True:
            head, sep, inner = tok.partition("(")
            if not (sep and tok.endswith(")")):
                break
            if head == "jit" or head not in _TRANSFORMS:
                tok = "" if head == "jit" else tok
                break
            transposed = transposed or head == "transpose"
            tok = inner[:-1]
        if tok == "rematted_computation":
            remat = True
        if (not tok or tok in _STRUCTURAL or tok.startswith("branch_")
                or "->" in tok or "," in tok):
            continue
        scopes.append(tok)
    if scopes and scopes[0] == "optimizer":
        phase = "optimizer"
    elif transposed:
        phase = "remat_forward" if remat else "backward"
    else:
        phase = "forward"
    return ("/".join(scopes) or UNNAMED), phase


def scope_seconds(trace: dict) -> dict:
    """Device SELF time by scope path and by phase, averaged over the
    devices; what carries no scope is `unnamed`, listed by category."""
    planes = tr.device_planes(trace)
    by_scope, by_phase, unnamed_cat = {}, dict.fromkeys(PHASES, 0.0), {}
    total = 0.0
    for plane in planes:
        for ev, own, _ in tr.self_times(tr.device_ops(plane)):
            sec = own * 1e-9 / len(planes)
            scope, phase = scope_of(ev[3].get("tf_op"))
            row = by_scope.setdefault(scope, dict.fromkeys(PHASES, 0.0))
            row[phase] += sec
            by_phase[phase] += sec
            total += sec
            if scope == UNNAMED:
                cat = tr.category_of(ev) or "op"
                unnamed_cat[cat] = unnamed_cat.get(cat, 0.0) + sec
    named = total - sum(by_scope.get(UNNAMED, {}).values())
    return {
        "total_s": total, "named_share": named / total if total else None,
        "by_phase": by_phase,
        "by_scope": dict(sorted(by_scope.items(),
                                key=lambda kv: -sum(kv[1].values()))),
        "unnamed_by_category": dict(sorted(unnamed_cat.items(),
                                           key=lambda kv: -kv[1])),
    }


def under(scope: str, prefix: str) -> bool:
    """Whether the scope path holds `prefix` as whole path elements
    (`attention`, `loss/head`), anywhere along it."""
    return "/" + prefix.strip("/") + "/" in "/" + scope + "/"


def scope_time(got: dict, prefix: str, phases=PHASES) -> float:
    """Seconds, out of `scope_seconds`' answer `got`, under the scopes
    that hold `prefix`, in the listed phases."""
    return sum(row[p] for scope, row in got["by_scope"].items()
               if under(scope, prefix) for p in phases)


def scope_share(trace: dict, prefix: str, phases=PHASES) -> float | None:
    """`scope_time` as a share of the device's self time."""
    got = scope_seconds(trace)
    if not got["total_s"]:
        return None
    return scope_time(got, prefix, phases) / got["total_s"]


# ---------------------------------------------------------------- rounds


def _merged(intervals) -> list:
    """Sorted, disjoint [start, end] lists covering the same points, so
    that a bisect on their starts finds a place."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clipped_union(ops: list, starts: list, lo: float, hi: float) -> float:
    """Length of [lo, hi) covered by the sorted intervals `ops`."""
    i = max(bisect.bisect_right(starts, lo) - 1, 0)
    cut = []
    while i < len(ops) and ops[i][0] < hi:
        s, e = max(ops[i][0], lo), min(ops[i][1], hi)
        if e > s:
            cut.append((s, e))
        i += 1
    return tr.union_length(cut)


def round_kinds(trace: dict) -> dict:
    """{kind: {"rounds", "wall_s", "device_busy_s", "round_ms_p50",
    "host_ms_p50"}} over the `engine.round` spans that lie whole inside
    the trace. A round's kind is the `kind` of its `engine.dispatch`
    child (`none`: a round that dispatched nothing); its host part is
    its duration less its `engine.fetch` child (the wait for the device).
    The device is asked for in order, so what it ran between a round's
    start and end is that round's work."""
    spans = program_spans(trace)
    planes = tr.device_planes(trace)
    merged = _merged((e[1], e[1] + e[2])
                     for e in tr.device_ops(planes[0])) if planes else []
    starts = [m[0] for m in merged]
    kids = {}
    for i, sp in enumerate(spans):
        kids.setdefault(sp["parent"], []).append(i)
    out = {}
    for i, sp in enumerate(spans):
        if sp["name"] != "engine.round":
            continue
        kind, fetch = "none", 0.0
        for k in kids.get(i, []):
            if spans[k]["name"] == "engine.dispatch":
                kind = str(spans[k]["args"].get("kind", "none"))
            elif spans[k]["name"] == "engine.fetch":
                fetch += spans[k]["end"] - spans[k]["start"]
        row = out.setdefault(kind, {"rounds": 0, "wall_s": 0.0,
                                    "device_busy_s": 0.0, "_ms": [],
                                    "_host": []})
        dur = sp["end"] - sp["start"]
        row["rounds"] += 1
        row["wall_s"] += dur * 1e-9
        row["device_busy_s"] += _clipped_union(
            merged, starts, sp["start"], sp["end"]) * 1e-9
        row["_ms"].append(dur * 1e-6)
        row["_host"].append((dur - fetch) * 1e-6)
    for row in out.values():
        row["round_ms_p50"] = statistics.median(row.pop("_ms"))
        row["host_ms_p50"] = statistics.median(row.pop("_host"))
    return out


# ----------------------------------------------------------- collectives


def _kind_of_collective(ev) -> str:
    text = (tr.category_of(ev) + " " + ev[0]).lower()
    for word in ("reduce-scatter", "all-gather", "all-reduce", "all-to-all",
                 "collective-permute"):
        if word in text or word.replace("-", "") in text:
            return word.replace("-", "_")
    return "collective"


def collective_owner(trace: dict) -> dict:
    """Exposed collective seconds (a collective running, or waited for,
    with no other operation beside it: `trace_reduce`'s measure) by
    owner, `<scope path>/<collective>`, averaged over the devices. The
    partitioner gives a collective the name of the operation it serves,
    so the owner is that operation's scope. Each collective is taken on
    its own: where two run at once, both count their exposed part."""
    planes = tr.device_planes(trace)
    out = {}
    for plane in planes:
        coll, other = [], []
        for ev, _, leaf in tr.self_times(tr.device_ops(plane)):
            if not leaf:
                continue
            span = (ev[1], ev[1] + ev[2])
            if tr.is_collective(ev):
                coll.append((span, ev))
            else:
                other.append(span)
        merged = _merged(other)
        starts = [m[0] for m in merged]
        for (s, e), ev in coll:
            exposed = (e - s) - _clipped_union(merged, starts, s, e)
            scope, _ = scope_of(ev[3].get("tf_op"))
            key = f"{scope}/{_kind_of_collective(ev)}"
            out[key] = out.get(key, 0.0) + exposed * 1e-9 / len(planes)
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


# ------------------------------------------------------------------- CLI


def report(trace: dict) -> dict:
    spans = program_spans(trace)
    names = {}
    for sp in spans:
        row = names.setdefault(sp["name"], {"count": 0, "total_s": 0.0,
                                            "self_s": 0.0, "lines": set()})
        row["count"] += 1
        row["total_s"] += (sp["end"] - sp["start"]) * 1e-9
        row["self_s"] += sp["self_ns"] * 1e-9
        row["lines"].add(sp["line"])
    for row in names.values():
        row["lines"] = sorted(row["lines"])
    lo, hi = capture_bounds(trace)
    return {
        "capture_s": (hi - lo) * 1e-9 if lo is not None else None,
        "device_busy_s": tr.busy_seconds(trace),
        "spans": names,
        "gap_attribution": gap_attribution(trace),
        "scope_seconds": scope_seconds(trace),
        "round_kinds": round_kinds(trace),
        "collective_owner": collective_owner(trace),
    }


def main(argv) -> int:
    if argv and argv[0] == "excerpt":
        path, out, t0, t1 = argv[1], argv[2], float(argv[3]), float(argv[4])
        trace = load(path)
        lo, _ = tr.span_of(trace)
        tr.save_excerpt(trace, out, lo + t0 * 1e6, lo + t1 * 1e6)
        return 0
    path = argv[0]
    trace = tr.load_excerpt(path) if path.endswith(".json.gz") \
        else load(path)
    print(json.dumps(report(trace), indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
