"""What every cell's run shares: finding the cell's files by the names in
BENCHMARK.json, refusing to run without the chips, the profiler window,
the per-layer metric readers, and the one result line.
"""

from __future__ import annotations

import glob
import json
import os
import shutil
import statistics
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
OUT_DIR = os.path.join(ROOT, "chiprun_out")
TRACE_SECONDS = 5.0  # how much of a --trace 1 window the profiler records


class NoChip(SystemExit):
    pass


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_cell(workload: str, bench_path: str | None = None,
              base: str | None = None) -> dict:
    """The cell's entry, its configuration, its traffic mix and the
    per-layer metric files that list it, all found by name."""
    base = base or HERE
    bench = load_json(bench_path or os.path.join(os.path.dirname(base),
                                                 "BENCHMARK.json"))
    entry = next((w for w in bench["workloads"] if w["name"] == workload),
                 None)
    if entry is None:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json")
    conf = next(c for c in bench["configs"] if c["name"] == entry["config"])
    root = os.path.dirname(base)
    cfg = load_json(root, conf["file"])
    mix = load_json(base, "traffic", entry["traffic"] + ".json")
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or workload in m["workloads"]]
    metrics = []
    for path in sorted(glob.glob(os.path.join(base, "metrics", "*.json"))):
        m = load_json(path)
        if "workloads" not in m or workload in m["workloads"]:
            metrics.append(m)
    limits = load_json(base, "limits", workload + ".json")["limits"]
    from . import families

    families.find(cfg, base)  # the family's files lie beside the cell's
    return {"name": workload, "entry": entry, "cfg": cfg, "mix": mix,
            "end_to_end": e2e, "per_layer": metrics, "limits": limits}


def require_chips(chips: int, allow_cpu: bool = False) -> dict:
    """The device as JAX reports it; no TPU, or fewer chips than the cell
    asks for, ends the run with no result."""
    import jax

    devs = jax.devices()
    platform = devs[0].platform
    if platform != "tpu" and not allow_cpu:
        print(f"no accelerator: JAX reports {platform!r}", file=sys.stderr)
        raise NoChip(3)
    if len(devs) < chips:
        print(f"cell needs {chips} chips, JAX reports {len(devs)}",
              file=sys.stderr)
        raise NoChip(3)
    return {"platform": platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes(chips: int) -> int:
    import jax

    peak = 0
    for d in jax.devices()[:max(chips, 1)]:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


class Stages:
    """Says on standard error how long each part of a run took: set-up
    is most of what a run costs, and this is where it is read."""

    def __init__(self, t0: float):
        self.t = t0

    def mark(self, name: str):
        now = time.perf_counter()
        print(f"stage {name} {now - self.t:.2f} s", file=sys.stderr,
              flush=True)
        self.t = now


class CompileCounter:
    """Counts backend compilations by the time they ended."""

    def __init__(self):
        import jax.monitoring as mon

        self.times = []
        mon.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if "backend_compile" in event:
            self.times.append(time.perf_counter())

    def between(self, t0: float, t1: float) -> int:
        return sum(1 for t in self.times if t0 <= t <= t1)


class TraceWindow:
    """Records the first `TRACE_SECONDS` of a window with the JAX
    profiler and stops it from a helper thread, so the measured loop
    never waits for the dump. `snapshot` (the serving cell's counters)
    is read as the recording starts and as it stops."""

    def __init__(self, enabled: bool, seconds: float):
        self.enabled = enabled
        self.seconds = min(seconds, TRACE_SECONDS)
        self.dir = os.path.join(ROOT, ".bench_trace")
        self.t0 = self.t1 = None
        self._thread = None
        self.snap0 = self.snap1 = None

    def start(self, snapshot=None):
        if not self.enabled:
            return
        import jax.profiler as jp

        shutil.rmtree(self.dir, ignore_errors=True)
        opts = jp.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jp.start_trace(self.dir, profiler_options=opts)
        self.snap0 = snapshot() if snapshot else None
        self.t0 = time.perf_counter()

        def stop():
            time.sleep(self.seconds)
            self.snap1 = snapshot() if snapshot else None
            self.t1 = time.perf_counter()
            jp.stop_trace()

        self._thread = threading.Thread(target=stop, daemon=True)
        self._thread.start()

    def finish(self):
        """Wait for the dump and flatten it. None when not tracing."""
        if not self.enabled:
            return None
        self._thread.join()
        from . import span_reduce

        paths = glob.glob(os.path.join(self.dir, "plugins", "profile", "*",
                                       "*.xplane.pb"))
        if not paths:
            raise RuntimeError("the profiler wrote no trace")
        # `trace_reduce.load_xplane`'s form with the program's spans kept
        # whole (their `kind`, `round`, `rid`): the span readers need them
        trace = span_reduce.load(paths[0])
        keep = os.environ.get("BENCH_KEEP_TRACE")
        if keep:
            os.makedirs(os.path.dirname(keep), exist_ok=True)
            shutil.copy(paths[0], keep)
        shutil.rmtree(self.dir, ignore_errors=True)
        return trace

    @property
    def window_s(self):
        return None if self.t0 is None else self.t1 - self.t0


def percentile(values, q: float) -> float:
    """The q-quantile (0..1) by linear interpolation; worst value for a
    sample too small to have one."""
    vals = sorted(values)
    if not vals:
        return float("nan")
    if len(vals) == 1:
        return float(vals[0])
    pos = q * (len(vals) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(vals) - 1)
    return float(vals[lo] + (vals[hi] - vals[lo]) * (pos - lo))


def median(values) -> float:
    return float(statistics.median(values)) if values else float("nan")


# ------------------------------------------------------- per-layer readers


def read_metric(spec: dict, ctx: dict):
    """One per-layer metric from what the run collected, by the reader
    its file names. A reader that finds nothing to read returns None and
    the metric is left out of the line."""
    reader = READERS[spec["reader"]]
    value = reader(ctx, spec.get("params", {}))
    if value is None or value != value:
        return None
    return float(value)


def _r_value(ctx, p):
    return ctx["values"].get(p["key"])


def _reduced(ctx, fn):
    """`fn(trace)`, worked out once a trace however many metric files
    read it."""
    done = ctx.setdefault("reduced", {})
    if fn not in done:
        done[fn] = fn(ctx["trace"])
    return done[fn]


def _r_idle_share(ctx, p):
    trace = ctx.get("trace")
    if not trace:
        return None
    from . import trace_reduce

    busy = _reduced(ctx, trace_reduce.busy_seconds)
    if not busy or not ctx["trace_window_s"]:
        return None
    return 100.0 * (1.0 - min(busy) / ctx["trace_window_s"])


def _r_exposed_collective(ctx, p):
    trace = ctx.get("trace")
    if not trace:
        return None
    from . import trace_reduce

    exposed = _reduced(ctx, trace_reduce.exposed_collective_seconds)
    if not exposed or not ctx["trace_window_s"]:
        return None
    return 100.0 * max(exposed) / ctx["trace_window_s"]


def _r_mfu(ctx, p):
    """Model FLOPs of the work done over (time x chips x peak)."""
    flops, seconds = ctx["values"].get(p["flops"]), \
        ctx["values"].get(p["seconds"])
    if not flops or not seconds:
        return None
    if not ctx.get("peaks"):
        return None
    peak = ctx["peaks"]["peak_bf16_flops"]
    return 100.0 * flops / (seconds * ctx["chips"] * peak)


def _work(ctx, p, what: str):
    """A roofline's FLOPs or bytes: the family's own function, kept with
    the benchmark and named by the metric file (`params.flops_fn`,
    `params.bytes_fn`), of the traced part's counters."""
    if not ctx.get("traced"):
        return None
    fn = getattr(ctx["family"], p[what + "_fn"])
    return fn(ctx["cfg"], ctx["use"], ctx["traced"])


def _r_site_roofline(ctx, p):
    """The least time the chip could take for the work (the larger of
    FLOPs over peak and bytes over bandwidth, both per chip) over the
    traced device time of the operations at the listed source `sites`
    and under the listed named `scopes`."""
    trace = ctx.get("trace")
    if not trace or not ctx.get("peaks"):
        return None
    from . import span_reduce, trace_reduce

    flops, nbytes = _work(ctx, p, "flops"), _work(ctx, p, "bytes")
    if not flops or not nbytes:
        return None
    spent = trace_reduce.site_seconds(trace, p["sites"]) \
        if p.get("sites") else 0.0
    if p.get("scopes"):
        got = _reduced(ctx, span_reduce.scope_seconds)
        spent += sum(span_reduce.scope_time(got, prefix)
                     for prefix in p["scopes"])
    if spent <= 0:
        return None
    chips = ctx["chips"]
    needed = max(flops / chips / ctx["peaks"]["peak_bf16_flops"],
                 nbytes / chips / ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * needed / spent


def _r_ratio(ctx, p):
    """`scale` x `num` / `den`, two keys of the run's values (counters,
    their `.window` or `.traced` differences); `one_minus`: the rest."""
    num, den = ctx["values"].get(p["num"]), ctx["values"].get(p["den"])
    if num is None or not den:
        return None
    share = num / den
    return p.get("scale", 1.0) * (1.0 - share if p.get("one_minus")
                                  else share)


def _r_scope_share(ctx, p):
    """Share of device self time under the named scope `prefix`, in the
    listed `phases` (all four without the key). A trace none of whose
    operations carries a scope has nothing to read."""
    trace = ctx.get("trace")
    if not trace:
        return None
    from . import span_reduce

    got = _reduced(ctx, span_reduce.scope_seconds)
    if set(got["by_scope"]) <= {span_reduce.UNNAMED}:
        return None
    # over the same sums it is a part of, so a share never passes 100
    total = sum(sum(row.values()) for row in got["by_scope"].values())
    return 100.0 * span_reduce.scope_time(
        got, p["prefix"], p.get("phases", span_reduce.PHASES)) / total


def _r_span_rounds(ctx, p):
    """One `field` (`host_ms_p50`, `round_ms_p50`, `rounds`, ...) of the
    `engine.round` spans of one `kind` (`decode`, `mixed`)."""
    trace = ctx.get("trace")
    if not trace:
        return None
    from . import span_reduce

    row = _reduced(ctx, span_reduce.round_kinds).get(p["kind"])
    return None if row is None else row.get(p["field"])


def _r_span_gap_share(ctx, p):
    """Share of the first device's idle seconds that lies under the
    program span `span` (innermost)."""
    trace = ctx.get("trace")
    if not trace:
        return None
    from . import span_reduce

    got = _reduced(ctx, span_reduce.gap_attribution)
    if not got["idle_s"] or not got["by_span"]:
        return None
    return 100.0 * got["by_span"].get(p["span"], 0.0) / got["idle_s"]


def _r_collective_owner(ctx, p):
    """Exposed collective seconds of the collectives that the scope
    `owner` owns, as a share of the traced window."""
    trace = ctx.get("trace")
    if not trace or not ctx["trace_window_s"]:
        return None
    from . import span_reduce

    owners = _reduced(ctx, span_reduce.collective_owner)
    if not owners:
        return None
    sec = sum(v for k, v in owners.items()
              if span_reduce.under(k, p["owner"]))
    return 100.0 * sec / ctx["trace_window_s"]


READERS = {
    "value": _r_value,
    "idle_share": _r_idle_share,
    "exposed_collective_share": _r_exposed_collective,
    "mfu": _r_mfu,
    "site_roofline": _r_site_roofline,
    "ratio": _r_ratio,
    "scope_share": _r_scope_share,
    "span_rounds": _r_span_rounds,
    "span_gap_share": _r_span_gap_share,
    "collective_owner": _r_collective_owner,
}


def reader_context(cell: dict, use: dict, values: dict, traced: dict,
                   tracer, trace, device: dict) -> dict:
    """What the per-layer readers see of a run. `traced`: the traced
    part's own counters. With a trace, `device` gets its busy seconds
    and the traced window's length."""
    from . import families, flops

    if trace:
        device.update(busy_s=mean_busy(trace), window_s=tracer.window_s)
    cfg = cell["cfg"]
    return {"values": values, "chips": cell["entry"]["chips"],
            "trace": trace, "trace_window_s": tracer.window_s,
            "cfg": cfg, "use": use, "family": families.find(cfg),
            "traced": traced,
            "peaks": flops.chip_peaks(device["kind"])
            if device["platform"] == "tpu" else None}


def mean_busy(trace: dict) -> float:
    """Seconds an operation ran on the device, averaged over the chips."""
    from . import trace_reduce

    busy = trace_reduce.busy_seconds(trace)
    return float(sum(busy) / len(busy)) if busy else 0.0


def breakdown(trace: dict) -> dict:
    from . import trace_reduce

    return {
        "device_ops": trace_reduce.top(
            trace_reduce.time_by_category_site(trace), 10),
        "idle_gaps": trace_reduce.idle_gaps(trace)[:10],
    }


# ----------------------------------------------------------- result line


def result_line(cell: dict, trace_on: bool, *, correct: bool, attempted: int,
                failed: int, values: dict, device: dict, ctx: dict,
                compared: dict) -> dict:
    """The contract's one JSON object. `--trace 0`: the cell's end-to-end
    metrics; `--trace 1`: its per-layer metrics."""
    metrics = {}
    if trace_on:
        for spec in cell["per_layer"]:
            v = read_metric(spec, ctx)
            if v is not None:
                metrics[spec["name"]] = {"value": v, "unit": spec["unit"]}
    else:
        for m in cell["end_to_end"]:
            metrics[m["name"]] = {"value": float(values[m["name"]]),
                                  "unit": m["unit"]}
    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed), "metrics": metrics, "device": device}
    if trace_on and ctx.get("trace"):
        out["breakdown"] = breakdown(ctx["trace"])
    out["compared"] = compared
    return out


def write_record(name: str, record: dict):
    """Per-step / per-request times of this run, for reading an outlier
    after the fact. Written after the window, small."""
    try:
        os.makedirs(OUT_DIR, exist_ok=True)
        with open(os.path.join(OUT_DIR, name), "w") as f:
            json.dump(record, f)
    except OSError as e:  # a read-only checkout loses the record only
        print(f"record not written: {e}", file=sys.stderr)
