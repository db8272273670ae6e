"""Host-side span tracer: one emitter, two sinks (ISSUE 13, ISSUE 26).

The serving engine and the trainer are host-driven schedulers around
jitted dispatches; diagnosing a stall ("why did request 41's TTFT blow
up at 02:13?") needs the host timeline — queue wait, admission, chunk
prefill, decode-scan dispatch, COW copies, checkpoint stalls — laid
against the device's. Every live span (`span`, `step_span`) therefore
goes to two sinks:

- ALWAYS a `jax.profiler.TraceAnnotation`: while a profiler capture
  runs (`benchmark/run.py --trace 1`, POST /profile,
  --profile_step_range) the span lands on the host plane of the
  `.xplane.pb`, on its thread's line, on the same clock as the device
  operations, its args as the event's stats. With no capture running
  the annotation is one TraceMe activity check.
- with `enabled=True` (a --trace_dir): also a bounded ring of Chrome
  trace events, exported as the `{"traceEvents": [...]}` JSON Perfetto
  and chrome://tracing load.

`complete` (a span only known after the fact, such as `queue_wait`) and
`instant` go to the ring only: a profiler annotation cannot be written
retroactively. A span keeps its own two clock reads (`t0`, `t1`,
`seconds`), so the emitter's counters are summed from the same reads.

Correlation model (docs/GUIDE.md "Observability"): every span carries
its emitter's args — engine spans the request id (`rid`) and round
number, trainer spans the train step — so a client-visible stall greps
from the SSE `id:` field to the exact engine rounds it spanned, and a
loss spike to the data-fetch/step/save spans around it.

The HARD contract (pinned by tests/test_telemetry.py and the
graft-check audit): emission is pure host bookkeeping — perf_counter
reads, dict literals, deque appends. No tracer method may touch a jax
value, so telemetry-on jitted steps are bitwise-identical to
telemetry-off by construction, and `analysis/lint.py` lists the emit
methods in GR006 HOT_PATHS so a device sync can never creep in.

A disabled tracer (`enabled=False`, what every component builds when no
--trace_dir is given) keeps no ring: a span then costs its annotation
and its two clock reads (measured on the chip's host: PERF.md).
"""

from __future__ import annotations

import json
import os
import threading
import time
from collections import deque
from typing import Optional

from jax.profiler import StepTraceAnnotation, TraceAnnotation

__all__ = ["SpanTracer"]


class _Span:
    """One live span: a profiler annotation for its lifetime and, with
    the ring on, a complete ("ph": "X") event on exit."""

    __slots__ = ("_tracer", "_name", "_args", "_ann", "t0", "t1")

    def __init__(self, tracer: "SpanTracer", name: str, args: dict, ann):
        self._tracer = tracer
        self._name = name
        self._args = args
        self._ann = ann

    def __enter__(self):
        self._ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        # pure host bookkeeping (GR006 HOT_PATHS): one clock read, the
        # annotation's end and one ring append — never a device value
        self.t1 = time.perf_counter()
        self._ann.__exit__(*exc)
        if self._tracer.enabled:
            self._tracer.complete(self._name, self.t0, self.t1,
                                  **self._args)
        return False

    def note(self, **kv) -> None:
        """Args only known once the span is open (how many requests an
        admission took, how many tokens a round booked). They reach the
        ring; the annotation took its arguments when it opened."""
        self._args.update(kv)

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


class SpanTracer:
    """Bounded ring of Chrome trace events with nestable span emitters.

    Nesting is positional, the Chrome trace-event way: a span emitted
    while another is open on the same thread lies inside it on the
    timeline (child `ts`/`ts+dur` contained in the parent's), so no
    explicit parent pointers are kept — the emit path stays O(1).

    `set_context(**kv)` attaches ambient correlation keys (e.g. the
    trainer's current `step`) merged into every subsequent event's args;
    per-call args win on collision.
    """

    def __init__(self, capacity: int = 65536, enabled: bool = True):
        self.enabled = enabled
        self.capacity = capacity
        self._events: deque = deque(maxlen=capacity)
        # serializes ring mutation vs events()/export(): iterating the
        # deque while another thread appends raises RuntimeError (the
        # HTTP/bench threads read while the serve loop emits)
        self._events_lock = threading.Lock()
        self._epoch = time.perf_counter()
        self._epoch_unix = time.time()
        self._context: dict = {}
        self._pid = os.getpid()
        # stable small tids: Perfetto tracks read better as "tid 1..n"
        # than 140737352472320
        self._tids: dict = {}
        self._tid_lock = threading.Lock()
        self.dropped = 0  # events pushed past capacity (ring overwrote)

    # -- emitters (GR006 HOT_PATHS: host bookkeeping only) -----------------

    def span(self, name: str, **args):
        """Context manager around one phase of a round or a step."""
        return self._live(TraceAnnotation, name, args)

    def step_span(self, name: str, step_num: int, **args):
        """`span` for one whole training step: the profiler's step
        marker (`StepTraceAnnotation`), which its tools group by."""
        args["step_num"] = step_num
        return self._live(StepTraceAnnotation, name, args)

    def _live(self, annotation, name: str, args: dict) -> _Span:
        if self._context:
            args = {**self._context, **args}
        return _Span(self, name, args, annotation(name, **args))

    def instant(self, name: str, **args) -> None:
        """Zero-duration marker event (ph "i")."""
        if not self.enabled:
            return
        if self._context:
            args = {**self._context, **args}
        self._push({"name": name, "ph": "i", "s": "t",
                    "ts": self._ts(time.perf_counter()),
                    "pid": self._pid, "tid": self._tid(), "args": args})

    def complete(self, name: str, t0: float, t1: float, **args) -> None:
        """Record a complete span from two perf_counter readings — the
        retroactive form: the engine books `queue_wait` at admission
        from the request's own submit/admit stamps, after the fact."""
        if not self.enabled:
            return
        if self._context:
            args = {**self._context, **args}
        self._push({"name": name, "ph": "X", "ts": self._ts(t0),
                    "dur": max(round((t1 - t0) * 1e6), 0),
                    "pid": self._pid, "tid": self._tid(), "args": args})

    def set_context(self, **kv) -> None:
        """Merge ambient correlation keys into subsequent events' args
        (e.g. `set_context(step=it)` each trainer iteration, the
        engine's `replica`). Every component owns its tracer, ring or
        no ring, so the keys reach its annotations either way."""
        self._context.update(kv)

    # -- internals ---------------------------------------------------------

    def _ts(self, t: float) -> int:
        return round((t - self._epoch) * 1e6)  # us since tracer epoch

    def _tid(self) -> int:
        ident = threading.get_ident()
        tid = self._tids.get(ident)
        if tid is None:
            with self._tid_lock:
                tid = self._tids.setdefault(ident, len(self._tids) + 1)
        return tid

    def _push(self, ev: dict) -> None:
        with self._events_lock:
            if len(self._events) == self.capacity:
                self.dropped += 1
            self._events.append(ev)

    # -- export ------------------------------------------------------------

    def events(self) -> list:
        """Snapshot of the ring, sorted by ts (deque appends from
        concurrent threads may interleave slightly out of order; the
        trace-event format wants monotone ts)."""
        with self._events_lock:
            evs = list(self._events)
        return sorted(evs, key=lambda e: (e["pid"], e["tid"], e["ts"]))

    def to_chrome_trace(self) -> dict:
        """The Chrome trace-event JSON object Perfetto loads."""
        evs = self.events()
        # thread-name metadata events so Perfetto labels the tracks
        meta = [{"name": "process_name", "ph": "M", "pid": self._pid,
                 "tid": 0, "args": {"name": "megatron_llm_tpu"}}]
        for ident, tid in sorted(self._tids.items(), key=lambda x: x[1]):
            meta.append({"name": "thread_name", "ph": "M",
                         "pid": self._pid, "tid": tid,
                         "args": {"name": f"host-thread-{tid}"}})
        return {
            "traceEvents": meta + evs,
            "displayTimeUnit": "ms",
            "otherData": {
                "epoch_unix": self._epoch_unix,
                "dropped_events": self.dropped,
            },
        }

    def export(self, path: str) -> Optional[str]:
        """Write the Chrome trace JSON artifact; returns the path (None
        when the tracer is disabled — nothing to write)."""
        if not self.enabled:
            return None
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        tmp = path + ".tmp"
        with open(tmp, "w", encoding="utf-8") as fh:
            json.dump(self.to_chrome_trace(), fh)
        os.replace(tmp, path)
        return path
