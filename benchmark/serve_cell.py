"""A serving cell's run: seeded weights, a started engine warmed on its
own buckets, load offered from one generator thread, requests timed from
the harness's side, then a sample of what was served against the
reference.

Open loop (`arrivals.process == "poisson"`): every request has an instant
at which it is due; it is timed from that instant, sent or not, and
followed to its end after arrivals stop (drain, capped). Backlog: the
generator keeps `waiting_per_slot` x slots requests waiting; the window
counts every output token emitted inside it.
"""

from __future__ import annotations

import gc
import sys
import threading
import time

import numpy as np

from . import check, families, harness, program, traffic


class Tap:
    """Takes the place of a request's stream queue: the engine's serve
    thread calls `put` for every generated token (None closes), and the
    call stamps the harness's clock. No thread, no polling."""

    __slots__ = ("stamps", "closed", "on_close")

    def __init__(self, on_close):
        self.stamps = []
        self.closed = None
        self.on_close = on_close

    def put(self, tok):
        now = time.perf_counter()
        if tok is None:
            self.closed = now
            self.on_close()
        else:
            self.stamps.append(now)


class Load:
    """The generator thread and the record of every request."""

    def __init__(self, prog, reqs, open_loop: bool, target_outstanding: int):
        self.prog, self.reqs = prog, reqs
        self.open_loop = open_loop
        self.target = target_outstanding
        self.records = []
        self.closed = 0
        self.stop = False
        self.t_open = None
        self.thread = threading.Thread(target=self._run, daemon=True)

    def _closed(self):
        self.closed += 1

    def _send(self, r, due_abs):
        rec = {"due": due_abs, "sent": time.perf_counter(),
               "p": len(r["prompt"]), "out": r["out"], "req": None,
               "tap": Tap(self._closed), "error": None}
        try:
            req = self.prog.submit(r["prompt"], r["out"])
            old, req.stream_q = req.stream_q, rec["tap"]
            while old is not None and not old.empty():  # booked meanwhile
                rec["tap"].put(old.get_nowait())
            rec["req"] = req
        except Exception as e:  # noqa: BLE001 refused: counted as failed
            rec["error"] = repr(e)
            self.closed += 1
        self.records.append(rec)
        return rec

    def _run(self):
        clock = time.perf_counter
        if self.open_loop:
            for r in self.reqs:
                due = self.t_open + r["due"]
                while not self.stop:
                    wait = due - clock()
                    if wait <= 0:
                        break
                    time.sleep(min(wait, 0.05))
                if self.stop:
                    return
                self._send(r, due)
            return
        for r in self.reqs:
            while not self.stop and \
                    len(self.records) - self.closed >= self.target:
                time.sleep(0.002)
            if self.stop:
                return
            self._send(r, clock())

    def warm(self, reqs):
        recs = [self._send(r, time.perf_counter()) for r in reqs]
        for rec in recs:
            rec["req"].result(timeout=600)
        self.records.clear()
        self.closed = 0


def run(cell: dict, seed: int, seconds: float, trace_on: bool, t_start: float,
        device: dict, stages=None, fault: str | None = None,
        control: str | None = None) -> dict:
    """`control` (prove.py and the tests only): a lower precision whose
    own reading of the same prompts and tokens goes into the line under
    `control`; a benchmark run never computes it."""
    cfg, mix = cell["cfg"], cell["mix"]
    use = cfg["serve"]
    fam = families.find(cfg)
    chips = cell["entry"]["chips"]
    layers, slots, vocab = use["num_hidden_layers"], use["slots"], \
        cfg["vocab_size"]
    arr = mix["arrivals"]
    open_loop = arr["process"] == "poisson"
    compiles = harness.CompileCounter()
    stages = stages or harness.Stages(t_start)

    prog = program.ServeProgram(cfg, seed, mark=stages.mark)
    if fault:
        prog.plant(fault)
    if open_loop:
        reqs = traffic.serve_requests(mix, seed, seconds, vocab)
        target = 0
    else:
        target = slots * (1 + arr["waiting_per_slot"])
        n = target + int((seconds + mix["ramp_s"]) * arr["max_rate_per_s"])
        reqs = traffic.serve_requests(mix, seed, seconds, vocab, n=n)
    load = Load(prog, reqs, open_loop, target)
    # warm traffic: every host path of a round fires once on real requests
    warm_mix = dict(mix, arrivals={"process": "backlog"}, length_cycle=None,
                    prompt_len={"dist": "constant", "value": 48},
                    output_len={"dist": "constant", "value": 12})
    load.warm(traffic.serve_requests(warm_mix, seed, 0, vocab, n=slots + 2))
    stages.mark("warm_traffic")

    if not open_loop:
        # the window opens on a full engine: the backlog runs `ramp_s`
        # before it, so the first wave of prompts is not in the rate
        load.thread.start()
        time.sleep(mix["ramp_s"])
        stages.mark("ramp")
    tracer = harness.TraceWindow(trace_on, seconds)
    gc.collect()
    gc.freeze()
    tracer.start(snapshot=prog.counters)
    c_open = prog.counters()
    t_open = time.perf_counter()
    if open_loop:
        load.t_open = t_open
        load.thread.start()
    deadline = t_open + seconds
    while True:
        wait = deadline - time.perf_counter()
        if wait <= 0:
            break
        time.sleep(min(wait, 0.25))
    t_close = time.perf_counter()
    c_close = prog.counters()
    load.stop = not open_loop  # open loop: every arrival is already due
    gc.unfreeze()
    setup_s = t_open - t_start
    window_s = t_close - t_open

    # follow the requests due in the window to their end (capped)
    records = list(load.records) if not open_loop else None
    cap = t_close + mix["drain_cap_s"]
    if open_loop:
        load.thread.join(timeout=5)
        load.stop = True
        records = list(load.records)
        for rec in records:
            if rec["req"] is not None:
                rec["req"].done.wait(max(cap - time.perf_counter(), 0))
    finished, failed = [], 0
    for rec in records:
        req = rec["req"]
        if req is None or (req.done.is_set() and req.error is not None):
            failed += 1
        elif req.done.is_set():
            finished.append(rec)
        elif open_loop:
            failed += 1  # outlasted the cap
    # the engine's thread would fight the trace reader for the interpreter
    prog.stop()
    stages.mark("window_and_drain")
    trace = tracer.finish()
    stages.mark("trace_read")

    in_window = lambda t: t_open <= t <= t_close  # noqa: E731
    out_tokens = sum(1 for rec in records for t in rec["tap"].stamps
                     if in_window(t))
    values = {
        "setup_s": setup_s,
        "window_s": window_s,
        "serve_tok_s": out_tokens / window_s,
        "compiles_in_window": compiles.between(t_open, t_close),
        "kernel_fallbacks": prog.kernel_fallbacks(),
    }
    worst_ms = (seconds + mix["drain_cap_s"]) * 1e3
    ttft, tpot, qwait, lag, busy_slot_s = [], [], [], [], 0.0
    for rec in records:
        st = rec["tap"].stamps
        req = rec["req"]
        lag.append((rec["sent"] - rec["due"]) * 1e3)
        ok = req is not None and req.done.is_set() and req.error is None
        if open_loop:
            ttft.append((st[0] - rec["due"]) * 1e3 if ok else worst_ms)
            tpot.append((st[-1] - st[0]) / (len(st) - 1) * 1e3
                        if ok and len(st) > 1 else worst_ms)
        elif st and in_window(st[0]):
            ttft.append((st[0] - rec["sent"]) * 1e3)
            if ok and len(st) > 1:
                tpot.append((st[-1] - st[0]) / (len(st) - 1) * 1e3)
        if req is not None and req.t_admit:
            qwait.append((req.t_admit - rec["due"]) * 1e3)
            end = req.t_done if req.t_done else t_close
            busy_slot_s += max(min(end, t_close) - max(req.t_admit, t_open),
                               0.0)
    if open_loop:
        values.update(ttft_p90_ms=harness.percentile(ttft, 0.9),
                      tpot_p90_ms=harness.percentile(tpot, 0.9),
                      tpot_p50_ms=harness.percentile(tpot, 0.5),
                      queue_wait_p90_ms=harness.percentile(qwait, 0.9),
                      generator_lag_p95_ms=harness.percentile(lag, 0.95))
    else:
        values.update(sat_ttft_p90_ms=harness.percentile(ttft, 0.9),
                      sat_tpot_p90_ms=harness.percentile(tpot, 0.9))
    values["batch_occupancy"] = 100.0 * busy_slot_s / (slots * window_s)
    values["window_flops"] = _flops(fam, cfg, layers, records, t_open,
                                    t_close)
    # every counter the engine has: its closing reading under its own
    # name, the window's difference as `<name>.window`
    for name, value in c_close.items():
        values.setdefault(name, value)
        values[name + ".window"] = value - c_open.get(name, 0)
    traced = {}
    if trace_on:
        t0, t1 = tracer.t0, tracer.t1
        s0, s1 = tracer.snap0, tracer.snap1
        out_tr = sum(1 for rec in records for t in rec["tap"].stamps
                     if t0 <= t <= t1)
        toks = out_tr + (s1["prefill_tokens"] - s0["prefill_tokens"])
        # the traced part's own counters, `<name>.traced` among the values
        traced = {name: s1[name] - s0.get(name, 0) for name in s1}
        traced.update(tokens=toks, out_tokens=out_tr)
        values.update({name + ".traced": v for name, v in traced.items()})
    peak = harness.memory_peak_bytes(chips)
    values["peak_hbm_gb"] = peak / 1e9 if peak else None
    device = dict(device, memory_peak_bytes=peak)
    print(f"requests {len(records)} finished {len(finished)} failed "
          f"{failed} out_tokens {out_tokens} tok_s "
          f"{values['serve_tok_s']:.2f} steps "
          f"{c_close['steps'] - c_open['steps']} prefill_tokens "
          f"{c_close['prefill_tokens'] - c_open['prefill_tokens']} "
          f"occupancy {values['batch_occupancy']:.1f} setup_s "
          f"{setup_s:.2f} lag_max_ms {max(lag) if lag else 0:.2f}",
          file=sys.stderr)
    harness.write_record(
        f"requests_{cell['name']}_{seed}_{int(trace_on)}.json",
        {"ttft_ms": ttft, "tpot_ms": tpot, "queue_wait_ms": qwait,
         "lag_ms": lag, "window_s": window_s, "setup_s": setup_s,
         "out_tokens": out_tokens})

    # the check: a sample of what was served, the longest in it
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 9])
    finished.sort(key=lambda r: -(r["p"] + len(r["req"].tokens)))
    pick = finished[:1]
    rest = finished[1:]
    k = min(mix["check_requests"] - 1, len(rest))
    pick += [rest[i] for i in rng.choice(len(rest), size=k, replace=False)] \
        if k else []
    samples = [{"tokens": list(r["req"].tokens), "prompt_len": r["p"]}
               for r in pick]
    n_records, n_finished = len(records), len(finished)
    prog.free()
    del load, records, finished, rest, pick
    gc.collect()
    numbers, control_numbers = {}, None
    if samples:
        t_ref = time.perf_counter()
        logits = check.serve_reference_logits(cfg, seed, samples)
        numbers = check.serve_numbers(samples, logits)
        print(f"reference took {time.perf_counter() - t_ref:.1f} s over "
              f"{len(samples)} requests", file=sys.stderr)
        if control:
            control_numbers = check.serve_numbers(
                samples, logits, check.serve_reference_logits(
                    cfg, seed, samples, precision=control))
    ok, compared = check.verdict(numbers, cell["limits"])
    attempted = n_records if open_loop else n_finished + failed
    ok = ok and failed == 0 and bool(samples)

    ctx = harness.reader_context(cell, use, values, traced, tracer, trace,
                                 device)
    line = harness.result_line(
        cell, trace_on, correct=ok, attempted=attempted, failed=failed,
        values=values, device=device, ctx=ctx, compared=compared)
    if control_numbers is not None:
        line["control"] = check.verdict(control_numbers, cell["limits"])[1]
    return line


def _flops(fam, cfg, layers, records, t0, t1) -> float:
    """Model FLOPs of every prompt and output token processed in
    [t0, t1]: a prompt is booked when its first token comes out, an
    output token when it is emitted (its own forward pass follows)."""
    total = 0.0
    for rec in records:
        st = rec["tap"].stamps
        if not st:
            continue
        p = rec["p"]
        if t0 <= st[0] <= t1:
            total += fam.serve_span_flops(cfg, layers, 0, p, 1)
        inside = [i for i, t in enumerate(st) if t0 <= t <= t1]
        if inside:
            total += fam.serve_span_flops(
                cfg, layers, p + inside[0], p + inside[-1] + 1, len(inside))
    return total
