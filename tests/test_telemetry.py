"""Flight-recorder telemetry suite (ISSUE 13): span tracer, flight
recorder, Prometheus histograms, engine/trainer wiring, and the hard
contract that telemetry NEVER changes the math.

Pinned here (tier-1):
- span nesting/ordering: child spans lie inside their parent on the
  timeline, instants and context keys land in args, the ring is
  bounded, a disabled tracer is a shared no-op;
- Chrome trace-event JSON validity: the export loads, every event
  carries name/ph/ts/pid/tid, complete events carry dur, and ts is
  monotone within each (pid, tid) track;
- flight-recorder ring bounds under sustained traffic, dump artifacts
  (path logged LOUDLY), and the no-directory/unwritable fallbacks;
- Prometheus exposition: cumulative histogram buckets with correct
  sums/counts, gauge rendering, the info metric for string facts, and
  the page parses;
- /metrics byte-compatibility: the default JSON response is exactly
  the legacy counters() schema (key set AND order AND formatting);
  content negotiation serves the text exposition with histograms;
- the bitwise contract: telemetry-on engine greedy streams and
  telemetry-on train losses/params equal telemetry-off TO THE BIT
  (the runtime half of the claim; the graft-check audit pins the
  compiled-artifact half);
- recorder dump triggers: engine serve-loop poison leaves an artifact
  correlating the queued/live request by rid (watchdog-rollback and
  SIGTERM artifacts are pinned in test_fault_tolerance.py);
- the profiler hook: POST-/profile-style request_profile() is a loud
  no-op when capture is unsupported, the engine keeps serving, and the
  hook re-arms.
"""

from __future__ import annotations

import glob
import json
import os
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from megatron_llm_tpu.config import ParallelConfig, TrainConfig, tiny_config
from megatron_llm_tpu.models import LlamaModel
from megatron_llm_tpu.telemetry import (
    FlightRecorder,
    Histogram,
    SpanTracer,
    parse_prometheus,
    render_prometheus,
)

jax.config.update("jax_platforms", "cpu")


# ---------------------------------------------------------------------------
# SpanTracer
# ---------------------------------------------------------------------------


class TestSpanTracer:
    def test_span_nesting_and_ordering(self):
        tr = SpanTracer()
        with tr.span("outer", rid=1):
            with tr.span("inner_a", rid=1):
                pass
            with tr.span("inner_b", rid=1):
                pass
        evs = {e["name"]: e for e in tr.events()}
        outer, a, b = evs["outer"], evs["inner_a"], evs["inner_b"]
        # children lie INSIDE the parent on the timeline (the Chrome
        # trace-event nesting model: containment, not pointers)
        for child in (a, b):
            assert outer["ts"] <= child["ts"]
            assert child["ts"] + child["dur"] <= outer["ts"] + outer["dur"]
        # siblings ordered: a completes before b starts
        assert a["ts"] + a["dur"] <= b["ts"]
        assert all(e["args"]["rid"] == 1 for e in (outer, a, b))

    def test_context_merges_into_args(self):
        tr = SpanTracer()
        tr.set_context(step=7)
        tr.instant("marker", extra=1)
        with tr.span("s", extra=2):
            pass
        m, s = tr.events()
        assert m["args"] == {"step": 7, "extra": 1}
        assert s["args"] == {"step": 7, "extra": 2}
        # per-call args win on collision
        tr.instant("override", step=9)
        assert tr.events()[-1]["args"]["step"] == 9

    def test_ring_bounded_and_counts_drops(self):
        tr = SpanTracer(capacity=64)
        for i in range(500):
            tr.instant("e", i=i)
        assert len(tr.events()) == 64
        assert tr.dropped == 500 - 64
        # the ring keeps the NEWEST events (a flight record, not a log)
        assert tr.events()[-1]["args"]["i"] == 499

    def test_disabled_tracer_keeps_no_ring_but_times_its_spans(self):
        """No --trace_dir: nothing reaches the ring, but a span is still
        a profiler annotation with its own two clock reads (the
        emitter's counters are summed from them)."""
        off = SpanTracer(enabled=False)
        off.set_context(replica=3)
        with off.span("x", rid=1) as sp:
            sp.note(booked=2)
        assert sp.t1 >= sp.t0 and sp.seconds == sp.t1 - sp.t0
        with off.step_span("s", step_num=4):
            pass
        off.instant("x")
        off.complete("x", 0.0, 1.0)
        assert off.events() == []

    def test_note_and_step_span_reach_the_ring(self):
        tr = SpanTracer()
        with tr.step_span("train", step_num=7):
            with tr.span("train.dispatch", minted=False) as sp:
                sp.note(late=1)
        evs = {e["name"]: e for e in tr.events()}
        assert evs["train"]["args"] == {"step_num": 7}
        assert evs["train.dispatch"]["args"] == {"minted": False, "late": 1}
        outer, inner = evs["train"], evs["train.dispatch"]
        assert outer["ts"] <= inner["ts"]
        assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"]

    def test_chrome_trace_export_valid(self, tmp_path):
        tr = SpanTracer()

        def worker():
            with tr.span("w"):
                tr.instant("w_marker")

        with tr.span("main", rid=3):
            t = threading.Thread(target=worker)
            t.start()
            t.join()
        path = tr.export(str(tmp_path / "trace.json"))
        with open(path) as f:
            doc = json.load(f)  # loads = valid JSON
        evs = doc["traceEvents"]
        assert isinstance(evs, list) and evs
        data_evs = [e for e in evs if e["ph"] != "M"]
        for e in data_evs:
            for key in ("name", "ph", "ts", "pid", "tid"):
                assert key in e, e
            assert e["ph"] in ("X", "i"), e
            if e["ph"] == "X":
                assert isinstance(e["dur"], int) and e["dur"] >= 0
        # ts monotone within each (pid, tid) track, in export order
        by_track = {}
        for e in data_evs:
            by_track.setdefault((e["pid"], e["tid"]), []).append(e["ts"])
        assert len(by_track) == 2  # main thread + worker thread
        for track, ts in by_track.items():
            assert ts == sorted(ts), (track, ts)
        # thread-name metadata present for Perfetto track labels
        assert any(e["ph"] == "M" and e["name"] == "thread_name"
                   for e in evs)

    def test_export_disabled_returns_none(self, tmp_path):
        assert SpanTracer(enabled=False).export(
            str(tmp_path / "x.json")) is None
        assert not (tmp_path / "x.json").exists()


# ---------------------------------------------------------------------------
# FlightRecorder
# ---------------------------------------------------------------------------


class TestFlightRecorder:
    def test_ring_bounded_under_sustained_traffic(self):
        rec = FlightRecorder(capacity=128)
        for i in range(10_000):
            rec.record("round", round=i, ms=0.5)
        snap = rec.snapshot()
        assert len(snap["events"]) == 128
        assert snap["dropped_events"] == 10_000 - 128
        # newest history survives — the whole point of a flight ring
        assert snap["events"][-1]["round"] == 9_999
        assert snap["events"][0]["round"] == 10_000 - 128

    def test_snapshot_shape_and_counters(self):
        rec = FlightRecorder(capacity=32)
        rec.record("submit", rid=5)
        rec.note_counters({"serve_tok_s": 12.5})
        snap = rec.snapshot(reason="unit", extra={"k": 1})
        assert snap["reason"] == "unit"
        assert snap["extra"] == {"k": 1}
        assert snap["counters"] == {"serve_tok_s": 12.5}
        assert snap["events"][0]["kind"] == "submit"
        assert snap["events"][0]["rid"] == 5
        assert "t" in snap["events"][0]

    def test_dump_writes_artifact_and_logs_loudly(self, tmp_path, caplog):
        rec = FlightRecorder(capacity=32)
        rec.record("poison", error="boom", rid=9)
        with caplog.at_level("ERROR",
                             logger="megatron_llm_tpu.telemetry.recorder"):
            path = rec.dump(str(tmp_path), "unit-test")
        assert path and os.path.exists(path)
        assert path in caplog.text  # the dump path IS the loud log line
        with open(path) as f:
            doc = json.load(f)
        assert doc["reason"] == "unit-test"
        assert doc["events"][0]["rid"] == 9

    def test_dump_without_dir_is_logged_summary(self, caplog):
        rec = FlightRecorder(capacity=32)
        rec.record("x")
        with caplog.at_level("ERROR",
                             logger="megatron_llm_tpu.telemetry.recorder"):
            assert rec.dump(None, "no-dir") is None
        assert "no record dir configured" in caplog.text

    def test_dump_write_failure_does_not_raise(self, tmp_path):
        rec = FlightRecorder(capacity=32)
        rec.record("x")
        blocker = tmp_path / "blocker"
        blocker.write_text("a file where a directory must go")
        # dumping "into" a file path fails os.makedirs/open — the
        # recorder must not mask the original failure with a second
        # traceback
        assert rec.dump(str(blocker / "sub"), "fail") is None


# ---------------------------------------------------------------------------
# Histogram + Prometheus exposition
# ---------------------------------------------------------------------------


class TestPrometheus:
    def test_histogram_cumulative_buckets_and_sum(self):
        h = Histogram("lat_ms", buckets=(1.0, 5.0, 25.0))
        for v in (0.5, 0.9, 3.0, 7.0, 100.0):
            h.observe(v)
        cum = dict(h.cumulative())
        assert cum[1.0] == 2        # <= 1
        assert cum[5.0] == 3        # <= 5
        assert cum[25.0] == 4       # <= 25
        assert cum[float("inf")] == 5
        assert h.count == 5
        assert h.sum == pytest.approx(111.4)
        # bucket counts are monotone non-decreasing (cumulative form)
        counts = [c for _, c in h.cumulative()]
        assert counts == sorted(counts)

    def test_boundary_is_le(self):
        h = Histogram("b", buckets=(10.0,))
        h.observe(10.0)  # le="10" INCLUDES 10.0 (Prometheus semantics)
        assert dict(h.cumulative())[10.0] == 1

    def test_exposition_parses_with_correct_values(self):
        h = Histogram("serve_ttft_ms", buckets=(1.0, 5.0))
        h.observe(0.4)
        h.observe(3.0)
        h.observe(40.0)
        text = render_prometheus(
            {"serve_tok_s": 123.5, "serve_queue_depth": 2,
             "serve_kv_dtype": "int8"}, [h])
        parsed = parse_prometheus(text)
        assert parsed["serve_tok_s"][""] == 123.5
        assert parsed["serve_queue_depth"][""] == 2
        assert parsed["serve_ttft_ms_bucket"]['le="1"'] == 1
        assert parsed["serve_ttft_ms_bucket"]['le="5"'] == 2
        assert parsed["serve_ttft_ms_bucket"]['le="+Inf"'] == 3
        assert parsed["serve_ttft_ms_sum"][""] == pytest.approx(43.4)
        assert parsed["serve_ttft_ms_count"][""] == 3
        # string facts collapse into the info metric, not a fake gauge
        assert parsed["build_info"]['serve_kv_dtype="int8"'] == 1
        assert "serve_kv_dtype" not in parsed
        # histogram TYPE line present for scrapers
        assert "# TYPE serve_ttft_ms histogram" in text


# ---------------------------------------------------------------------------
# Engine wiring (tiny model, CPU)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def tiny_model():
    cfg = tiny_config(compute_dtype=jnp.float32, use_decode_attn=False)
    model = LlamaModel(cfg)
    params = model.init(jax.random.key(7))
    return model, params


def _engine(tiny_model, tmp=None, **over):
    from megatron_llm_tpu.inference.engine import DecodeEngine

    model, params = tiny_model
    kw = dict(slots=2, page_size=16, max_context=64,
              prefill_chunk_tokens=16, vocab_size=256,
              termination_id=None)
    if tmp is not None:
        kw.update(trace_dir=str(tmp), record_dir=str(tmp))
    kw.update(over)
    return DecodeEngine(model, params, **kw)


# the legacy /metrics JSON schema for a plain (no prefix cache, no spec
# decode) engine — key set AND order, pinned so the default JSON stays
# byte-compatible while the Prometheus surface grows beside it
LEGACY_METRICS_KEYS = [
    "serve_kv_dtype", "serve_kv_pool_bytes", "serve_kv_bytes_per_token",
    "serve_slot_occupancy", "serve_queue_depth", "serve_pages_in_use",
    "serve_pages_free", "serve_admitted", "serve_retired",
    "serve_timed_out", "serve_cancelled", "serve_steps", "serve_tok_s",
    "serve_prefill_tokens", "serve_ttft_p50_ms", "serve_ttft_p95_ms",
    "serve_decode_p95_ms",
    # ISSUE 26: always on, and LAST in counters() whatever feature
    # groups come before them, so everything pinned above stays a
    # byte-compatible prefix
    "serve_rows_computed", "serve_rows_useful",
    "serve_rounds_mixed", "serve_rounds_decode", "serve_rounds_spec",
    "serve_round_ms_mixed", "serve_round_ms_decode", "serve_round_ms_spec",
    "serve_host_ms_schedule", "serve_host_ms_build_inputs",
    "serve_host_ms_dispatch", "serve_host_ms_fetch", "serve_host_ms_book",
    "serve_host_ms_wait",
    # the parts of two phases, after every key pinned before them
    "serve_host_ms_upload", "serve_host_ms_fetch_wait",
    "serve_host_ms_fetch_copy",
]


class TestEngineTelemetry:
    PROMPT = [5, 6, 7, 8, 9, 10, 11]

    @pytest.fixture(scope="class")
    def engines(self, tiny_model, tmp_path_factory):
        tmp = tmp_path_factory.mktemp("eng_trace")
        on = _engine(tiny_model, tmp=tmp)
        off = _engine(tiny_model)
        return on, off, tmp

    def test_greedy_stream_bitwise_on_vs_off(self, engines):
        """The acceptance contract: telemetry-on jitted steps are
        bitwise telemetry-off — same greedy tokens AND logprobs."""
        on, off, _ = engines
        outs = []
        for eng in (on, off):
            reqs = [eng.submit(self.PROMPT, 12, top_k=1,
                               return_log_probs=True),
                    eng.submit(self.PROMPT[:3], 8, top_k=1)]
            eng.drain()
            outs.append([r.result(5) for r in reqs])
        (toks_a, lp_a), (toks_b, _) = outs[0]
        (toks_a2, lp_a2), (toks_b2, _) = outs[1]
        assert toks_a == toks_a2 and toks_b == toks_b2
        assert lp_a == lp_a2  # float-exact
        assert len(on.tracer.events()) > 0
        assert off.tracer.events() == []  # no trace_dir, no ring

    def test_spans_and_events_correlate_by_rid(self, engines):
        on, _, _ = engines
        req = on.submit(self.PROMPT, 6, top_k=1)
        on.drain()
        req.result(5)
        evs = on.tracer.events()
        for name in ("queue_wait", "first_token", "retire"):
            assert any(e["name"] == name
                       and e["args"].get("rid") == req.rid
                       for e in evs), (name, req.rid)
        kinds = {}
        for e in on.recorder.snapshot()["events"]:
            kinds.setdefault(e["kind"], []).append(e)
        for kind in ("submit", "admit", "retire"):
            assert any(e.get("rid") == req.rid for e in kinds[kind]), kind
        assert any(k.startswith("round.") for k in kinds)
        # a mixed (chunk-prefill) round names the chunk's rid
        assert any(e.get("rid") == req.rid
                   for e in kinds.get("round.mixed", [])), kinds.keys()

    def test_histograms_observe_the_traffic(self, engines):
        on, _, _ = engines
        before = on._hists["serve_ttft_ms"].count
        req = on.submit(self.PROMPT, 4, top_k=1)
        on.drain()
        req.result(5)
        assert on._hists["serve_ttft_ms"].count == before + 1
        assert on._hists["serve_queue_wait_ms"].count >= before + 1
        assert on._hists["serve_decode_round_ms"].count > 0
        text = on.prometheus_metrics()
        parsed = parse_prometheus(text)
        assert parsed["serve_ttft_ms_count"][""] == before + 1
        # every numeric legacy counter appears as a gauge
        for key in ("serve_tok_s", "serve_pages_in_use",
                    "serve_admitted"):
            assert key in parsed, key

    def test_flight_record_snapshot_carries_counters(self, engines):
        on, _, _ = engines
        snap = on.flight_record()
        assert snap["reason"] == "on-demand"
        assert snap["counters"].get("serve_admitted", 0) >= 1
        assert snap["events"]

    def test_counters_schema_unchanged(self, engines):
        """The byte-compat half at the source: counters() keeps exactly
        the legacy key set and order — no telemetry key leaked into
        the JSON schema dashboards already parse."""
        _, off, _ = engines
        assert list(off.counters().keys()) == LEGACY_METRICS_KEYS

    def test_poison_dump_correlates_failing_request(self, tiny_model,
                                                    tmp_path,
                                                    monkeypatch):
        """Engine serve-loop poison auto-dumps the flight record with
        the dying round's context; the artifact loads and names the
        in-flight request by rid (ISSUE 13 acceptance)."""
        eng = _engine(tiny_model, tmp=tmp_path)

        def boom():
            raise RuntimeError("synthetic poison")

        monkeypatch.setattr(eng, "_step_inner", boom)
        req = eng.submit(self.PROMPT, 4, top_k=1)  # queued pre-start
        eng.start()
        with pytest.raises(RuntimeError, match="synthetic poison"):
            req.result(30)
        eng.stop(drain=False)
        arts = glob.glob(str(tmp_path / "flight_record_engine-poison_*"
                                        ".json"))
        assert arts, sorted(os.listdir(tmp_path))
        with open(arts[0]) as f:
            rec = json.load(f)
        assert rec["reason"] == "engine-poison"
        poison = [e for e in rec["events"] if e["kind"] == "poison"]
        assert poison and "synthetic poison" in poison[0]["error"]
        assert poison[0]["queue_depth"] == 1
        # rid correlation: the artifact names the request that was
        # queued when the loop died
        assert any(e["kind"] == "submit" and e.get("rid") == req.rid
                   for e in rec["events"])
        # counters snapshot rode along
        assert "serve_queue_depth" in rec["counters"]

    def test_profiler_hook_noop_when_unsupported(self, engines,
                                                 monkeypatch):
        """request_profile on a runtime without jax.profiler capture:
        the serve path keeps working, the no-op is recorded loudly,
        and the hook re-arms for the next attempt."""
        on, _, _ = engines

        def no_profiler(*a, **k):
            raise RuntimeError("profiler unsupported here")

        monkeypatch.setattr(jax.profiler, "start_trace", no_profiler)
        res = on.request_profile(2, trace_dir="/tmp/unused")
        assert res["ok"]
        req = on.submit(self.PROMPT, 4, top_k=1)
        on.drain()
        req.result(5)  # traffic unaffected by the failed capture
        kinds = [e["kind"] for e in on.recorder.snapshot()["events"]]
        assert "profile_unsupported" in kinds
        assert "profile_start" not in kinds
        # the failed capture released the slot: re-arming works
        res2 = on.request_profile(1)
        assert res2["ok"], res2
        on._profile_pending = None  # disarm for later tests

    def test_request_profile_validates_and_refuses_overlap(self,
                                                           engines):
        on, _, _ = engines
        with pytest.raises(ValueError):
            on.request_profile(0)
        res = on.request_profile(4, trace_dir="/tmp/unused2")
        assert res["ok"]
        busy = on.request_profile(4)
        assert not busy["ok"] and "in progress" in busy["error"]
        on._profile_pending = None  # disarm: no serve loop running


# ---------------------------------------------------------------------------
# HTTP surface: byte-compat JSON + negotiated Prometheus + observability
# endpoints (no generation traffic — cheap tier-1)
# ---------------------------------------------------------------------------


class _Tok:
    eod = 0
    bos = 1
    vocab_size = 256

    def tokenize(self, s):
        return [min(ord(c), 255) for c in s]

    def detokenize(self, ids):
        return "".join(chr(min(i, 127)) for i in ids)


@pytest.fixture(scope="module")
def http_server(tiny_model):
    from megatron_llm_tpu.inference.server import MegatronServer

    eng = _engine(tiny_model)
    srv = MegatronServer(*tiny_model, _Tok(), engine=eng)
    httpd = srv.run("127.0.0.1", 0, block=False)
    port = httpd.server_address[1]
    yield eng, port
    httpd.shutdown()
    eng.stop(drain=False)


def _http(port, method, path, payload=None, headers=None):
    from http.client import HTTPConnection

    conn = HTTPConnection("127.0.0.1", port, timeout=30)
    body = json.dumps(payload) if payload is not None else None
    conn.request(method, path, body, headers or {})
    resp = conn.getresponse()
    raw = resp.read()
    ct = resp.getheader("Content-Type")
    conn.close()
    return resp.status, raw, ct


class TestMetricsHTTP:
    def test_default_json_byte_compatible(self, http_server):
        """GET /metrics without negotiation returns EXACTLY the legacy
        surface: application/json, json.dumps formatting (round-trip
        byte-stable), and the pre-telemetry key set in order."""
        _, port = http_server
        status, raw, ct = _http(port, "GET", "/metrics")
        assert status == 200 and ct == "application/json"
        body = raw.decode()
        parsed = json.loads(body)
        # byte-stability: re-serializing the parsed dict (insertion
        # order preserved) reproduces the response byte for byte —
        # formatting and ordering unchanged
        assert json.dumps(parsed) == body
        assert list(parsed.keys()) == LEGACY_METRICS_KEYS

    @pytest.mark.parametrize("how", ["accept", "query", "openmetrics"])
    def test_negotiated_prometheus_text(self, http_server, how):
        _, port = http_server
        path, headers = "/metrics", {}
        if how == "accept":
            headers = {"Accept": "text/plain"}
        elif how == "openmetrics":
            headers = {"Accept": "application/openmetrics-text"}
        else:
            path = "/metrics?format=prometheus"
        status, raw, ct = _http(port, "GET", path, headers=headers)
        assert status == 200
        assert ct.startswith("text/plain; version=0.0.4")
        parsed = parse_prometheus(raw.decode())
        for name in ("serve_tok_s", "serve_queue_depth",
                     "serve_ttft_ms_count"):
            assert name in parsed, name
        assert 'le="+Inf"' in parsed["serve_ttft_ms_bucket"]

    def test_json_fallback_accept_stays_json(self, http_server):
        """A client that merely LISTS text/plain as a fallback (axios'
        default Accept) must keep getting the legacy JSON — only a
        client that PREFERS text/openmetrics gets the exposition."""
        _, port = http_server
        status, raw, ct = _http(
            port, "GET", "/metrics",
            headers={"Accept": "application/json, text/plain, */*"})
        assert status == 200 and ct == "application/json"
        assert list(json.loads(raw).keys()) == LEGACY_METRICS_KEYS
        # the real Prometheus scraper default: openmetrics preferred
        status, raw, ct = _http(
            port, "GET", "/metrics",
            headers={"Accept": "application/openmetrics-text;version="
                               "1.0.0,text/plain;version=0.0.4;q=0.5,"
                               "*/*;q=0.1"})
        assert ct.startswith("text/plain; version=0.0.4")

    def test_flight_record_endpoint(self, http_server):
        _, port = http_server
        status, raw, ct = _http(port, "GET", "/flight_record")
        assert status == 200 and ct == "application/json"
        snap = json.loads(raw)
        assert snap["reason"] == "on-demand"
        assert "events" in snap and "counters" in snap

    def test_memory_endpoint(self, http_server):
        _, port = http_server
        status, raw, _ = _http(port, "GET", "/memory")
        assert status == 200
        devs = json.loads(raw)["devices"]
        assert devs and all("device" in d for d in devs)

    def test_profile_endpoint_validates(self, http_server):
        eng, port = http_server
        status, raw, _ = _http(port, "POST", "/profile",
                               {"rounds": 0})
        assert status == 400
        # valid JSON that is not an object must 400, not crash the
        # handler thread with an AttributeError
        status, raw, _ = _http(port, "POST", "/profile", [1])
        assert status == 400
        status, raw, _ = _http(port, "POST", "/profile", 5)
        assert status == 400
        status, raw, _ = _http(port, "POST", "/wrong")
        assert status == 404
        # a valid arm answers ok; a second one 409s; then disarm (the
        # idle serve loop would otherwise start a real capture)
        status, raw, _ = _http(
            port, "POST", "/profile",
            {"rounds": 3, "trace_dir": "/tmp/unused3"})
        body = json.loads(raw)
        # the idle loop may already have started the capture between
        # the two requests; either way the second arm must be refused
        if status == 200:
            status2, raw2, _ = _http(port, "POST", "/profile",
                                     {"rounds": 1})
            assert status2 == 409, raw2
        eng._profile_pending = None
        eng._stop_profile()


# ---------------------------------------------------------------------------
# Trainer wiring
# ---------------------------------------------------------------------------


def _train(cfg, steps, trace_dir=None, record_dir=None):
    from megatron_llm_tpu.training.trainer import Trainer

    tcfg = TrainConfig(micro_batch_size=2, global_batch_size=2, lr=1e-3,
                       train_iters=steps, log_interval=10**9,
                       eval_interval=0, trace_dir=trace_dir,
                       flight_record_dir=record_dir)
    trainer = Trainer(LlamaModel(cfg), tcfg,
                      ParallelConfig(num_microbatches=1))
    state = trainer.setup()
    rs = np.random.RandomState(11)

    def batches():
        while True:
            yield rs.randint(0, cfg.padded_vocab_size,
                             (1, 2, cfg.seq_length + 1)).astype(np.int32)

    trainer.train_data_iterator = batches()
    state = trainer.train(state)
    return trainer, state


class TestTrainerTelemetry:
    @pytest.fixture(scope="class")
    def runs(self, tmp_path_factory):
        cfg = tiny_config(seq_length=16, max_position_embeddings=16,
                          compute_dtype=jnp.float32,
                          params_dtype=jnp.float32)
        tmp = tmp_path_factory.mktemp("train_trace")
        on = _train(cfg, 3, trace_dir=str(tmp))
        off = _train(cfg, 3)
        return on, off, tmp

    def test_losses_and_params_bitwise_on_vs_off(self, runs):
        (tr_on, st_on), (tr_off, st_off), _ = runs
        on_losses = [e for e in tr_on.recorder.snapshot()["events"]
                     if e["kind"] == "step"]
        off_losses = [e for e in tr_off.recorder.snapshot()["events"]
                      if e["kind"] == "step"]
        assert [e["loss"] for e in on_losses] == \
            [e["loss"] for e in off_losses]
        for a, b in zip(jax.tree.leaves(st_on.params),
                        jax.tree.leaves(st_off.params)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_trace_exported_with_step_correlation(self, runs):
        (tr_on, _), _, tmp = runs
        traces = glob.glob(str(tmp / "trace_train_*.json"))
        assert traces
        with open(traces[0]) as f:
            doc = json.load(f)
        steps = [e for e in doc["traceEvents"]
                 if e["name"] == "train-step"]
        assert [e["args"]["step"] for e in steps] == [1, 2, 3]
        names = {e["name"] for e in doc["traceEvents"]}
        assert "batch-generator" in names  # timers ride the tracer

    def test_recorder_always_on_and_histogram_counts(self, runs):
        (tr_on, _), (tr_off, _), _ = runs
        for tr in (tr_on, tr_off):  # recorder is NOT opt-in
            steps = [e for e in tr.recorder.snapshot()["events"]
                     if e["kind"] == "step"]
            assert [e["step"] for e in steps] == [1, 2, 3]
            assert tr._step_ms_hist.count == 3
        assert tr_off.tracer.events() == []  # tracer IS opt-in

    def test_watchdog_records_verdicts(self):
        from megatron_llm_tpu.training.watchdog import LossWatchdog

        rec = FlightRecorder(64)
        wd = LossWatchdog(k_sigma=3.0, window=8, patience=2,
                          min_history=4, recorder=rec)
        for i in range(6):
            assert not wd.observe(5.0 + 0.01 * (i % 3), step=i)
        assert wd.observe(50.0, step=6)
        assert wd.observe(float("nan"), step=7)
        wd.note_rollback(step=7, restored_step=4)
        kinds = [(e["kind"], e.get("step"))
                 for e in rec.snapshot()["events"]]
        assert ("watchdog_bad", 6) in kinds
        assert ("watchdog_bad", 7) in kinds
        assert ("watchdog_rollback", 7) in kinds


# ---------------------------------------------------------------------------
# ISSUE 26: the spans on the profiler's clock, the scopes on the device,
# the counters of a round
# ---------------------------------------------------------------------------

ROUND_PHASES = ["engine.schedule", "engine.build_inputs", "engine.dispatch",
                "engine.fetch", "engine.book"]
# scripted run: prompts of 11 and 5 tokens, 5 tokens out each, 2 slots,
# chunk 8, horizon 4. Mixed rounds: 8 of prompt A (width 8), its last 3
# (width 4), the 5 of B beside A's first decode token (width 8); then a
# scan of 4 over both slots and one last step for B alone. A mixed round
# computes its chunk's width plus one row a slot, a scan slots x horizon.
SCRIPT = dict(prompts=[list(range(5, 16)), list(range(40, 45))], out=5,
              rows_computed=(8 + 2) + (4 + 2) + (8 + 2) + 2 * 4 + 2 * 1,
              rows_useful=11 + 5 + 5 + 5, mixed=3, decode=2)
# operand copies to the device a round: twelve for a mixed step, eleven
# for a decode scan (the carried logits stay on the device)
UPLOADS = {"mixed": 12, "decode": 11}


def _span_reduce():
    return _bench("span_reduce")


def _bench(name):
    """A module of the repo's `benchmark/` package."""
    import importlib
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    return importlib.import_module("benchmark." + name)


def _children(spans):
    kids = {}
    for i, sp in enumerate(spans):
        kids.setdefault(sp["parent"], []).append(i)
    return kids


def _ring_as_trace(tracer):
    """The ring's complete events in the reducer's trace form, so one
    function reads the nesting of both sinks."""
    lines = {}
    for e in tracer.events():
        if e["ph"] == "X":
            lines.setdefault(e["tid"], []).append(
                [e["name"], e["ts"] * 1e3, max(e["dur"], 1) * 1e3,
                 e["args"]])
    return {"planes": [{"name": "/host:CPU", "lines": [
        {"name": f"tid-{tid}", "events": evs}
        for tid, evs in lines.items()]}]}


@pytest.fixture(scope="module")
def captured(tiny_model, tmp_path_factory):
    """One CPU `jax.profiler` capture, NO trace_dir anywhere: a started
    engine serves the scripted run, then a trainer takes three steps."""
    import glob as _glob

    import jax.profiler as jp

    sr = _span_reduce()
    tmp = tmp_path_factory.mktemp("capture")
    eng = _engine(tiny_model, prefill_chunk_tokens=8, step_horizon=4)
    eng.warmup()
    cfg = tiny_config(seq_length=16, max_position_embeddings=16,
                      compute_dtype=jnp.float32, params_dtype=jnp.float32)
    opts = jp.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jp.start_trace(str(tmp), profiler_options=opts)
    try:
        eng.start()
        reqs = [eng.submit(p, SCRIPT["out"], top_k=1)
                for p in SCRIPT["prompts"]]
        tokens = [r.result(60)[0] for r in reqs]
        import time as _time

        _time.sleep(0.12)  # the idle loop waits for work at least once
        eng.stop()
        trainer, _ = _train(cfg, 3)
    finally:
        jp.stop_trace()
    path = _glob.glob(str(tmp / "plugins" / "profile" / "*" /
                          "*.xplane.pb"))[0]
    spans = sr.program_spans(sr.load(path))
    return {"spans": spans, "tokens": tokens, "rids": [r.rid for r in reqs],
            "engine": eng, "trainer": trainer, "cfg": cfg}


class TestSpansInTheProfilersTrace:
    def test_engine_rounds_nest_as_the_table_says(self, captured):
        spans, kids = captured["spans"], _children(captured["spans"])
        rounds = [i for i, sp in enumerate(spans)
                  if sp["name"] == "engine.round"]
        assert len(rounds) == SCRIPT["mixed"] + SCRIPT["decode"]
        kinds, rids, uploads = [], set(), []
        for n, i in enumerate(rounds):
            assert spans[i]["line"] == "engine-serve"
            assert spans[i]["parent"] is None
            assert spans[i]["args"]["round"] == \
                spans[rounds[0]]["args"]["round"] + n
            assert [spans[k]["name"] for k in kids[i]] == ROUND_PHASES
            disp = spans[kids[i][2]]["args"]
            kinds.append(disp["kind"])
            assert disp["fn"] == {"mixed": "mixed_step",
                                  "decode": "decode_scan"}[disp["kind"]]
            assert disp["width"] >= 1 and "decode_slots" in disp
            if disp["kind"] == "mixed":
                rids.add(disp["rid"])
            # the uploads inside the build, the wait and the copy back
            # inside the fetch
            build, fetch = kids[i][1], kids[i][3]
            assert [spans[k]["name"] for k in kids[build]] == \
                ["engine.upload"]
            assert [spans[k]["name"] for k in kids[fetch]] == \
                ["engine.fetch.wait", "engine.fetch.copy"]
            up = spans[kids[build][0]]["args"]
            assert up["copies"] == UPLOADS[disp["kind"]] and up["bytes"] > 0
            uploads.append(up["copies"])
            copy = spans[kids[fetch][1]]["args"]
            # the tokens alone: no request asked for log-probs, the
            # model does not route
            assert copy["copies"] == 1 and copy["bytes"] > 0
            assert spans[i]["self_ns"] >= 0
        assert kinds == ["mixed"] * 3 + ["decode"] * 2
        assert sum(uploads) == 3 * 12 + 2 * 11
        assert rids == set(captured["rids"])
        waits = [sp for sp in spans if sp["name"] == "engine.wait_for_work"]
        assert waits and all(w["line"] == "engine-serve"
                             and w["parent"] is None for w in waits)
        assert {"live_slots", "queue_depth"} <= set(waits[0]["args"])

    def test_train_steps_nest_as_the_table_says(self, captured):
        spans, kids = captured["spans"], _children(captured["spans"])
        steps = [i for i, sp in enumerate(spans) if sp["name"] == "train"]
        assert [spans[i]["args"]["step_num"] for i in steps] == [1, 2, 3]
        for i in steps:
            assert [spans[k]["name"] for k in kids[i]] == \
                ["train.get_batch", "train.dispatch"]
            assert spans[i]["args"]["step"] == spans[i]["args"]["step_num"]
        line = spans[steps[0]]["line"]
        for name in ("train.data_wait", "train.loss_fetch"):
            got = [sp for sp in spans if sp["name"] == name]
            assert len(got) == 3, name
            assert all(sp["parent"] is None and sp["line"] == line
                       for sp in got)
        assert not captured["trainer"].tracer.events()  # no ring was on

    def test_ring_agrees_in_names_and_nesting(self, captured, tiny_model,
                                              tmp_path):
        """The same scripted run with a trace_dir and no capture: the
        ring holds the same spans, nested the same way, and the tokens
        are the same to the bit."""
        sr = _span_reduce()
        eng = _engine(tiny_model, tmp=tmp_path, prefill_chunk_tokens=8,
                      step_horizon=4)
        reqs = [eng.submit(p, SCRIPT["out"], top_k=1)
                for p in SCRIPT["prompts"]]
        eng.drain()
        assert [r.result(5)[0] for r in reqs] == captured["tokens"]
        ring = sr.program_spans(_ring_as_trace(eng.tracer))
        kids = _children(ring)

        def shape(spans, kids):
            return [(spans[i]["name"], [spans[k]["name"] for k in kids[i]])
                    for i in kids[None]
                    if spans[i]["name"] == "engine.round"]

        assert shape(ring, kids) == shape(captured["spans"],
                                          _children(captured["spans"]))
        book = [sp for sp in ring if sp["name"] == "engine.book"]
        assert sum(sp["args"]["booked"] for sp in book) == 2 * SCRIPT["out"]
        assert sum(sp["args"]["retired"] for sp in book) == 2
        tr_on, _ = _train(captured["cfg"], 3, trace_dir=str(tmp_path))
        ring = sr.program_spans(_ring_as_trace(tr_on.tracer))
        kids = _children(ring)
        steps = [i for i in kids[None] if ring[i]["name"] == "train"]
        assert [[ring[k]["name"] for k in kids[i]] for i in steps] == \
            [["train.get_batch", "train.dispatch"]] * 3
        on = [e["loss"] for e in tr_on.recorder.snapshot()["events"]
              if e["kind"] == "step"]
        under_capture = [e["loss"] for e in
                         captured["trainer"].recorder.snapshot()["events"]
                         if e["kind"] == "step"]
        assert on == under_capture  # float-exact


class TestRoundCounters:
    def test_counters_add_up_on_the_scripted_run(self, tiny_model):
        eng = _engine(tiny_model, prefill_chunk_tokens=8, step_horizon=4)
        reqs = [eng.submit(p, SCRIPT["out"], top_k=1)
                for p in SCRIPT["prompts"]]
        eng.drain()
        for r in reqs:
            r.result(5)
        c = eng.counters()
        assert c["serve_rows_computed"] == SCRIPT["rows_computed"]
        assert c["serve_rows_useful"] == SCRIPT["rows_useful"]
        assert (c["serve_rounds_mixed"], c["serve_rounds_decode"],
                c["serve_rounds_spec"]) == (SCRIPT["mixed"],
                                            SCRIPT["decode"], 0)
        log = list(eng._round_log)
        assert len(log) == SCRIPT["mixed"] + SCRIPT["decode"]
        assert sum(r["prefill_tokens"] + r["decode_slots"]
                   * r["decode_steps"] for r in log) == SCRIPT["rows_useful"]
        wall = c["serve_round_ms_mixed"] + c["serve_round_ms_decode"]
        assert wall == pytest.approx(sum(r["ms"] for r in log), abs=0.01)
        assert c["serve_round_ms_spec"] == 0.0
        phases = ["schedule", "build_inputs", "dispatch", "fetch", "book"]
        spent = [c["serve_host_ms_" + p] for p in phases]
        assert all(x > 0 for x in spent)
        assert sum(spent) <= wall + 0.01  # children of the rounds
        assert c["serve_host_ms_wait"] == 0.0  # drain() never waits
        # the parts nest in their phases
        for part, whole in (("upload", "build_inputs"),
                            ("fetch_wait", "fetch"), ("fetch_copy", "fetch")):
            assert 0 < c["serve_host_ms_" + part] <= \
                c["serve_host_ms_" + whole], part
        # the flight recorder kept its event names
        kinds = [e["kind"] for e in eng.recorder.snapshot()["events"]]
        assert kinds.count("round.mixed") == SCRIPT["mixed"]
        assert kinds.count("round.decode_scan") == SCRIPT["decode"]

    def test_emit_helper_is_a_hot_path(self):
        from megatron_llm_tpu.analysis.lint import HOT_PATHS

        assert {"DecodeEngine._emit_round", "DecodeEngine._upload",
                "DecodeEngine._fetch"} <= \
            HOT_PATHS["megatron_llm_tpu/inference/engine.py"]
        assert {"SpanTracer.step_span", "_Span.note"} <= \
            HOT_PATHS["megatron_llm_tpu/telemetry/trace.py"]


class TestUploadsAndFetches:
    # a prompt on which the tiny model's greedy continuation cycles, so
    # the drafter proposes and spec rounds run (tests/test_spec_decode.py)
    CYCLE = [9, 206, 145, 115]
    UPLOADS = {"mixed": 12, "decode": 11, "spec": 11}
    # arrays copied back a round: the tokens (spec: also the greedy
    # targets and accepted counts), and the log-probs when asked for
    COPIES = {False: {"mixed": 1, "decode": 1, "spec": 3},
              True: {"mixed": 3, "decode": 2, "spec": 5}}

    @pytest.mark.parametrize("log_probs", [False, True])
    def test_copies_by_round_kind(self, tiny_model, tmp_path, log_probs):
        """Every round's `engine.upload` and `engine.fetch.copy`, read
        from the ring, count what its kind copies up and back."""
        sr = _span_reduce()
        eng = _engine(tiny_model, tmp=tmp_path, prefill_chunk_tokens=8,
                      spec_decode_k=4)
        req = eng.submit(self.CYCLE, 30, top_k=1,
                         return_log_probs=log_probs)
        eng.drain()
        req.result(5)
        spans = sr.program_spans(_ring_as_trace(eng.tracer))
        kids = _children(spans)
        seen = set()
        for i in kids[None]:
            if spans[i]["name"] != "engine.round":
                continue
            phase = {spans[k]["name"]: k for k in kids[i]}
            kind = spans[phase["engine.dispatch"]]["args"]["kind"]
            seen.add(kind)
            up, = kids[phase["engine.build_inputs"]]
            assert spans[up]["args"]["copies"] == self.UPLOADS[kind]
            wait, copy = kids[phase["engine.fetch"]]
            assert spans[copy]["name"] == "engine.fetch.copy"
            assert spans[copy]["args"]["copies"] == \
                self.COPIES[log_probs][kind], kind
        assert seen == {"mixed", "decode", "spec"}
        c = eng.counters()
        assert c["serve_spec_rounds"] > 0
        assert 0 < c["serve_host_ms_upload"] <= c["serve_host_ms_build_inputs"]


def _two_rounds(wait=True):
    """A hand-made trace of two serve rounds (times in us). Round 1 is
    causal: its one device operation runs 130-250 inside the host's
    wait 120-280. Round 2's operation ends at 580, after its wait ended
    at 560, which no clock that agrees with the host's can show."""
    def ev(name, t0, t1, **args):
        return [name, t0 * 1e3, (t1 - t0) * 1e3, args]

    serve = []
    for rnd, base in ((1, 0), (2, 300)):
        serve += [ev("engine.round", base + 90, base + 310, round=rnd),
                  ev("engine.dispatch", base + 100, base + 120),
                  ev("engine.fetch", base + 120, base + 300)]
        if wait:
            serve += [ev("engine.fetch.wait", base + 120, base + 260
                         if rnd == 2 else base + 280),
                      ev("engine.fetch.copy", base + 280, base + 300)]
    ops = [ev("fusion.1", 130, 250), ev("fusion.2", 440, 580)]
    return {"planes": [
        {"name": "/device:TPU:0",
         "lines": [{"name": "XLA Ops", "events": ops}]},
        {"name": "/host:CPU",
         "lines": [{"name": "engine-serve", "events": serve}]}]}


class TestClockCheck:
    def test_lags_counts_and_reader_on_a_hand_made_trace(self):
        cc = _bench("clock_check")
        trace = _two_rounds()
        lags = cc.ready_lags(trace)
        assert [r["round"] for r in lags] == [1, 2]
        # ready: 280 - 250 and 560 - 580; launch: 130 - 100, 440 - 400
        assert [r["ready_lag_ms"] for r in lags] == \
            [pytest.approx(0.030), pytest.approx(-0.020)]
        assert [r["launch_lag_ms"] for r in lags] == \
            [pytest.approx(0.030), pytest.approx(0.040)]
        got = cc.clock_check(trace)
        assert got["rounds"] == 2 and got["acausal_rounds"] == 1
        assert got["ready_lag_ms_min"] == pytest.approx(-0.020)
        assert got["ready_lag_ms_p50"] == pytest.approx(0.005)
        assert got["launch_lag_ms_min"] == pytest.approx(0.030)
        # the device plane reads 0.02-0.03 ms later than the host's
        assert got["skew_ms"] == [pytest.approx(0.020),
                                  pytest.approx(0.030)]
        assert cc.read_ready_lag({"trace": trace}, {}) == \
            pytest.approx(0.005)

    def test_a_program_without_the_wait_span_reads_nothing(self):
        cc = _bench("clock_check")
        trace = _two_rounds(wait=False)
        assert cc.ready_lags(trace) == []
        assert cc.clock_check(trace)["rounds"] == 0
        assert cc.read_ready_lag({"trace": trace}, {}) is None
        assert cc.read_ready_lag({"trace": None}, {}) is None


TRAIN_SCOPES = ["embed", "layers", "block", "norm", "attention",
                "qkv_proj", "rope", "attn_core", "out_proj", "mlp", "up",
                "act", "down", "loss", "head", "vocab_parallel",
                "optimizer", "clip", "adam"]
SERVE_SCOPES = ["embed", "layers", "block", "norm", "attention",
                "qkv_proj", "rope", "attn_core", "kv_write", "page_gather",
                "out_proj", "mlp", "up", "act", "down", "head", "sample"]


def _scopes_in(lowered):
    import re

    found = set()
    for name in re.findall(r'loc\("([^"]+)"', lowered.as_text(
            debug_info=True)):
        for tok in name.split("/"):
            found.update(re.findall(r"[A-Za-z_]+", tok))
    return found


class TestNamedScopes:
    """Scopes are metadata: every name of docs/GUIDE.md's list is in the
    lowered text, and the compiled programs hold what they held (no
    collective on one device; the tp inventories stay pinned by the
    graft-check audit in test_static_analysis.py)."""

    def test_engine_steps_carry_every_scope(self, tiny_model):
        from megatron_llm_tpu.analysis.audit import collectives_in_text

        eng = _engine(tiny_model, prefill_chunk_tokens=8)
        with eng.mesh_scope():
            for fn, args in ((eng._mixed_fn(8, True),
                              eng._null_mixed_args(8)),
                             (eng._step_fn(1, False),
                              eng._null_scan_args(1))):
                lowered = fn.lower(*args)
                missing = set(SERVE_SCOPES) - _scopes_in(lowered)
                assert not missing, missing
                assert collectives_in_text(
                    lowered.compile().as_text()) == frozenset()

    def test_train_step_carries_every_scope(self):
        from megatron_llm_tpu.analysis.audit import collectives_in_text
        from megatron_llm_tpu.training.trainer import Trainer

        cfg = tiny_config(seq_length=16, max_position_embeddings=16,
                          compute_dtype=jnp.float32,
                          params_dtype=jnp.float32)
        tcfg = TrainConfig(micro_batch_size=2, global_batch_size=2,
                           lr=1e-3, train_iters=1, log_interval=10**9,
                           eval_interval=0)
        trainer = Trainer(LlamaModel(cfg), tcfg,
                          ParallelConfig(num_microbatches=1))
        state = trainer.setup()
        text = np.zeros((1, 2, cfg.seq_length + 1), np.int32)
        from megatron_llm_tpu.training.trainer import get_batch

        lowered = trainer._get_step_fn(1).lower(
            state.params, state.opt_state, get_batch(text),
            jnp.float32(1e-3), jnp.float32(0.0), None, jnp.float32(1e9))
        missing = set(TRAIN_SCOPES) - _scopes_in(lowered)
        assert not missing, missing
        assert collectives_in_text(lowered.compile().as_text()) == \
            frozenset()
