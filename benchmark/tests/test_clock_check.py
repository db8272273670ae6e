"""The clock check (`clock_check`): a hand-made trace of two serve rounds
whose lags are known by inspection, the recorded batch excerpt of a
program that has no `engine.fetch.wait` span, and the metric files that
read the round's upload and copy back."""

import glob
import json
import os

import pytest

from benchmark import clock_check as cc
from benchmark import harness
from benchmark import trace_reduce as tr

DATA = os.path.join(harness.HERE, "tests", "data")


def ev(name, t0_us, t1_us, **args):
    return [name, t0_us * 1e3, (t1_us - t0_us) * 1e3, args]


def two_rounds():
    """Round 1: its device operation 130-250 lies inside the host's wait
    120-280 (causal). Round 2: its operation runs 440-580, past the end
    of its wait at 560 (the planes disagree). Round 3 dispatches nothing
    the device ran: it is left out."""
    serve = []
    for rnd, base, wait_end in ((1, 0, 280), (2, 300, 560), (3, 700, 860)):
        serve += [ev("engine.round", base + 90, base + 310, round=rnd),
                  ev("engine.build_inputs", base + 92, base + 100),
                  ev("engine.upload", base + 95, base + 100, copies=11,
                     bytes=1024),
                  ev("engine.dispatch", base + 100, base + 120),
                  ev("engine.fetch", base + 120, base + 300),
                  ev("engine.fetch.wait", base + 120, wait_end),
                  ev("engine.fetch.copy", wait_end, base + 300, copies=1,
                     bytes=32)]
    ops = [ev("fusion.1", 130, 250), ev("fusion.2", 440, 580)]
    return {"planes": [
        {"name": "/device:TPU:0",
         "lines": [{"name": "XLA Ops", "events": ops}]},
        {"name": "/host:CPU",
         "lines": [{"name": "engine-serve", "events": serve}]}]}


def test_ready_and_launch_lags():
    lags = cc.ready_lags(two_rounds())
    assert [r["round"] for r in lags] == [1, 2]
    assert [r["ready_lag_ms"] for r in lags] == [pytest.approx(0.030),
                                                 pytest.approx(-0.020)]
    assert [r["launch_lag_ms"] for r in lags] == [pytest.approx(0.030),
                                                  pytest.approx(0.040)]


def test_clock_check_counts_and_bounds():
    got = cc.clock_check(two_rounds())
    assert (got["rounds"], got["acausal_rounds"]) == (2, 1)
    assert got["ready_lag_ms_min"] == pytest.approx(-0.020)
    assert got["ready_lag_ms_p50"] == pytest.approx(0.005)
    assert got["launch_lag_ms_min"] == pytest.approx(0.030)
    assert got["launch_lag_ms_p50"] == pytest.approx(0.035)
    assert got["skew_ms"] == [pytest.approx(0.020), pytest.approx(0.030)]


def test_reader_and_per_round_split():
    trace = two_rounds()
    assert cc.read_ready_lag({"trace": trace}, {}) == pytest.approx(0.005)
    split = cc.per_round_ms(trace)
    assert split["rounds"] == 3
    assert split["engine.upload"] == pytest.approx(0.005)
    assert split["engine.fetch.copy"] == pytest.approx((20 + 40 + 140)
                                                       / 3 * 1e-3)
    # idle: from the first event (90) to 130, 250-440, and 580 to the
    # last event's end (1010)
    assert split["device_idle"] == pytest.approx((40 + 190 + 430) / 3
                                                 * 1e-3)


def test_a_program_without_the_wait_span_reads_nothing():
    """The recorded excerpt of the batch cell comes from a program whose
    `engine.fetch` has no child: no round can be read, and the reader
    gives nothing rather than raising."""
    trace = tr.load_excerpt(os.path.join(DATA, "serve_excerpt.json.gz"))
    assert cc.ready_lags(trace) == []
    assert cc.clock_check(trace)["rounds"] == 0
    assert cc.read_ready_lag({"trace": trace}, {}) is None
    assert cc.read_ready_lag({"trace": None}, {}) is None


NEW_METRICS = ("upload_ms_per_step", "fetch_copy_ms_per_step")
SERVING = {"batch": "falcon7b-serve-batch", "chat": "falcon7b-serve-chat",
           "lfm": "lfm2moe-serve-batch",
           "nemo": "nemotron3super-serve-reasoning"}


def test_the_upload_and_copy_metric_files():
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        cells = {w["name"] for w in json.load(f)["workloads"]}
    found = set()
    for base in NEW_METRICS:
        for path in glob.glob(os.path.join(harness.HERE, "metrics",
                                           base + ".*.json")):
            m = harness.load_json(path)
            sfx = m["name"].split(".", 1)[1]
            assert m["reader"] in harness.READERS
            assert m["workloads"] == [SERVING[sfx]]
            assert set(m["workloads"]) <= cells
            assert m["moves"] == ("tpot_p90_ms" if sfx == "chat"
                                  else "serve_tok_s")
            assert m["params"]["den"] == "serve_steps.window"
            found.add(m["name"])
    assert found == {f"{b}.{s}" for b in NEW_METRICS for s in SERVING}


def test_the_ratio_reads_the_engine_counters():
    """ms a step from the window's counters; nothing where the program
    has no such counter (the parent's engine)."""
    spec = harness.load_json(harness.HERE, "metrics",
                             "upload_ms_per_step.lfm.json")
    ctx = {"values": {"serve_host_ms_upload.window": 120.5,
                      "serve_steps.window": 241}}
    assert harness.read_metric(spec, ctx) == pytest.approx(0.5)
    ctx = {"values": {"serve_steps.window": 241}}
    assert harness.read_metric(spec, ctx) is None
