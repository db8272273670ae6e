"""The trace reduction, on a hand-made trace whose answers are known by
inspection and on the small excerpt recorded from a chip run."""

import os

import pytest

from benchmark import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))


def op(name, start_us, dur_us, category, source=""):
    return [name, start_us * 1e3, dur_us * 1e3,
            {"hlo_category": category, "source": source}]


def hand_made():
    src = "/root/repo/megatron_llm_tpu/"
    ops = [
        op("while.1", 0, 200, "while"),  # covers the three below: 0 of its own
        op("fusion.1", 0, 100, "convolution fusion",
           src + "ops/quantization.py:117"),
        op("fusion.2", 100, 50, "loop fusion", src + "ops/flash_attention.py:89"),
        op("all-reduce.1", 150, 50, "all-reduce", ""),
        op("copy.1", 300, 100, "data formatting", ""),
    ]
    host = [["step", 0.0, 400e3, {}], ["fetch", 210e3, 80e3, {}]]
    return {"planes": [
        {"name": "/device:TPU:0",
         "lines": [{"name": "XLA Ops", "events": ops},
                   {"name": "Steps", "events": []}]},
        {"name": "/host:CPU", "lines": [{"name": "main", "events": host}]},
    ]}


def test_busy_sites_collectives_and_gaps():
    t = hand_made()
    assert tr.busy_seconds(t) == [pytest.approx(300e-6)]  # 0-200, 300-400
    sites = tr.time_by_site(t)
    assert sites["ops/quantization.py:117"] == pytest.approx(100e-6)
    assert sites["ops/flash_attention.py:89"] == pytest.approx(50e-6)
    assert sites["data formatting"] == pytest.approx(100e-6)
    assert tr.site_seconds(t, ["ops/flash_attention.py", "ops/quant"]) == \
        pytest.approx(150e-6)
    assert tr.exposed_collective_seconds(t) == [pytest.approx(50e-6)]
    gaps = tr.idle_gaps(t, min_ns=20_000.0)
    assert gaps[0][0].startswith("fetch") or gaps[0][0].startswith("step")
    assert gaps[0][1] == pytest.approx(100e-6)
    named = tr.time_by_category_site(t)
    assert named["convolution_fusion___ops/quantization.py:117"] == \
        pytest.approx(100e-6)


def test_self_times_count_nothing_twice():
    t = hand_made()
    rows = tr.self_times(tr.device_ops(t["planes"][0]))
    own = {ev[0]: (sec, leaf) for ev, sec, leaf in rows}
    assert own["while.1"] == (pytest.approx(0.0), False)
    assert own["fusion.1"] == (pytest.approx(100e3), True)
    assert sum(tr.time_by_site(t).values()) == pytest.approx(300e-6)


def test_recorded_excerpt():
    """320 ms of a chip trace of falcon7b-train-2k (my chip run, PR 24):
    the layer scans and the chunked loss are `while` operations that
    cover their bodies."""
    t = tr.load_excerpt(os.path.join(HERE, "data", "train_excerpt.json.gz"))
    lo, hi = tr.span_of(t)
    assert (hi - lo) * 1e-9 == pytest.approx(0.360953, abs=1e-6)
    busy = tr.busy_seconds(t)
    assert busy == [pytest.approx(0.350912, abs=1e-6)]
    sites = tr.time_by_site(t)
    assert sum(sites.values()) == pytest.approx(busy[0], rel=1e-9)
    assert sites["models/language_model.py:146"] == \
        pytest.approx(0.105360, abs=1e-6)
    assert tr.site_seconds(t, ["ops/flash_attention.py",
                               "models/attention.py"]) == \
        pytest.approx(0.074603, abs=1e-6)
    assert tr.exposed_collective_seconds(t) == [0.0]
    gaps = tr.idle_gaps(t)
    assert gaps[0][0] == "DoEnqueueProgram_x2"
    assert gaps[0][1] == pytest.approx(0.002796, abs=1e-6)
