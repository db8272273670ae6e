"""CPU rehearsal of every cell at a tiny size through the harness's own
functions: the shape of the last line, the control flow, a control and
each planted fault coming out as not correct, and a configuration, a mix,
a cell and a per-layer metric added by new files and entries alone (the
`tiny_base` copy adds them). No device number comes from here."""

import json
import os
import subprocess
import sys
import time

import pytest

from benchmark import harness

KEYS = ["correct", "attempted", "failed", "metrics", "device"]


def run_cell(tiny_base, name, trace=False, seconds=1.0, **kw):
    from benchmark import serve_cell, train_cell

    tmp = os.path.dirname(tiny_base)
    cell = harness.load_cell(name, os.path.join(tmp, "BENCHMARK.json"),
                             tiny_base)
    dev = harness.require_chips(cell["entry"]["chips"], allow_cpu=True)
    runner = train_cell if cell["mix"]["kind"] == "train" else serve_cell
    line = runner.run(cell, 2**31 + 99, seconds, trace, time.perf_counter(),
                      dev, **kw)
    json.dumps(line)
    return cell, line


@pytest.mark.parametrize("name", ["tiny-train", "tiny-chat", "tiny-batch",
                                  "tiny-train40-tp4", "tiny2-train",
                                  "tiny2-batch"])
def test_last_line_end_to_end(tiny_base, name):
    cell, line = run_cell(tiny_base, name)
    assert list(line)[:5] == KEYS and list(line)[-1] == "compared"
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert set(line["metrics"]) == {m["name"] for m in cell["end_to_end"]}
    for m in line["metrics"].values():
        assert m["value"] > 0 and m["unit"]
    for row in line["compared"].values():
        assert set(row) == {"value", "limit"}


@pytest.mark.parametrize("name", ["tiny-train", "tiny-chat"])
def test_last_line_traced(tiny_base, name):
    cell, line = run_cell(tiny_base, name, trace=True)
    assert line["correct"] is True
    listed = {m["name"] for m in cell["per_layer"]}
    assert set(line["metrics"]) <= listed
    assert "tiny_window_s" in line["metrics"]  # the metric the copy added
    # no device: nothing that needs the chip's peaks or trace is printed
    for name_ in line["metrics"]:
        assert "mfu" not in name_ and "roofline" not in name_
        assert "idle" not in name_ and "peak_hbm" not in name_
    assert {"busy_s", "window_s"} <= set(line["device"])


@pytest.mark.parametrize("name,excerpt,metric,value", [
    ("tiny2-train", "train_scopes_excerpt", "optimizer_share.tiny2", 9.4510),
    ("tiny2-batch", "serve_excerpt", "rows_useful_share.tiny2", None)])
def test_second_family_traced(tiny_base, monkeypatch, name, excerpt, metric,
                              value):
    """The second family's cells, traced, with a trace recorded on the
    chip put in the place of the CPU's (which has no device plane): the
    metrics its new files bring, read by the new readers, are on the
    line."""
    from benchmark import trace_reduce

    recorded = trace_reduce.load_excerpt(os.path.join(
        harness.HERE, "tests", "data", excerpt + ".json.gz"))
    monkeypatch.setattr(harness.TraceWindow, "finish",
                        lambda self: self._thread.join() or recorded)
    cell, line = run_cell(tiny_base, name, trace=True)
    assert line["correct"] is True
    assert metric in line["metrics"]
    # the one file without a `workloads` list covers a cell added later
    assert line["metrics"]["compiles_in_window"]["value"] == 0
    got = line["metrics"][metric]["value"]
    assert 0 < got <= 100
    if value is not None:
        assert got == pytest.approx(value, abs=1e-3)
    assert line["device"]["busy_s"] > 0


@pytest.mark.parametrize("name,fault", [
    ("tiny-train", "state_unchanged"), ("tiny-train", "half_batch"),
    ("tiny-train40-tp4", "no_exchange"), ("tiny2-train", "half_batch"),
    ("tiny2-train", "state_unchanged")])
def test_training_faults_are_caught(tiny_base, name, fault):
    _, line = run_cell(tiny_base, name, fault=fault)
    assert line["correct"] is False


@pytest.mark.parametrize("fault", ["half_batch", "no_exchange"])
def test_faults_planted_in_the_reference_read_far(tiny_base, fault):
    """What prove.py reads on the chip: the reference with the fault, put
    in the program's place, against the sound reference."""
    from benchmark import check, traffic

    tmp = os.path.dirname(tiny_base)
    cell = harness.load_cell("tiny-train40-tp4",
                             os.path.join(tmp, "BENCHMARK.json"), tiny_base)
    texts = traffic.train_batches(cell["mix"], 5, 3, cell["cfg"]["vocab_size"])
    want = check.train_reference(cell["cfg"], 5, texts)
    got = check.train_reference(cell["cfg"], 5, texts, fault=fault)
    ok, _ = check.verdict(check.train_numbers(got, want), cell["limits"])
    assert not ok


@pytest.mark.parametrize("name", ["tiny-chat", "tiny2-batch"])
def test_altered_token_is_caught(tiny_base, name):
    _, line = run_cell(tiny_base, name, fault="token_altered")
    assert line["correct"] is False


def test_int8_control_fails_serving(tiny_base):
    """The control for a served model: at each position of the same
    prompts and tokens, the token the int8 reference puts first lies
    further below the float32 reference's best than the limit allows.
    A toy's two dozen served tokens are too few to show it, so it is
    read over 4 x 127 positions, with weights as wide as the 7B's
    logits are (a std of 0.3 at width 64)."""
    import numpy as np

    from benchmark import check

    tmp = os.path.dirname(tiny_base)
    cell = harness.load_cell("tiny-chat", os.path.join(tmp, "BENCHMARK.json"),
                             tiny_base)
    cfg = dict(cell["cfg"], initializer_range=0.3)
    rng = np.random.default_rng(7)
    samples = [{"tokens": rng.integers(0, cfg["vocab_size"], 128).tolist(),
                "prompt_len": 1} for _ in range(4)]
    want = check.serve_reference_logits(cfg, 5, samples)
    ctl = check.serve_reference_logits(cfg, 5, samples, precision="int8")
    same = check.serve_numbers(samples, want, want)
    assert same["logit_gap"] == 0.0 and same["tokens_compared"] == 4 * 127
    ok, _ = check.verdict(check.serve_numbers(samples, want, ctl),
                          cell["limits"])
    assert not ok


@pytest.mark.parametrize("name", ["tiny-train", "tiny2-train"])
def test_int8_control_fails_training(tiny_base, name):
    """The control in the program's place: the int8 reference against
    the float32 one, under the tiny cell's limits; each family's."""
    from benchmark import check, traffic

    tmp = os.path.dirname(tiny_base)
    cell = harness.load_cell(name,
                             os.path.join(tmp, "BENCHMARK.json"), tiny_base)
    texts = traffic.train_batches(cell["mix"], 5, 3, cell["cfg"]["vocab_size"])
    want = check.train_reference(cell["cfg"], 5, texts)
    got = check.train_reference(cell["cfg"], 5, texts, precision="int8")
    ok, _ = check.verdict(check.train_numbers(got, want), cell["limits"])
    assert not ok


def test_run_py_refuses_without_a_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(harness.HERE, "run.py"), "--workload",
         "falcon7b-train-2k", "--seed", "1", "--seconds", "1", "--trace",
         "0"], env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
