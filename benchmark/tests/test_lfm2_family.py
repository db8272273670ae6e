"""CPU rehearsal of the family whose blocks are of three kinds
(`families/lfm2_moe.py`: convolution + dense MLP, attention + routed MLP,
convolution + routed MLP) through the harness's own functions, at a toy
size: a saturated serving cell end to end through `serve_cell.run`, the
control and the planted fault coming out as not correct. The toy cell is
added to a copy of the rehearsal's copy of the benchmark by new files and
entries alone (`lfm2_family/`: its configuration and its limit) and
borrows the per-layer metric files of the full-size cell
`lfm2moe-serve-batch`, as `conftest.py`'s toy cells borrow theirs. No
device number comes from here."""

import json
import os
import shutil
import time

import numpy as np
import pytest

from benchmark import harness

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = {"name": "tiny-lfm2-batch", "config": "tiny-lfm2",
        "traffic": "tiny-batch", "chips": 1, "why": "rehearsal"}
LIKE = "lfm2moe-serve-batch"  # whose metrics the toy cell borrows


@pytest.fixture(scope="module")
def lfm2_base(tiny_base, tmp_path_factory):
    tmp = str(tmp_path_factory.mktemp("bench_copy_lfm2"))
    shutil.rmtree(tmp)
    shutil.copytree(os.path.dirname(tiny_base), tmp)
    base = os.path.join(tmp, "benchmark")
    with open(os.path.join(tmp, "BENCHMARK.json")) as f:
        bench = json.load(f)
    new = os.path.join(HERE, "lfm2_family")
    for sub in sorted(os.listdir(new)):
        for name in sorted(os.listdir(os.path.join(new, sub))):
            target = os.path.join(base, sub, name)
            assert not os.path.exists(target), target
            shutil.copy(os.path.join(new, sub, name), target)
    for name in sorted(os.listdir(os.path.join(base, "metrics"))):
        target = os.path.join(base, "metrics", name)
        m = harness.load_json(target)
        if LIKE in m.get("workloads", ()):
            m["workloads"].append(CELL["name"])
            with open(target, "w") as f:
                json.dump(m, f)
    bench["configs"].append({
        "name": "tiny-lfm2", "source": "benchmark/tests",
        "file": "benchmark/configs/tiny-lfm2.json", "reduced": [],
        "why": "rehearsal of a family of three kinds"})
    bench["workloads"].append(CELL)
    for m in bench["end_to_end"] + bench["per_layer"]:
        if LIKE in m.get("workloads", ()):
            m["workloads"].append(CELL["name"])
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return base


def load(lfm2_base):
    return harness.load_cell(
        CELL["name"], os.path.join(os.path.dirname(lfm2_base),
                                   "BENCHMARK.json"), lfm2_base)


def run_cell(lfm2_base, trace=False, **kw):
    from benchmark import serve_cell

    cell = load(lfm2_base)
    dev = harness.require_chips(1, allow_cpu=True)
    line = serve_cell.run(cell, 2**31 + 99, 1.0, trace, time.perf_counter(),
                          dev, **kw)
    json.dumps(line)
    return cell, line


def test_last_line_end_to_end(lfm2_base):
    cell, line = run_cell(lfm2_base)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert set(line["metrics"]) == {"serve_tok_s", "setup_s"}
    assert set(line["compared"]) == {"logit_gap", "tokens_compared"}


def test_last_line_traced(lfm2_base):
    """The two metrics that are ratios of the engine's routing counters
    need no device: they are on the line, inside what they can read."""
    cell, line = run_cell(lfm2_base, trace=True)
    assert line["correct"] is True
    touched = line["metrics"]["experts_touched_share.lfm"]["value"]
    peak = line["metrics"]["expert_load_peak_share.lfm"]["value"]
    experts = cell["cfg"]["num_experts"]
    assert 100.0 / experts <= touched <= 100.0
    assert 100.0 / experts <= peak <= 100.0
    assert line["metrics"]["compiles_in_window"]["value"] == 0
    for name in line["metrics"]:
        assert "mfu" not in name and "roofline" not in name


def test_altered_token_is_caught(lfm2_base):
    _, line = run_cell(lfm2_base, fault="token_altered")
    assert line["correct"] is False


def test_int8_control_fails(lfm2_base):
    """As `test_rehearsal.py`'s control for a served model: over 4 x 127
    positions of the same tokens, with weights as wide as the real
    model's logits are, the token the int8 reference puts first lies
    further below the float32 reference's best than the toy's limit."""
    from benchmark import check

    cell = load(lfm2_base)
    cfg = dict(cell["cfg"], initializer_range=0.3)
    rng = np.random.default_rng(7)
    samples = [{"tokens": rng.integers(0, cfg["vocab_size"], 128).tolist(),
                "prompt_len": 1} for _ in range(4)]
    want = check.serve_reference_logits(cfg, 5, samples)
    ctl = check.serve_reference_logits(cfg, 5, samples, precision="int8")
    assert check.serve_numbers(samples, want, want)["logit_gap"] == 0.0
    ok, _ = check.verdict(check.serve_numbers(samples, want, ctl),
                          cell["limits"])
    assert not ok
