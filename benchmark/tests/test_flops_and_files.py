"""Hand counts for the yardstick's arithmetic, and the shape of the data
files the harness is driven by."""

import glob
import json
import os
import re

import pytest

from benchmark import families, flops, harness

ROOT = os.path.dirname(harness.HERE)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def cfg(name):
    return harness.load_json(harness.HERE, "configs", name + ".json")


def bench():
    return harness.load_json(ROOT, "BENCHMARK.json")


def test_falcon_7b_hand_counts():
    c = cfg("falcon-7b")
    fam = families.find(c)
    # block: 4544*4672 + 4544*4544 + 2*4544*18176 + 2*4544 (one norm)
    assert fam.layer_params(c) == 21229568 + 20647936 + 165183488 + 9088
    assert fam.embedding_params(c) == 65024 * 4544
    assert round(fam.n_params(c, 32) / 1e9, 2) == 6.92
    assert round(fam.n_params(c, 2) / 1e6, 1) == 709.6
    assert fam.kv_bytes_per_token(c, 32) == 8192
    assert round(fam.weight_bytes(c, 32) / 1e9, 2) == 13.84


def test_falcon_40b_hand_counts():
    c = cfg("falcon-40b")
    fam = families.find(c)
    assert round(fam.layer_params(c) / 1e6, 1) == 679.5
    assert round(fam.embedding_params(c) / 1e6, 1) == 532.7
    assert round(fam.n_params(c, 4) / 1e9, 2) == 3.25
    assert round(fam.n_params(c, 3) / 1e9, 2) == 2.57


def test_train_flops_per_token():
    c = cfg("falcon-7b")
    fam = families.find(c)
    n = 2 * (21229568 + 20647936 + 165183488) + 65024 * 4544
    assert fam.train_flops_per_token(c, 2, 2048) == \
        6.0 * n + 6.0 * 2 * 4544 * 2048
    # attention alone: 4 FLOPs a (query, key, channel) forward, 3x with
    # the backward, halved by the causal mask
    assert fam.train_attention_flops(c, 2, 2048, 4096) == \
        4 * 3 * 0.5 * 2 * 4544 * 2048 * 4096


def test_serve_span_is_the_sum_of_its_tokens():
    c = cfg("falcon-7b")
    fam = families.find(c)
    one_by_one = sum(fam.serve_token_flops(c, 32, p, p >= 7)
                     for p in range(3, 10))
    assert fam.serve_span_flops(c, 32, 3, 10, 3) == \
        pytest.approx(one_by_one, rel=1e-12)


def test_unknown_chip_is_an_error():
    assert flops.chip_peaks("TPU v5 lite")["peak_bf16_flops"] == 197e12
    assert flops.chip_peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(ValueError):
        flops.chip_peaks("TPU v9")


def test_names_units_and_sizes():
    b = bench()
    names = set()
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in b[group]:
            assert NAME.match(e["name"]), e["name"]
            assert e["name"] not in names
            names.add(e["name"])
    for m in b["end_to_end"] + b["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for w in b["workloads"]:
        assert NAME.match(w["traffic"]) and len(w["why"]) <= 200
    assert sum(w["chips"] == 4 for w in b["workloads"]) <= max(
        1, len(b["workloads"]) // 4)
    assert 1 <= len([m for m in b["end_to_end"]
                     if m["name"] != "setup_s"]) <= 4
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 65536
    assert (2 + 14 * 24) * (b["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


def test_benchmark_json_keeps_to_the_contract():
    b = bench()
    assert list(b) == ["command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"]
    line = lambda t: 1 <= len(t) <= 200 and "\n" not in t and "\t" not in t
    assert 1 <= len(b["command"]) <= 32 and all(map(line, b["command"]))
    assert not any(w.startswith("/") or ".." in w for w in b["command"])
    assert 1 <= len(b["paths"]) <= 16 and 1 <= b["run_seconds"] <= 51
    for p in b["paths"]:
        assert re.match(r"^[A-Za-z0-9_./-]{1,200}$", p) and ".." not in p
    cells = {w["name"]: w for w in b["workloads"]}
    used = {w["config"] for w in b["workloads"]}
    pairs = [(w["config"], w["traffic"]) for w in b["workloads"]]
    assert len(set(pairs)) == len(pairs) and 1 <= len(pairs) <= 24
    widths = re.compile(r"(hidden_size|intermediate|latent|state_size|proj|head_dim|"
                        r"_dim$|_rank$|expan|experts_per)")
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["name"] in used and line(c["why"]) and line(c["source"])
        assert any(c["file"].startswith(p + "/") for p in b["paths"])
        assert len(c["reduced"]) <= 16
        assert not any(widths.search(k) for k in c["reduced"])
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and line(w["why"])
        assert w["config"] in {c["name"] for c in b["configs"]}
    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert "setup_s" in e2e and len(e2e) <= 16

    def reporting(m):
        return set(m.get("workloads", cells))

    for m in e2e.values():
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
        assert reporting(m) <= set(cells)
    for m in b["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert line(m["layer"])
        assert reporting(m) <= reporting(e2e[m["moves"]]), m["name"]
    for name in cells:
        mine = [m for m in e2e.values() if name in reporting(m)]
        assert len(mine) >= 2 and any(m["name"] == "setup_s" for m in mine)
        assert any(name in reporting(m) for m in b["per_layer"])


def test_every_cell_finds_its_files_and_reports_what_it_must():
    b = bench()
    for w in b["workloads"]:
        cell = harness.load_cell(w["name"])
        e2e = {m["name"] for m in cell["end_to_end"]}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert cell["per_layer"], w["name"]
        assert cell["limits"]
        for m in cell["per_layer"]:
            assert m["moves"] in e2e, (w["name"], m["name"])
            assert m["reader"] in harness.READERS


def test_metric_files_and_index_agree():
    b = bench()
    index = {m["name"]: m for m in b["per_layer"]}
    files = {}
    for path in glob.glob(os.path.join(harness.HERE, "metrics", "*.json")):
        with open(path) as f:
            m = json.load(f)
        assert os.path.basename(path) == m["name"] + ".json"
        files[m["name"]] = m
    assert set(files) == set(index)
    for name, m in files.items():
        for key in ("unit", "better", "source", "layer", "moves",
                    "workloads"):
            assert m.get(key) == index[name].get(key), (name, key)
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    layers = {m["layer"] for m in b["per_layer"]}
    assert all("\n" not in layer and len(layer) <= 200 for layer in layers)


def test_configs_state_every_change():
    b = bench()
    for c in b["configs"]:
        body = harness.load_json(ROOT, c["file"])
        assert c["file"].startswith("benchmark/")
        assert body["source"] == c["source"]
        assert body["head_dim"] * body["num_attention_heads"] == \
            body["hidden_size"]
        assert body["ffn_hidden_size"] == 4 * body["hidden_size"]
        for key in c["reduced"]:
            assert key in body["published"]


def _four_chip_readings():
    path = os.path.join(harness.HERE, "tests", "data",
                        "train4c_readings.jsonl")
    with open(path) as f:
        return [json.loads(line) for line in f]


@pytest.mark.parametrize("what,fails", [
    ("program", None),
    ("control_int8", {"loss_gap_step3", "grad_norm_gap", "change_norm_gap"}),
    ("fault_half_batch", {"loss_gap_step1", "grad_norm_gap",
                          "change_norm_gap"}),
    ("fault_no_exchange", {"loss_gap_step1", "grad_norm_gap",
                           "change_norm_gap"})])
def test_four_chip_limits_part_the_chips_own_readings(what, fails):
    """Every reading taken on the four chips (my chip runs, PR 28:
    `prove.py` on 8 seeds, the cell's 13 runs; PR 35: `prove.py` on 17
    more; the ledger's reading that refused PR 31), through the cell's
    own `verdict` under the limits file as committed: each run of the
    program comes out correct, the int8 control and each planted fault
    on every seed not, and each fails at least the numbers the limits
    file says it is held against (since PR 35 the second step's loss is
    not the control's to fail: one of its six seeds reads under it)."""
    from benchmark import check

    limits = harness.load_json(harness.HERE, "limits",
                               "falcon40b-train-4chip.json")["limits"]
    rows = [r for r in _four_chip_readings() if r["what"] == what]
    assert len(rows) >= 6
    for row in rows:
        ok, compared = check.verdict(row["numbers"], limits)
        assert ok == (fails is None), row
        if fails:
            over = {k for k, c in compared.items() if c["value"] > c["limit"]}
            assert fails <= over, (row["seed"], over)
