"""CPU rehearsal fixtures: a temporary copy of `benchmark/` with the tiny
configuration, mixes, limits and cells ADDED as new files and entries, the
way a later PR adds a cell, and nothing that is there edited."""

import json
import os
import shutil
import sys

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault(
    "XLA_FLAGS", "--xla_force_host_platform_device_count=4")

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

TINY_CELLS = [
    {"name": "tiny-train", "config": "tiny-falcon", "traffic": "tiny-train",
     "chips": 1, "why": "rehearsal"},
    {"name": "tiny-chat", "config": "tiny-falcon", "traffic": "tiny-chat",
     "chips": 1, "why": "rehearsal"},
    {"name": "tiny-batch", "config": "tiny-falcon", "traffic": "tiny-batch",
     "chips": 1, "why": "rehearsal"},
    {"name": "tiny-train40-tp4", "config": "tiny-falcon40",
     "traffic": "tiny-train", "chips": 4, "why": "rehearsal"},
]
# which real cell's metrics and limits each tiny cell borrows
LIKE = {"tiny-train": "falcon7b-train-2k", "tiny-chat": "falcon7b-serve-chat",
        "tiny-batch": "falcon7b-serve-batch",
        "tiny-train40-tp4": "falcon7b-train-2k"}
# a SECOND FAMILY, added the way a later PR adds one: `second_family/`
# holds new files only (a family module, its reference, a configuration,
# per-layer metrics, limits); the cells reuse two mixes that are there
SECOND_CELLS = [
    {"name": "tiny2-train", "config": "tiny2", "traffic": "tiny-train",
     "chips": 1, "why": "rehearsal of a second family"},
    {"name": "tiny2-batch", "config": "tiny2", "traffic": "tiny-batch",
     "chips": 1, "why": "rehearsal of a second family"},
]
SECOND_E2E = {"tiny2-train": "train_tok_s_chip", "tiny2-batch": "serve_tok_s"}
# CPU float32-vs-bf16 readings at toy sizes; not the chip's limits
TINY_LIMITS = {
    "train": {"loss_gap_step1": 2e-3, "loss_gap_step2": 2e-3,
              "loss_gap_step3": 2e-3, "grad_norm_gap": 0.05,
              "change_norm_gap": 0.05},
    "serve": {"logit_gap": 0.05},
}


def build_copy(tmp: str) -> str:
    """Returns the copy's `benchmark/` directory."""
    base = os.path.join(tmp, "benchmark")
    shutil.copytree(os.path.join(ROOT, "benchmark"), base,
                    ignore=shutil.ignore_patterns("__pycache__"))
    tiny = os.path.join(HERE, "tiny")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for sub in ("configs", "traffic", "metrics"):
        for f in os.listdir(os.path.join(tiny, sub)):
            shutil.copy(os.path.join(tiny, sub, f), os.path.join(base, sub, f))
            if sub == "metrics":  # a per-layer metric a four-chip cell brings
                with open(os.path.join(tiny, sub, f)) as g:
                    m = json.load(g)
                bench["per_layer"].append({k: m[k] for k in (
                    "name", "unit", "better", "source", "layer", "moves",
                    "workloads")})
    for name in ("tiny-falcon", "tiny-falcon40"):
        bench["configs"].append({
            "name": name, "source": "benchmark/tests",
            "file": f"benchmark/configs/{name}.json",
            "reduced": [], "why": "rehearsal"})
    bench["workloads"] += TINY_CELLS
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] += [t for t, r in LIKE.items()
                               if r in m["workloads"]]
    # a per-layer metric of its own, added as a new file + entry
    extra = {"name": "tiny_window_s", "unit": "s", "better": "lower",
             "source": "host_clock", "layer": "entry points and set-up",
             "moves": "setup_s", "workloads": [c["name"] for c in TINY_CELLS],
             "reader": "value", "params": {"key": "window_s"}}
    with open(os.path.join(base, "metrics", "tiny_window_s.json"), "w") as f:
        json.dump(extra, f)
    bench["per_layer"].append({k: extra[k] for k in (
        "name", "unit", "better", "source", "layer", "moves", "workloads")})
    for path in os.listdir(os.path.join(base, "metrics")):
        full = os.path.join(base, "metrics", path)
        with open(full) as f:
            m = json.load(f)
        if "workloads" in m and path not in os.listdir(
                os.path.join(tiny, "metrics")) + ["tiny_window_s.json"]:
            m["workloads"] += [t for t, r in LIKE.items()
                               if r in m["workloads"]]
            # (the copy edits these only to borrow the real metrics for
            # the toy cells; a later PR lists its cell in its own files)
            with open(full, "w") as f:
                json.dump(m, f)
    for cell in TINY_CELLS:
        kind = "train" if "train" in cell["name"] else "serve"
        with open(os.path.join(base, "limits", cell["name"] + ".json"),
                  "w") as f:
            json.dump({"cell": cell["name"], "limits": TINY_LIMITS[kind]}, f)
    add_second_family(base, bench)
    # a family whose blocks are of two kinds: files only, no cell (the
    # program has no such model to run one: test_two_kinds.py)
    add_new_files(base, bench, os.path.join(HERE, "two_kinds"))
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return base


def add_new_files(base: str, bench: dict, new: str):
    """A family's new files copied in, its per-layer metrics appended to
    the index; no file of the copy is opened for writing."""
    for sub in sorted(os.listdir(new)):
        for f in sorted(os.listdir(os.path.join(new, sub))):
            target = os.path.join(base, sub, f)
            assert not os.path.exists(target), target
            shutil.copy(os.path.join(new, sub, f), target)
            if sub == "metrics":
                with open(target) as g:
                    m = json.load(g)
                bench["per_layer"].append({k: m[k] for k in (
                    "name", "unit", "better", "source", "layer", "moves",
                    "workloads")})


def add_second_family(base: str, bench: dict):
    add_new_files(base, bench, os.path.join(HERE, "second_family"))
    bench["configs"].append({
        "name": "tiny2", "source": "benchmark/tests",
        "file": "benchmark/configs/tiny2.json", "reduced": [],
        "why": "rehearsal of a second family"})
    bench["workloads"] += SECOND_CELLS
    for m in bench["end_to_end"]:
        if "workloads" in m:
            m["workloads"] += [c for c, e in SECOND_E2E.items()
                               if e == m["name"]]


@pytest.fixture(scope="session")
def tiny_base(tmp_path_factory):
    return build_copy(str(tmp_path_factory.mktemp("bench_copy")))
