"""The attribution by the program's own names (`span_reduce`): a hand-made
trace whose answers are known by inspection, and the excerpts recorded
from chip runs (the parent's training trace, which has neither spans nor
scopes, and this program's batch cell)."""

import os

import pytest

from benchmark import span_reduce as sr
from benchmark import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))
PRE = "jit(step)/jvp(layers)/while/body/closed_call/"
BWD = "jit(step)/transpose(jvp(layers))/while/body/closed_call/checkpoint/"


def op(name, start_us, dur_us, category, tf_op=None):
    stats = {"hlo_category": category}
    if tf_op:
        stats["tf_op"] = tf_op
    return [name, start_us * 1e3, dur_us * 1e3, stats]


def span(name, start_us, end_us, **args):
    return [name, start_us * 1e3, (end_us - start_us) * 1e3, args]


def hand_made():
    """us:   0      100      200      300      400      500      600 620 650
    device    |  -   |   A    |   -    |   B    |   -    |   C    | D |  -  |
    serve     | wait | [round: sched | build | dispatch | fetch | book]
    A forward matmul, B its rematerialised twin, C a backward all-reduce
    with nothing beside it, D a copy the compiler gave no name."""
    ops = [
        op("fusion.1", 100, 100, "convolution fusion",
           PRE + "block/mlp/up/dot_general:"),
        op("fusion.2", 300, 100, "convolution fusion",
           BWD + "rematted_computation/block/attention/attn_core/"
           "bgqst,btgd->bsgqd/dot_general:"),
        op("all-reduce.3", 500, 100, "all-reduce",
           BWD + "block/mlp/down/dot_general:"),
        op("copy.4", 600, 20, "data formatting"),
    ]
    serve = [
        span("engine.wait_for_work", 10, 90, live_slots=0, queue_depth=0),
        span("engine.round", 100, 390, round=7),
        span("engine.schedule", 100, 180, queue_depth=1),
        span("engine.build_inputs", 180, 250, transfers=12),
        span("engine.dispatch", 250, 320, fn="mixed_step", kind="mixed",
             width=8, rid=3, prefill_tokens=8, decode_slots=1),
        span("engine.fetch", 320, 380),
        span("engine.book", 380, 390),
    ]
    other = [["runtime thing", 0.0, 650e3, {}]]
    return {"planes": [
        {"name": "/device:TPU:0",
         "lines": [{"name": "XLA Ops", "events": ops}]},
        {"name": "/host:CPU",
         "lines": [{"name": "engine-serve", "events": serve},
                   {"name": "main", "events": other}]},
    ]}


def test_spans_nest_and_keep_their_args():
    spans = sr.program_spans(hand_made())
    by_name = {sp["name"]: sp for sp in spans}
    rnd = spans.index(by_name["engine.round"])
    inside = [sp["name"] for sp in spans if sp["parent"] == rnd]
    assert inside == ["engine.schedule", "engine.build_inputs",
                      "engine.dispatch", "engine.fetch", "engine.book"]
    assert by_name["engine.round"]["self_ns"] == pytest.approx(0.0)
    assert by_name["engine.wait_for_work"]["parent"] is None
    assert by_name["engine.dispatch"]["args"]["rid"] == 3
    assert {sp["line"] for sp in spans} == {"engine-serve"}


def test_gaps_are_split_over_the_innermost_spans():
    got = sr.gap_attribution(hand_made())
    # head 0-100, 200-300, 400-500, tail 620-650
    assert got["idle_s"] == pytest.approx(330e-6)
    assert (got["head_s"], got["between_ops_s"], got["tail_s"]) == (
        pytest.approx(100e-6), pytest.approx(200e-6), pytest.approx(30e-6))
    # 200-300 lies across two spans; 400-500 and the tail under none
    assert got["by_span"] == {
        "engine.wait_for_work": pytest.approx(80e-6),
        "engine.build_inputs": pytest.approx(50e-6),
        "engine.dispatch": pytest.approx(50e-6)}
    assert got[sr.OUTSIDE] == pytest.approx(150e-6)
    assert got["under_span_share"] == pytest.approx(180 / 330)


def test_scope_and_phase_of_an_operation():
    assert sr.scope_of(PRE + "block/mlp/up/dot_general:") == \
        ("layers/block/mlp/up", "forward")
    assert sr.scope_of(BWD + "block/norm/reduce_sum:") == \
        ("layers/block/norm", "backward")
    assert sr.scope_of(BWD + "rematted_computation/block/attention/rope/"
                       "mul:") == ("layers/block/attention/rope",
                                   "remat_forward")
    assert sr.scope_of("jit(step)/jvp(loss)/head/dot_general:") == \
        ("loss/head", "forward")
    assert sr.scope_of("jit(step)/transpose(jvp(embed))/jit(_take)/"
                       "scatter-add:") == ("embed", "backward")
    assert sr.scope_of("jit(step)/optimizer/adam/mul:") == \
        ("optimizer/adam", "optimizer")
    # what the parent's traces carry: structure, no scope
    assert sr.scope_of("jit(train_step)/transpose(jvp())/while:") == \
        (sr.UNNAMED, "backward")
    assert sr.scope_of(None) == (sr.UNNAMED, "forward")


def test_scope_seconds_rounds_and_collective_owner():
    t = hand_made()
    got = sr.scope_seconds(t)
    assert got["total_s"] == pytest.approx(320e-6)
    assert got["by_phase"] == {
        "forward": pytest.approx(120e-6),  # A and the nameless copy
        "remat_forward": pytest.approx(100e-6),
        "backward": pytest.approx(100e-6), "optimizer": 0.0}
    assert got["by_scope"]["layers/block/attention/attn_core"][
        "remat_forward"] == pytest.approx(100e-6)
    assert got["unnamed_by_category"] == {
        "data formatting": pytest.approx(20e-6)}
    assert got["named_share"] == pytest.approx(300 / 320)
    assert sr.scope_share(t, "mlp") == pytest.approx(200 / 320)
    assert sr.scope_share(t, "attention", ["backward"]) == 0.0
    rounds = sr.round_kinds(t)
    assert set(rounds) == {"mixed"}
    assert rounds["mixed"]["rounds"] == 1
    assert rounds["mixed"]["wall_s"] == pytest.approx(290e-6)
    assert rounds["mixed"]["device_busy_s"] == pytest.approx(190e-6)
    assert rounds["mixed"]["host_ms_p50"] == pytest.approx(0.230)
    assert sr.collective_owner(t) == {
        "layers/block/mlp/down/all_reduce": pytest.approx(100e-6)}
    assert tr.exposed_collective_seconds(t) == [pytest.approx(100e-6)]


def test_a_trace_without_spans_or_scopes_reads_empty():
    """The parent's recorded training excerpt (PR 24): every idle second
    lies outside a program span and no operation carries a scope, and
    nothing raises."""
    t = tr.load_excerpt(os.path.join(HERE, "data", "train_excerpt.json.gz"))
    assert sr.program_spans(t) == []
    gaps = sr.gap_attribution(t)
    assert gaps["by_span"] == {} and gaps["under_span_share"] == 0.0
    assert gaps[sr.OUTSIDE] == pytest.approx(gaps["idle_s"])
    scopes = sr.scope_seconds(t)
    assert scopes["total_s"] == pytest.approx(tr.busy_seconds(t)[0])
    assert set(scopes["by_scope"]) == {sr.UNNAMED}
    assert scopes["by_phase"]["remat_forward"] > 0  # JAX's own wrappers
    assert sr.round_kinds(t) == {} and sr.collective_owner(t) == {}


def test_recorded_serve_excerpt():
    """350 ms of a chip trace of falcon7b-serve-batch (my chip run, PR 26,
    executables compiled by this build, so the scopes are in): eleven
    decode rounds of the serve loop, each nested as the table in
    docs/GUIDE.md says."""
    t = tr.load_excerpt(os.path.join(HERE, "data", "serve_excerpt.json.gz"))
    spans = sr.program_spans(t)
    rounds = [i for i, sp in enumerate(spans) if sp["name"] == "engine.round"]
    assert len(rounds) == 11
    for i in rounds:
        assert spans[i]["line"] == "engine-serve"
        inside = [sp["name"] for sp in spans if sp["parent"] == i]
        assert inside == ["engine.schedule", "engine.build_inputs",
                          "engine.dispatch", "engine.fetch", "engine.book"]
    assert [spans[i]["args"]["round"] for i in rounds] == \
        list(range(119, 130))
    got = sr.round_kinds(t)
    assert set(got) == {"decode"} and got["decode"]["rounds"] == 11
    assert got["decode"]["round_ms_p50"] == pytest.approx(30.383078, abs=1e-5)
    assert got["decode"]["host_ms_p50"] == pytest.approx(4.17594, abs=1e-5)
    assert got["decode"]["device_busy_s"] == pytest.approx(0.278188, abs=1e-6)
    gaps = sr.gap_attribution(t)
    assert gaps["idle_s"] == pytest.approx(0.065935, abs=1e-6)
    assert gaps["by_span"]["engine.build_inputs"] == \
        pytest.approx(0.028325, abs=1e-6)
    assert gaps["by_span"]["engine.fetch"] == pytest.approx(0.027509, abs=1e-6)
    assert gaps["under_span_share"] == pytest.approx(0.954703, abs=1e-5)
    assert gaps["idle_s"] == pytest.approx(
        sum(gaps["by_span"].values()) + gaps[sr.OUTSIDE], rel=1e-9)
    scopes = sr.scope_seconds(t)
    assert scopes["total_s"] == pytest.approx(tr.busy_seconds(t)[0], rel=1e-9)
    assert scopes["named_share"] == pytest.approx(0.791745, abs=1e-5)
    assert scopes["by_scope"]["layers/block/mlp/down"]["forward"] == \
        pytest.approx(0.080210, abs=1e-6)
    assert scopes["unnamed_by_category"]["data formatting"] == \
        pytest.approx(0.058828, abs=1e-6)  # the tied head's copy, a round
    assert sr.scope_share(t, "mlp") == pytest.approx(0.557674, abs=1e-5)


def test_recorded_training_excerpt_with_scopes():
    """340 ms of a chip trace of falcon7b-train-2k (my chip run, PR 26,
    compiled by this build): one step marker with its two children, and
    the device time split by scope and by phase."""
    t = tr.load_excerpt(os.path.join(HERE, "data",
                                     "train_scopes_excerpt.json.gz"))
    spans = sr.program_spans(t)
    assert [sp["name"] for sp in spans] == ["train", "train.get_batch",
                                            "train.dispatch"]
    assert spans[0]["args"]["step_num"] == 11
    assert [sp["parent"] for sp in spans] == [None, 0, 0]
    scopes = sr.scope_seconds(t)
    assert scopes["total_s"] == pytest.approx(0.350908, abs=1e-6)
    assert scopes["named_share"] == pytest.approx(0.867269, abs=1e-5)
    assert scopes["by_phase"] == {
        "forward": pytest.approx(0.111884, abs=1e-6),
        "remat_forward": pytest.approx(0.064054, abs=1e-6),
        "backward": pytest.approx(0.141805, abs=1e-6),
        "optimizer": pytest.approx(0.033164, abs=1e-6)}
    core = scopes["by_scope"]["layers/block/attention/attn_core"]
    assert core["backward"] == pytest.approx(0.036024, abs=1e-6)
    assert core["remat_forward"] == pytest.approx(0.019198, abs=1e-6)
    assert sr.scope_share(t, "loss") == pytest.approx(0.300545, abs=1e-5)
    assert sr.scope_share(t, "optimizer") == pytest.approx(0.094510, abs=1e-5)
    assert sr.scope_share(t, "attention", ["backward"]) > 0.1
    gaps = sr.gap_attribution(t)
    assert gaps["by_span"]["train.get_batch"] == \
        pytest.approx(0.006149, abs=1e-6)
