"""The per-layer readers a metric file can name, each on a recorded
excerpt of a chip trace (or, for the two that read no trace, on values)
with the answer known."""

import os

import pytest

from benchmark import harness, span_reduce
from benchmark import trace_reduce as tr

DATA = os.path.join(harness.HERE, "tests", "data")


def ctx_of(excerpt=None, trace=None, **kw):
    if excerpt:
        trace = tr.load_excerpt(os.path.join(DATA, excerpt + ".json.gz"))
    ctx = {"values": {}, "chips": 1, "trace": trace, "trace_window_s": None,
           "peaks": None}
    ctx.update(kw)
    return ctx


def read(reader, params, ctx):
    return harness.read_metric({"reader": reader, "params": params}, ctx)


def test_ratio():
    ctx = ctx_of(values={"serve_rows_useful.window": 43462,
                         "serve_rows_computed.window": 45386, "zero": 0})
    p = {"num": "serve_rows_useful.window",
         "den": "serve_rows_computed.window", "scale": 100.0}
    assert read("ratio", p, ctx) == pytest.approx(95.7608, abs=1e-4)
    assert read("ratio", dict(p, one_minus=True), ctx) == \
        pytest.approx(4.2392, abs=1e-4)
    # nothing to read: a counter the engine lacks, or no rows at all
    assert read("ratio", dict(p, num="absent"), ctx) is None
    assert read("ratio", dict(p, den="zero"), ctx) is None


def test_scope_share():
    ctx = ctx_of("train_scopes_excerpt")
    assert read("scope_share", {"prefix": "optimizer"}, ctx) == \
        pytest.approx(9.4510, abs=1e-3)
    assert read("scope_share", {"prefix": "loss"}, ctx) == \
        pytest.approx(30.0545, abs=1e-3)
    bwd = read("scope_share", {"prefix": "attention",
                               "phases": ["backward"]}, ctx)
    assert 10.0 < bwd < read("scope_share", {"prefix": "attention"}, ctx)
    assert read("scope_share", {"prefix": "unnamed"}, ctx) == \
        pytest.approx(100.0 - 86.7269, abs=1e-3)
    # the parent's excerpt carries no scope at all: nothing to read, and
    # never "100 % unnamed"
    assert read("scope_share", {"prefix": "unnamed"},
                ctx_of("train_excerpt")) is None


def test_span_rounds():
    ctx = ctx_of("serve_excerpt")
    p = {"kind": "decode", "field": "host_ms_p50"}
    assert read("span_rounds", p, ctx) == pytest.approx(4.17594, abs=1e-5)
    assert read("span_rounds", dict(p, field="rounds"), ctx) == 11
    assert read("span_rounds", dict(p, kind="mixed"), ctx) is None
    assert read("span_rounds", p, ctx_of("train_excerpt")) is None


def test_span_gap_share():
    ctx = ctx_of("serve_excerpt")
    assert read("span_gap_share", {"span": "engine.build_inputs"}, ctx) == \
        pytest.approx(100 * 0.028325 / 0.065935, abs=1e-2)
    assert read("span_gap_share", {"span": "engine.wait_for_work"},
                ctx) == 0.0  # spans there, none of this name over a gap
    train = ctx_of("train_scopes_excerpt")
    idle = span_reduce.gap_attribution(train["trace"])["idle_s"]
    assert read("span_gap_share", {"span": "train.get_batch"}, train) == \
        pytest.approx(100 * 0.006149 / idle, abs=1e-2)
    assert read("span_gap_share", {"span": "train.get_batch"},
                ctx_of("train_excerpt")) is None  # no program span at all


def test_collective_owner():
    """The hand-made trace: one backward all-reduce of 100 us owned by
    `layers/block/mlp/down`, alone on the device, in a 650 us window."""
    ev = lambda name, at, dur, cat, op: [  # noqa: E731
        name, at * 1e3, dur * 1e3, {"hlo_category": cat, "tf_op": op}]
    bwd = "jit(step)/transpose(jvp(layers))/while/body/closed_call/"
    ops = [ev("fusion.1", 100, 100, "convolution fusion",
              "jit(step)/jvp(layers)/while/body/block/mlp/up/dot_general:"),
           ev("all-reduce.3", 500, 100, "all-reduce",
              bwd + "block/mlp/down/dot_general:")]
    trace = {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": ops}]}]}
    ctx = ctx_of(trace=trace, trace_window_s=650e-6)
    assert read("collective_owner", {"owner": "mlp/down"}, ctx) == \
        pytest.approx(100 * 100 / 650)
    assert read("collective_owner", {"owner": "mlp"}, ctx) == \
        pytest.approx(100 * 100 / 650)
    assert read("collective_owner", {"owner": "loss"}, ctx) == 0.0
    # 300 ms (one step and the start of the next) of the four-chip cell's
    # trace (my chip run, PR 28): owners by the operation each serves
    four = ctx_of("train4c_excerpt", chips=4, trace_window_s=0.3)
    read4 = lambda owner: read("collective_owner", {"owner": owner}, four)  # noqa: E731
    assert read4("attention/qkv_proj") == pytest.approx(2.217991, abs=1e-5)
    assert read4("loss") == pytest.approx(1.517835, abs=1e-5)
    assert read4("mlp/down") == pytest.approx(0.001575, abs=1e-5)
    assert read4("attention") == pytest.approx(2.921690, abs=1e-5)
    # by owner: the mean over the chips, each collective on its own; the
    # whole: the worst chip's union. They agree to a hundredth here.
    whole = read("exposed_collective_share", {}, four)
    assert whole == pytest.approx(6.904172, abs=1e-5)
    owners = span_reduce.collective_owner(four["trace"])
    assert 100 * sum(owners.values()) / 0.3 == pytest.approx(whole, rel=0.01)
    # a one-chip trace has no collective: nothing to read
    assert read("collective_owner", {"owner": "loss"}, ctx_of(
        "train_scopes_excerpt", trace_window_s=0.34)) is None


def test_site_roofline_by_family_function_and_scopes():
    """A roofline whose work is reckoned by a function kept with the
    family (`flops_fn` / `bytes_fn`, over the traced part's counters)
    and whose time is that of named scopes."""
    from benchmark import families

    cfg = harness.load_json(harness.HERE, "configs", "falcon-7b.json")
    fam = families.find(cfg)
    use = cfg["train"]
    traced = {"tokens": 4096, "seq_length": 2048}
    trace = tr.load_excerpt(os.path.join(DATA,
                                         "train_scopes_excerpt.json.gz"))
    peaks = {"peak_bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    ctx = ctx_of(trace=trace, peaks=peaks, cfg=cfg, use=use, family=fam,
                 traced=traced)
    p = {"flops_fn": "traced_attention_flops",
         "bytes_fn": "traced_attention_bytes",
         "scopes": ["attention/attn_core"]}
    spent = span_reduce.scope_time(span_reduce.scope_seconds(trace),
                                   "attention/attn_core")
    need = max(fam.train_attention_flops(cfg, 2, 2048, 4096) / 197e12,
               fam.train_attention_bytes(cfg, 2, 2048, 4096) / 819e9)
    got = read("site_roofline", p, ctx)
    assert got == pytest.approx(100 * need / spent) and 0 < got < 100
    # source sites beside the scopes: more time under the same work
    both = read("site_roofline", dict(p, sites=["models/attention.py"]),
                ctx)
    assert both is None or both <= got
    assert read("site_roofline", p, dict(ctx, traced={})) is None


def test_the_serving_roofline_work_is_the_familys_too():
    """`weight_matmul_roofline.*`: every token through the blocks, every
    output token through the head, one pass over the weights a round."""
    from benchmark import families

    cfg = harness.load_json(harness.HERE, "configs", "falcon-7b.json")
    fam, use = families.find(cfg), cfg["serve"]
    traced = {"tokens": 900, "out_tokens": 600, "steps": 150}
    assert fam.traced_weight_matmul_flops(cfg, use, traced) == \
        fam.weight_matmul_flops(cfg, 32, 900, 600)
    assert fam.traced_weight_bytes(cfg, use, traced) == \
        150 * fam.weight_bytes(cfg, 32)


FOUR_CHIP = sorted(
    f for f in os.listdir(os.path.join(harness.HERE, "metrics"))
    if f.endswith(".train4c.json") and harness.load_json(
        harness.HERE, "metrics", f)["reader"] not in (
            "value", "mfu", "site_roofline"))  # those read the run's values


@pytest.mark.parametrize("path", FOUR_CHIP)
def test_four_chip_metric_files_on_the_four_chip_excerpt(path):
    """Every `.train4c` file that reads the trace finds something to read
    in 300 ms of the cell's own trace (my chip run, PR 28), and no share
    passes 100."""
    spec = harness.load_json(harness.HERE, "metrics", path)
    got = harness.read_metric(
        spec, ctx_of("train4c_excerpt", chips=4, trace_window_s=0.3))
    assert got is not None and 0.0 <= got < 100.0
