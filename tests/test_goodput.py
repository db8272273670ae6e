"""Goodput ledger + compiled-cost registry + perf sentinel (ISSUE 15).

Pinned here (tier-1):
- chipspec: detection source labels, override wins (env + arg),
  unknown override raises, CPU default fallback, the shared
  flops-per-token models;
- GoodputLedger: the sum-to-wall partition invariant (buckets +
  derived idle == wall, exactly; overcount surfaces instead of
  silently balancing), bucket discipline;
- CostRegistry: capture yields real FLOPs/bytes/temp/args, the mint
  listener (contracts.add_mint_listener) mirrors record_variant, MINT-
  TIME-ONLY capture on a live engine (serving more rounds captures
  nothing new), owner filtering, roofline modeled_seconds;
- trainer integration: ledger buckets populated (compile on the first
  step, productive after, data_wait real), gauges present, and the
  bitwise contract — ledger+registry+sentinel+chip-override ON equals
  OFF to the bit on losses AND final params;
- engine integration: cost-on greedy streams bitwise vs cost-off, the
  per-request cost record on retire events (prefill/decode/spec
  split, page-rounds, modeled FLOPs), gated counters keys absent when
  off (the /metrics JSON byte-compat half);
- PerfSentinel: trips on an injected sustained stall — engine-level,
  with the auto-dumped flight record loading and correlating the trip
  (the poison/rollback postmortem path, pointed at latency);
- HTTPReplica histogram proxying (the PR-14 gap): Prometheus text ->
  rebuilt Histogram -> merged fleet distribution round-trips exactly.
"""

import json
import math
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from megatron_llm_tpu.analysis import contracts
from megatron_llm_tpu.config import (
    ParallelConfig,
    TrainConfig,
    tiny_config,
)
from megatron_llm_tpu.inference.engine import DecodeEngine
from megatron_llm_tpu.models import LlamaModel
from megatron_llm_tpu.telemetry import (
    GOODPUT_BUCKETS,
    CostRegistry,
    FlightRecorder,
    GoodputLedger,
    Histogram,
    PerfSentinel,
    detect_chip,
    histograms_from_prometheus,
    render_prometheus,
)
from megatron_llm_tpu.telemetry.chipspec import (
    CHIP_SPECS,
    decode_flops_per_token,
    train_flops_per_token,
)


@pytest.fixture(scope="module")
def tiny_model():
    cfg = tiny_config(compute_dtype=jnp.float32, use_decode_attn=False)
    model = LlamaModel(cfg)
    params = model.init(jax.random.key(0))
    return model, params


# ---------------------------------------------------------------------------
# chipspec
# ---------------------------------------------------------------------------


class TestChipSpec:
    def test_override_wins_and_is_labeled(self):
        c = detect_chip(override="v5e")
        assert c.name == "v5e" and c.source == "override"
        assert c.label() == "v5e:override"
        assert c.peak_flops_for("bf16") == 197e12
        assert c.peak_flops_for("bfloat16") == 197e12
        assert c.peak_flops_for("int8") == 394e12
        # fp32 maps to the MXU bf16 peak (documented)
        assert c.peak_flops_for("float32") == 197e12
        assert c.hbm_bytes_s == 819e9

    def test_env_override(self, monkeypatch):
        monkeypatch.setenv("MEGATRON_TPU_CHIPSPEC", "v5p")
        c = detect_chip()
        assert c.name == "v5p" and c.source == "override"

    def test_unknown_override_raises(self):
        with pytest.raises(ValueError, match="unknown chip spec"):
            detect_chip(override="v99")

    def test_detection_never_assumes(self):
        # the CPU harness has no chip: None, so callers drop their
        # MFU/roofline gauges
        assert detect_chip() is None

        class Dev:
            platform = "tpu"

            def __init__(self, kind):
                self.device_kind = kind

        assert detect_chip(devices=[Dev("TPU v5 lite")]).label() \
            == "v5e:detected"
        # a TPU that is not in the table is an error, not a default
        with pytest.raises(ValueError, match="not in the chip spec table"):
            detect_chip(devices=[Dev("TPU v5")])

    def test_table_sanity(self):
        for name, spec in CHIP_SPECS.items():
            assert spec.peak_flops["bf16"] > 0
            assert spec.hbm_bytes_s > 0 and spec.hbm_bytes > 0
            assert spec.name == name

    def test_flops_models(self):
        # 6N dominates, attention term scales with seq/context
        n, L, h = 10_000, 2, 64
        t = train_flops_per_token(n, L, h, 128)
        assert t == 6 * n + 6 * L * h * 128
        d = decode_flops_per_token(n, L, h, 128)
        assert d == 2 * n + 4 * L * h * 128


# ---------------------------------------------------------------------------
# GoodputLedger
# ---------------------------------------------------------------------------


class TestGoodputLedger:
    def test_sum_to_wall_invariant(self):
        """The acceptance pin: buckets provably partition wall. The
        explicit buckets plus the derived idle sum to the wall clock
        (idle is the remainder by construction); the STATED tolerance
        is 1e-5 s — the snapshot rounds each bucket to 6 decimals, so
        the rounded sum may drift from the rounded wall by up to
        0.5us x bucket count and no more."""
        led = GoodputLedger()
        led.start()
        t0 = time.perf_counter()
        led.note("productive", 0.010)
        led.note("compile", 0.005)
        led.note("data_wait", 0.002)
        time.sleep(0.03)
        snap = led.snapshot()
        wall_independent = time.perf_counter() - t0
        total = sum(snap["buckets"].values())
        assert abs(total - snap["wall_s"]) < 1e-5
        assert snap["overcount_s"] == 0.0
        # the ledger's wall is the real wall (measured independently)
        assert abs(snap["wall_s"] - wall_independent) < 0.05
        assert set(snap["buckets"]) == set(GOODPUT_BUCKETS)
        assert snap["buckets"]["idle"] > 0  # the sleep

    def test_overcount_surfaces_instead_of_balancing(self):
        led = GoodputLedger()
        led.start()
        led.note("productive", 5.0)  # >> actual wall
        snap = led.snapshot()
        assert snap["overcount_s"] > 4.9
        assert snap["buckets"]["idle"] == 0.0

    def test_idle_is_derived_not_notable(self):
        led = GoodputLedger()
        led.start()
        with pytest.raises(ValueError, match="derived"):
            led.note("idle", 1.0)
        with pytest.raises(KeyError):
            led.note("nonsense_bucket", 1.0)

    def test_counters_form(self):
        led = GoodputLedger()
        led.start()
        led.note("productive", 0.5)
        c = led.counters()
        assert "goodput_fraction" in c and "goodput_wall_s" in c
        for b in GOODPUT_BUCKETS:
            assert f"goodput_{b}_s" in c


# ---------------------------------------------------------------------------
# CostRegistry
# ---------------------------------------------------------------------------


class TestCostRegistry:
    def test_capture_real_facts_and_roofline(self):
        reg = CostRegistry(chip=detect_chip(override="v5e"))

        @jax.jit
        def f(x, y):
            return jnp.dot(x, y) + 1.0

        x = jnp.ones((64, 64))
        rec = reg.capture("test.entry_a", ("k",), f, (x, x))
        assert rec.flops and rec.flops > 2 * 64 ** 3 * 0.9
        assert rec.bytes_accessed and rec.bytes_accessed > 0
        assert rec.temp_bytes is not None and rec.arg_bytes > 0
        assert rec.source == "compiled"
        m = rec.modeled_seconds(reg.chip)
        assert m is not None and 0 < m < 1e-3
        # no chip -> no modeled time (callers drop the gauge)
        assert rec.modeled_seconds(None) is None
        # record() is the hot-loop read
        assert reg.record("test.entry_a", ("k",)) is rec
        assert reg.record("test.entry_a") is rec
        assert reg.record("test.missing") is None
        lines = reg.prometheus_lines()
        assert any("cost_flops{" in ln for ln in lines)

    def test_mint_listener_mirrors_record_variant(self):
        from megatron_llm_tpu.analysis.contracts import (
            compile_contract,
        )

        @compile_contract("test.goodput_mint", max_variants=8)
        def make(scale):
            return jax.jit(lambda x: x * scale)

        reg = CostRegistry().attach()
        try:
            fn = make(3.0, contract_key="s3")
            assert ("test.goodput_mint", repr("s3")) in reg._pending
            rows = reg.rows()
            assert any(r.get("pending") and r["contract"] ==
                       "test.goodput_mint" for r in rows)
            # capture resolves the pending row
            reg.capture("test.goodput_mint", "s3", fn,
                        (jnp.ones((8,)),))
            assert ("test.goodput_mint", repr("s3")) not in reg._pending
            # a SECOND mint of the same key does not re-fire (the
            # contracts hook fires on NEW variants only)
            before = dict(reg._pending)
            make(3.0, contract_key="s3")
            assert reg._pending == before
        finally:
            reg.detach()

    def test_owner_filter(self):
        from megatron_llm_tpu.analysis.contracts import (
            compile_contract,
        )

        @compile_contract("test.goodput_owned", max_variants=8)
        def make(scale):
            return jax.jit(lambda x: x * scale)

        class _Owner:  # plain object() is not weakref-able
            pass

        owner_a, owner_b = _Owner(), _Owner()
        reg = CostRegistry(owner=owner_a).attach()
        try:
            make(1.0, contract_key="a", contract_owner=owner_a)
            make(2.0, contract_key="b", contract_owner=owner_b)
            keys = {k for _, k in reg._pending}
            assert repr("a") in keys and repr("b") not in keys
        finally:
            reg.detach()

    def test_capture_error_is_swallowed(self):
        reg = CostRegistry()
        rec = reg.capture("x", "k", object(), ())  # no .lower
        assert rec is None and reg.capture_errors == 1


# ---------------------------------------------------------------------------
# trainer integration
# ---------------------------------------------------------------------------


def _run_trainer(cfg, steps=6, **tcfg_kw):
    from megatron_llm_tpu.training.trainer import Trainer

    tcfg = TrainConfig(
        micro_batch_size=2, global_batch_size=2, lr=1e-3,
        train_iters=steps, log_interval=3, eval_interval=0, **tcfg_kw)
    trainer = Trainer(LlamaModel(cfg), tcfg,
                      ParallelConfig(num_microbatches=1))

    class _It:
        def __iter__(self):
            rs = np.random.RandomState(3)
            while True:
                yield rs.randint(
                    0, cfg.padded_vocab_size,
                    (1, 2, cfg.seq_length + 1)).astype(np.int32)

    trainer.train_data_iterator = _It()
    state = trainer.setup()
    state = trainer.train(state)
    losses = [e["loss"] for e in
              trainer.recorder.snapshot(reason="t")["events"]
              if e["kind"] == "step"]
    return trainer, state, losses


class TestTrainerGoodput:
    @pytest.fixture(scope="class")
    def runs(self):
        cfg = tiny_config(compute_dtype=jnp.float32,
                          use_decode_attn=False)
        off = _run_trainer(cfg)
        on = _run_trainer(
            cfg, device_cost_registry=True, chip_spec="v5e",
            perf_sentinel_ksigma=50.0, perf_sentinel_window=4,
            perf_sentinel_patience=2)
        return off, on

    def test_ledger_partition_and_buckets(self, runs):
        (trainer, _, _), _ = runs
        snap = trainer.ledger.snapshot()
        # stated tolerance: 6-decimal bucket rounding x bucket count
        assert abs(sum(snap["buckets"].values()) - snap["wall_s"]) \
            < 1e-5
        assert snap["overcount_s"] == 0.0
        # first step paid the compile; the rest were productive
        assert snap["buckets"]["compile"] > 0
        assert snap["buckets"]["productive"] > 0
        assert snap["buckets"]["data_wait"] >= 0
        assert snap["productive_steps"] == 5  # 6 steps - 1 mint
        # every step event carries its bucket
        evs = [e for e in trainer.recorder.snapshot(reason="t")["events"]
               if e["kind"] == "step"]
        assert evs[0]["bucket"] == "compile"
        assert all(e["bucket"] == "productive" for e in evs[1:])

    def test_bitwise_on_vs_off(self, runs):
        """The acceptance pin: ledger+registry+sentinel+chip-override
        ON is bitwise OFF on losses and final params."""
        (_, st_off, losses_off), (_, st_on, losses_on) = runs
        assert losses_on == losses_off
        for a, b in zip(jax.tree.leaves(st_off.params),
                        jax.tree.leaves(st_on.params)):
            assert bool((a == b).all())

    def test_cost_capture_and_gauges(self, runs):
        _, (trainer, _, _) = runs
        rec = trainer.costs.record("train.step")
        assert rec is not None and rec.flops and rec.flops > 0
        assert rec.temp_bytes is not None
        g = trainer.timers.gauges()
        assert g["train_mfu_source"] == "registry"
        assert g["chip_spec"] == "v5e:override"
        assert g["train_mfu"] >= 0
        assert "train_mfu_effective" in g
        assert g["train_step_achieved_gbps"] > 0
        assert 0 <= g["train_step_hbm_frac"] <= 1
        for b in GOODPUT_BUCKETS:
            assert f"goodput_{b}_s" in g

    def test_no_chip_no_mfu_gauges(self, runs):
        """Without a known chip spec the MFU/roofline gauges are
        ABSENT — never reported against a guessed peak."""
        (trainer, _, _), _ = runs
        assert trainer.chip is None  # CPU harness, no override
        g = trainer.timers.gauges()
        assert "train_mfu" not in g
        assert "train_step_achieved_gbps" not in g
        # the ledger gauges are chip-independent and present
        assert "goodput_fraction" in g


# ---------------------------------------------------------------------------
# engine integration
# ---------------------------------------------------------------------------


def _serve(model, params, prompts, gen=10, **kw):
    eng = DecodeEngine(model, params, slots=2, page_size=16,
                       max_context=64, prefill_chunk_tokens=16,
                       spec_decode_k=2, vocab_size=256, **kw)
    reqs = [eng.submit(p, gen, top_k=1) for p in prompts]
    eng.drain()
    return eng, [r.result(5)[0] for r in reqs]


class TestEngineCosts:
    @pytest.fixture(scope="class")
    def served(self, tiny_model):
        model, params = tiny_model
        rs = np.random.RandomState(0)
        prompts = [[int(x) for x in rs.randint(1, 200, size=12)]
                   for _ in range(4)]
        off = _serve(model, params, prompts)
        on = _serve(model, params, prompts, cost_registry=True,
                    chip_spec="v5e")
        return off, on

    def test_streams_bitwise_on_vs_off(self, served):
        (_, off), (_, on) = served
        assert on == off

    def test_mint_time_only_capture(self, served, tiny_model):
        """The GR006 contract made executable: after warmup() has
        minted (and captured) every bucket the config can reach,
        serving traffic captures NOTHING new — capture fires at mint
        sites only, never in the round loop."""
        _, (eng, _) = served
        eng.warmup()  # mints any bucket traffic has not touched yet
        captured = eng.costs.captures
        assert captured > 0
        # the registry's inventory mirrors the live variants: nothing
        # pending (every mint was captured at its site)
        assert not [r for r in eng.costs.rows() if r.get("pending")]
        rs = np.random.RandomState(7)
        more = [[int(x) for x in rs.randint(1, 200, size=12)]
                for _ in range(3)]
        reqs = [eng.submit(p, 8, top_k=1) for p in more]
        eng.drain()
        for r in reqs:
            r.result(5)
        assert eng.costs.captures == captured, (
            "serving traffic over warmed buckets captured new cost "
            "records — capture leaked out of mint time")

    def test_retire_cost_record(self, served):
        _, (eng, _) = served
        evs = eng.flight_record()["events"]
        retires = [e for e in evs if e["kind"] == "retire"
                   and "cost" in e]
        assert retires, "no retire event carries a cost record"
        c = retires[0]["cost"]
        for key in ("prompt_tokens", "cached_tokens", "prefill_tokens",
                    "decode_tokens", "spec_accepted", "rounds_held",
                    "pages", "page_rounds", "modeled_mflops"):
            assert key in c, key
        assert c["prompt_tokens"] == 12
        assert c["prefill_tokens"] == 12  # no prefix cache: full prompt
        assert c["rounds_held"] >= 1 and c["pages"] >= 1
        assert c["page_rounds"] == c["pages"] * c["rounds_held"]
        assert c["modeled_mflops"] > 0

    def test_gated_counters(self, served):
        (eng_off, _), (eng_on, _) = served
        c_on, c_off = eng_on.counters(), eng_off.counters()
        for key in ("serve_modeled_gflops", "serve_page_rounds",
                    "serve_cost_records", "serve_chip_spec",
                    "serve_dispatch_overhead_pct"):
            assert key in c_on, key
            assert key not in c_off, key
        assert c_on["serve_modeled_gflops"] > 0
        assert c_on["serve_cost_records"] == eng_on.costs.captures
        # dispatch overhead is a percentage of measured round wall
        assert c_on["serve_dispatch_overhead_pct"] <= 100.0
        prom = eng_on.prometheus_metrics()
        assert "cost_flops{contract=" in prom
        assert "cost_flops{" not in eng_off.prometheus_metrics()

    def test_flight_record_carries_cost_table(self, served):
        _, (eng, _) = served
        snap = eng.flight_record()
        table = snap["extra"]["costs"]
        assert table["captures"] == eng.costs.captures
        assert any(r["contract"] == "engine.mixed_step"
                   for r in table["records"])
        # json-serializable end to end (the dump path)
        json.dumps(snap, default=str)

    def test_off_engine_schema_untouched(self, tiny_model):
        from tests.test_telemetry import LEGACY_METRICS_KEYS

        model, params = tiny_model
        eng = DecodeEngine(model, params, slots=2, page_size=16,
                           max_context=64, prefill_chunk_tokens=16,
                           vocab_size=256)
        assert list(eng.counters().keys()) == LEGACY_METRICS_KEYS


# ---------------------------------------------------------------------------
# perf sentinel
# ---------------------------------------------------------------------------


class TestPerfSentinel:
    def test_units_trip_and_rearm(self):
        rec = FlightRecorder(128)
        s = PerfSentinel(k_sigma=3.0, window=16, patience=3,
                         min_history=8, recorder=rec, name="round_ms")
        assert not s.enabled or s.k_sigma > 0
        for i in range(12):
            assert not s.observe(10.0 + (i % 3) * 0.1, step=i)
        thr = s.threshold()
        assert math.isfinite(thr)
        # two bad rounds do not trip at patience 3; the third does
        assert not s.observe(500.0, step=20)
        assert not s.observe(500.0, step=21)
        assert s.observe(500.0, step=22)
        assert s.trips == 1
        evs = rec.snapshot()["events"]
        bads = [e for e in evs if e["kind"] == "perf_bad.round_ms"]
        trips = [e for e in evs
                 if e["kind"] == "perf_regression.round_ms"]
        assert len(bads) == 3 and len(trips) == 1
        assert trips[0]["step"] == 22
        assert trips[0]["baseline_median_ms"] == pytest.approx(10.1,
                                                               abs=0.2)
        # post-trip the window cleared: the new normal re-arms instead
        # of tripping forever
        assert s.threshold() == math.inf
        for i in range(10):
            s.observe(500.0 + (i % 3), step=30 + i)
        assert s.trips == 1  # the regression became the baseline

    def test_good_streak_resets_patience(self):
        s = PerfSentinel(k_sigma=3.0, window=16, patience=2,
                         min_history=4)
        # noisy-but-healthy baseline: a flat window would shrink MAD
        # to the floor and flag the noise itself
        for i in range(9):
            assert not s.observe(10.0 + (i % 3) * 0.1, step=i)
        assert not s.observe(400.0, step=10)
        assert not s.observe(10.1, step=11)  # streak broken
        assert not s.observe(400.0, step=12)
        assert s.observe(400.0, step=13)  # 2 consecutive now

    def test_disabled_sentinel_never_trips(self):
        s = PerfSentinel(k_sigma=0.0)
        assert not s.enabled
        for _ in range(50):
            assert not s.observe(1e9)
        assert s.trips == 0

    def test_engine_trip_dumps_correlatable_record(self, tiny_model,
                                                   tmp_path):
        """ISSUE 15 acceptance: the sentinel trips on an injected
        stall and auto-dumps a flight record that loads and correlates
        — the verdict trail (perf_bad rounds), the trip event with
        threshold/baseline, and live counters, through the same
        postmortem path as poison."""
        model, params = tiny_model
        eng = DecodeEngine(
            model, params, slots=2, page_size=16, max_context=64,
            prefill_chunk_tokens=16, vocab_size=256,
            # horizon 1: every decoded token is its own round, so the
            # stalled stretch yields enough bad samples for patience
            step_horizon=1,
            record_dir=str(tmp_path),
            perf_sentinel_ksigma=3.0, perf_sentinel_window=8,
            perf_sentinel_patience=3)
        rs = np.random.RandomState(1)
        # baseline traffic arms the window at healthy round latency
        # (each decode round contributes one sample; run waves until
        # min_history is met)
        for _ in range(6):
            reqs = [eng.submit(
                [int(x) for x in rs.randint(1, 200, size=8)],
                12, top_k=1) for _ in range(3)]
            eng.drain()
            for r in reqs:
                r.result(5)
            if len(eng._sentinel._stat) >= 8:
                break
        assert len(eng._sentinel._stat) >= 8, "window did not arm"
        # inject the stall INSIDE the round's measured wall (the
        # deadline sweep runs at the top of every _step_inner): each
        # subsequent round's per-token-advance latency regresses by
        # orders of magnitude
        orig_expire = eng._expire_deadlines

        def slow_expire():
            time.sleep(0.05)
            orig_expire()

        eng._expire_deadlines = slow_expire
        req = eng.submit([1, 2, 3, 4, 5, 6, 7, 8], 16, top_k=1)
        eng.drain()
        req.result(5)
        assert eng._sentinel.trips >= 1, (
            "injected 50ms/round stall did not trip the sentinel",
            eng._sentinel.last_threshold)
        assert eng.counters()["serve_perf_regressions"] >= 1
        dumps = [f for f in os.listdir(tmp_path)
                 if f.startswith("flight_record_perf-regression")]
        assert dumps, os.listdir(tmp_path)
        art = json.loads((tmp_path / dumps[0]).read_text())
        assert art["reason"] == "perf-regression"
        assert art["extra"]["trip"] >= 1
        assert art["extra"]["threshold_ms"] > 0
        kinds = [e["kind"] for e in art["events"]]
        assert "perf_bad.decode_round_ms" in kinds
        assert "perf_regression.decode_round_ms" in kinds
        # the dump carries live counters (note_counters ran pre-dump)
        assert art["counters"].get("serve_admitted", 0) >= 1

    def test_sentinel_off_keeps_legacy_schema(self, tiny_model):
        from tests.test_telemetry import LEGACY_METRICS_KEYS

        model, params = tiny_model
        eng = DecodeEngine(model, params, slots=2, page_size=16,
                           max_context=64, prefill_chunk_tokens=16,
                           vocab_size=256)
        assert eng._sentinel is None
        assert "serve_perf_regressions" not in eng.counters()
        assert list(eng.counters().keys()) == LEGACY_METRICS_KEYS


# ---------------------------------------------------------------------------
# HTTPReplica histogram proxying (PR-14 gap closed)
# ---------------------------------------------------------------------------


class TestRemoteHistograms:
    def _hist(self, values, name="serve_ttft_ms"):
        h = Histogram(name)
        for v in values:
            h.observe(v)
        return h

    def test_prometheus_roundtrip_exact(self):
        h = self._hist([0.4, 3.0, 7.5, 42.0, 900.0, 1e6])
        text = render_prometheus({"serve_admitted": 6}, [h])
        (h2,) = histograms_from_prometheus(text)
        assert h2.name == h.name
        assert h2.cumulative() == h.cumulative()
        assert h2.sum == h.sum and h2.count == h.count

    def test_merged_fleet_includes_remote(self):
        local = self._hist([1.0, 10.0, 100.0])
        remote_src = self._hist([2.0, 20.0, 200.0, 2000.0])
        text = render_prometheus({}, [remote_src])
        (remote,) = histograms_from_prometheus(text)
        merged = Histogram.merged([local, remote])
        assert merged.count == 7
        assert merged.sum == pytest.approx(local.sum + remote_src.sum)
        ref = Histogram.merged([local, remote_src])
        assert merged.cumulative() == ref.cumulative()

    def test_httpreplica_scrapes_prometheus(self, monkeypatch):
        from megatron_llm_tpu.inference.router import HTTPReplica

        src = self._hist([5.0, 50.0])
        text = render_prometheus({"serve_admitted": 2}, [src])
        rep = HTTPReplica(3, "http://replica:5000")

        def fake_raw(path, accept=None):
            if "format=prometheus" in path:
                assert accept == "text/plain"
                return text.encode()
            if path == "/health":
                return json.dumps(
                    {"status": "ok",
                     "engine": {"alive": True, "broken": None,
                                "queue_depth": 0,
                                "slots_busy": 0}}).encode()
            if path == "/metrics":
                return json.dumps({"serve_admitted": 2}).encode()
            raise AssertionError(path)

        monkeypatch.setattr(rep, "_get_raw", fake_raw)
        hs = rep.histograms()
        assert len(hs) == 1
        assert hs[0].cumulative() == src.cumulative()
        assert rep.health()["alive"]

    def test_httpreplica_scrape_failure_degrades(self, monkeypatch):
        from megatron_llm_tpu.inference.router import HTTPReplica

        rep = HTTPReplica(4, "http://replica:5000")

        def fake_raw(path, accept=None):
            if "format=prometheus" in path:
                raise OSError("boom")
            if path == "/health":
                return json.dumps(
                    {"status": "ok",
                     "engine": {"alive": True, "broken": None,
                                "queue_depth": 0,
                                "slots_busy": 0}}).encode()
            return json.dumps({}).encode()

        monkeypatch.setattr(rep, "_get_raw", fake_raw)
        assert rep.histograms() == []
        assert rep.health()["alive"]  # liveness unaffected

    def test_malformed_exposition_raises(self):
        bad = ("# TYPE serve_ttft_ms histogram\n"
               'serve_ttft_ms_bucket{le="5"} 3\n'
               'serve_ttft_ms_bucket{le="10"} 1\n'  # non-monotone
               'serve_ttft_ms_bucket{le="+Inf"} 3\n'
               "serve_ttft_ms_sum 9\nserve_ttft_ms_count 3\n")
        with pytest.raises(ValueError, match="non-monotone"):
            histograms_from_prometheus(bad)
