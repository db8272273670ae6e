"""graft-check (ISSUE 7): the static-analysis subsystem's own tests.

Tier-1 on purpose — this file IS the gate that keeps the gate honest:

- every lint rule (GR001-GR007) fires exactly on the marked lines of
  its bad fixture (tests/fixtures/lint/) and stays quiet on the
  idiomatic counterpart;
- baseline semantics: line-number-free keys survive code motion, empty
  justifications are rejected, stale keys are reported;
- the contract registry: budget violations raise AT MINT TIME,
  eviction releases, owners are isolated, the decorator records;
- the AOT audit: a DELIBERATELY broken contract (undeclared collective,
  blown temp budget, host callback, fp64) fails loudly, and the fixed
  declaration passes;
- the repo gate: `tools/graft_check.py all` exits 0 over the real
  package — lint clean vs baseline, >= 6 entry points audited over
  tp2 + dp2x2 mesh shapes, markers consistent (the tier-1 CI wiring).
"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from megatron_llm_tpu.analysis import audit as audit_mod
from megatron_llm_tpu.analysis import lint
from megatron_llm_tpu.analysis.contracts import (
    CompileContract,
    ContractViolation,
    compile_contract,
    jit_cache_size,
    record_variant,
    register_contract,
    release_variant,
    variant_count,
    variants,
)

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "fixtures", "lint")
_BASELINE = os.path.join(_REPO, "megatron_llm_tpu", "analysis",
                         "lint_baseline.json")

# rule -> package_scope for its fixtures: GR007 (unregistered jit entry)
# only applies inside megatron_llm_tpu/, everything else is scope-free
_RULES = ["GR001", "GR002", "GR003", "GR004", "GR005", "GR006", "GR007"]
_SCOPED = {"GR007"}


def _read_fixture(name):
    with open(os.path.join(_FIXTURES, name), "r", encoding="utf-8") as fh:
        return fh.read()


def _lint_fixture(name, rule, monkeypatch):
    src = _read_fixture(name)
    if rule == "GR006":
        # the hot-path list is repo-config; scope the fixture's method
        # hot the same way engine/trainer methods are
        monkeypatch.setitem(lint.HOT_PATHS, name, {"Engine.serve_round"})
    findings = lint.lint_source(src, name,
                                package_scope=rule in _SCOPED)
    marked = {i for i, ln in enumerate(src.splitlines(), 1)
              if "# LINT" in ln}
    return findings, marked


class TestLintRules:
    @pytest.mark.parametrize("rule", _RULES)
    def test_bad_fixture_fires_exactly_on_marked_lines(
            self, rule, monkeypatch):
        name = f"{rule.lower()}_bad.py"
        findings, marked = _lint_fixture(name, rule, monkeypatch)
        got = {f.line for f in findings if f.rule == rule}
        assert got == marked, (
            f"{rule} fired on {sorted(got)}, fixture marks "
            f"{sorted(marked)}")
        # fixture purity: the bad fixture trips ONLY its own rule, so a
        # rule regression can never hide behind a neighbor's finding
        assert {f.rule for f in findings} == {rule}, [
            f.to_dict() for f in findings]

    @pytest.mark.parametrize("rule", _RULES)
    def test_good_fixture_stays_quiet(self, rule, monkeypatch):
        name = f"{rule.lower()}_good.py"
        findings, _ = _lint_fixture(name, rule, monkeypatch)
        assert findings == [], [f.to_dict() for f in findings]

    def test_gr006_span_emission_fixtures(self, monkeypatch):
        """ISSUE 13: telemetry emission on a hot round/step path must be
        pure host bookkeeping. The bad fixture syncs the device to
        decorate its spans/events (fires exactly on the marked lines);
        the good fixture is the telemetry/ package's pattern — clock
        reads + ring appends on already-fetched host scalars (quiet)."""
        hot = {"Tracer.complete", "Recorder.record"}
        for name, expect_fire in (("gr006_span_bad.py", True),
                                  ("gr006_span_good.py", False)):
            src = _read_fixture(name)
            monkeypatch.setitem(lint.HOT_PATHS, name, hot)
            findings = lint.lint_source(src, name)
            marked = {i for i, ln in enumerate(src.splitlines(), 1)
                      if "# LINT" in ln}
            got = {f.line for f in findings if f.rule == "GR006"}
            if expect_fire:
                assert got == marked and marked, (
                    f"{name}: GR006 fired on {sorted(got)}, marks "
                    f"{sorted(marked)}")
                assert {f.rule for f in findings} == {"GR006"}, [
                    f.to_dict() for f in findings]
            else:
                assert findings == [], [f.to_dict() for f in findings]

    def test_gr006_cost_accounting_fixtures(self, monkeypatch):
        """ISSUE 15: per-round/per-retire device-cost bookkeeping must
        be pure host arithmetic — the mint-time registry record exists
        so pricing a round never costs a transfer. The bad fixture
        fetches device values to price rounds/requests (fires exactly
        on the marked lines); the good fixture is the
        CostRegistry.record / engine._request_cost pattern — dict
        lookups and host-mirror indexing (quiet)."""
        hot = {"CostBook.note_round", "CostBook.request_cost"}
        for name, expect_fire in (("gr006_cost_bad.py", True),
                                  ("gr006_cost_good.py", False)):
            src = _read_fixture(name)
            monkeypatch.setitem(lint.HOT_PATHS, name, hot)
            findings = lint.lint_source(src, name)
            marked = {i for i, ln in enumerate(src.splitlines(), 1)
                      if "# LINT" in ln}
            got = {f.line for f in findings if f.rule == "GR006"}
            if expect_fire:
                assert got == marked and marked, (
                    f"{name}: GR006 fired on {sorted(got)}, marks "
                    f"{sorted(marked)}")
                assert {f.rule for f in findings} == {"GR006"}, [
                    f.to_dict() for f in findings]
            else:
                assert findings == [], [f.to_dict() for f in findings]

    def test_telemetry_emit_sites_are_hot_paths(self):
        """The GR006 scope covers the telemetry emit sites (ISSUE 13):
        a device sync added to span/event/histogram emission — code
        that runs per round/step — must fail the lint gate, and the
        real modules must currently be clean under that scope."""
        for path, needed in (
            ("megatron_llm_tpu/telemetry/trace.py",
             {"SpanTracer.complete", "SpanTracer.instant",
              "_Span.__exit__"}),
            ("megatron_llm_tpu/telemetry/recorder.py",
             {"FlightRecorder.record"}),
            ("megatron_llm_tpu/telemetry/prometheus.py",
             {"Histogram.observe"}),
            ("megatron_llm_tpu/inference/engine.py",
             {"DecodeEngine.step", "DecodeEngine._step_inner"}),
        ):
            assert needed <= lint.HOT_PATHS.get(path, set()), (
                path, needed)
        findings = lint.lint_paths(
            [os.path.join(_REPO, "megatron_llm_tpu", "telemetry", f)
             for f in ("trace.py", "recorder.py", "prometheus.py")],
            _REPO)
        assert [f for f in findings if f.rule == "GR006"] == [], [
            f.to_dict() for f in findings]

    def test_finding_keys_are_line_number_free(self):
        """Pure code motion (leading blank lines) must not churn the
        baseline: keys carry qualname+detail+ordinal, never line."""
        src = _read_fixture("gr001_bad.py")
        k1 = {f.key for f in lint.lint_source(src, "m.py")}
        k2 = {f.key for f in lint.lint_source("\n\n\n\n" + src, "m.py")}
        assert k1 == k2
        assert k1  # non-vacuous

    def test_duplicate_details_get_ordinals(self):
        """Two findings with the same (rule, qualname, detail) stay
        distinct baseline keys via #ordinal."""
        src = ("import jax, numpy as np\n"
               "@jax.jit\n"
               "def f(x):\n"
               "    return np.asarray(x) + np.asarray(x)\n")
        keys = sorted(f.key for f in lint.lint_source(src, "m.py"))
        assert keys == ["GR001:m.py:f:np.asarray#0",
                        "GR001:m.py:f:np.asarray#1"]


class TestBaseline:
    def test_empty_justification_rejected(self, tmp_path):
        p = tmp_path / "b.json"
        p.write_text(json.dumps({"entries": [
            {"key": "GR001:x.py:f:.item()#0", "justification": "   "}]}))
        with pytest.raises(ValueError, match="justification"):
            lint.load_baseline(str(p))

    def test_missing_file_is_empty_baseline(self, tmp_path):
        assert lint.load_baseline(str(tmp_path / "nope.json")) == {}

    def test_new_accepted_stale_split(self, monkeypatch):
        findings, _ = _lint_fixture("gr001_bad.py", "GR001", monkeypatch)
        first = findings[0]
        baseline = {first.key: "accepted for the test",
                    "GR001:gone.py:f:.item()#0": "code is gone"}
        new, accepted, stale = lint.apply_baseline(findings, baseline)
        assert first in accepted and first not in new
        assert set(new) == set(findings) - {first}
        # stale keys FAIL the gate: the baseline can only shrink honestly
        assert stale == ["GR001:gone.py:f:.item()#0"]


class TestContractRegistry:
    def test_budget_violation_raises_at_mint_time(self):
        register_contract(CompileContract("test.sa.budget", max_variants=2))
        owner = DummyOwner()
        assert record_variant("test.sa.budget", "a", owner=owner)
        assert record_variant("test.sa.budget", "b", owner=owner)
        # re-minting a live key is a cache hit, not a new variant
        assert not record_variant("test.sa.budget", "a", owner=owner)
        with pytest.raises(ContractViolation, match="declared budget of 2"):
            record_variant("test.sa.budget", "c", owner=owner)

    def test_release_uncounts_live_variants(self):
        register_contract(CompileContract("test.sa.lru", max_variants=2))
        owner = DummyOwner()
        record_variant("test.sa.lru", 1, owner=owner)
        record_variant("test.sa.lru", 2, owner=owner)
        # the LRU-eviction path: release makes room for the next mint
        assert release_variant("test.sa.lru", 1, owner=owner)
        assert not release_variant("test.sa.lru", 1, owner=owner)
        record_variant("test.sa.lru", 3, owner=owner)
        assert variants("test.sa.lru", owner=owner) == {2, 3}

    def test_owners_are_isolated(self):
        register_contract(CompileContract("test.sa.owners", max_variants=1))
        a, b = DummyOwner(), DummyOwner()
        record_variant("test.sa.owners", "x", owner=a)
        # a second ENGINE minting the same entry point has its own budget
        record_variant("test.sa.owners", "x", owner=b)
        assert variant_count("test.sa.owners", owner=a) == 1
        assert variant_count("test.sa.owners", owner=b) == 1

    def test_call_site_budget_tightens_declared_max(self):
        register_contract(CompileContract("test.sa.tight", max_variants=8))
        owner = DummyOwner()
        record_variant("test.sa.tight", 1, owner=owner, budget=1)
        with pytest.raises(ContractViolation, match="budget of 1"):
            record_variant("test.sa.tight", 2, owner=owner, budget=1)

    def test_decorator_registers_and_records(self):
        built = []

        @compile_contract("test.sa.builder", max_variants=2)
        def make_fn(width, greedy=True):
            built.append((width, greedy))
            return lambda x: x

        make_fn(4)
        # auto key = the hashable primitive args actually PASSED (the
        # jit statics); defaults don't appear, explicit kwargs do
        assert variants("test.sa.builder") == {(4,)}
        make_fn(8, contract_key=("explicit", 8))
        assert ("explicit", 8) in variants("test.sa.builder")
        with pytest.raises(ContractViolation):
            make_fn(16)
        assert built == [(4, True), (8, True), (16, True)]

    def test_unknown_collective_opcode_rejected(self):
        with pytest.raises(ValueError, match="unknown collective"):
            CompileContract("test.sa.badop", collectives={
                "single": frozenset({"all-shuffle"})})

    def test_unregistered_name_is_loud(self):
        with pytest.raises(KeyError, match="no compile contract"):
            record_variant("test.sa.never-registered", 1)

    def test_jit_cache_size_counts_executables(self):
        fn = jax.jit(lambda x: x + 1)
        assert jit_cache_size(fn) == 0
        fn(jnp.zeros((2,), jnp.float32))
        assert jit_cache_size(fn) == 1
        fn(jnp.zeros((2,), jnp.float32))  # cache hit
        assert jit_cache_size(fn) == 1
        fn(jnp.zeros((3,), jnp.float32))  # new shape -> new executable
        assert jit_cache_size(fn) == 2


class DummyOwner:
    """Weakref-able stand-in for an engine/trainer owner."""


class TestAudit:
    def test_collectives_in_text(self):
        text = ("%all-reduce.7 = f32[4]{0} all-reduce(%p), ...\n"
                "%ag = f32[8]{0} all-gather(%q)\n"
                "  no collective-permute here: the word permute alone\n")
        assert audit_mod.collectives_in_text(text) == frozenset(
            {"all-reduce", "all-gather", "collective-permute"})
        assert audit_mod.collectives_in_text("%add = f32[] add(a, b)") \
            == frozenset()

    def test_deliberate_collective_break_fails_loudly(self):
        """THE acceptance-criterion test: declare an empty collective
        inventory, lower a psum — the audit must fail with the mismatch
        named; fixing the declaration makes the same lowering pass."""
        from jax.sharding import PartitionSpec as P

        register_contract(CompileContract(
            "test.sa.break", collectives={"single": frozenset()}))
        mesh = jax.make_mesh((2,), ("x",))
        fn = jax.jit(jax.shard_map(
            lambda x: jax.lax.psum(x, "x"),
            mesh=mesh, in_specs=P("x"), out_specs=P()))
        arg = jnp.zeros((4,), jnp.float32)

        res = audit_mod.audit_lowered("test.sa.break", "single", fn, (arg,))
        assert not res.ok
        assert any("collective inventory mismatch" in f
                   for f in res.failures), res.failures
        assert "all-reduce" in res.facts["collectives"]

        # the fix: declare what the artifact actually contains
        register_contract(CompileContract(
            "test.sa.break",
            collectives={"single": frozenset({"all-reduce"})}))
        res2 = audit_mod.audit_lowered(
            "test.sa.break", "single", fn, (arg,))
        assert res2.ok, res2.failures

    def test_undeclared_mesh_tag_fails(self):
        register_contract(CompileContract(
            "test.sa.mesh", collectives={"single": frozenset()}))
        fn = jax.jit(lambda x: x * 2.0)
        res = audit_mod.audit_lowered(
            "test.sa.mesh", "tp2", fn, (jnp.zeros((2,), jnp.float32),))
        assert not res.ok
        assert any("not declared" in f for f in res.failures)

    def test_tmp_bytes_budget_break(self):
        """A 1-byte budget against a matmul whose intermediate must
        materialize: the audit reports the measured temp bytes."""
        register_contract(CompileContract(
            "test.sa.tmp", tmp_bytes_budget=1))
        fn = jax.jit(lambda x: (x @ x).sum())
        res = audit_mod.audit_lowered(
            "test.sa.tmp", "single", fn,
            (jnp.ones((64, 64), jnp.float32),))
        assert not res.ok
        assert any("exceeds the declared budget" in f
                   for f in res.failures), res.failures
        assert res.facts["temp_bytes"] > 1

    def test_host_callback_detected(self):
        register_contract(CompileContract("test.sa.cb"))
        fn = jax.jit(lambda x: jax.pure_callback(
            np.sin, jax.ShapeDtypeStruct(x.shape, x.dtype), x))
        res = audit_mod.audit_lowered(
            "test.sa.cb", "single", fn, (jnp.zeros((4,), jnp.float32),))
        assert not res.ok
        assert any("host callbacks" in f for f in res.failures)
        # ... and allowed when the contract says so, with justification
        register_contract(CompileContract(
            "test.sa.cb", allow_host_callbacks=True))
        res2 = audit_mod.audit_lowered(
            "test.sa.cb", "single", fn, (jnp.zeros((4,), jnp.float32),))
        assert res2.ok, res2.failures

    def test_f64_detected(self):
        register_contract(CompileContract("test.sa.f64"))
        with jax.enable_x64():
            fn = jax.jit(lambda x: x.astype(jnp.float64) * 2.0)
            res = audit_mod.audit_lowered(
                "test.sa.f64", "single", fn,
                (jnp.zeros((4,), jnp.float32),))
        assert not res.ok
        assert any("fp64" in f for f in res.failures)
        assert res.facts["f64"] is True

    def test_marker_consistency_check(self, tmp_path):
        # registers the engine contracts the real marker scan relies on
        import megatron_llm_tpu.inference.engine  # noqa: F401

        pkg = tmp_path / "megatron_llm_tpu"
        pkg.mkdir()
        (pkg / "ok.py").write_text(
            "# graft-contract: engine.decode_scan\nx = 1\n")
        (pkg / "bogus.py").write_text(
            "# graft-contract: no.such.contract\ny = 2\n")
        problems = audit_mod.check_contract_markers(str(tmp_path))
        assert len(problems) == 1
        assert "no.such.contract" in problems[0]
        assert "bogus.py" in problems[0]


class TestRepoGate:
    def test_repo_lint_clean_vs_baseline(self):
        """Pass 1 over the REAL package: no new findings, no stale
        baseline keys. A failure here prints the keys to baseline (with
        justification) or the entries to delete."""
        findings = lint.lint_paths(lint.default_paths(_REPO), _REPO)
        baseline = lint.load_baseline(_BASELINE)
        new, accepted, stale = lint.apply_baseline(findings, baseline)
        assert not new, "\n".join(
            f"{f.key}\n  {f.path}:{f.line} {f.message}" for f in new)
        assert not stale, stale
        assert accepted, "baseline unexpectedly empty"

    def test_hot_paths_cover_live_code(self):
        """GR006's hot-path list must name real methods — a rename that
        silently un-scopes the engine round loop would turn the rule
        into a no-op."""
        for rel, quals in lint.HOT_PATHS.items():
            path = os.path.join(_REPO, rel)
            assert os.path.exists(path), rel
            with open(path, "r", encoding="utf-8") as fh:
                src = fh.read()
            for q in quals:
                meth = q.rsplit(".", 1)[-1]
                assert f"def {meth}(" in src, (
                    f"HOT_PATHS names {q} but {rel} has no def {meth}")

    def test_graft_check_gate(self, tmp_path):
        """The tier-1 CI wiring: the gate tool itself, all THREE passes
        (lint + audit + costs, ISSUE 15) PLUS the folded go/no-go
        verdict (ROADMAP 5c), over the real repo, under
        JAX_PLATFORMS=cpu — exit 0, >= 6 entry points audited,
        collective inventories pinned on >= 2 mesh shapes, markers
        consistent, KNOWN_FAILURES.md linked + present, the
        compiled-cost diff clean against the checked-in baseline, and
        the verdict object naming every gate GO."""
        out = tmp_path / "report.json"
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        proc = subprocess.run(
            [sys.executable,
             os.path.join(_REPO, "tools", "graft_check.py"),
             "verdict", "--json", str(out)],
            capture_output=True, text=True, timeout=420, env=env,
            cwd=_REPO)
        assert proc.returncode == 0, proc.stdout + "\n" + proc.stderr
        report = json.loads(out.read_text())
        assert report["ok"]
        # the folded per-PR go/no-go object: every gate named, GO, no
        # reasons
        v = report["verdict"]
        assert v["verdict"] == "GO" and v["ok"], v
        assert v["gates"] == {"lint": True, "audit": True,
                              "costs": True}
        assert v["reasons"] == []
        assert "-> GO" in proc.stdout
        assert report["lint"]["ok"] and not report["lint"]["new"]
        aud = report["audit"]
        assert len(aud["entry_points_audited"]) >= 6, \
            aud["entry_points_audited"]
        assert {"tp2", "dp2tp2"} <= set(aud["mesh_tags"])
        assert all(t["ok"] for t in aud["targets"])
        assert not aud["marker_problems"]
        # train.step's inventory is PINNED on both forecast meshes
        pinned = {(t["contract"], t["mesh"]): t["facts"]["collectives"]
                  for t in aud["targets"]}
        # (jax 0.9.0 serves the vocab-parallel embedding lookup by
        # all-reduce; the old build's all-gather is gone — train_step.py)
        assert pinned[("train.step", "tp2")] == ["all-reduce"]
        assert pinned[("train.step", "dp2tp2")] == ["all-reduce"]
        # the honest-triage doc the report links must be checked in
        assert aud["known_failures"] == "KNOWN_FAILURES.md"
        assert os.path.exists(os.path.join(_REPO, "KNOWN_FAILURES.md"))
        # compiled-cost regression gate (ISSUE 15): clean vs baseline,
        # with real per-contract FLOPs rows on both hot-path families
        costs = report["costs"]
        assert costs["ok"], costs
        assert not costs["regressions"] and not costs["missing_keys"] \
            and not costs["stale_keys"]
        assert any(k.startswith("engine.") for k in costs["rows"])
        assert "train.step[dp2]" in costs["rows"]
        assert costs["rows"]["train.step[dp2]"]["flops"] > 0
        # the +costs / cost-registry parity rows lowered and passed
        tags = {(t["contract"], t["mesh"]) for t in aud["targets"]
                if t["facts"].get("costs")}
        assert ("train.step", "dp2+costs") in tags
        assert ("engine.decode_scan", "single") in {
            (c, m) for c, m in tags if c.startswith("engine.")} or any(
            c == "engine.decode_scan" for c, _ in tags)

    def test_cost_gate_fails_on_injected_regression(self, tmp_path):
        """ISSUE 15 acceptance: a deliberately injected per-contract
        FLOPs/temp-bytes regression — simulated by halving the
        baseline's pinned values, exactly what the checked-in file
        would look like if an entry point's compiled cost silently
        doubled — fails `graft_check.py costs` loudly. Also: a stale
        baseline key (an audited row that no longer exists) fails, the
        same only-shrinks-honestly workflow as the lint baseline. Runs
        run_costs directly against a synthetic audit report built FROM
        the checked-in baseline (a clean world by construction), no
        subprocess needed."""
        from tools.graft_check import (
            COST_BASELINE,
            load_cost_baseline,
            run_costs,
        )

        base = load_cost_baseline(COST_BASELINE)
        # a fake audit report whose rows ARE the baseline (a clean
        # world), then inject the regression baseline-side
        rows = {k: {"flops": e["flops"], "temp_bytes": e["temp_bytes"]}
                for k, e in base.items()}
        fake_report = {"targets": [
            {"contract": k.split("[")[0],
             "mesh": k.split("[")[1].rstrip("]"),
             "ok": True,
             "facts": {"flops": v["flops"],
                       "temp_bytes": v["temp_bytes"]}}
            for k, v in rows.items()]}
        clean = run_costs(fake_report, baseline_path=COST_BASELINE)
        assert clean["ok"], clean

        injected = {"_comment": [], "entries": []}
        for k, e in base.items():
            entry = dict(e)
            injected["entries"].append(entry)
        # halve one engine row's flops and one train row's temp bytes:
        # current measurements are now a >=2x "regression" vs baseline
        eng_key = next(k for k in rows if k.startswith("engine."))
        trn_key = next(k for k in rows if k.startswith("train.step"))
        for entry in injected["entries"]:
            if entry["key"] == eng_key:
                entry["flops"] = max(entry["flops"] // 2, 1)
            if entry["key"] == trn_key and entry.get("temp_bytes"):
                entry["temp_bytes"] = max(entry["temp_bytes"] // 2, 1)
        p = tmp_path / "cost_baseline.json"
        p.write_text(json.dumps(injected))
        bad = run_costs(fake_report, baseline_path=str(p))
        assert not bad["ok"]
        assert any(eng_key in r and "flops" in r
                   for r in bad["regressions"]), bad["regressions"]
        assert any(trn_key in r and "temp_bytes" in r
                   for r in bad["regressions"]), bad["regressions"]
        # stale-key workflow: a baseline entry whose audited row is gone
        injected["entries"].append(
            {"key": "engine.retired_contract[single]", "flops": 1,
             "temp_bytes": 1, "justification": "x"})
        p.write_text(json.dumps(injected))
        stale = run_costs(fake_report, baseline_path=str(p))
        assert "engine.retired_contract[single]" in stale["stale_keys"]
        # missing-key workflow: a new audited row the baseline lacks
        fake_report["targets"].append(
            {"contract": "engine.new_entry", "mesh": "single",
             "ok": True, "facts": {"flops": 10, "temp_bytes": 10}})
        missing = run_costs(fake_report, baseline_path=str(p))
        assert "engine.new_entry[single]" in missing["missing_keys"]
        # justification discipline: the loader rejects empty ones
        p.write_text(json.dumps({"entries": [
            {"key": "x[y]", "flops": 1, "temp_bytes": 1,
             "justification": "  "}]}))
        with pytest.raises(ValueError, match="justification"):
            load_cost_baseline(str(p))

    def test_verdict_folds_gates(self):
        """ROADMAP 5c acceptance, pure-function half: build_verdict
        turns the section reports into the one go/no-go object — any
        failing gate is NO-GO with a reason naming it."""
        from tools.graft_check import build_verdict

        clean = {
            "lint": {"ok": True, "new": [], "stale_baseline_keys": []},
            "audit": {"ok": True, "targets": [],
                      "marker_problems": []},
            "costs": {"ok": True, "regressions": [],
                      "missing_keys": [], "stale_keys": []},
        }
        v = build_verdict(clean)
        assert v["verdict"] == "GO" and not v["reasons"]
        # one failed gate => NO-GO with a reason that names it
        broken = dict(clean, costs={
            "ok": False, "regressions": ["train.step[dp2]: flops …"],
            "missing_keys": [], "stale_keys": []})
        v = build_verdict(broken)
        assert v["verdict"] == "NO-GO" and not v["gates"]["costs"]
        assert any("costs" in r for r in v["reasons"])


class TestOnePagedEntryPoint:
    """ISSUE 18's structural guarantee: `ops/` exposes exactly ONE
    paged-attention entry point. The six-way fork collapsed into
    `ragged_paged_attention`; this guard keeps a seventh variant from
    growing back under a new name."""

    def test_ops_exposes_exactly_one_paged_attention_entry(self):
        import ast

        ops_dir = os.path.join(_REPO, "megatron_llm_tpu", "ops")
        public_paged = []
        for fname in sorted(os.listdir(ops_dir)):
            if not fname.endswith(".py"):
                continue
            with open(os.path.join(ops_dir, fname), encoding="utf-8") as fh:
                tree = ast.parse(fh.read(), filename=fname)
            for node in tree.body:
                if not isinstance(node, (ast.FunctionDef,
                                         ast.AsyncFunctionDef)):
                    continue
                name = node.name
                if name.startswith("_"):
                    continue
                if "paged" in name and ("attention" in name
                                        or "prefill" in name
                                        or "decode" in name):
                    public_paged.append(f"{fname}:{name}")
        assert public_paged == [
            "prefill_attention.py:ragged_paged_attention"], public_paged

    def test_retired_kernel_names_are_gone(self):
        """The replaced entry points must not linger anywhere in the
        package — a stale import would resurrect the fork silently."""
        retired = ("paged_decode_attention", "ragged_paged_prefill",
                   "ragged_prefill_block", "paged_decode_attn_block",
                   "_xla_paged_decode", "_xla_ragged_prefill")
        pkg = os.path.join(_REPO, "megatron_llm_tpu")
        hits = []
        for root, _, files in os.walk(pkg):
            for fname in files:
                if not fname.endswith(".py"):
                    continue
                path = os.path.join(root, fname)
                with open(path, encoding="utf-8") as fh:
                    src = fh.read()
                for name in retired:
                    if name in src:
                        hits.append(f"{os.path.relpath(path, _REPO)}: "
                                    f"{name}")
        assert not hits, hits

    def test_ops_exports_the_one_entry(self):
        from megatron_llm_tpu import ops

        assert hasattr(ops, "ragged_paged_attention")
        assert hasattr(ops, "ragged_paged_block")
        for legacy in ("paged_decode_attention", "ragged_paged_prefill"):
            assert not hasattr(ops, legacy), legacy
