"""Self-driving fleet (ISSUE 20): chaos matrix, in-flight recovery,
replace cycles, load-adaptive scaling.

Pinned here:
- ChaosPolicy: the --chaos spec grammar (unknown keys fail loudly),
  seeded determinism (same seed -> same probe-drop sequence), the
  kill arming rule, and the metadata-only hand-off corruption;
- in-flight request recovery over scripted replicas: a replica death
  transparently resubmits queued/un-streamed requests to a healthy
  replica and the retried token streams are BITWISE the no-death
  oracle's; partially-streamed requests fail LOUDLY (the error names
  the streamed count + Retry-After) and the stream closes — never
  hangs; deadline-shed and cancelled requests are not resurrected;
- probe hardening: HTTPReplica's re-probe interval doubles per
  consecutive failure (capped), resets on success, and surfaces as
  the router_reprobe_backoff_s gauge;
- corrupt KV hand-off degrades (local prefill on the decode replica,
  serve_handoff_rejected counter) instead of failing the request;
- eviction events carry the condemned replica's flight-record dump
  path (ROADMAP 5a correlation);
- FleetController: poison + sentinel-trip replace cycles (condemn ->
  drain -> stop -> spawn warmed replacement -> rotate back in,
  serve_fleet_replaced counter), scale-up/down with hysteresis (no
  flap inside the dead band or on alternating verdicts), and scale
  decisions REPLAYABLE from their recorded inputs alone;
- off-by-default invisibility: an unmanaged, non-recovering router
  keeps the legacy /metrics and flight_record schemas byte-shape;
- (slow) kill-a-real-replica convergence: zero failed requests,
  chaos-run streams bitwise vs the no-chaos oracle, the controller's
  replace event carries the recovery time.
"""

import queue as queue_mod
import threading
import time

import pytest

from megatron_llm_tpu.inference.chaos import ChaosFault, ChaosPolicy
from megatron_llm_tpu.inference.engine import QueueFull
from megatron_llm_tpu.inference.fleet import FleetController
from megatron_llm_tpu.inference.router import (
    EngineReplica,
    FleetUnavailable,
    HTTPReplica,
    ReplicaRouter,
)


def oracle_tokens(prompt, n):
    """What ANY healthy scripted replica generates for a prompt —
    deterministic in the prompt alone, like a greedy engine."""
    return [(sum(prompt) + i) % 251 for i in range(n)]


class ScriptedReq:
    """EngineRequest-shaped scripted request."""

    def __init__(self, rid, replica_id, prompt, n, kw):
        self.rid = rid
        self.replica_id = replica_id
        self._prompt = list(prompt)
        self._n = n
        self.tokens = []
        self.log_probs = []
        self.return_log_probs = bool(kw.get("return_log_probs"))
        self.error = None
        self.timed_out = False
        self.cancelled = False
        self.done = threading.Event()
        self.stream_q = (queue_mod.SimpleQueue() if kw.get("stream")
                         else None)
        self.t_submit = time.perf_counter()
        self.t_first = 0.0
        self.t_done = 0.0

    def finish_ok(self):
        for t in oracle_tokens(self._prompt, self._n):
            self.tokens.append(t)
            if self.stream_q is not None:
                self.stream_q.put(t)
        self.t_first = self.t_done = time.perf_counter()
        self.done.set()
        if self.stream_q is not None:
            self.stream_q.put(None)

    def stream_some(self, k):
        """Stream the first k tokens WITHOUT finishing."""
        for t in oracle_tokens(self._prompt, self._n)[:k]:
            self.tokens.append(t)
            self.stream_q.put(t)

    def fail(self, msg, timed_out=False):
        self.error = msg
        self.timed_out = timed_out
        self.done.set()
        if self.stream_q is not None:
            self.stream_q.put(None)

    def result(self, timeout=None):
        if not self.done.wait(timeout):
            raise TimeoutError("scripted request still running")
        if self.timed_out:
            raise TimeoutError(self.error)
        if self.error is not None:
            raise RuntimeError(self.error)
        return self.tokens, (self.log_probs if self.return_log_probs
                             else None)


class FleetReplica:
    """Scripted replica for the fleet tests: deterministic greedy
    results, a die() that fails pending requests through the engine
    poison-path error shape, sentinel/backlog knobs."""

    def __init__(self, rid, load=0, auto_finish=True, dump_path=None):
        self.replica_id = rid
        self._load = load
        self._alive = True
        self._broken = None
        self.full = False
        self.auto_finish = auto_finish
        self.pending = []
        self.submits = []
        self.cancelled = []
        self.drained = 0
        self.stopped = []
        self.started = 0
        self.warmed = 0
        self.page_size = 16
        self.max_context = 64
        self.num_pages = 9
        self.perf_regressions = 0
        self.modeled_backlog = None  # seconds, or None = cannot model
        self.import_error = None  # ValueError to raise on import
        self.imports = []
        self._dump_path = dump_path
        self._next_rid = 0

    def submit(self, prompt, n, **kw):
        if self._broken is not None:
            raise RuntimeError(f"engine is stopped: {self._broken}")
        if self.full:
            raise QueueFull("queue full")
        self._next_rid += 1
        req = ScriptedReq(self._next_rid - 1, self.replica_id,
                          prompt, n, kw)
        self.submits.append(list(prompt))
        if self.auto_finish:
            req.finish_ok()
        else:
            self.pending.append(req)
        return req

    def die(self, msg="chaos: injected kill"):
        """The engine serve-loop poison path, scripted: _broken set,
        every pending waiter failed with the poison error shape."""
        self._broken = f"engine step failed: {msg}"
        self._alive = False
        for req in self.pending:
            if not req.done.is_set():
                req.fail(self._broken)
        self.pending = []

    def cancel(self, req):
        self.cancelled.append(req.rid)
        req.cancelled = True

    def health(self):
        return {"alive": self._alive, "broken": self._broken,
                "queue_depth": len(self.pending) + self._load,
                "slots_busy": 0}

    def load(self):
        return self._load

    def modeled_backlog_flops(self):
        return None

    def modeled_backlog_s(self):
        return self.modeled_backlog

    def counters(self):
        out = {"serve_replica_id": self.replica_id,
               "serve_admitted": len(self.submits)}
        if self.perf_regressions:
            out["serve_perf_regressions"] = self.perf_regressions
        return out

    def fleet_kv_pool_bytes(self):
        return 1000

    def histograms(self):
        return []

    def flight_record(self):
        return {"events": []}

    def last_dump_path(self):
        return self._dump_path

    def export_prefix(self, prompt):
        return {"pages": 2, "page_size": self.page_size,
                "tokens": list(prompt)}

    def import_prefix(self, payload):
        self.imports.append(dict(payload))
        if self.import_error is not None:
            raise self.import_error
        return {"pages": int(payload.get("pages", 0)), "registered": 1}

    def warmup(self):
        self.warmed += 1

    def start(self):
        self.started += 1

    def stop(self, drain=True):
        self.stopped.append(drain)
        self._alive = False

    def drain(self):
        self.drained += 1


class TestChaosPolicy:
    def test_parse_grammar(self):
        p = ChaosPolicy.parse(
            "kill=1@8, stall=0:5.5x3, submit_latency_ms=2, "
            "probe_latency_ms=1.5, probe_drop=0.25@2, "
            "corrupt_handoff, seed=7")
        assert p.kill_replica == 1 and p.kill_after_submits == 8
        assert p.stall_replica == 0 and p.stall_ms == 5.5
        assert p.stall_rounds == 3
        assert p.submit_latency_ms == 2.0
        assert p.probe_latency_ms == 1.5
        assert p.probe_drop_rate == 0.25 and p.probe_drop_replica == 2
        assert p.corrupt_handoff is True
        assert p.seed == 7

    def test_parse_unknown_key_fails_loudly(self):
        with pytest.raises(ValueError, match="unknown fault"):
            ChaosPolicy.parse("kil=1")

    def test_parse_bad_rate_rejected(self):
        with pytest.raises(ValueError, match="probe_drop_rate"):
            ChaosPolicy.parse("probe_drop=1.5")

    def test_probe_drops_are_seeded_deterministic(self):
        a = ChaosPolicy(seed=3, probe_drop_rate=0.5)
        b = ChaosPolicy(seed=3, probe_drop_rate=0.5)
        seq_a = [a.on_probe(0) for _ in range(32)]
        seq_b = [b.on_probe(0) for _ in range(32)]
        assert seq_a == seq_b
        assert any(seq_a) and not all(seq_a)  # it actually drops some

    def test_kill_arms_after_n_submits_and_fires_once(self):
        p = ChaosPolicy(kill_replica=1, kill_after_submits=2)
        hook = p.engine_hook(1)
        assert not p.kill_armed(1)
        p.on_submit(1)
        assert not p.kill_armed(1)
        p.on_submit(1)
        assert p.kill_armed(1)
        assert not p.kill_armed(0)  # wrong replica never arms
        with pytest.raises(ChaosFault, match="chaos: injected kill"):
            hook(None)
        assert p.killed == [1]
        hook(None)  # already fired: a replacement engine is safe
        assert p.killed == [1]

    def test_stall_fires_exactly_k_rounds(self):
        p = ChaosPolicy(stall_replica=0, stall_ms=1.0, stall_rounds=2)
        hook = p.engine_hook(0)
        for _ in range(5):
            hook(None)
        stalls = [e for e in p.events if e["kind"] == "stall"]
        assert len(stalls) == 2

    def test_corrupt_handoff_is_metadata_only_on_a_copy(self):
        p = ChaosPolicy()
        p.corrupt_handoff = True
        payload = {"pages": 2, "page_size": 16, "tokens": [1, 2]}
        bad = p.on_export(0, payload)
        assert bad["page_size"] == 17
        assert payload["page_size"] == 16  # donor payload untouched
        assert p.on_export(0, None) is None


class TestInFlightRecovery:
    def _fleet(self, **kw):
        r0 = FleetReplica(0, auto_finish=False)
        r1 = FleetReplica(1, load=5)  # load keeps dispatch on r0
        router = ReplicaRouter([r0, r1], recover_requests=True,
                               unhealthy_cooldown_s=60.0, **kw)
        return r0, r1, router

    def test_kill_mid_queue_resubmits_bitwise(self):
        r0, r1, router = self._fleet()
        prompts = [[2 + i] * 20 for i in range(3)]
        reqs = [router.submit(p, 4, top_k=1) for p in prompts]
        assert len(r0.pending) == 3  # all queued on r0
        r0.die()
        got = [r.result(timeout=10)[0] for r in reqs]
        assert got == [oracle_tokens(p, 4) for p in prompts]
        # every request finished on the healthy replica
        assert all(r.replica_id == 1 for r in reqs)
        stats = router.router_stats()
        assert stats["serve_resubmitted"] == 3

    def test_kill_before_stream_resubmits_transparently(self):
        r0, r1, router = self._fleet()
        p = [3] * 20
        req = router.submit(p, 4, top_k=1, stream=True)
        time.sleep(0.05)  # let the pump attach to r0's stream
        r0.die()
        toks = []
        while True:
            t = req.stream_q.get(timeout=10)
            if t is None:
                break
            toks.append(t)
        assert toks == oracle_tokens(p, 4)
        assert req.result(timeout=10)[0] == toks
        assert router.router_stats()["serve_resubmitted"] == 1

    def test_kill_mid_stream_fails_loudly_never_hangs(self):
        r0, r1, router = self._fleet()
        p = [4] * 20
        req = router.submit(p, 4, top_k=1, stream=True)
        inner = r0.pending[0]
        inner.stream_some(2)  # two tokens reach the client
        time.sleep(0.05)
        r0.die()
        toks = []
        while True:  # the stream CLOSES (None sentinel), never hangs
            t = req.stream_q.get(timeout=10)
            if t is None:
                break
            toks.append(t)
        assert toks == oracle_tokens(p, 4)[:2]
        with pytest.raises(RuntimeError) as ei:
            req.result(timeout=10)
        msg = str(ei.value)
        assert "2 token(s)" in msg
        assert "never resubmitted" in msg
        assert "Retry-After" in msg
        # loud failure is NOT a retry
        assert "serve_resubmitted" in router.router_stats()
        assert router.router_stats()["serve_resubmitted"] == 0

    def test_cancelled_request_is_not_resurrected(self):
        r0, r1, router = self._fleet()
        req = router.submit([5] * 20, 4, top_k=1)
        router.cancel(req)
        r0.die()
        with pytest.raises(RuntimeError):
            req.result(timeout=10)
        assert router.router_stats()["serve_resubmitted"] == 0

    def test_whole_fleet_death_surfaces_503_shape(self):
        r0, r1, router = self._fleet()
        req = router.submit([6] * 20, 4, top_k=1)
        r1.die()
        r0.die()
        # the resubmit finds no healthy replica: FleetUnavailable (a
        # QueueFull -> the HTTP 503 + Retry-After shape), not a hang
        with pytest.raises((FleetUnavailable, RuntimeError)):
            req.result(timeout=10)

    def test_resubmit_budget_bounds_retries(self):
        r0, r1, router = self._fleet(max_resubmits=0)
        req = router.submit([7] * 20, 4, top_k=1)
        r0.die()
        with pytest.raises(RuntimeError, match="engine step failed"):
            req.result(timeout=10)
        assert router.router_stats()["serve_resubmitted"] == 0

    def test_eviction_event_attaches_flight_dump(self):
        r0, r1, router = self._fleet()
        r0._dump_path = "/tmp/flight_record_engine-poison_1_1.json"
        req = router.submit([8] * 20, 4, top_k=1)
        r0.die()
        req.result(timeout=10)
        evs = router.evictions()
        assert evs and evs[0]["replica"] == 0
        assert evs[0]["flight_dump"] == r0._dump_path
        assert "engine step failed" in evs[0]["why"]
        assert router.flight_record()["evictions"] == evs


class TestProbeHardening:
    def _remote(self):
        rep = HTTPReplica(0, "http://test.invalid:1",
                          probe_ttl_s=0.05, probe_timeout_s=0.1,
                          probe_backoff_cap_s=0.4)

        def refuse(path, accept=None, timeout=None):
            raise ConnectionError("connection refused")

        rep._get_raw = refuse
        return rep

    def test_backoff_doubles_per_failure_and_caps(self):
        rep = self._remote()
        want = [0.05, 0.1, 0.2, 0.4, 0.4]  # ttl * 2^k, capped
        got = []
        for _ in want:
            rep._probe = (0.0, {})  # force an immediate re-probe
            h = rep.health()
            assert h["alive"] is False
            got.append(rep.reprobe_backoff_s())
        assert got == pytest.approx(want)

    def test_success_resets_backoff(self):
        import json

        rep = self._remote()
        rep._probe = (0.0, {})
        rep.health()
        assert rep.reprobe_backoff_s() > 0

        def ok(path, accept=None, timeout=None):
            if path == "/health":
                return json.dumps(
                    {"status": "ok",
                     "engine": {"alive": True, "broken": None,
                                "queue_depth": 0,
                                "slots_busy": 0}}).encode()
            return json.dumps({}).encode()

        rep._get_raw = ok
        rep._probe = (0.0, {})
        h = rep.health()
        assert h["alive"] is True
        assert rep.reprobe_backoff_s() == 0.0

    def test_backoff_stretches_snapshot_ttl(self):
        rep = self._remote()
        rep._probe = (0.0, {})
        rep.health()
        back = rep.reprobe_backoff_s()
        assert back > 0
        # within ttl + backoff the cached (unhealthy) snapshot serves
        # without re-probing: the fail streak must not advance
        streak = rep._fail_streak
        rep.health()
        assert rep._fail_streak == streak

    def test_router_reprobe_backoff_gauge(self):
        rep = self._remote()
        router = ReplicaRouter([rep])
        assert "router_reprobe_backoff_s" not in router.router_stats()
        rep._probe = (0.0, {})
        rep.health()
        stats = router.router_stats()
        assert stats["router_reprobe_backoff_s"] == pytest.approx(0.05)

    def test_chaos_probe_drop_counts_as_failure(self):
        import json

        chaos = ChaosPolicy(seed=0, probe_drop_rate=1.0)
        rep = HTTPReplica(0, "http://test.invalid:1",
                          probe_ttl_s=0.05, chaos=chaos)
        rep._get_raw = lambda *a, **k: json.dumps({}).encode()
        h = rep.health()
        assert h["alive"] is False
        assert "chaos: health probe dropped" in str(h["broken"])
        assert rep.reprobe_backoff_s() > 0


class TestCorruptHandoffDegrades:
    def test_corrupt_payload_degrades_to_local_prefill(self):
        pre = FleetReplica(0)
        dec = FleetReplica(1)
        dec.import_error = ValueError(
            "import_prefix: payload page_size 17 != pool page_size 16")
        router = ReplicaRouter(prefill_replicas=[pre],
                               decode_replicas=[dec],
                               disagg_min_prompt_pages=2)
        p = list(range(2, 40))  # >= 2 full pages -> two-stage path
        req = router.submit(p, 4, top_k=1)
        toks, _ = req.result(timeout=10)
        # the request SUCCEEDED (decode replica prefilled locally)
        assert toks == oracle_tokens(p, 4)
        assert len(dec.imports) == 1  # the splice was attempted...
        stats = router.router_stats()
        assert stats["serve_handoff_rejected"] == 1  # ...and refused
        # no pages counted as transferred
        assert stats["serve_transfer_pages"] == 0

    def test_clean_handoff_keeps_legacy_counters(self):
        pre = FleetReplica(0)
        dec = FleetReplica(1)
        router = ReplicaRouter(prefill_replicas=[pre],
                               decode_replicas=[dec],
                               disagg_min_prompt_pages=2)
        req = router.submit(list(range(2, 40)), 4, top_k=1)
        req.result(timeout=10)
        assert "serve_handoff_rejected" not in router.router_stats()


class TestFleetController:
    def _managed(self, spawn=True, **kw):
        r0 = FleetReplica(0)
        r1 = FleetReplica(1)
        router = ReplicaRouter([r0, r1], unhealthy_cooldown_s=60.0)
        spawned = []

        def spawn_replica(old):
            rep = FleetReplica(old.replica_id)
            spawned.append(rep)
            return rep

        ctl = FleetController(
            router, spawn_replica=spawn_replica if spawn else None,
            drain_timeout_s=0.5, **kw)
        return r0, r1, router, ctl, spawned

    def test_poison_verdict_runs_full_replace_cycle(self):
        r0, r1, router, ctl, spawned = self._managed()
        ctl.tick()  # healthy fleet: nothing happens
        assert not spawned
        r0._dump_path = "/tmp/flight_record_engine-poison_2_1.json"
        r0.die()
        ctl.tick()
        assert len(spawned) == 1
        new = spawned[0]
        # warmed BEFORE rotation back in, then started
        assert new.warmed == 1 and new.started == 1
        assert router._by_id[0] is new
        # the old replica was stopped and its dump rode the events
        assert r0.stopped
        evs = ctl.flight_events()
        rep_evs = [e for e in evs if e["kind"] == "replace"]
        assert len(rep_evs) == 1
        assert rep_evs[0]["flight_dump"] == r0._dump_path
        assert rep_evs[0]["recovery_s"] >= 0
        stats = router.router_stats()
        assert stats["serve_fleet_replaced"] == 1
        # the replacement is immediately routable
        req = router.submit([9] * 20, 2, top_k=1)
        req.result(timeout=10)
        assert len(new.submits) + len(r1.submits) >= 1

    def test_sentinel_trip_condemns_and_replaces(self):
        r0, r1, router, ctl, spawned = self._managed()
        ctl.tick()  # baseline snapshot: 0 regressions everywhere
        r0.perf_regressions = 1
        ctl.tick()
        assert len(spawned) == 1
        evs = [e for e in ctl.flight_events()
               if e["kind"] == "replace"]
        assert "sentinel" in evs[0]["why"]

    def test_condemn_only_without_spawn_callback(self):
        r0, r1, router, ctl, spawned = self._managed(spawn=False)
        r0.die()
        ctl.tick()
        ctl.tick()  # idempotent: no replace loop on later ticks
        evs = ctl.flight_events()
        assert [e["kind"] for e in evs] == ["condemn"]
        # the condemned replica never re-enters rotation
        req = router.submit([10] * 20, 2, top_k=1)
        req.result(timeout=10)
        assert req.replica_id == 1

    def test_decide_is_pure_and_threshold_correct(self):
        d = FleetController.decide
        assert d([20.0, 20.0], 2, 10.0, 1.0) == "up"
        assert d([0.1, 0.1], 2, 10.0, 1.0) == "down"
        assert d([5.0, 5.0], 2, 10.0, 1.0) == "hold"  # dead band
        assert d([20.0, None], 2, 10.0, 1.0) == "hold"  # partial model
        assert d([], 0, 10.0, 1.0) == "hold"
        assert d([20.0], 1, None, None) == "hold"  # scaling disabled

    def test_scale_up_down_with_hysteresis(self):
        r0, r1, router, ctl, spawned = self._managed(
            scale_up_backlog_s=10.0, scale_down_backlog_s=1.0,
            scale_patience=2, min_replicas=1, max_replicas=3,
            standby=[FleetReplica(2)])
        r0.modeled_backlog = r1.modeled_backlog = 20.0
        ctl.tick()  # streak 1: patience not met, no action
        assert len(router.replicas) == 2
        ctl.tick()  # streak 2: scale UP from standby
        assert len(router.replicas) == 3
        new = router._by_id[2]
        assert new.warmed == 1 and new.started == 1
        assert router.router_stats()["serve_scale_events"] == 1
        # now idle: consistent "down" verdicts shed one replica
        for rep in router.replicas:
            rep.modeled_backlog = 0.1
        ctl.tick()
        ctl.tick()
        assert len(router.replicas) == 2
        assert router.router_stats()["serve_scale_events"] == 2
        assert len(ctl.standby) == 1  # shed replica back on standby

    def test_no_flap_on_alternating_verdicts_or_dead_band(self):
        r0, r1, router, ctl, spawned = self._managed(
            scale_up_backlog_s=10.0, scale_down_backlog_s=1.0,
            scale_patience=2, standby=[FleetReplica(2)])
        # alternate up/down: the streak never reaches patience
        for backlog in (20.0, 0.1, 20.0, 0.1, 20.0, 0.1):
            r0.modeled_backlog = r1.modeled_backlog = backlog
            ctl.tick()
        assert len(router.replicas) == 2
        # steady load inside the dead band: hold forever
        r0.modeled_backlog = r1.modeled_backlog = 5.0
        for _ in range(5):
            ctl.tick()
        assert len(router.replicas) == 2
        assert router.router_stats()["serve_scale_events"] == 0

    def test_scale_decisions_replay_from_recorded_inputs(self):
        r0, r1, router, ctl, spawned = self._managed(
            scale_up_backlog_s=10.0, scale_down_backlog_s=1.0,
            scale_patience=2, standby=[FleetReplica(2)])
        for backlog in (20.0, 20.0, 0.1, 0.1, 5.0):
            for rep in router.replicas:
                rep.modeled_backlog = backlog
            ctl.tick()
        evs = [e for e in ctl.flight_events()
               if e["kind"] == "scale_decision"]
        assert len(evs) == 5
        for e in evs:  # the reproducibility bar: inputs -> verdict
            assert FleetController.decide(
                e["backlogs"], e["n_active"], e["up_threshold_s"],
                e["down_threshold_s"]) == e["verdict"]

    def test_scale_bounds_hold(self):
        r0, r1, router, ctl, spawned = self._managed(
            scale_up_backlog_s=10.0, scale_down_backlog_s=1.0,
            scale_patience=1, min_replicas=2, max_replicas=2)
        r0.modeled_backlog = r1.modeled_backlog = 20.0
        ctl.tick()
        assert len(router.replicas) == 2  # capped at max_replicas
        r0.modeled_backlog = r1.modeled_backlog = 0.1
        ctl.tick()
        assert len(router.replicas) == 2  # floored at min_replicas
        acted = [e["acted"] for e in ctl.flight_events()
                 if e["kind"] == "scale_decision"]
        assert acted == ["held:max_replicas", "held:min_replicas"]

    def test_dead_band_required(self):
        router = ReplicaRouter([FleetReplica(0)])
        with pytest.raises(ValueError, match="dead band"):
            FleetController(router, scale_up_backlog_s=1.0,
                            scale_down_backlog_s=2.0)

    def test_elastic_scaling_rejected_on_disagg(self):
        router = ReplicaRouter(prefill_replicas=[FleetReplica(0)],
                               decode_replicas=[FleetReplica(1)])
        with pytest.raises(ValueError, match="elastic"):
            router.add_replica(FleetReplica(2))
        with pytest.raises(ValueError, match="elastic"):
            router.remove_replica(1)


class TestOffByDefaultInvisibility:
    def test_unmanaged_router_keeps_legacy_schema(self):
        r0 = FleetReplica(0)
        router = ReplicaRouter([r0, FleetReplica(1)])
        req = router.submit([11] * 20, 2, top_k=1)
        assert isinstance(req, ScriptedReq)  # no recovery proxy
        stats = router.router_stats()
        for key in ("serve_resubmitted", "serve_fleet_replaced",
                    "serve_scale_events", "serve_handoff_rejected",
                    "router_reprobe_backoff_s"):
            assert key not in stats, key
        fr = router.flight_record()
        assert "evictions" not in fr
        assert "fleet" not in fr

    def test_chaos_none_leaves_engine_hook_uninstalled(self):
        class Eng:
            replica_id = 0
            page_size = 16
            max_context = 64
            num_pages = 9
            _fault_hook = None

        eng = Eng()
        EngineReplica(eng)
        assert eng._fault_hook is None
        EngineReplica(eng, chaos=ChaosPolicy(kill_replica=0))
        assert eng._fault_hook is not None


@pytest.mark.slow
class TestRealReplicaConvergence:
    """The ROADMAP acceptance bar on real engines: kill one replica of
    two under live traffic; the fleet converges with ZERO failed
    requests and bitwise streams vs the no-chaos oracle."""

    @pytest.fixture(scope="class")
    def tiny_model(self):
        import jax
        import jax.numpy as jnp

        from megatron_llm_tpu.config import tiny_config
        from megatron_llm_tpu.models import LlamaModel

        cfg = tiny_config(compute_dtype=jnp.float32,
                          use_decode_attn=False)
        model = LlamaModel(cfg)
        return model, model.init(jax.random.key(7))

    def test_kill_real_replica_zero_failed_requests(self, tiny_model):
        import jax
        import numpy as np

        from megatron_llm_tpu.inference.engine import DecodeEngine
        from megatron_llm_tpu.inference.fleet import FleetController
        from megatron_llm_tpu.inference.router import (
            EngineReplica,
            ReplicaRouter,
        )

        model, params = tiny_model
        devs = jax.devices()
        rs = np.random.RandomState(0)
        prompts = [list(rs.randint(2, 256, 24)) for _ in range(6)]

        def build(rid=None):
            over = {} if rid is None else dict(
                replica_id=rid, devices=[devs[rid]])
            return DecodeEngine(
                model, params, slots=2, page_size=16, max_context=96,
                max_queue=6, termination_id=None, vocab_size=256,
                prefill_chunk_tokens=16, prefix_cache=True,
                step_horizon=4, **over)

        # oracle: one plain engine, same traffic, no chaos
        oracle = build()
        oreqs = [oracle.submit(p, 8, top_k=1) for p in prompts]
        oracle.drain()
        want = [r.result(60)[0] for r in oreqs]

        # replica 0 dies through the engine's real poison path after 2
        # accepted submits; the controller rebuilds it on its device
        chaos = ChaosPolicy(seed=0, kill_replica=0, kill_after_submits=2)
        router = ReplicaRouter(
            [EngineReplica(build(i), chaos=chaos) for i in range(2)],
            recover_requests=True, unhealthy_cooldown_s=60.0)
        ctl = FleetController(
            router, check_interval_s=0.05, drain_timeout_s=5.0,
            spawn_replica=lambda old: EngineReplica(
                build(old.replica_id)))
        router.start()
        ctl.start()
        try:
            reqs = [router.submit(p, 8, top_k=1) for p in prompts]
            # zero failed requests: every result() returns
            got = [r.result(timeout=600.0)[0] for r in reqs]
            deadline = time.monotonic() + 120.0
            while (router.router_stats().get("serve_fleet_replaced", 0)
                   < 1 and time.monotonic() < deadline):
                time.sleep(0.1)
            stats = router.router_stats()
            replace_evs = [e for e in ctl.flight_events()
                           if e["kind"] == "replace"]
        finally:
            ctl.stop()
            router.stop(drain=True)
        assert got == want  # resubmitted streams bitwise the oracle's
        assert stats["serve_fleet_replaced"] == 1
        assert stats["serve_resubmitted"] >= 1
        assert max(e["recovery_s"] for e in replace_evs) > 0
