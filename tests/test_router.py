"""Prefix-affinity replica router (ISSUE 14).

Pinned here:
- routing policy units over scripted fake replicas (no device work):
  affinity hit routes to the indexed replica regardless of load;
  affinity miss falls back least-queue-depth; `affinity=False` takes
  the (seeded) fallback policy; a poisoned/503 replica leaves rotation
  (its index entries drop) and submit-time failures FAIL OVER to the
  next candidate; QueueFull surfaces only when every healthy replica's
  queue is full; stop(drain=True) drains every replica;
- the page-aligned affinity index: full pages only, capped at
  len(prompt) - 1 (mirroring PrefixCache registration), longest-match
  wins, LRU-bounded, drop_replica removes exactly that replica's
  entries;
- replica_id threading (the ISSUE 14 satellite): a tagged engine's
  counters() lead with serve_replica_id, its flight-recorder events
  carry replica=, EngineRequest.replica_id is stamped at submit, and
  the SSE `id:` field becomes "replica-rid" — while an UNTAGGED engine
  keeps every schema byte-compatible (test_telemetry pins the full
  legacy key list; here we pin the absence);
- fleet aggregation: additive counters sum, latency histograms merge
  by cumulative bucket (Histogram.merged), /health answers for the
  fleet;
- (slow) two real engine replicas end to end: affinity keeps a shared
  prefix on one replica whose PrefixCache then HITS, streams match the
  single-engine oracle.
"""

import threading
import time

import pytest

from megatron_llm_tpu.inference.engine import DecodeEngine, QueueFull
from megatron_llm_tpu.inference.router import (
    EngineReplica,
    PrefixAffinityIndex,
    ReplicaRouter,
)
from megatron_llm_tpu.telemetry import Histogram


class FakeReq:
    def __init__(self, rid, replica_id):
        self.rid = rid
        self.replica_id = replica_id


class FakeReplica:
    """Scripted replica: the protocol surface the router speaks, with
    load/health/queue-full knobs the tests flip."""

    def __init__(self, rid, load=0):
        self.replica_id = rid
        self._load = load
        self._alive = True
        self._broken = None
        self.full = False
        self.fail_submit = None  # exception to raise from submit
        self.submits = []
        self.cancelled = []
        self.drained = 0
        self.stopped = []
        self.page_size = 16
        self.max_context = 64
        self.num_pages = 9
        self._next_rid = 0

    def submit(self, prompt, n, **kw):
        if self.full:
            raise QueueFull("queue full")
        if self.fail_submit is not None:
            raise self.fail_submit
        self.submits.append(list(prompt))
        self._next_rid += 1
        return FakeReq(self._next_rid - 1, self.replica_id)

    def cancel(self, req):
        self.cancelled.append(req.rid)

    def health(self):
        return {"alive": self._alive, "broken": self._broken,
                "queue_depth": self._load, "slots_busy": 0}

    def load(self):
        return self._load

    def counters(self):
        return {"serve_replica_id": self.replica_id,
                "serve_admitted": len(self.submits),
                "serve_queue_depth": self._load,
                "serve_kv_pool_bytes": 1000,  # per-chip by contract
                "serve_ttft_p95_ms": 10.0 * (self.replica_id + 1)}

    def fleet_kv_pool_bytes(self):
        return 2000  # per-chip x an emulated tp=2 mesh

    def histograms(self):
        h = Histogram("serve_ttft_ms")
        for _ in range(self.replica_id + 1):
            h.observe(5.0)
        return [h]

    def flight_record(self):
        return {"events": []}

    def start(self):
        pass

    def stop(self, drain=True):
        self.stopped.append(drain)

    def drain(self):
        self.drained += 1


def _router(*reps, **kw):
    return ReplicaRouter(list(reps), **kw)


class TestAffinityIndex:
    def test_page_aligned_cap_and_longest_match(self):
        idx = PrefixAffinityIndex(4)
        p = list(range(17))  # 17 tokens -> (17-1)//4 = 4 full pages
        idx.register(p, 1)
        assert len(idx) == 4
        # full prompt matches all 4 pages
        assert idx.lookup(p) == (1, 4)
        # a prompt sharing 2 pages matches depth 2
        q = p[:8] + [99] * 9
        assert idx.lookup(q) == (1, 2)
        # sub-page prefix: no full page -> miss
        assert idx.lookup(p[:4]) == (None, 0)  # cap: (4-1)//4 == 0

    def test_lru_bound_and_drop_replica(self):
        idx = PrefixAffinityIndex(4, cap_entries=3)
        idx.register(list(range(17)), 0)  # 4 entries -> oldest evicted
        assert len(idx) == 3
        idx2 = PrefixAffinityIndex(4)
        idx2.register(list(range(17)), 0)
        idx2.register([50 + i for i in range(17)], 1)
        assert idx2.drop_replica(1) == 4
        assert idx2.lookup([50 + i for i in range(17)]) == (None, 0)
        assert idx2.lookup(list(range(17)))[0] == 0

    def test_last_writer_wins(self):
        idx = PrefixAffinityIndex(4)
        p = list(range(17))
        idx.register(p, 0)
        idx.register(p, 1)
        assert idx.lookup(p) == (1, 4)


class TestRoutingPolicy:
    PROMPT = list(range(40))  # 2 full pages at ps=16

    def test_miss_routes_least_loaded_then_affinity_sticks(self):
        a, b = FakeReplica(0, load=3), FakeReplica(1, load=1)
        r = _router(a, b)
        assert r.submit(self.PROMPT, 4).replica_id == 1  # least loaded
        b._load = 99  # affinity now outweighs load
        assert r.submit(self.PROMPT, 4).replica_id == 1
        s = r.router_stats()
        assert s["router_affinity_hits"] == 1
        assert s["router_dispatches"] == 2

    def test_affinity_off_uses_seeded_fallback(self):
        a, b = FakeReplica(0), FakeReplica(1)
        r1 = _router(a, b, affinity=False, fallback="random", rng_seed=7)
        picks1 = [r1.submit(self.PROMPT, 4).replica_id
                  for _ in range(8)]
        a2, b2 = FakeReplica(0), FakeReplica(1)
        r2 = _router(a2, b2, affinity=False, fallback="random",
                     rng_seed=7)
        picks2 = [r2.submit(self.PROMPT, 4).replica_id
                  for _ in range(8)]
        assert picks1 == picks2  # deterministic control arm
        assert set(picks1) == {0, 1}  # actually scatters
        assert r1.router_stats()["router_affinity_hits"] == 0

    def test_poisoned_replica_leaves_rotation_and_drops_index(self):
        a, b = FakeReplica(0, load=5), FakeReplica(1, load=0)
        r = _router(a, b, unhealthy_cooldown_s=30.0)
        assert r.submit(self.PROMPT, 4).replica_id == 1
        b._broken = "engine step failed"
        # affinity points at b, but b is out of rotation -> a
        assert r.submit(self.PROMPT, 4).replica_id == 0
        assert len(r._index) == 0 or all(
            v != 1 for v in r._index._map.values())
        # recovered but still cooling down: stays out
        b._broken = None
        assert r.submit(self.PROMPT, 4).replica_id == 0

    def test_submit_failure_fails_over_then_marks_down(self):
        a, b = FakeReplica(0, load=0), FakeReplica(1, load=5)
        r = _router(a, b)
        a.fail_submit = RuntimeError("engine is stopped: poisoned")
        req = r.submit(self.PROMPT, 4)
        assert req.replica_id == 1
        s = r.router_stats()
        assert s["router_failovers"] == 1
        # a is now out of rotation: next dispatch goes straight to b
        assert r.submit(self.PROMPT, 4).replica_id == 1

    def test_queue_full_fails_over_then_surfaces(self):
        a, b = FakeReplica(0), FakeReplica(1)
        r = _router(a, b)
        a.full = True
        assert r.submit(self.PROMPT, 4).replica_id == 1
        b.full = True
        with pytest.raises(QueueFull):
            r.submit(self.PROMPT, 4)
        assert r.router_stats()["router_rejected"] == 1

    def test_all_replicas_down_is_a_503_shape(self):
        """A fleet with no healthy replica is TRANSIENT overload
        (cooldown + re-probe), so it must surface as the QueueFull
        family the HTTP layer maps to 503 + Retry-After — a bare
        RuntimeError would answer 500 and get the endpoint ejected by
        load balancers exactly when it is about to recover."""
        from megatron_llm_tpu.inference.router import FleetUnavailable

        a = FakeReplica(0)
        a._alive = False
        r = _router(a)
        with pytest.raises(FleetUnavailable, match="no healthy replica"):
            r.submit(self.PROMPT, 4)
        assert issubclass(FleetUnavailable, QueueFull)

    def test_value_error_propagates_without_failover(self):
        a, b = FakeReplica(0), FakeReplica(1)
        r = _router(a, b)
        a.fail_submit = ValueError("request too large")
        b2_before = len(b.submits)
        with pytest.raises(ValueError):
            r.submit(self.PROMPT, 4)
        assert len(b.submits) == b2_before  # no retry of a bad request

    def test_cancel_routes_by_replica_id(self):
        a, b = FakeReplica(0), FakeReplica(1, load=1)
        r = _router(a, b)
        req = r.submit(self.PROMPT, 4)
        r.cancel(req)
        assert (b if req.replica_id == 1 else a).cancelled == [req.rid]

    def test_stop_drains_every_replica(self):
        a, b = FakeReplica(0), FakeReplica(1)
        r = _router(a, b)
        r.start()
        assert r._thread is not None  # the server.run duck-type flag
        r.stop(drain=True)
        assert a.stopped == [True] and b.stopped == [True]
        assert r._thread is None

    def test_mismatched_page_size_rejected(self):
        a, b = FakeReplica(0), FakeReplica(1)
        b.page_size = 32
        with pytest.raises(ValueError, match="page_size"):
            _router(a, b)

    def test_duplicate_replica_ids_rejected(self):
        with pytest.raises(ValueError, match="duplicate"):
            _router(FakeReplica(0), FakeReplica(0))


class TestAggregation:
    def test_counters_sum_additive_and_keep_per_replica(self):
        a, b = FakeReplica(0), FakeReplica(1)
        r = _router(a, b)
        r.submit(list(range(40)), 4)
        c = r.counters()
        assert c["router_dispatches"] == 1
        assert c["serve_admitted"] == 1  # summed
        assert set(c["replicas"]) == {0, 1}
        assert c["replicas"][0]["serve_replica_id"] == 0
        # non-additive gauges never aggregate (summing a p95 would
        # fabricate a number)
        assert "serve_ttft_p95_ms" not in c
        # the per-chip capacity gauge never sums raw either: the fleet
        # number scales each replica by its tp, under its own key
        assert "serve_kv_pool_bytes" not in c
        assert c["serve_kv_pool_bytes_fleet"] == 4000

    def test_health_answers_for_the_fleet(self):
        a, b = FakeReplica(0, load=2), FakeReplica(1, load=3)
        r = _router(a, b)
        h = r.health()
        assert h["alive"] and h["broken"] is None
        assert h["queue_depth"] == 5
        a._alive = False
        b._broken = "poisoned"
        h = r.health()
        assert not h["alive"] and h["broken"] == "all replicas down"

    def test_histograms_merge_cumulative_buckets(self):
        a, b = FakeReplica(0), FakeReplica(1)
        r = _router(a, b)
        merged = {h.name: h for h in r.histograms()}
        assert merged["serve_ttft_ms"].count == 3  # 1 + 2 observations
        text = r.prometheus_metrics()
        assert "router_dispatches" in text
        assert "serve_ttft_ms_count 3" in text

    def test_histogram_merged_rejects_mismatched_buckets(self):
        h1 = Histogram("x", buckets=[1.0, 2.0])
        h2 = Histogram("x", buckets=[1.0, 4.0])
        with pytest.raises(AssertionError):
            Histogram.merged([h1, h2])


class TestReplicaIdThreading:
    """The satellite: replica_id through counters, recorder events,
    EngineRequest, and the SSE id field — absent everywhere when the
    engine is untagged (the byte-compat default test_telemetry pins in
    full)."""

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

    def _engine(self, tiny_model, **over):
        model, params = tiny_model
        kw = dict(slots=2, page_size=16, max_context=64,
                  prefill_chunk_tokens=16, vocab_size=256,
                  termination_id=None)
        kw.update(over)
        return DecodeEngine(model, params, **kw)

    def test_tagged_engine_threads_replica_id(self, tiny_model):
        eng = self._engine(tiny_model, replica_id=3)
        c = eng.counters()
        assert list(c)[0] == "serve_replica_id" and c[
            "serve_replica_id"] == 3
        req = eng.submit([5, 6, 7], 2, top_k=1)
        assert req.replica_id == 3
        evs = eng.recorder.snapshot()["events"]
        assert evs and all(e["replica"] == 3 for e in evs)
        assert "serve_replica_id 3" in eng.prometheus_metrics()
        eng._fail_all("test teardown")

    def test_untagged_engine_keeps_legacy_schema(self, tiny_model):
        eng = self._engine(tiny_model)
        assert "serve_replica_id" not in eng.counters()
        req = eng.submit([5, 6, 7], 2, top_k=1)
        assert req.replica_id is None
        evs = eng.recorder.snapshot()["events"]
        assert evs and all("replica" not in e for e in evs)
        eng._fail_all("test teardown")

    def test_sse_id_carries_replica_tag(self, tiny_model):
        """put_stream writes `id: <replica>-<rid>` for a tagged
        engine and the bare rid for an untagged one."""
        import queue as queue_mod

        from megatron_llm_tpu.inference.engine import EngineRequest
        from megatron_llm_tpu.inference.server import MegatronGenerate

        class FakeTok:
            bos = 1

            def tokenize(self, s):
                return [2, 3, 4]

            def detokenize(self, ids):
                return "x" * len(ids)

        class FakeEngine:
            replica_id = None

            def __init__(self, rep):
                self.rep = rep

            def submit(self, ids, n, **kw):
                req = EngineRequest(
                    rid=7, prompt=list(ids), tokens_to_generate=n,
                    replica_id=self.rep,
                    stream_q=queue_mod.SimpleQueue())
                for t in (11, 12):
                    req.stream_q.put(t)
                req.stream_q.put(None)
                req.done.set()
                return req

        for rep, want in ((1, "1-7"), (None, 7)):
            gen = MegatronGenerate(None, None, FakeTok(),
                                   engine=FakeEngine(rep))
            ids_seen = []

            def write_event(obj, rid=None):
                ids_seen.append(rid)

            err = gen.put_stream(
                {"prompts": ["hi"], "tokens_to_generate": 4},
                start_response=lambda: None, write_event=write_event)
            assert err is None
            assert ids_seen and all(i == want for i in ids_seen), (
                rep, ids_seen)


pytestmark_slow = pytest.mark.slow


@pytest.mark.slow
class TestEngineReplicasEndToEnd:
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

    def _fleet(self, tiny_model, n=2, **over):
        import jax

        model, params = tiny_model
        devs = jax.devices()
        kw = dict(slots=2, page_size=16, max_context=96, max_queue=16,
                  prefill_chunk_tokens=16, prefix_cache=True,
                  vocab_size=256, termination_id=None)
        kw.update(over)
        engines = [DecodeEngine(model, params, replica_id=i,
                                devices=[devs[i]], **kw)
                   for i in range(n)]
        return engines

    def test_affinity_lands_shared_prefix_on_one_replica(
            self, tiny_model):
        import numpy as np

        model, params = tiny_model
        rs = np.random.RandomState(0)
        sysp = list(rs.randint(2, 256, 40))
        prompts = [sysp + list(rs.randint(2, 256, 4))
                   for _ in range(4)]

        # oracle: one plain engine, same traffic
        oracle = DecodeEngine(model, params, slots=2, page_size=16,
                              max_context=96, max_queue=16,
                              prefill_chunk_tokens=16,
                              prefix_cache=True, vocab_size=256,
                              termination_id=None)
        oreqs = [oracle.submit(p, 8, top_k=1) for p in prompts]
        oracle.drain()
        want = [r.result(60)[0] for r in oreqs]

        engines = self._fleet(tiny_model)
        router = ReplicaRouter([EngineReplica(e) for e in engines])
        router.start()
        reqs = [router.submit(p, 8, top_k=1) for p in prompts]
        got = [r.result(60)[0] for r in reqs]
        router.stop(drain=True)
        assert got == want
        # every shared-prefix request landed on ONE replica...
        homes = {r.replica_id for r in reqs}
        assert len(homes) == 1, homes
        home = engines[homes.pop()]
        # ...whose own PrefixCache then hit (the whole point)
        assert home.counters()["serve_prefix_hits"] >= 1
        stats = router.router_stats()
        assert stats["router_affinity_hits"] >= 1
