"""Disaggregated prefill/decode serving (ISSUE 17).

Pinned here:
- two-stage routing units over scripted fakes (no device work): long
  prompts dispatch prefill-replica -> hand-off -> decode-replica,
  short prompts and return_log_probs go direct, a broken donor falls
  back to direct prefill, a decode replica dying mid-transfer fails
  over (the donor needs no cleanup), import_prefix=False degrades to
  local prefill, and the gated router_stats/decision-log keys appear
  ONLY in disagg/SLO mode (the PR 15 byte-compat pin, extended);
- modeled placement: candidate ordering follows modeled FLOPs only
  when EVERY candidate reports them (mixed fleets fall back to
  occupancy), SLO admission rejects with BacklogExceeded carrying a
  clamped modeled Retry-After and stays OPEN when any candidate
  cannot model;
- the Retry-After clamp ([1, 60] s, constant 1 when nothing models);
- (slow) real engines on CPU: export/import round trip with a partial
  last page, geometry/dtype gates, int8 (data, scale) pair integrity,
  refcount handoff on the receiving PrefixCache (registered but
  unreferenced => evictable), donor-side reclaim after a receiver
  failure mid-transfer, pool-full fallback, and greedy BITWISE parity
  vs the single-engine oracle through the live two-stage router —
  including spec decode on the decode replica.
"""

import threading
import time

import pytest

from megatron_llm_tpu.inference.engine import DecodeEngine, QueueFull
from megatron_llm_tpu.inference.router import (
    BacklogExceeded,
    EngineReplica,
    ReplicaRouter,
)


class DoneReq:
    """A completed request handle: the protocol surface the two-stage
    orchestration thread touches (result/done/t_* mirrors)."""

    def __init__(self, rid, replica_id, tokens=(1, 2, 3)):
        self.rid = rid
        self.replica_id = replica_id
        self.tokens = list(tokens)
        self.log_probs = []
        self.return_log_probs = False
        self.error = None
        self.timed_out = False
        self.stream_q = None
        self.done = threading.Event()
        self.done.set()
        now = time.perf_counter()
        self.t_submit, self.t_first, self.t_done = now, now, now

    def result(self, timeout=None):
        return list(self.tokens), list(self.log_probs)


class DisaggFakeReplica:
    """Scripted replica speaking the FULL disagg router protocol:
    submit/cancel/health plus export_prefix/import_prefix and the
    modeled-backlog surface, with failure knobs the tests flip."""

    def __init__(self, rid, load=0, modeled_flops=None, modeled_s=None,
                 retry_after=None):
        self.replica_id = rid
        self._load = load
        self._alive = True
        self._broken = None
        self.full = False
        self.fail_submit = None
        self.fail_import = None
        self.import_result = "echo"  # echo payload pages / False
        self.export_payload = {"pages": 2, "tokens": list(range(32)),
                               "page_size": 16}
        self.modeled_flops = modeled_flops
        self.modeled_s = modeled_s
        self.retry_after = retry_after
        self.submits = []  # (prompt, n, kw)
        self.imports = []
        self.exports = []
        self.cancelled = []
        self.page_size = 16
        self.max_context = 64
        self.num_pages = 9
        self._next_rid = 0

    # -- dispatch surface --------------------------------------------------

    def submit(self, prompt, n, **kw):
        if self.full:
            raise QueueFull("queue full")
        if self.fail_submit is not None:
            raise self.fail_submit
        self.submits.append((list(prompt), n, dict(kw)))
        self._next_rid += 1
        return DoneReq(self._next_rid - 1, self.replica_id)

    def cancel(self, req):
        self.cancelled.append(req.rid)

    # -- hand-off surface --------------------------------------------------

    def export_prefix(self, prompt):
        self.exports.append(list(prompt))
        return self.export_payload

    def import_prefix(self, payload):
        if self.fail_import is not None:
            raise self.fail_import
        self.imports.append(payload)
        if self.import_result == "echo":
            return {"pages": int(payload.get("pages", 0)),
                    "registered": int(payload.get("pages", 0))}
        return self.import_result

    # -- health / modeled backlog ------------------------------------------

    def health(self):
        return {"alive": self._alive, "broken": self._broken,
                "queue_depth": self._load, "slots_busy": 0}

    def load(self):
        return self._load

    def modeled_backlog_flops(self):
        return self.modeled_flops

    def modeled_backlog_s(self):
        return self.modeled_s

    def retry_after_s(self):
        return self.retry_after

    def counters(self):
        return {"serve_replica_id": self.replica_id}

    def fleet_kv_pool_bytes(self):
        return 0

    def histograms(self):
        return []

    def flight_record(self):
        return {"events": []}

    def start(self):
        pass

    def stop(self, drain=True):
        pass

    def drain(self):
        pass


def _disagg(pre, dec, **kw):
    return ReplicaRouter(prefill_replicas=list(pre),
                         decode_replicas=list(dec), **kw)


LONG = list(range(2, 35))  # 33 tokens -> (33-1)//16 = 2 full pages
SHORT = list(range(2, 18))  # 16 tokens -> 0 full pages


# ---------------------------------------------------------------------------
# two-stage dispatch policy (fakes)
# ---------------------------------------------------------------------------


class TestTwoStageRouting:
    def test_ctor_validation(self):
        p, d = DisaggFakeReplica(0), DisaggFakeReplica(1)
        with pytest.raises(ValueError, match="BOTH"):
            ReplicaRouter(prefill_replicas=[p])
        with pytest.raises(ValueError, match="not both"):
            ReplicaRouter([p], prefill_replicas=[p],
                          decode_replicas=[d])
        with pytest.raises(ValueError, match="at least one"):
            ReplicaRouter(prefill_replicas=[], decode_replicas=[d])

    def test_long_prompt_goes_two_stage(self):
        pre, dec = DisaggFakeReplica(0), DisaggFakeReplica(1)
        r = _disagg([pre], [dec])
        req = r.submit(LONG, 8, top_k=1)
        tokens, _ = req.result(timeout=10)
        assert tokens == [1, 2, 3]
        assert req.replica_id == 1  # the decode replica served it
        # stage 1: a 1-token full-prefill run on the prefill replica
        assert len(pre.submits) == 1
        assert pre.submits[0][1] == 1
        assert pre.exports == [LONG]
        # stage 2 + 3: import then the real submit on the decode side
        assert len(dec.imports) == 1
        assert len(dec.submits) == 1
        assert dec.submits[0][1] == 8
        stats = r.router_stats()
        assert stats["serve_prefill_replica"] == 1
        assert stats["serve_transfer_pages"] == 2
        paths = [d["path"] for d in r.decision_log()]
        assert paths == ["two_stage"]
        two = r.decision_log()[0]
        assert two["prefill"] == 0 and two["decode"] == 1
        assert two["pages"] == 2

    def test_greedy_handoff_stamps_ttft_at_prefill_completion(self):
        pre, dec = DisaggFakeReplica(0), DisaggFakeReplica(1)
        r = _disagg([pre], [dec])
        req = r.submit(LONG, 8, top_k=1)
        req.result(timeout=10)
        # the donor's 1-token run produced the continuation's first
        # token; the proxy's t_first is that moment, not the decode
        # replica's re-generation
        assert req.t_first > 0
        assert req.t_done >= req.t_first

    def test_short_prompt_goes_direct_to_decode(self):
        pre, dec = DisaggFakeReplica(0), DisaggFakeReplica(1)
        r = _disagg([pre], [dec])
        req = r.submit(SHORT, 4, top_k=1)
        assert req.replica_id == 1
        assert pre.submits == [] and pre.exports == []
        assert dec.imports == []
        assert [d["path"] for d in r.decision_log()] == ["direct"]

    def test_return_log_probs_goes_direct(self):
        pre, dec = DisaggFakeReplica(0), DisaggFakeReplica(1)
        r = _disagg([pre], [dec])
        r.submit(LONG, 4, return_log_probs=True)
        assert pre.submits == []
        assert len(dec.submits) == 1

    def test_prefill_replica_down_degrades_to_direct(self):
        pre, dec = DisaggFakeReplica(0), DisaggFakeReplica(1)
        pre._alive = False
        r = _disagg([pre], [dec])
        req = r.submit(LONG, 4, top_k=1)
        assert req.replica_id == 1
        assert pre.submits == []

    def test_prefill_failure_falls_back_to_direct_prefill(self):
        pre, dec = DisaggFakeReplica(0), DisaggFakeReplica(1)
        pre.fail_submit = RuntimeError("donor died")
        r = _disagg([pre], [dec])
        req = r.submit(LONG, 8, top_k=1)
        tokens, _ = req.result(timeout=10)
        assert tokens == [1, 2, 3]
        # no payload arrived, the decode replica prefilled locally
        assert dec.imports == []
        assert len(dec.submits) == 1
        # the broken donor left rotation
        assert 0 in r._down_until
        assert r.router_stats()["serve_transfer_pages"] == 0

    def test_export_none_skips_import(self):
        pre, dec = DisaggFakeReplica(0), DisaggFakeReplica(1)
        pre.export_payload = None
        r = _disagg([pre], [dec])
        req = r.submit(LONG, 8, top_k=1)
        req.result(timeout=10)
        assert dec.imports == []
        assert len(dec.submits) == 1

    def test_decode_death_mid_transfer_fails_over(self):
        """Satellite 3: a decode replica dying on import fails over to
        the next by backlog order; the donor needs no cleanup."""
        pre = DisaggFakeReplica(0)
        d1 = DisaggFakeReplica(1)
        d2 = DisaggFakeReplica(2, load=5)  # ordered after d1
        d1.fail_import = RuntimeError("receiver died mid-transfer")
        r = _disagg([pre], [d1, d2])
        req = r.submit(LONG, 8, top_k=1)
        tokens, _ = req.result(timeout=10)
        assert tokens == [1, 2, 3]
        assert req.replica_id == 2
        assert len(d2.imports) == 1 and len(d2.submits) == 1
        assert d1.submits == []
        assert 1 in r._down_until  # the dead receiver left rotation
        # the transfer that COMPLETED is the one accounted
        assert r.router_stats()["serve_transfer_pages"] == 2

    def test_import_false_degrades_to_local_prefill(self):
        pre, dec = DisaggFakeReplica(0), DisaggFakeReplica(1)
        dec.import_result = False  # pool full of live pages
        r = _disagg([pre], [dec])
        req = r.submit(LONG, 8, top_k=1)
        req.result(timeout=10)
        assert len(dec.submits) == 1
        assert r.router_stats()["serve_transfer_pages"] == 0

    def test_decode_queue_full_fails_over(self):
        pre = DisaggFakeReplica(0)
        d1, d2 = DisaggFakeReplica(1), DisaggFakeReplica(2, load=5)
        d1.full = True
        r = _disagg([pre], [d1, d2])
        req = r.submit(LONG, 8, top_k=1)
        req.result(timeout=10)
        assert req.replica_id == 2
        assert 1 not in r._down_until  # full is transient, not broken

    def test_all_decode_failures_fail_the_proxy(self):
        pre, dec = DisaggFakeReplica(0), DisaggFakeReplica(1)
        dec.fail_submit = RuntimeError("decode engine poisoned")
        r = _disagg([pre], [dec])
        req = r.submit(LONG, 8, top_k=1)
        with pytest.raises(RuntimeError, match="two-stage"):
            req.result(timeout=10)

    def test_cancel_routes_to_inner_request(self):
        pre, dec = DisaggFakeReplica(0), DisaggFakeReplica(1)
        r = _disagg([pre], [dec])
        req = r.submit(LONG, 8, top_k=1)
        req.result(timeout=10)
        r.cancel(req)
        assert dec.cancelled  # routed to the decode replica's engine

    def test_gated_stats_keys(self):
        """The PR 15 byte-compat pin extended: disagg/SLO keys appear
        ONLY in their modes."""
        sym = ReplicaRouter([DisaggFakeReplica(0)])
        s = sym.router_stats()
        for key in ("serve_prefill_replica", "serve_transfer_pages",
                    "serve_transfer_ms", "router_prefill_replicas",
                    "router_decode_replicas", "router_slo_rejected"):
            assert key not in s, key
        assert "decisions" not in sym.flight_record()
        dis = _disagg([DisaggFakeReplica(0)], [DisaggFakeReplica(1)],
                      ttft_slo_s=5.0)
        d = dis.router_stats()
        assert d["router_prefill_replicas"] == 1
        assert d["router_decode_replicas"] == 1
        assert d["serve_transfer_pages"] == 0
        assert d["router_slo_rejected"] == 0
        assert "decisions" in dis.flight_record()


# ---------------------------------------------------------------------------
# modeled placement + SLO admission (fakes)
# ---------------------------------------------------------------------------


class TestModeledPlacement:
    def test_order_by_backlog_prefers_modeled_flops(self):
        order = ReplicaRouter._order_by_backlog(
            [0, 1], {0: 0, 1: 5}, {0: 1e12, 1: 1e9})
        assert order == [1, 0]  # modeled FLOPs outrank queue depth

    def test_order_falls_back_when_any_candidate_lacks_model(self):
        order = ReplicaRouter._order_by_backlog(
            [0, 1], {0: 0, 1: 5}, {1: 1e9})  # 0 cannot model
        assert order == [0, 1]  # occupancy ordering

    def test_direct_dispatch_places_by_modeled_backlog(self):
        d1 = DisaggFakeReplica(1, load=0, modeled_flops=1e12)
        d2 = DisaggFakeReplica(2, load=5, modeled_flops=1e9)
        r = ReplicaRouter([d1, d2], affinity=False)
        req = r.submit(SHORT, 4, top_k=1)
        assert req.replica_id == 2  # queue-depth would have said 1

    def test_two_stage_places_decode_by_modeled_backlog(self):
        pre = DisaggFakeReplica(0, modeled_flops=0.0)
        d1 = DisaggFakeReplica(1, load=0, modeled_flops=1e12)
        d2 = DisaggFakeReplica(2, load=5, modeled_flops=1e9)
        r = _disagg([pre], [d1, d2])
        req = r.submit(LONG, 8, top_k=1)
        req.result(timeout=10)
        assert req.replica_id == 2
        dec = [d for d in r.decision_log()
               if d["path"] == "two_stage"][0]
        # reproducibility: the decision carries the snapshot it used
        assert dec["modeled_flops"][2] == pytest.approx(1e9)


class TestSLOAdmission:
    def test_rejects_when_every_candidate_exceeds_budget(self):
        d1 = DisaggFakeReplica(1, modeled_s=12.0, retry_after=12.0)
        d2 = DisaggFakeReplica(2, modeled_s=30.0, retry_after=30.0)
        r = ReplicaRouter([d1, d2], ttft_slo_s=5.0)
        with pytest.raises(BacklogExceeded) as ei:
            r.submit(SHORT, 4, top_k=1)
        assert ei.value.retry_after_s == pytest.approx(12.0)
        assert isinstance(ei.value, QueueFull)  # the HTTP 503 family
        stats = r.router_stats()
        assert stats["router_slo_rejected"] == 1
        assert stats["router_rejected"] == 1
        dec = r.decision_log()[-1]
        assert dec["path"] == "slo_reject"
        assert dec["modeled_backlog_s"] == pytest.approx(12.0)

    def test_retry_after_is_clamped(self):
        d = DisaggFakeReplica(1, modeled_s=500.0, retry_after=500.0)
        r = ReplicaRouter([d], ttft_slo_s=5.0)
        with pytest.raises(BacklogExceeded) as ei:
            r.submit(SHORT, 4, top_k=1)
        assert ei.value.retry_after_s == 60.0

    def test_admits_when_any_candidate_cannot_model(self):
        d1 = DisaggFakeReplica(1, modeled_s=None)
        d2 = DisaggFakeReplica(2, modeled_s=30.0)
        r = ReplicaRouter([d1, d2], ttft_slo_s=5.0)
        req = r.submit(SHORT, 4, top_k=1)  # gate stays open
        assert req is not None
        assert r.router_stats()["router_slo_rejected"] == 0

    def test_admits_under_budget(self):
        d = DisaggFakeReplica(1, modeled_s=0.5)
        r = ReplicaRouter([d], ttft_slo_s=5.0)
        assert r.submit(SHORT, 4, top_k=1) is not None


class TestRetryAfterClamp:
    def test_fleet_retry_after_is_min_then_clamped(self):
        r = ReplicaRouter([DisaggFakeReplica(0, retry_after=5.0),
                           DisaggFakeReplica(1, retry_after=90.0)])
        assert r.retry_after_s() == 5.0
        r2 = ReplicaRouter([DisaggFakeReplica(0, retry_after=90.0)])
        assert r2.retry_after_s() == 60.0

    def test_constant_fallback_when_nothing_models(self):
        r = ReplicaRouter([DisaggFakeReplica(0, retry_after=None)])
        assert r.retry_after_s() == 1.0


# ---------------------------------------------------------------------------
# real engines end to end (slow)
# ---------------------------------------------------------------------------


@pytest.mark.slow
class TestHandoffEnginesEndToEnd:
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
        kw = dict(slots=2, page_size=16, max_context=96, max_queue=16,
                  prefill_chunk_tokens=16, prefix_cache=True,
                  vocab_size=256, termination_id=None)
        kw.update(over)
        return DecodeEngine(model, params, **kw)

    def _prefill(self, eng, prompt):
        req = eng.submit(prompt, 1, top_k=1)
        eng.drain()
        req.result(60)
        return req

    @staticmethod
    def _prompt(n, seed=0):
        import numpy as np

        return list(np.random.RandomState(seed).randint(2, 256, n))

    def test_roundtrip_parity_with_partial_last_page(self, tiny_model):
        """40-token prompt: 2 full pages travel, the 8-token partial
        page does NOT — the receiver re-prefills the suffix and the
        greedy stream is bitwise the oracle's."""
        prompt = self._prompt(40)
        a = self._engine(tiny_model)
        self._prefill(a, prompt)
        payload = a.export_prefix(prompt)
        assert payload["pages"] == 2
        assert len(payload["tokens"]) == 32  # full pages only
        assert payload["page_size"] == 16
        assert payload["dtype"] == a.kv_pool_dtype()
        assert len(payload["k"]) == len(a._pools_k)
        assert a.counters()["serve_transfers_out"] == 1
        assert a.counters()["serve_transfer_pages_out"] == 2

        oracle = self._engine(tiny_model)
        oreq = oracle.submit(prompt, 8, top_k=1)
        oracle.drain()
        want = oreq.result(60)[0]

        b = self._engine(tiny_model)
        res = b.import_prefix(payload)
        assert res == {"pages": 2, "registered": 2}
        assert b.counters()["serve_transfer_pages_in"] == 2
        breq = b.submit(prompt, 8, top_k=1)
        b.drain()
        assert breq.result(60)[0] == want
        # the transferred chain HIT (the whole point of the hand-off)
        assert b.counters()["serve_prefix_hits"] >= 1

    def test_export_misses_return_none(self, tiny_model):
        a = self._engine(tiny_model)
        assert a.export_prefix(self._prompt(40)) is None  # never seen
        short = self._prompt(8)
        self._prefill(a, short)
        assert a.export_prefix(short) is None  # no full page exists

    def test_export_requires_prefix_cache(self, tiny_model):
        a = self._engine(tiny_model, prefix_cache=False,
                         prefill_chunk_tokens=0)
        with pytest.raises(ValueError, match="prefix_cache"):
            a.export_prefix(self._prompt(40))
        with pytest.raises(ValueError, match="prefix_cache"):
            a.import_prefix({"pages": 1})

    def test_import_geometry_and_dtype_gates(self, tiny_model):
        prompt = self._prompt(40)
        a = self._engine(tiny_model)
        self._prefill(a, prompt)
        payload = a.export_prefix(prompt)

        wrong_ps = self._engine(tiny_model, page_size=32,
                                max_context=192)
        with pytest.raises(ValueError, match="page_size"):
            wrong_ps.import_prefix(payload)

        b = self._engine(tiny_model)
        bad = dict(payload, tokens=payload["tokens"][:-1])
        with pytest.raises(ValueError, match="prefix tokens"):
            b.import_prefix(bad)
        bad = dict(payload, dtype="int8")
        with pytest.raises(ValueError, match="dtype"):
            b.import_prefix(bad)
        bad = dict(payload, pages=0)
        with pytest.raises(ValueError, match="pages"):
            b.import_prefix(bad)

    def test_int8_pair_integrity(self, tiny_model):
        """int8 hand-off: the (data, scale) pools travel together —
        a payload missing its scale blocks is refused, and the
        round trip matches the int8 oracle bitwise."""
        prompt = self._prompt(40, seed=3)
        a = self._engine(tiny_model, kv_dtype="int8")
        self._prefill(a, prompt)
        payload = a.export_prefix(prompt)
        assert payload["dtype"] == "int8"
        assert len(payload["ks"]) == len(a._pools_ks) > 0
        assert len(payload["vs"]) == len(a._pools_vs) > 0

        b = self._engine(tiny_model, kv_dtype="int8")
        with pytest.raises(ValueError, match="travel together"):
            b.import_prefix(dict(payload, ks=[]))
        # a bf16 receiver refuses the int8 payload outright
        bf = self._engine(tiny_model)
        with pytest.raises(ValueError, match="dtype"):
            bf.import_prefix(payload)

        oracle = self._engine(tiny_model, kv_dtype="int8")
        oreq = oracle.submit(prompt, 8, top_k=1)
        oracle.drain()
        want = oreq.result(60)[0]
        assert b.import_prefix(payload)["registered"] == 2
        breq = b.submit(prompt, 8, top_k=1)
        b.drain()
        assert breq.result(60)[0] == want

    def test_refcount_handoff_on_receiver(self, tiny_model):
        """Transferred pages land registered but UNREFERENCED: normal
        LRU eviction can reclaim them until a slot acquires them."""
        prompt = self._prompt(40)
        a = self._engine(tiny_model)
        self._prefill(a, prompt)
        payload = a.export_prefix(prompt)
        b = self._engine(tiny_model)
        free_before = len(b._free_pages)
        assert b.import_prefix(payload)["registered"] == 2
        assert len(b._free_pages) == free_before - 2
        match = b._prefix.lookup(prompt)
        assert match.full_pages == 2
        # unreferenced => evictable; the pages flow back to the caller
        evicted = b._prefix.evict(2)
        assert len(evicted) == 2
        assert b._prefix.lookup(prompt).full_pages == 0

    def test_donor_reclaim_after_receiver_failure(self, tiny_model):
        """A receiver dying mid-transfer needs NO donor-side cleanup:
        the exported pages stayed registered and unreferenced on the
        donor, re-exportable and reclaimable by its own eviction."""
        prompt = self._prompt(40)
        a = self._engine(tiny_model)
        self._prefill(a, prompt)
        payload = a.export_prefix(prompt)
        assert payload is not None
        # the receiver is never heard from again; the donor still
        # holds the chain and can serve the next decode replica
        again = a.export_prefix(prompt)
        assert again is not None and again["pages"] == 2
        assert a._prefix.lookup(prompt).full_pages == 2
        # and under pool pressure the donor reclaims them normally
        assert len(a._prefix.evict(2)) == 2

    def test_receiver_pool_full_returns_false(self, tiny_model):
        prompt = self._prompt(40)
        a = self._engine(tiny_model)
        self._prefill(a, prompt)
        payload = a.export_prefix(prompt)
        b = self._engine(tiny_model)
        held = list(b._free_pages)
        b._free_pages.clear()  # every page live outside the cache
        try:
            assert b.import_prefix(payload) is False
        finally:
            b._free_pages.extend(held)

    def test_two_stage_router_parity_with_spec_decode(self, tiny_model):
        """Greedy token streams through the LIVE two-stage router are
        bitwise the single-engine oracle's — mid-page splits, a
        spec-decoding decode replica, prefix hits on transferred
        pages, shorts direct."""
        import jax

        model, params = tiny_model
        devs = jax.devices()
        prompts = [self._prompt(40, seed=1), self._prompt(56, seed=2),
                   self._prompt(8, seed=4)]

        oracle = self._engine(tiny_model, spec_decode_k=2)
        oreqs = [oracle.submit(p, 8, top_k=1) for p in prompts]
        oracle.drain()
        want = [r.result(60)[0] for r in oreqs]

        pre = self._engine(tiny_model, replica_id=0,
                           devices=[devs[0]])
        dec = self._engine(tiny_model, replica_id=1, spec_decode_k=2,
                           devices=[devs[0]])
        router = ReplicaRouter(prefill_replicas=[EngineReplica(pre)],
                               decode_replicas=[EngineReplica(dec)],
                               disagg_min_prompt_pages=2)
        router.start()
        try:
            reqs = [router.submit(p, 8, top_k=1) for p in prompts]
            got = [r.result(120)[0] for r in reqs]
        finally:
            router.stop(drain=True)
        assert got == want
        # both longs handed off; the short went direct
        stats = router.router_stats()
        assert stats["serve_prefill_replica"] == 2
        assert stats["serve_transfer_pages"] == 2 + 3  # 40->2, 56->3
        assert dec.counters()["serve_prefix_hits"] >= 2
        paths = sorted(d["path"] for d in router.decision_log())
        assert paths == ["direct", "two_stage", "two_stage"]

    def test_modeled_retry_after_on_engine(self, tiny_model):
        eng = self._engine(tiny_model, cost_registry=True,
                           chip_spec="v5e")
        assert eng.modeled_backlog_flops() == 0.0
        assert eng.retry_after_s() == 1.0  # clamp floor when idle
        eng.submit(self._prompt(64), 16, top_k=1)  # queued, no loop
        assert eng.modeled_backlog_flops() > 0
        assert 1.0 <= eng.retry_after_s() <= 60.0
        # the clamp itself
        eng.modeled_backlog_seconds = lambda: 500.0
        assert eng.retry_after_s() == 60.0
        eng.modeled_backlog_seconds = lambda: 0.001
        assert eng.retry_after_s() == 1.0

    def test_costs_off_keeps_constant_retry_after(self, tiny_model):
        eng = self._engine(tiny_model)
        eng.submit(self._prompt(64), 16, top_k=1)
        assert eng.modeled_backlog_flops() is None
        assert eng.modeled_backlog_seconds() is None
        assert eng.retry_after_s() == 1.0  # the pre-ISSUE-17 header
