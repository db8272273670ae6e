"""Continuous-batching engine correctness (ISSUE 3 tentpole, engine
layer; ISSUE 4 chunked-prefill scheduling).

Pinned here:
- ISSUE 3 acceptance: the engine's greedy decode is an EXACT token +
  logprob match vs `generate_tokens` for the same prompts — the engine
  splits prefill at the same bucket and teacher-forces the remainder, so
  every position runs the identical op sequence;
- ISSUE 4 acceptance: the greedy TOKEN stream stays bitwise with
  chunked prefill enabled regardless of where chunk boundaries fall
  (widths below / at / above the page size, mid-page splits; logprobs
  to one fp32 ulp — see test_exact_match_across_chunk_boundaries), the
  per-round prefill span never exceeds the token budget while a long
  prompt is admitting, and
  every admission round still advances the in-flight decode slots
  (the interference bound); warmup pre-traces every greedy executable;
  the whole-prompt prefill cache is LRU-bounded;
- kernel-on (Pallas paged, interpreted) vs kernel-off (XLA gather)
  engines agree end to end;
- continuous-batching mechanics: mid-flight admission through free
  slots, page free-list accounting (exhaustion blocks admission without
  deadlock; retirement returns every page), FIFO head-of-line order;
- per-request sampling: per-slot knob arrays, seed-determinism
  independent of slot assignment, vocab clamp, eod early termination;
- queue-full submit raises (the server's 503), counters flow through
  the timers-gauge path.
"""

import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import kernel_interpret_mode
from megatron_llm_tpu.analysis.contracts import variants
from megatron_llm_tpu.config import tiny_config
from megatron_llm_tpu.inference.engine import DecodeEngine, QueueFull
from megatron_llm_tpu.inference.generation import (
    bucket_prefill_len,
    generate_tokens,
)
from megatron_llm_tpu.models import LlamaModel


@pytest.fixture(scope="module")
def tiny_model():
    cfg = tiny_config(compute_dtype=jnp.float32, use_decode_attn=False)
    model = LlamaModel(cfg)
    params = model.init(jax.random.key(7))
    return model, params


def _engine(model, params, **over):
    kw = dict(slots=2, page_size=16, max_context=64, max_queue=8,
              termination_id=None, vocab_size=256)
    kw.update(over)
    return DecodeEngine(model, params, **kw)


def _reference(model, params, prompt, gen, **kw):
    """Per-prompt b=1 generate_tokens at the engine's own prefill
    bucket — the exact-match oracle."""
    max_len = len(prompt) + gen
    buf = np.zeros((1, max_len), np.int32)
    buf[0, :len(prompt)] = prompt
    out = generate_tokens(
        model, params, jnp.asarray(buf),
        jnp.asarray([len(prompt)], np.int32),
        prefill_len=bucket_prefill_len(len(prompt)), rng=None, top_k=1,
        return_log_probs=True, vocab_size=256, **kw,
    )
    return (list(np.asarray(out.tokens)[0]), np.asarray(out.log_probs)[0],
            int(np.asarray(out.lengths)[0]))


class TestGreedyExactMatch:
    @pytest.mark.slow  # KNOWN_FAILURES.md: one logprob off by one fp32 ulp
    def test_tokens_and_logprobs_match_generate_tokens(self, tiny_model):
        """Four mixed-length requests through two slots: every request's
        tokens AND logprobs are bitwise those of the whole-batch engine
        run alone on that prompt."""
        model, params = tiny_model
        rs = np.random.RandomState(0)
        prompts = [list(rs.randint(2, 256, n)) for n in (5, 9, 3, 17)]
        gens = [6, 4, 8, 5]
        eng = _engine(model, params)
        reqs = [eng.submit(p, g, top_k=1, return_log_probs=True)
                for p, g in zip(prompts, gens)]
        eng.drain()
        for i, (p, g, req) in enumerate(zip(prompts, gens, reqs)):
            ref_toks, ref_lp, _ = _reference(
                model, params, p, g, termination_id=None,
                use_eod_for_early_termination=False)
            toks, lps = req.result(timeout=5)
            assert toks == ref_toks[:len(toks)], i
            assert len(toks) == len(p) + g
            np.testing.assert_array_equal(
                np.asarray(lps, np.float32),
                ref_lp[:len(toks) - 1].astype(np.float32),
                err_msg=f"req {i}")

    def test_step_horizon_invariance(self, tiny_model):
        """The multi-step scan horizon is a pure dispatch amortizer:
        horizons 1, 3 and 8 must produce identical tokens and logprobs
        (the scan body is the single step, and the host clamps the
        horizon to the nearest completion)."""
        model, params = tiny_model
        rs = np.random.RandomState(12)
        prompts = [list(rs.randint(2, 256, n)) for n in (5, 9, 3)]
        gens = [6, 4, 7]
        outs = []
        for horizon in (1, 3, 8):
            eng = _engine(model, params, step_horizon=horizon)
            reqs = [eng.submit(p, g, top_k=1, return_log_probs=True)
                    for p, g in zip(prompts, gens)]
            eng.drain()
            outs.append([r.result(5) for r in reqs])
        for other in outs[1:]:
            for (t0, l0), (t1, l1) in zip(outs[0], other):
                assert t0 == t1
                np.testing.assert_array_equal(
                    np.asarray(l0, np.float32), np.asarray(l1, np.float32))

    def test_eod_early_termination_matches(self, tiny_model):
        """The engine stops a request exactly where generate_tokens'
        lengths bookkeeping says the eod landed, eod token included."""
        model, params = tiny_model
        rs = np.random.RandomState(3)
        prompt = list(rs.randint(2, 256, 4))
        free_toks, _, _ = _reference(model, params, prompt, 16,
                                     termination_id=None,
                                     use_eod_for_early_termination=False)
        eod = free_toks[8]  # a token greedy decode WILL emit
        ref_toks, _, ref_len = _reference(
            model, params, prompt, 16, termination_id=eod,
            use_eod_for_early_termination=True)
        eng = _engine(model, params, max_context=32, termination_id=eod)
        req = eng.submit(prompt, 16, top_k=1)
        eng.drain()
        toks, _ = req.result(timeout=5)
        assert toks == ref_toks[:ref_len]
        assert toks[-1] == eod


class TestChunkedPrefill:
    """ISSUE 4: mixed prefill+decode scheduling over the paged pool."""

    def test_exact_match_across_chunk_boundaries(self, tiny_model):
        """Acceptance: the greedy TOKEN stream is bitwise that of the
        whole-batch engine regardless of chunk placement — widths below
        / at / above the 16-token page (4 splits mid-page) and a width
        covering whole prompts in one chunk — and logprobs match to one
        fp32 ulp. (Logprobs are bitwise too whenever the chunk width
        equals the reference prefill shape; this CPU harness splits the
        host into 8 virtual devices, and XLA's thread-dependent matmul
        blocking can flip the last mantissa bit between a width-4 chunk
        and the width-16 reference forward — shape luck, not a
        scheduling difference, so the pin is tokens-bitwise +
        logprobs-to-1-ulp.)"""
        model, params = tiny_model
        rs = np.random.RandomState(21)
        prompts = [list(rs.randint(2, 256, n)) for n in (5, 9, 3, 17)]
        gens = [6, 4, 8, 5]
        refs = [_reference(model, params, p, g, termination_id=None,
                           use_eod_for_early_termination=False)
                for p, g in zip(prompts, gens)]
        for chunk in (4, 8, 16, 64):
            eng = _engine(model, params, prefill_chunk_tokens=chunk)
            reqs = [eng.submit(p, g, top_k=1, return_log_probs=True)
                    for p, g in zip(prompts, gens)]
            eng.drain()
            for i, (req, (ref_toks, ref_lp, _)) in enumerate(
                    zip(reqs, refs)):
                toks, lps = req.result(timeout=5)
                assert toks == ref_toks, (chunk, i)
                np.testing.assert_allclose(
                    np.asarray(lps, np.float32),
                    ref_lp[:len(toks) - 1].astype(np.float32),
                    rtol=0, atol=1e-6,
                    err_msg=f"chunk={chunk} req={i}")

    def test_whole_prompt_mode_still_exact(self, tiny_model):
        """prefill_chunk_tokens=0 restores whole-prompt admission and
        its exactness (the pre-ISSUE-4 path must not rot)."""
        model, params = tiny_model
        rs = np.random.RandomState(22)
        p = list(rs.randint(2, 256, 9))
        eng = _engine(model, params, prefill_chunk_tokens=0)
        req = eng.submit(p, 5, top_k=1, return_log_probs=True)
        eng.drain()
        ref_toks, ref_lp, _ = _reference(
            model, params, p, 5, termination_id=None,
            use_eod_for_early_termination=False)
        toks, lps = req.result(5)
        assert toks == ref_toks
        np.testing.assert_array_equal(
            np.asarray(lps, np.float32),
            ref_lp[:len(toks) - 1].astype(np.float32))

    def test_interference_bound_during_long_admission(self, tiny_model):
        """Acceptance: while a max-length prompt admits, NO round's
        prefill span exceeds the token budget, and every admission
        round advances the in-flight decode slot (the structural
        win chunking exists for) — pinned on the engine's own
        round-accounting trail."""
        model, params = tiny_model
        chunk = 8
        eng = _engine(model, params, max_context=64,
                      prefill_chunk_tokens=chunk)
        rs = np.random.RandomState(23)
        r1 = eng.submit(list(rs.randint(2, 256, 4)), 30, top_k=1)
        while r1.t_first == 0:
            eng.step()
        s1 = next(s for s in eng._slots if s.req is r1)
        gen_before = s1.generated
        base = len(eng._round_log)
        long_prompt = list(rs.randint(2, 256, 40))  # fills 3 pages
        r2 = eng.submit(long_prompt, 8, top_k=1)
        while r2.t_admit == 0 or any(s.prefilling for s in eng._slots):
            eng.step()
        mixed = [e for e in list(eng._round_log)[base:]
                 if e["prefill_tokens"] > 0]
        assert len(mixed) == 5  # ceil(40 / 8) budget-bounded rounds
        assert all(e["prefill_tokens"] <= chunk for e in mixed)
        assert all(e["decode_slots"] == 1 for e in mixed)
        assert s1.generated - gen_before >= len(mixed)
        eng.drain()
        # exactness under interference, both requests
        for p, g, r in ((r1.prompt, 30, r1), (long_prompt, 8, r2)):
            ref_toks, _, _ = _reference(
                model, params, list(p), g, termination_id=None,
                use_eod_for_early_termination=False)
            assert r.result(5)[0] == ref_toks

    def test_warmup_pretraces_all_greedy_buckets(self, tiny_model):
        """warmup() mints every greedy scan-horizon and mixed-width
        executable up front, is invisible to traffic (tokens still
        exact), and live greedy traffic mints nothing new."""
        model, params = tiny_model
        eng = _engine(model, params, prefill_chunk_tokens=8,
                      step_horizon=8)
        eng.warmup()
        want = {(w, True) for w in (1, 2, 4, 8)}
        # the compile-contract registry is the ONE executable counter
        # (analysis/contracts.py); the engine's fn dicts must stay thin
        # views of the same live-variant sets
        assert want <= variants("engine.decode_scan", owner=eng)
        assert want <= variants("engine.mixed_step", owner=eng)
        assert variants("engine.decode_scan", owner=eng) \
            == set(eng._step_fns)
        assert variants("engine.mixed_step", owner=eng) \
            == set(eng._mixed_fns)
        step_keys = variants("engine.decode_scan", owner=eng)
        mixed_keys = variants("engine.mixed_step", owner=eng)
        rs = np.random.RandomState(24)
        p = list(rs.randint(2, 256, 7))
        req = eng.submit(p, 6, top_k=1)
        eng.drain()
        assert variants("engine.decode_scan", owner=eng) == step_keys
        assert variants("engine.mixed_step", owner=eng) == mixed_keys
        ref_toks, _, _ = _reference(
            model, params, p, 6, termination_id=None,
            use_eod_for_early_termination=False)
        assert req.result(5)[0] == ref_toks

    def test_prefill_cache_lru_bounded(self, tiny_model, caplog):
        """Whole-prompt mode's per-bucket prefill executables are
        LRU-bounded with requeue-on-hit and a loud eviction warning
        (the pp decode cache contract)."""
        import logging

        model, params = tiny_model
        eng = _engine(model, params, prefill_chunk_tokens=0)
        with caplog.at_level(logging.WARNING,
                             logger="megatron_llm_tpu.inference.engine"):
            for plen in range(1, 12):
                eng._prefill_fn(plen)
        assert len(eng._prefill_fns) == eng._PREFILL_CACHE_CAP
        # eviction releases its variant: the registry's LIVE count IS
        # the cache occupancy (the contract's whole point)
        assert variants("engine.prefill_bucket", owner=eng) \
            == set(eng._prefill_fns)
        assert any("evicting LRU bucket" in r.message
                   for r in caplog.records)
        # requeue-on-hit: touching the LRU head saves it
        head = next(iter(eng._prefill_fns))
        eng._prefill_fn(head)
        eng._prefill_fn(99)
        assert head in eng._prefill_fns
        assert head in variants("engine.prefill_bucket", owner=eng)

    def test_latency_gauges_flow(self, tiny_model):
        """ttft/decode-latency gauges populate and ride the timers
        path next to the ISSUE-3 counters."""
        from megatron_llm_tpu.training.timers import Timers

        model, params = tiny_model
        eng = _engine(model, params, prefill_chunk_tokens=8)
        eng.submit([3, 4, 5, 6, 7], 4, top_k=1)
        eng.drain()
        c = eng.counters()
        assert c["serve_ttft_p50_ms"] > 0
        assert c["serve_ttft_p95_ms"] >= c["serve_ttft_p50_ms"]
        assert c["serve_decode_p95_ms"] > 0
        assert c["serve_prefill_tokens"] == 5
        timers = Timers()
        eng.export_gauges(timers)
        g = timers.gauges()
        for key in ("serve_ttft_p50_ms", "serve_ttft_p95_ms",
                    "serve_decode_p95_ms", "serve_prefill_tokens"):
            assert key in g


class TestKernelParity:
    def test_paged_kernel_engine_matches_xla_engine(self):
        """Same traffic through a kernel-on (interpreted Pallas paged)
        and a kernel-off engine: identical tokens, logprobs to 1e-5."""
        import dataclasses

        cfg = tiny_config(
            hidden_size=512, num_attention_heads=4,
            num_attention_heads_kv=2, kv_channels=128,
            ffn_hidden_size=256, compute_dtype=jnp.float32,
            use_decode_attn=True, decode_attn_interpret=kernel_interpret_mode(),
            decode_attn_min_cache=0,
        )
        model_on = LlamaModel(cfg)
        params = model_on.init(jax.random.key(7))
        model_off = LlamaModel(
            dataclasses.replace(cfg, use_decode_attn=False))
        rs = np.random.RandomState(1)
        prompts = [list(rs.randint(2, 256, n)) for n in (5, 11)]
        outs = {}
        for name, m in (("kernel", model_on), ("xla", model_off)):
            eng = _engine(m, params)
            reqs = [eng.submit(p, 5, top_k=1, return_log_probs=True)
                    for p in prompts]
            eng.drain()
            outs[name] = [r.result(5) for r in reqs]
        for a, b in zip(outs["kernel"], outs["xla"]):
            assert a[0] == b[0]
            np.testing.assert_allclose(a[1], b[1], atol=1e-5)


class TestScheduling:
    def test_pages_retire_to_free_list(self, tiny_model):
        model, params = tiny_model
        eng = _engine(model, params)
        total_pages = eng.num_pages - 1
        rs = np.random.RandomState(4)
        reqs = [eng.submit(list(rs.randint(2, 256, 5)), 4)
                for _ in range(5)]
        saw_full_occupancy = False
        while eng.step():
            c = eng.counters()
            assert c["serve_pages_in_use"] + c["serve_pages_free"] \
                == total_pages
            saw_full_occupancy |= c["serve_slot_occupancy"] == 1.0
        assert saw_full_occupancy  # continuous batching actually batched
        c = eng.counters()
        assert c["serve_pages_in_use"] == 0
        assert c["serve_pages_free"] == total_pages
        assert c["serve_admitted"] == c["serve_retired"] == 5
        assert sorted(eng._free_pages) == list(range(1, eng.num_pages))
        for r in reqs:
            assert r.done.is_set()

    def test_page_exhaustion_blocks_admission_then_recovers(
            self, tiny_model):
        """A page budget below the full reservation: the queue's head
        waits for pages (no deadlock, FIFO preserved) and is admitted
        as soon as a retirement frees them."""
        model, params = tiny_model
        # 3 slots but only 4 pages: each request needs 2 pages
        # (5 prompt + 20 gen = 25 tokens > one 16-token page), so the
        # third request has a free SLOT and must still wait for PAGES
        eng = _engine(model, params, slots=3, max_context=32,
                      page_budget=4 * 16)
        rs = np.random.RandomState(5)
        reqs = [eng.submit(list(rs.randint(2, 256, 5)), 20)
                for _ in range(3)]
        eng.step()
        c = eng.counters()
        assert c["serve_admitted"] == 2 and c["serve_queue_depth"] == 1
        assert c["serve_pages_free"] == 0
        eng.drain()
        assert eng.counters()["serve_retired"] == 3
        done_at = [r.t_done for r in reqs]
        assert done_at[2] >= max(done_at[:2])  # FIFO head-of-line

    def test_mid_flight_admission_exact(self, tiny_model):
        """A request admitted into a slot mid-flight (after a
        retirement) still matches its solo reference exactly."""
        model, params = tiny_model
        eng = _engine(model, params, slots=1)
        rs = np.random.RandomState(6)
        p1 = list(rs.randint(2, 256, 5))
        p2 = list(rs.randint(2, 256, 9))
        r1 = eng.submit(p1, 3, top_k=1)
        r2 = eng.submit(p2, 4, top_k=1)
        eng.drain()
        for p, g, r in ((p1, 3, r1), (p2, 4, r2)):
            ref_toks, _, _ = _reference(
                model, params, p, g, termination_id=None,
                use_eod_for_early_termination=False)
            assert r.result(5)[0] == ref_toks

    def test_queue_full_raises(self, tiny_model):
        model, params = tiny_model
        eng = _engine(model, params, max_queue=2)
        eng.submit([3, 4], 2)
        eng.submit([5, 6], 2)
        with pytest.raises(QueueFull):
            eng.submit([7, 8], 2)
        eng.drain()

    def test_oversize_request_rejected(self, tiny_model):
        model, params = tiny_model
        eng = _engine(model, params, max_context=32)
        with pytest.raises(ValueError):
            eng.submit(list(range(2, 30)), 8)  # 28 + 8 > 32
        # fits max_context but not the (oversubscribed) page pool: must
        # be rejected at submit, or it would starve the FIFO forever
        eng = _engine(model, params, max_context=64,
                      page_budget=2 * 16)
        with pytest.raises(ValueError, match="pages"):
            eng.submit(list(range(2, 30)), 20)  # 48 tokens > 32 pooled
        eng.submit(list(range(2, 20)), 8)  # 26 tokens fits
        eng.drain()

    def test_step_error_fails_requests_and_stop_does_not_hang(
            self, tiny_model, monkeypatch):
        """A fatal error on the serve loop must fail every waiter
        loudly (no hung result(), no deadlocked stop) and poison later
        submits."""
        model, params = tiny_model
        eng = _engine(model, params)

        def boom():
            raise RuntimeError("device fell over")

        monkeypatch.setattr(eng, "step", boom)
        req = eng.submit([3, 4, 5], 2)  # queued before the loop starts
        eng.start()
        assert req.done.wait(timeout=10)
        with pytest.raises(RuntimeError, match="device fell over"):
            req.result(timeout=1)
        eng.stop(drain=True)  # must return, not spin on the dead loop
        with pytest.raises(RuntimeError, match="engine is stopped"):
            eng.submit([3, 4], 1)


class TestStreamingAndCancel:
    """ISSUE 6: the per-request token queue (the SSE layer's feed) and
    cancel() — the engine half of mid-stream disconnect handling."""

    def test_stream_queue_orders_tokens_then_sentinel(self, tiny_model):
        model, params = tiny_model
        eng = _engine(model, params)
        r = eng.submit([3, 4, 5, 6], 5, top_k=1, stream=True)
        eng.drain()
        got = []
        while True:
            t = r.stream_q.get(timeout=1)
            if t is None:
                break
            got.append(t)
        toks, _ = r.result(5)
        assert got == toks[4:]  # generated tokens, in order

    def test_cancel_queued_fails_waiter_and_closes_stream(
            self, tiny_model):
        model, params = tiny_model
        eng = _engine(model, params)
        r = eng.submit([3, 4, 5], 4, top_k=1, stream=True)
        eng.cancel(r)
        assert r.done.is_set()
        assert r.stream_q.get(timeout=1) is None
        with pytest.raises(RuntimeError, match="cancelled"):
            r.result(1)
        assert not eng.step()  # nothing left to schedule

    def test_cancel_running_retires_slot_and_reclaims_pages(
            self, tiny_model):
        model, params = tiny_model
        eng = _engine(model, params)
        r = eng.submit([3, 4, 5, 6], 30, top_k=1, stream=True)
        while r.t_first == 0:
            eng.step()
        eng.cancel(r)
        eng.step()  # the scheduler reaps it
        assert r.done.is_set()
        with pytest.raises(RuntimeError, match="cancelled"):
            r.result(1)
        c = eng.counters()
        assert c["serve_pages_in_use"] == 0
        assert c["serve_cancelled"] == 1
        # the stream closed with the sentinel after the booked tokens
        drained = []
        while True:
            t = r.stream_q.get(timeout=1)
            if t is None:
                break
            drained.append(t)
        assert drained == r.tokens[4:]
        # cancel is idempotent on finished requests
        eng.cancel(r)
        assert eng.counters()["serve_cancelled"] == 1


class TestSampling:
    def test_seed_determinism_independent_of_slot(self, tiny_model):
        """The same (prompt, seed) produces the same stream no matter
        which slot it lands in or what its neighbours do."""
        model, params = tiny_model
        rs = np.random.RandomState(8)
        p1 = list(rs.randint(2, 256, 5))
        p2 = list(rs.randint(2, 256, 9))

        eng = _engine(model, params)
        a1 = eng.submit(p1, 5, top_k=0, top_p=0.9, temperature=0.8,
                        seed=3)
        a2 = eng.submit(p2, 5, top_k=5, temperature=1.2, seed=4)
        eng.drain()

        eng2 = _engine(model, params)
        b2 = eng2.submit(p2, 5, top_k=5, temperature=1.2, seed=4)
        b1 = eng2.submit(p1, 5, top_k=0, top_p=0.9, temperature=0.8,
                         seed=3)
        eng2.drain()
        assert a1.result(5)[0] == b1.result(5)[0]
        assert a2.result(5)[0] == b2.result(5)[0]

    def test_vocab_clamp(self, tiny_model):
        model, params = tiny_model
        eng = _engine(model, params, vocab_size=200)
        rs = np.random.RandomState(9)
        reqs = [eng.submit(list(rs.randint(2, 200, 4)), 8, top_k=0,
                           top_p=0.9, temperature=1.5, seed=s)
                for s in range(3)]
        eng.drain()
        for r in reqs:
            assert max(r.result(5)[0]) < 200


class TestServeLoopAndCounters:
    def test_background_loop_and_graceful_drain(self, tiny_model):
        model, params = tiny_model
        eng = _engine(model, params)
        eng.start()
        rs = np.random.RandomState(10)
        reqs = [eng.submit(list(rs.randint(2, 256, 5)), 4)
                for _ in range(3)]
        # stop(drain=True) must finish everything before returning
        eng.stop(drain=True)
        for r in reqs:
            assert r.done.is_set() and r.error is None
            assert len(r.tokens) == 5 + 4

    def test_submit_from_threads_serializes(self, tiny_model):
        model, params = tiny_model
        eng = _engine(model, params, max_queue=32)
        eng.start()
        rs = np.random.RandomState(11)
        prompts = [list(rs.randint(2, 256, 4 + i)) for i in range(6)]
        results = [None] * 6

        def worker(i):
            req = eng.submit(prompts[i], 3, top_k=1)
            results[i] = req.result(timeout=60)[0]

        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(6)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        eng.stop(drain=True)
        for i in range(6):
            ref_toks, _, _ = _reference(
                model, params, prompts[i], 3, termination_id=None,
                use_eod_for_early_termination=False)
            assert results[i] == ref_toks

    def test_counters_export_through_timers_gauges(self, tiny_model):
        from megatron_llm_tpu.training.timers import Timers

        model, params = tiny_model
        eng = _engine(model, params)
        eng.submit([3, 4, 5], 2)
        eng.drain()
        timers = Timers()
        eng.export_gauges(timers)
        g = timers.gauges()
        assert g["serve_admitted"] == 1 and g["serve_retired"] == 1
        assert g["serve_pages_in_use"] == 0
        assert g["serve_tok_s"] > 0
