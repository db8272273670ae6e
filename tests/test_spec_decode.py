"""Speculative decoding on the paged pool (ISSUE 6): prompt-lookup
drafts verified by one width-(k+1) ragged chunk per slot.

Pinned here:
- ISSUE 6 acceptance: greedy token streams are BITWISE identical vs
  generate_tokens with speculative decoding ON (any k) and OFF — on
  traffic the drafter accelerates (greedy cycles, where acceptance is
  high) AND on traffic it can't (random continuations, acceptance ~0);
  logprobs match to one fp32 ulp (the chunk-width caveat of
  test_engine.py::test_exact_match_across_chunk_boundaries);
- spec composes with prefix sharing (both ISSUE 6 features on, still
  bitwise);
- executable-count regression guard: all spec traffic verifies through
  ONE width-(spec_decode_k+1) executable per greedy specialization —
  draft lengths pad via chunk_lens, never minting new buckets;
- rejection rollback: budget caps and eod inside an accepted run book
  exactly the right tokens (stale chunk positions never surface);
- sampled requests ride spec rounds as plain decode rows with their
  usual seed determinism;
- acceptance-rate gauges flow through counters()/export_gauges.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from megatron_llm_tpu.analysis.contracts import variants
from megatron_llm_tpu.config import tiny_config
from megatron_llm_tpu.inference.engine import DecodeEngine
from megatron_llm_tpu.inference.generation import (
    bucket_prefill_len,
    generate_tokens,
)
from megatron_llm_tpu.models import LlamaModel

# greedy decode from this prompt settles into a 3-cycle on the seed-7
# tiny model (probed; pinned by test_cycle_traffic_accepts below) —
# exactly the traffic prompt-lookup drafting exists for
CYCLE_PROMPT = [9, 206, 145, 115]


@pytest.fixture(scope="module")
def tiny_model():
    cfg = tiny_config(compute_dtype=jnp.float32, use_decode_attn=False)
    model = LlamaModel(cfg)
    params = model.init(jax.random.key(7))
    return model, params


def _engine(model, params, **over):
    kw = dict(slots=2, page_size=16, max_context=64, max_queue=8,
              termination_id=None, vocab_size=256, spec_decode_k=4)
    kw.update(over)
    return DecodeEngine(model, params, **kw)


def _reference(model, params, prompt, gen, **kw):
    kw.setdefault("termination_id", None)
    kw.setdefault("use_eod_for_early_termination", False)
    max_len = len(prompt) + gen
    buf = np.zeros((1, max_len), np.int32)
    buf[0, :len(prompt)] = prompt
    out = generate_tokens(
        model, params, jnp.asarray(buf),
        jnp.asarray([len(prompt)], np.int32),
        prefill_len=bucket_prefill_len(len(prompt)), rng=None, top_k=1,
        return_log_probs=True, vocab_size=256, **kw,
    )
    return (list(np.asarray(out.tokens)[0]),
            np.asarray(out.log_probs)[0],
            int(np.asarray(out.lengths)[0]))


class TestGreedyParity:
    def test_cycle_traffic_accepts_and_stays_bitwise(self, tiny_model):
        """Acceptance: spec ON at k in {1, 2, 4} vs spec OFF vs
        generate_tokens — bitwise tokens, 1-ulp logprobs — on traffic
        where drafts actually accept (the greedy cycle)."""
        model, params = tiny_model
        ref_toks, ref_lp, _ = _reference(model, params, CYCLE_PROMPT, 40)
        off = _engine(model, params, spec_decode_k=0)
        r = off.submit(CYCLE_PROMPT, 40, top_k=1, return_log_probs=True)
        off.drain()
        off_toks, off_lps = r.result(5)
        assert off_toks == ref_toks
        for k in (1, 2, 4):
            eng = _engine(model, params, spec_decode_k=k)
            r = eng.submit(CYCLE_PROMPT, 40, top_k=1,
                           return_log_probs=True)
            eng.drain()
            toks, lps = r.result(5)
            assert toks == ref_toks, k
            np.testing.assert_allclose(
                np.asarray(lps, np.float32),
                ref_lp[:len(toks) - 1].astype(np.float32),
                rtol=0, atol=1e-6, err_msg=f"k={k}")
            c = eng.counters()
            assert c["serve_spec_rounds"] > 0, k
            assert c["serve_spec_accepted"] > 0, k  # the cycle accepts
            # fewer dispatches than tokens: the point of the feature
            assert c["serve_steps"] < 4 + 40, k

    def test_random_traffic_stays_bitwise(self, tiny_model):
        """Low/zero acceptance must not corrupt anything: random
        prompts where the drafter's proposals mostly reject."""
        model, params = tiny_model
        rs = np.random.RandomState(11)
        # repeated bigrams in the PROMPT make the drafter fire, but the
        # model's continuation won't match -> rejection path exercised
        prompts = [
            list(rs.randint(2, 256, 5)) * 2,
            list(rs.randint(2, 256, 9)),
            [7, 8] * 6,
        ]
        eng = _engine(model, params, spec_decode_k=3)
        reqs = [eng.submit(p, 8, top_k=1, return_log_probs=True)
                for p in prompts]
        eng.drain()
        for p, r in zip(prompts, reqs):
            ref_toks, ref_lp, _ = _reference(model, params, p, 8)
            toks, lps = r.result(5)
            assert toks == ref_toks, p
            np.testing.assert_allclose(
                np.asarray(lps, np.float32),
                ref_lp[:len(toks) - 1].astype(np.float32),
                rtol=0, atol=1e-6)

    def test_spec_composes_with_prefix_sharing(self, tiny_model):
        """Both ISSUE 6 features on: cache-hit admission followed by
        speculative generation, bitwise."""
        model, params = tiny_model
        rs = np.random.RandomState(12)
        sysp = list(rs.randint(2, 256, 32))
        eng = _engine(model, params, spec_decode_k=4, prefix_cache=True)
        p1 = sysp + CYCLE_PROMPT
        r1 = eng.submit(p1, 20, top_k=1)
        eng.drain()
        p2 = sysp + list(rs.randint(2, 256, 3))
        r2 = eng.submit(p2, 12, top_k=1)
        eng.drain()
        assert eng.counters()["serve_prefix_hit_tokens"] >= 32
        assert r1.result(5)[0] == _reference(model, params, p1, 20)[0]
        assert r2.result(5)[0] == _reference(model, params, p2, 12)[0]

    def test_eod_inside_accepted_run(self, tiny_model):
        """An eod token emitted mid-accepted-run retires the slot right
        there — the booked stream equals the reference's eod-truncated
        stream, stale chunk tail discarded."""
        model, params = tiny_model
        free_toks, _, _ = _reference(model, params, CYCLE_PROMPT, 40)
        eod = free_toks[-1]  # a cycle member: will appear mid-run
        ref_toks, _, ref_len = _reference(
            model, params, CYCLE_PROMPT, 40, termination_id=eod,
            use_eod_for_early_termination=True)
        eng = _engine(model, params, spec_decode_k=4,
                      termination_id=eod)
        r = eng.submit(CYCLE_PROMPT, 40, top_k=1)
        eng.drain()
        toks, _ = r.result(5)
        assert toks == ref_toks[:ref_len]
        assert toks[-1] == eod

    def test_drafter_drafts_on_period_one_repetition(self, tiny_model):
        """A constant-token run must still draft: the NEWEST bigram
        occurrence sits at the tail with an empty continuation, so the
        drafter falls back to an older occurrence — and the stream
        stays bitwise."""
        model, params = tiny_model
        eng = _engine(model, params, spec_decode_k=4)
        r = eng.submit([7] * 8, 6, top_k=1)
        while any(s.prefilling for s in eng._slots) or not any(
                s.req is r for s in eng._slots):
            eng.step()
        si = next(i for i, s in enumerate(eng._slots) if s.req is r)
        assert eng._draft(si) == [7] * 4
        eng.drain()
        assert r.result(5)[0] == _reference(model, params, [7] * 8, 6)[0]

    def test_budget_cap_books_exactly(self, tiny_model):
        """tokens_to_generate caps the accepted run: draft capping
        guarantees the chunk never writes past the reserved reach, and
        booking stops exactly at the budget."""
        model, params = tiny_model
        # warm the cycle into the drafter's history, then a tiny budget
        eng = _engine(model, params, spec_decode_k=4)
        for gen in (2, 3, 17):
            r = eng.submit(CYCLE_PROMPT, gen, top_k=1)
            eng.drain()
            ref_toks, _, _ = _reference(model, params, CYCLE_PROMPT, gen)
            assert r.result(5)[0] == ref_toks
            assert len(r.result(5)[0]) == len(CYCLE_PROMPT) + gen


class TestSchedulingAndGuards:
    def test_executable_count_guard(self, tiny_model):
        """The width-k verification buckets are a FIXED set: every spec
        round verifies through width spec_decode_k + 1 — greedy-only
        traffic mints exactly {(k+1, True)}, mixed traffic adds only
        (k+1, False), and more traffic mints nothing new."""
        model, params = tiny_model
        k = 4
        eng = _engine(model, params, spec_decode_k=k)
        rs = np.random.RandomState(13)
        for gen in (10, 24, 40):
            eng.submit(CYCLE_PROMPT, gen, top_k=1)
            eng.submit([7, 8] * 4, gen // 2, top_k=1)
            eng.drain()
        # the compile-contract registry is the ONE executable counter
        # (analysis/contracts.py, contract "engine.spec_verify"); the
        # engine's _spec_fns dict must stay a thin view of it
        assert variants("engine.spec_verify", owner=eng) \
            == {(k + 1, True)}
        assert set(eng._spec_fns) == {(k + 1, True)}
        # sampled alongside greedy: ONE more specialization, same width
        eng.submit(CYCLE_PROMPT, 16, top_k=1)
        eng.submit(list(rs.randint(2, 256, 6)), 6, top_k=5, seed=3)
        eng.drain()
        assert variants("engine.spec_verify", owner=eng) \
            <= {(k + 1, True), (k + 1, False)}
        assert set(eng._spec_fns) \
            == variants("engine.spec_verify", owner=eng)
        minted = variants("engine.spec_verify", owner=eng)
        for _ in range(2):  # steady-state traffic mints nothing new
            eng.submit(CYCLE_PROMPT, 12, top_k=1)
            eng.drain()
        assert variants("engine.spec_verify", owner=eng) == minted

    def test_warmup_pretraces_spec_executable(self, tiny_model):
        model, params = tiny_model
        k = 3
        eng = _engine(model, params, spec_decode_k=k,
                      prefill_chunk_tokens=8, step_horizon=4)
        eng.warmup()
        assert (k + 1, True) in eng._spec_fns
        keys = set(eng._spec_fns)
        r = eng.submit(CYCLE_PROMPT, 20, top_k=1)
        eng.drain()
        assert set(eng._spec_fns) == keys  # greedy traffic minted none
        assert r.result(5)[0] == _reference(model, params,
                                            CYCLE_PROMPT, 20)[0]

    def test_sampled_requests_ride_spec_rounds_deterministically(
            self, tiny_model):
        """A sampled request sharing the engine with a drafting greedy
        slot rides spec rounds as a plain decode row — its stream is
        identical to the same (prompt, seed) on a spec-off engine."""
        model, params = tiny_model
        rs = np.random.RandomState(14)
        sp = list(rs.randint(2, 256, 6))

        off = _engine(model, params, spec_decode_k=0)
        ref = off.submit(sp, 10, top_k=5, temperature=1.2, seed=9)
        off.drain()

        eng = _engine(model, params, spec_decode_k=4)
        g = eng.submit(CYCLE_PROMPT, 30, top_k=1)
        s = eng.submit(sp, 10, top_k=5, temperature=1.2, seed=9)
        eng.drain()
        assert eng.counters()["serve_spec_rounds"] > 0
        assert s.result(5)[0] == ref.result(5)[0]
        assert g.result(5)[0] == _reference(model, params,
                                            CYCLE_PROMPT, 30)[0]

    def test_acceptance_gauges_flow(self, tiny_model):
        from megatron_llm_tpu.training.timers import Timers

        model, params = tiny_model
        eng = _engine(model, params, spec_decode_k=4)
        eng.submit(CYCLE_PROMPT, 30, top_k=1)
        eng.drain()
        c = eng.counters()
        assert c["serve_spec_proposed"] >= c["serve_spec_accepted"] > 0
        assert 0 < c["serve_spec_accept_rate"] <= 1
        timers = Timers()
        eng.export_gauges(timers)
        g = timers.gauges()
        for key in ("serve_spec_rounds", "serve_spec_proposed",
                    "serve_spec_accepted", "serve_spec_accept_rate"):
            assert key in g, key
