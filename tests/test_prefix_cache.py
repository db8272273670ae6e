"""Prefix-sharing scheduler (ISSUE 6 tentpole): the refcounted
page-aligned prefix cache over the paged pool.

Pinned here:
- PrefixCache unit semantics (tier-1, no model): page-aligned match
  walk with the len(prompt)-1 cap, mid-page COW candidates, insert
  dedupe, refcount-gated release, LRU leaf-first eviction that never
  touches a referenced page or a parent with live children;
- ISSUE 6 acceptance: greedy token streams are BITWISE identical vs
  generate_tokens with prefix sharing ON and OFF — including requests
  admitted onto cache-hit pages, mid-page prefix divergence (COW), and
  a prompt that exactly equals a cached prefix;
- lifecycle: two live requests map the SAME physical pages (refcount
  2), refcounts fall at retirement without freeing cached pages,
  eviction reclaims only unreferenced prefixes under pool pressure,
  and a post-eviction request falls back to unshared admission;
- return_log_probs requests bypass MATCHING (full prompt logprobs)
  but still register their pages;
- the prefix gauges ride counters()/export_gauges.
"""

import logging

import numpy as np
import pytest

from megatron_llm_tpu.inference.prefix_cache import PrefixCache

# ---------------------------------------------------------------------------
# PrefixCache unit semantics (tier-1: no model, no device)
# ---------------------------------------------------------------------------


def _seed_chain(c: PrefixCache, tokens, pages):
    """Register consecutive full pages of `tokens` as `pages`."""
    ps = c.page_size
    for i, pg in enumerate(pages):
        assert c.insert(list(tokens[: (i + 1) * ps]), pg)


class TestPrefixCacheUnit:
    def test_match_walk_cap_and_cow(self):
        c = PrefixCache(page_size=4)
        toks = list(range(1, 13))  # 3 full pages
        _seed_chain(c, toks, [11, 12, 13])

        # identical prompt: the cap (len-1) forbids a full-cover hit —
        # 2 full pages + COW on the last with valid = 11
        m = c.lookup(list(toks))
        assert m.pages == [11, 12] and m.matched == 11
        assert m.cow_src == 13

        # longer prompt sharing all 3 pages: full hits, no COW needed
        m = c.lookup(toks + [99, 98])
        assert m.pages == [11, 12, 13] and m.matched == 12
        assert m.cow_src is None

        # mid-page divergence: 9 shared tokens -> 2 full + 1-token COW
        m = c.lookup(toks[:9] + [99, 98, 97])
        assert m.pages == [11, 12] and m.matched == 9
        assert m.cow_src == 13

        # divergence inside the FIRST page: COW only
        m = c.lookup([1, 2, 3, 99, 98])
        assert m.pages == [] and m.matched == 3 and m.cow_src == 11

        # nothing shared
        m = c.lookup([99, 98, 97, 96, 95])
        assert m.pages == [] and m.matched == 0 and m.cow_src is None

    def test_insert_dedupe_and_note_accounting(self):
        c = PrefixCache(page_size=4)
        assert c.insert([1, 2, 3, 4], 7)
        assert not c.insert([1, 2, 3, 4], 8)  # lost race: stays untracked
        assert c.owns(7) and not c.owns(8)
        c.note(10, 4)
        c.note(10, 0)
        s = c.stats()
        assert s["prefix_hits"] == 1 and s["prefix_lookups"] == 2
        assert s["prefix_hit_rate"] == pytest.approx(4 / 20)

    def test_refcount_gates_release(self):
        c = PrefixCache(page_size=4)
        _seed_chain(c, list(range(8)), [5, 6])
        # drop the registering slot's references: retained, evictable
        assert c.release(5) is True and c.release(6) is True
        m = c.lookup(list(range(8)) + [99])
        c.acquire(m)
        c.acquire(m)  # two slots share
        assert c.shared_pages == 2
        assert c.release(5) is True and c.release(6) is True  # slot 1 out
        assert c.shared_pages == 0
        assert c.referenced_pages == 2  # slot 2 still maps both
        assert c.release(5) is True and c.release(6) is True  # slot 2 out
        assert c.referenced_pages == 0
        assert c.cached_pages == 2  # retained, never freed to caller
        # untracked page: caller keeps it
        assert c.release(42) is False

    def test_evict_lru_leaves_first_never_referenced(self, caplog):
        c = PrefixCache(page_size=4)
        _seed_chain(c, list(range(8)), [5, 6])  # parent 5, child 6
        _seed_chain(c, [50, 51, 52, 53], [7])
        for pg in (5, 6, 7):
            assert c.release(pg) is True  # all unreferenced now
        # re-reference the [50..] entry through a lookup+acquire
        m = c.lookup([50, 51, 52, 53, 99])
        c.acquire(m)
        with caplog.at_level(
                logging.WARNING,
                logger="megatron_llm_tpu.inference.prefix_cache"):
            freed = c.evict(10)
        # referenced page 7 survives; child 6 must go before parent 5
        assert freed == [6, 5]
        assert c.owns(7) and not c.owns(6) and not c.owns(5)
        assert any("evicted" in r.message for r in caplog.records)
        assert c.evicted_pages == 2
        # parent pinned by child: re-seed and evict ONE page -> the leaf
        _seed_chain(c, list(range(8)), [5, 6])
        c.release(5), c.release(6)
        assert c.evict(1) == [6]

    def test_evict_lru_order(self):
        c = PrefixCache(page_size=4)
        c.insert([1, 2, 3, 4], 5)
        c.insert([9, 9, 9, 9], 6)
        c.release(5), c.release(6)
        # touch the older entry via lookup: it becomes most-recent
        c.lookup([1, 2, 3, 4, 7])
        assert c.evict(1) == [6]


# ---------------------------------------------------------------------------
# Engine lifecycle (tiny model; slow)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def tiny_model():
    import jax
    import jax.numpy as jnp

    from megatron_llm_tpu.config import tiny_config
    from megatron_llm_tpu.models import LlamaModel

    cfg = tiny_config(compute_dtype=jnp.float32, use_decode_attn=False)
    model = LlamaModel(cfg)
    params = model.init(jax.random.key(7))
    return model, params


def _engine(model, params, **over):
    from megatron_llm_tpu.inference.engine import DecodeEngine

    kw = dict(slots=2, page_size=16, max_context=64, max_queue=8,
              termination_id=None, vocab_size=256, prefix_cache=True)
    kw.update(over)
    return DecodeEngine(model, params, **kw)


def _reference(model, params, prompt, gen):
    import jax.numpy as jnp

    from megatron_llm_tpu.inference.generation import (
        bucket_prefill_len,
        generate_tokens,
    )

    max_len = len(prompt) + gen
    buf = np.zeros((1, max_len), np.int32)
    buf[0, :len(prompt)] = prompt
    out = generate_tokens(
        model, params, jnp.asarray(buf),
        jnp.asarray([len(prompt)], np.int32),
        prefill_len=bucket_prefill_len(len(prompt)), rng=None, top_k=1,
        return_log_probs=True, vocab_size=256, termination_id=None,
        use_eod_for_early_termination=False,
    )
    return (list(np.asarray(out.tokens)[0]),
            np.asarray(out.log_probs)[0])


@pytest.fixture(scope="module")
def sys_prompt():
    rs = np.random.RandomState(0)
    return list(rs.randint(2, 256, 48))  # 3 full 16-token pages


@pytest.mark.slow
class TestEnginePrefixSharing:
    def test_bitwise_with_sharing_on_off_and_vs_reference(
            self, tiny_model, sys_prompt):
        """Acceptance: greedy token streams are bitwise identical with
        prefix sharing ON and OFF and vs generate_tokens — for the
        cache-miss request, cache-hit requests, and a mid-page
        divergence."""
        model, params = tiny_model
        rs = np.random.RandomState(1)
        prompts = [
            sys_prompt + list(rs.randint(2, 256, 6)),   # miss, registers
            sys_prompt + list(rs.randint(2, 256, 4)),   # full-page hits
            sys_prompt[:36] + list(rs.randint(2, 256, 8)),  # COW mid-page
        ]
        outs = {}
        for share in (True, False):
            eng = _engine(model, params, prefix_cache=share)
            toks = []
            for p in prompts:  # sequential: later prompts see the cache
                r = eng.submit(p, 6, top_k=1)
                eng.drain()
                toks.append(r.result(5)[0])
            outs[share] = toks
        for p, on, off in zip(prompts, outs[True], outs[False]):
            ref_toks, _ = _reference(model, params, p, 6)
            assert on == off == ref_toks
        # and sharing actually happened
        eng = _engine(model, params)
        for p in prompts:
            eng.submit(p, 6, top_k=1)
            eng.drain()
        c = eng.counters()
        assert c["serve_prefix_hit_tokens"] >= 48 + 36
        assert c["serve_prefix_cow_copies"] == 1
        # a hit token is not prefilled again
        assert c["serve_prefill_tokens"] == (
            sum(len(p) for p in prompts) - c["serve_prefix_hit_tokens"])

    def test_live_requests_share_physical_pages_refcount(
            self, tiny_model, sys_prompt):
        """Two in-flight requests with the same system prompt map the
        SAME pool pages (refcount 2 -> shared_pages gauge), and
        retirement drops refcounts without freeing cached pages."""
        model, params = tiny_model
        rs = np.random.RandomState(2)
        eng = _engine(model, params)
        p1 = sys_prompt + list(rs.randint(2, 256, 4))
        r1 = eng.submit(p1, 12, top_k=1)
        # prefill p1 completely so its prefix pages are registered
        while any(s.prefilling for s in eng._slots) or r1.t_first == 0:
            eng.step()
        p2 = sys_prompt + list(rs.randint(2, 256, 6))
        r2 = eng.submit(p2, 4, top_k=1)
        saw_shared = 0
        while not (r1.done.is_set() and r2.done.is_set()):
            eng.step()
            saw_shared = max(saw_shared,
                             eng.counters()["serve_prefix_shared_pages"])
        assert saw_shared == 3  # the 3 full sys-prompt pages, ref 2
        # both slots' page tables pointed at the same physical pages
        assert r2.result(5)[0] == _reference(model, params, p2, 4)[0]
        assert r1.result(5)[0] == _reference(model, params, p1, 12)[0]
        # retired: no references, pages retained in cache (not free)
        c = eng.counters()
        assert c["serve_prefix_shared_pages"] == 0
        assert c["serve_prefix_cached_pages"] >= 3
        total = eng.num_pages - 1
        assert c["serve_pages_free"] == total - c["serve_prefix_cached_pages"]

    def test_prompt_exactly_equals_cached_prefix(self, tiny_model,
                                                 sys_prompt):
        """A prompt identical to a cached prefix still prefills its
        LAST token (the engine needs those logits): the final page
        rides a COW copy at valid = len(prompt) - 1, bitwise."""
        model, params = tiny_model
        eng = _engine(model, params)
        r1 = eng.submit(list(sys_prompt), 6, top_k=1)
        eng.drain()
        r2 = eng.submit(list(sys_prompt), 6, top_k=1)
        eng.drain()
        ref_toks, _ = _reference(model, params, list(sys_prompt), 6)
        assert r1.result(5)[0] == ref_toks
        assert r2.result(5)[0] == ref_toks
        c = eng.counters()
        assert c["serve_prefix_cow_copies"] == 1
        assert c["serve_prefix_hit_tokens"] == 47  # 2 pages + 15 COW rows

    def test_eviction_under_pressure_never_frees_referenced(
            self, tiny_model, sys_prompt, caplog):
        """A pool too small to hold cache + new traffic evicts
        UNREFERENCED cached prefixes (loud) and never a page a live
        slot maps; the evicted-prefix request then admits unshared and
        stays exact."""
        model, params = tiny_model
        # pool: 6 pages. r1 (48+6+10 tok) needs 4. cache keeps 3.
        eng = _engine(model, params, slots=2, max_context=64,
                      page_budget=6 * 16)
        rs = np.random.RandomState(3)
        p1 = sys_prompt + list(rs.randint(2, 256, 6))
        r1 = eng.submit(p1, 10, top_k=1)
        eng.drain()
        c = eng.counters()
        assert c["serve_prefix_cached_pages"] == 3
        # r2 shares the prefix: needs 3 shared refs + 1 fresh; while it
        # RUNS, a colliding unique request needs 4 pages but only
        # 6 - 3(shared, referenced) - 1 = 2 are reclaimable -> it must
        # WAIT (referenced pages never evicted), then admit after r2
        # retires and its unreferenced prefix evicts.
        p2 = sys_prompt + list(rs.randint(2, 256, 8))
        r2 = eng.submit(p2, 2, top_k=1)
        uniq = list(rs.randint(2, 256, 40))
        r3 = eng.submit(uniq, 10, top_k=1)
        with caplog.at_level(
                logging.WARNING,
                logger="megatron_llm_tpu.inference.prefix_cache"):
            eng.drain()
        assert r2.result(5)[0] == _reference(model, params, p2, 2)[0]
        assert r3.result(5)[0] == _reference(model, params, uniq, 10)[0]
        assert any("evicted" in r.message for r in caplog.records)
        assert eng.counters()["serve_prefix_evicted_pages"] >= 1
        # a shared-prefix request after partial eviction admits on
        # whatever prefix survives — still bitwise
        r4 = eng.submit(p1, 4, top_k=1)
        eng.drain()
        assert r4.result(5)[0] == _reference(model, params, p1, 4)[0]
        # FULL eviction: the next shared prompt admits UNSHARED (the
        # pool-exhaustion fallback) and stays bitwise
        eng._free_pages.extend(eng._prefix.evict(eng.num_pages))
        assert eng.counters()["serve_prefix_cached_pages"] == 0
        hits_before = eng._prefix.hit_tokens
        r5 = eng.submit(p2, 3, top_k=1)
        eng.drain()
        assert r5.result(5)[0] == _reference(model, params, p2, 3)[0]
        assert eng._prefix.hit_tokens == hits_before  # nothing to hit

    def test_pool_accounting_invariant_with_cache(self, tiny_model,
                                                  sys_prompt):
        """free + referenced-by-slots + cached-unreferenced == pool,
        every round (the loud-accounting bar)."""
        model, params = tiny_model
        eng = _engine(model, params, page_budget=7 * 16, max_context=64)
        rs = np.random.RandomState(4)
        reqs = [eng.submit(sys_prompt + list(rs.randint(2, 256, 4)), 4,
                           top_k=1) for _ in range(3)]
        total = eng.num_pages - 1
        while any(not r.done.is_set() for r in reqs):
            eng.step()
            c = eng.counters()
            assert c["serve_pages_in_use"] + c["serve_pages_free"] == total
        eng.drain()

    def test_logprob_requests_bypass_matching_but_register(
            self, tiny_model, sys_prompt):
        """return_log_probs needs every prompt position's forward, so
        it never maps cached pages — but its own pages register, and
        its logprobs stay bitwise vs generate_tokens."""
        model, params = tiny_model
        eng = _engine(model, params)
        p = sys_prompt + [7, 8, 9]
        r1 = eng.submit(p, 5, top_k=1, return_log_probs=True)
        eng.drain()
        assert eng._prefix.hit_tokens == 0
        assert eng.counters()["serve_prefix_cached_pages"] == 3
        ref_toks, ref_lp = _reference(model, params, p, 5)
        toks, lps = r1.result(5)
        assert toks == ref_toks
        np.testing.assert_allclose(
            np.asarray(lps, np.float32),
            ref_lp[:len(toks) - 1].astype(np.float32), rtol=0, atol=1e-6)
        # a later logprob request ALSO bypasses (no hit) yet stays exact
        r2 = eng.submit(p, 5, top_k=1, return_log_probs=True)
        eng.drain()
        assert eng._prefix.hit_tokens == 0
        assert r2.result(5)[0] == ref_toks

    def test_whole_prompt_mode_rejects_prefix_cache(self, tiny_model):
        model, params = tiny_model
        with pytest.raises(ValueError, match="chunked admission"):
            _engine(model, params, prefill_chunk_tokens=0)

    def test_prefix_gauges_flow_through_timers(self, tiny_model,
                                               sys_prompt):
        from megatron_llm_tpu.training.timers import Timers

        model, params = tiny_model
        eng = _engine(model, params)
        for _ in range(2):
            eng.submit(sys_prompt + [3, 4], 2, top_k=1)
            eng.drain()
        timers = Timers()
        eng.export_gauges(timers)
        g = timers.gauges()
        for key in ("serve_prefix_hit_rate", "serve_prefix_hit_tokens",
                    "serve_prefix_cached_pages",
                    "serve_prefix_shared_pages",
                    "serve_prefix_cow_copies",
                    "serve_prefix_evicted_pages"):
            assert key in g, key
        assert g["serve_prefix_hit_rate"] > 0
