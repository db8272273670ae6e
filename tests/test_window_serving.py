"""Sliding-window long-context serving (ISSUE 19): the engine-level
pins, tier-1 on CPU (the `_xla_paged_reference` serving path — the
same code serving runs off-TPU; the kernel-level window sweep lives in
tests/test_paged_attention.py).

Pinned here:
- reclamation is FREE, not approximate: greedy token streams AND
  logprobs with out-of-window page reclamation ON are bitwise the
  reclamation-OFF (mask-only) engine's — the kernels never read a
  reclaimed page by construction, so freeing it cannot change a bit;
- a window covering max_context is bitwise the no-window engine (the
  lower bound never binds, the trace is the pre-window trace);
- compositions: prefix cache (shared pages are refcounted, never
  free-listed), speculative decoding (draft cap at the window edge),
  and int8 KV pools all keep the ON == OFF bitwise contract;
- the capacity win is REAL: a request whose full reach overflows the
  pool serves fine under a window (admission prices O(window), the
  frontier tops up lazily, out-of-window pages recycle), peak live
  pages stay at the _window_slot_pages bound, and every page returns
  to the free list at drain;
- the /metrics gate: serve_window_size / serve_window_reclaimed_pages
  appear ONLY on window-enabled engines — the legacy JSON schema
  (tests/test_telemetry.py pins bytes) is untouched when off;
- loud config/ctor errors: window < 1 and window-without-chunked-
  admission fail at construction, not mid-traffic.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import pytest

from megatron_llm_tpu.config import tiny_config
from megatron_llm_tpu.models import LlamaModel

jax.config.update("jax_platforms", "cpu")

# one long-context-capable config family: params are window- and
# length-independent (rotary tables come from the config at call time),
# so every engine below shares ONE init — bitwise comparisons across
# engines are comparisons of the window machinery alone.
BASE = dict(compute_dtype=jnp.float32, use_decode_attn=False,
            seq_length=256, max_position_embeddings=256)


@pytest.fixture(scope="module")
def tiny_params():
    model = LlamaModel(tiny_config(**BASE))
    return model.init(jax.random.key(7))


def _model(window=None):
    return LlamaModel(tiny_config(**BASE, attention_window_size=window))


def _engine(model, params, **over):
    from megatron_llm_tpu.inference.engine import DecodeEngine

    kw = dict(slots=2, page_size=16, max_context=64,
              prefill_chunk_tokens=16, vocab_size=256,
              termination_id=None)
    kw.update(over)
    return DecodeEngine(model, params, **kw)


def _run(eng, specs):
    """Submit (prompt, gen) pairs, drain, return [(tokens, logprobs)]."""
    reqs = [eng.submit(list(p), g, top_k=1, return_log_probs=True)
            for p, g in specs]
    eng.drain()
    return [r.result(30) for r in reqs]


TRAFFIC = [(range(5, 12), 12), (range(3, 6), 20), (range(2, 26), 36)]


class TestReclamationBitwise:
    def test_reclaim_on_bitwise_off_with_traffic(self, tiny_params):
        """The acceptance contract: mixed-length greedy streams on the
        reclaiming engine equal the mask-only engine TO THE BIT (tokens
        and logprobs), and reclamation actually happened."""
        model = _model(window=24)
        on = _engine(model, tiny_params)
        off = _engine(model, tiny_params, window_reclaim=False)
        got_on = _run(on, TRAFFIC)
        got_off = _run(off, TRAFFIC)
        assert got_on == got_off  # tokens AND float-exact logprobs
        assert on._window_reclaimed > 0
        assert off._window_reclaimed == 0

    def test_window_covering_context_is_the_plain_engine(self,
                                                         tiny_params):
        """W >= max_context: the lower bound never binds and nothing
        ever leaves a live window — streams are bitwise the no-window
        engine's and the reclaim counter stays 0."""
        win = _engine(_model(window=4096), tiny_params)
        plain = _engine(_model(), tiny_params)
        assert _run(win, TRAFFIC) == _run(plain, TRAFFIC)
        assert win._window_reclaimed == 0

    def test_prefix_cache_composition(self, tiny_params):
        """Shared prefix pages are refcounted cache property — the
        reclaimer hands them back to the CACHE, never the free list —
        and the streams stay bitwise with cache hits happening."""
        model = _model(window=24)
        shared = list(range(4, 52))  # 3 full pages of shared prefix
        specs = [(shared + [90], 16), (shared + [91], 16),
                 (shared + [92], 12)]
        outs = []
        for reclaim in (True, False):
            eng = _engine(model, tiny_params, max_context=128,
                          prefix_cache=True, window_reclaim=reclaim)
            # plain greedy (return_log_probs requests bypass prefix
            # MATCHING by design — their scores need the full prompt)
            reqs = [eng.submit(list(p), g, top_k=1) for p, g in specs]
            eng.drain()
            outs.append([r.result(30) for r in reqs])
            if reclaim:
                assert eng.counters()["serve_prefix_hits"] > 0
                assert eng._window_reclaimed > 0
        assert outs[0] == outs[1]

    def test_spec_decode_composition(self, tiny_params):
        """Prompt-lookup drafts cap at the window edge; greedy verify
        keeps ON == OFF bitwise on repetitive traffic."""
        model = _model(window=24)
        prompt = [7, 8, 9, 10] * 6  # repetitive: n-gram drafts fire
        outs = []
        for reclaim in (True, False):
            eng = _engine(model, tiny_params, spec_decode_k=4,
                          window_reclaim=reclaim)
            outs.append(_run(eng, [(prompt, 20)]))
            if reclaim:
                assert eng.counters()["serve_spec_rounds"] > 0
        assert outs[0] == outs[1]

    def test_int8_composition(self, tiny_params):
        """int8 KV pools: scale pool entries ride the same page
        indices, reclaimed scale pages are as unread as their data
        pages — ON == OFF bitwise."""
        model = _model(window=40)
        outs = []
        for reclaim in (True, False):
            eng = _engine(model, tiny_params, page_size=32,
                          kv_dtype="int8", window_reclaim=reclaim)
            outs.append(_run(eng, TRAFFIC))
        assert outs[0] == outs[1]


class TestWindowCapacity:
    def test_long_request_serves_in_a_small_pool(self, tiny_params):
        """160 tokens of reach through a 6-page (96-token) pool: the
        plain engine refuses at submit (can never fit); the windowed
        engine admits at the window price, tops the frontier up
        lazily, recycles out-of-window pages, and finishes — with peak
        live pages AT the _window_slot_pages bound and the whole pool
        free again after drain."""
        plain = _engine(_model(), tiny_params, max_context=192,
                        page_budget=96)
        with pytest.raises(ValueError, match="needs 10 pages"):
            plain.submit(list(range(2, 10)), 152, top_k=1)
        eng = _engine(_model(window=48), tiny_params, max_context=192,
                      page_budget=96)
        req = eng.submit(list(range(2, 10)), 152, top_k=1)
        eng.drain()
        toks, _ = req.result(60)
        assert len(toks) == 8 + 152  # prompt echo + every token
        bound = eng._window_slot_pages()
        assert bound <= 5
        peak = max(s.mapped - s.reclaimed for s in eng._slots)
        assert peak <= bound
        assert eng._window_reclaimed >= 10 - bound
        c = eng.counters()
        assert c["serve_pages_in_use"] == 0
        assert c["serve_pages_free"] == eng.num_pages - 1
        assert c["serve_window_reclaimed_pages"] == eng._window_reclaimed

    def test_metrics_gate(self, tiny_params):
        """Window gauges appear ONLY on window-enabled engines; the
        window-off counters keep the exact legacy key set."""
        win = _engine(_model(window=32), tiny_params)
        c = win.counters()
        assert c["serve_window_size"] == 32
        assert c["serve_window_reclaimed_pages"] == 0
        off = _engine(_model(), tiny_params)
        assert not any(k.startswith("serve_window")
                       for k in off.counters())

    def test_window_requires_chunked_admission(self, tiny_params):
        """Whole-prompt admission prefills through the DENSE path,
        which has no window mask — the ctor refuses the combination
        loudly instead of serving a cache the windowed steps would
        disagree with."""
        with pytest.raises(ValueError, match="chunked admission"):
            _engine(_model(window=32), tiny_params,
                    prefill_chunk_tokens=0)

    def test_config_validates_window(self):
        with pytest.raises(ValueError, match="attention_window_size"):
            tiny_config(**BASE, attention_window_size=0)
        cfg = tiny_config(**BASE, attention_window_size=64)
        assert dataclasses.replace(cfg).attention_window_size == 64
