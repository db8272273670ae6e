"""tp-sharded serving engine (ISSUE 14 tentpole).

The contract, pinned here:

- **Sharding rules.** `kv_pool_axis`/`kv_pool_spec` shard exactly the
  group axis of a paged-pool leaf (data AND int8 scale pools) when tp
  divides it; the engine's live pools follow the rule, page tables /
  lengths / sampling arrays stay replicated, and the decode param tree
  shards by `decode_param_specs` (which refuses the flattened-GLU
  layout whose gate|up concat crosses the shard boundary).
- **Parity.** The tp2 virtual-CPU-mesh engine's greedy TOKEN streams
  are BITWISE the single-chip engine's across chunked prefill,
  prefix-cache COW, speculative decoding, whole-prompt prefill, and
  int8 KV. Logprobs match to a tight absolute bound but NOT bitwise:
  the tp all-reduce reorders the row-parallel wo/w2 reduction — the
  same last-ulps latitude the engine already documents for the
  backend's matmul blocking across chunk widths (engine.py module
  docstring). The bound is pinned, not assumed.
- **Page accounting.** The host-side page/refcount machinery is
  mesh-blind: pages_in_use / free-list / prefix-cache gauges match the
  single-chip engine exactly through a COW + eviction workload.
- **Per-chip gauges (the small-fix satellite).** kv_pool_bytes /
  kv_bytes_per_token derive from LIVE shardings: tp2 reports exactly
  half the single-chip bytes (the start() capacity log prints the same
  numbers); int8 scale pools shard with their data.
- **Construction gates.** serving_tp must divide num_query_groups;
  quantize_weights (flattened-GLU decode tree) is refused on a mesh.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from megatron_llm_tpu.config import tiny_config
from megatron_llm_tpu.inference.engine import DecodeEngine
from megatron_llm_tpu.models import LlamaModel
from megatron_llm_tpu.parallel.mesh import MODEL_AXIS
from megatron_llm_tpu.parallel.sharding import (
    decode_param_specs,
    kv_pool_axis,
    kv_pool_spec,
)


@pytest.fixture(scope="module")
def tiny_model():
    cfg = tiny_config(compute_dtype=jnp.float32, use_decode_attn=False)
    model = LlamaModel(cfg)
    params = model.init(jax.random.key(7))
    return model, params


def _engine(model, params, **over):
    kw = dict(slots=2, page_size=16, max_context=96, max_queue=16,
              prefill_chunk_tokens=16, termination_id=None,
              vocab_size=256)
    kw.update(over)
    return DecodeEngine(model, params, **kw)


# ---------------------------------------------------------------------------
# tier-1: the one-rule spec, construction gates, per-chip gauges
# ---------------------------------------------------------------------------


class TestPoolSpecRule:
    def test_kv_pool_axis_is_the_group_axis_or_none(self):
        """A token's heads lie side by side along the lanes (index 2 of
        the lane-packed pool) and the GROUPS decide: a chip's slice is
        its own heads' lanes."""
        assert kv_pool_axis((9, 16, 32), 2, 4) == 2   # data pool, 4 x 8
        assert kv_pool_axis((9, 16, 4), 2, 4) == 2    # int8 scale pool
        assert kv_pool_axis((9, 16, 32), 1, 4) is None  # tp=1
        assert kv_pool_axis((9, 16, 24), 2, 3) is None  # indivisible
        assert kv_pool_axis((9, 16, 64), 2, 1) is None  # MQA: g < tp,
        # although 64 lanes would halve: Falcon-7B's one head is not cut
        with pytest.raises(ValueError, match="num_pages, page_size"):
            kv_pool_axis((9, 16, 4, 8), 2, 4)  # the 4-D pool is gone

    def test_kv_pool_spec_mirrors_the_axis(self):
        assert kv_pool_spec((9, 16, 32), 2, 4) == P(
            None, None, MODEL_AXIS)
        assert kv_pool_spec((9, 16, 4), 2, 4) == P(None, None, MODEL_AXIS)
        assert kv_pool_spec((9, 16, 32), 1, 4) == P()
        assert kv_pool_spec((9, 16, 64), 2, 1) == P()

    def test_decode_param_specs_refuses_flattened_glu(self, tiny_model):
        model, params = tiny_model
        flat = model.prepare_decode_params(params)  # flatten_glu=True
        with pytest.raises(AssertionError, match="UNFLATTENED"):
            decode_param_specs(model.cfg, flat)

    def test_decode_param_specs_structure_matches_tree(self, tiny_model):
        model, params = tiny_model
        dec = model.prepare_decode_params(params, flatten_glu=False)
        specs = decode_param_specs(model.cfg, dec)
        # one spec per leaf, same treedef — device_put(dec, shardings)
        # depends on this
        jax.tree.map(lambda a, s: None, dec, specs,
                     is_leaf=lambda x: isinstance(x, P))
        l0 = specs["layers"][0]
        assert l0["attention"]["wqkv"] == P(MODEL_AXIS, None, None)
        assert l0["attention"]["wo"] == P(MODEL_AXIS, None)
        assert l0["mlp"]["w1"] == P(None, None, MODEL_AXIS)
        assert l0["mlp"]["w2"] == P(MODEL_AXIS, None)
        assert specs["embedding"]["word_embeddings"] == P(
            MODEL_AXIS, None)


class TestConstructionGates:
    def test_serving_tp_must_divide_groups(self, tiny_model):
        model, params = tiny_model
        assert model.cfg.num_query_groups == 2
        with pytest.raises(ValueError, match="divide the KV group"):
            _engine(model, params, serving_tp=4)  # 2 groups % 4 != 0

    def test_quantize_weights_refused_on_mesh(self, tiny_model):
        model, params = tiny_model
        with pytest.raises(ValueError, match="single-chip-layout"):
            _engine(model, params, serving_tp=2, quantize_weights=True)

    def test_flattened_glu_refused_for_quantless_mesh_prep(
            self, tiny_model):
        model, params = tiny_model
        with pytest.raises(ValueError, match="flattened GLU"):
            model.prepare_decode_params(params, quantize_int8=True,
                                        flatten_glu=False)


class TestPerChipGauges:
    """The small-fix satellite: capacity gauges report PER-CHIP bytes
    from live shardings — a tp mesh halves them; the old global-size
    formula would overstate per-chip capacity by tp×."""

    def test_tp2_pools_sharded_and_gauges_halved(self, tiny_model):
        model, params = tiny_model
        e1 = _engine(model, params)
        e2 = _engine(model, params, serving_tp=2)
        # pools follow the one rule; scalar-prefetch operands replicated
        g, d = model.cfg.num_query_groups, model.cfg.head_dim
        for pool in (*e2._pools_k, *e2._pools_v):
            assert pool.shape[2] == g * d  # lane-packed
            assert pool.sharding.spec == kv_pool_spec(pool.shape, 2, g)
            assert pool.sharding.shard_shape(pool.shape)[2] == g // 2 * d
        assert e1.kv_pool_bytes() == 2 * e2.kv_pool_bytes()
        assert e1.kv_bytes_per_token() == 2 * e2.kv_bytes_per_token()
        c = e2.counters()
        assert c["serve_kv_pool_bytes"] == e2.kv_pool_bytes()

    def test_int8_scale_pools_shard_with_their_data(self, tiny_model):
        model, params = tiny_model
        e1 = _engine(model, params, kv_dtype="int8", page_size=32,
                     max_context=96)
        e2 = _engine(model, params, kv_dtype="int8", page_size=32,
                     max_context=96, serving_tp=2)
        for pool in (*e2._pools_ks, *e2._pools_vs):
            assert pool.sharding.spec == kv_pool_spec(
                pool.shape, 2, model.cfg.num_query_groups)
        assert e1.kv_pool_bytes() == 2 * e2.kv_pool_bytes()

    def test_single_chip_gauges_unchanged(self, tiny_model):
        """The fix must be a no-op at tp=1: per-chip == global."""
        model, params = tiny_model
        eng = _engine(model, params)
        expect = sum(x.size * x.dtype.itemsize
                     for x in (*eng._pools_k, *eng._pools_v))
        assert eng.kv_pool_bytes() == expect


# ---------------------------------------------------------------------------
# slow: tp2-mesh parity vs the single-chip engine
# ---------------------------------------------------------------------------

# measured on this backend: a few fp32 ulps of logit drift from the tp
# all-reduce's reduction reorder propagates to ~5e-7 logprob drift; the
# pin is an order of magnitude above the measurement and far below
# anything a real bug would produce
LOGPROB_ATOL = 5e-6


def _run(eng, traffic, timeout=120):
    reqs = [eng.submit(p, g, top_k=1, return_log_probs=lp)
            for p, g, lp in traffic]
    eng.drain()
    out = []
    for r in reqs:
        toks, lps = r.result(timeout)
        out.append((toks, lps))
    return out


def _assert_parity(single, tp):
    for (t1, l1), (t2, l2) in zip(single, tp):
        assert t1 == t2, "greedy token stream diverged across the mesh"
        if l1 is not None:
            np.testing.assert_allclose(l1, l2, rtol=0,
                                       atol=LOGPROB_ATOL)


@pytest.mark.slow
class TestTP2Parity:
    def test_chunked_prefill_streams_bitwise(self, tiny_model):
        """Chunk boundaries at/below/above the page size, logprobs
        requested (the full decode + mixed surface)."""
        model, params = tiny_model
        traffic = [(list(range(5, 45)), 20, True),   # 2.5 pages
                   ([7, 8, 9, 10, 11], 24, True),    # sub-page
                   (list(range(60, 93)), 12, False)]  # chunk-straddling
        o1 = _run(_engine(model, params), traffic)
        o2 = _run(_engine(model, params, serving_tp=2), traffic)
        _assert_parity(o1, o2)

    def test_whole_prompt_prefill_streams_bitwise(self, tiny_model):
        model, params = tiny_model
        traffic = [(list(range(5, 30)), 12, True),
                   ([3, 4, 5, 6], 10, False)]
        o1 = _run(_engine(model, params, prefill_chunk_tokens=0),
                  traffic)
        o2 = _run(_engine(model, params, prefill_chunk_tokens=0,
                          serving_tp=2), traffic)
        _assert_parity(o1, o2)

    def test_prefix_cow_compose_and_page_accounting(self, tiny_model):
        """Shared system prompt + mid-page divergence (the COW path)
        on both engines: streams bitwise AND the host-side page
        accounting — pages in use, free list, prefix gauges — is
        mesh-blind, so every gauge matches exactly."""
        model, params = tiny_model
        rs = np.random.RandomState(3)
        sysp = list(rs.randint(2, 256, 40))
        traffic = (
            [(sysp + list(rs.randint(2, 256, 4)), 10, False)
             for _ in range(3)]
            # mid-page divergence: shares 24 of page 2's rows
            + [(sysp[:24] + list(rs.randint(2, 256, 12)), 8, False)]
        )
        outs, gauges = [], []
        for tp in (1, 2):
            eng = _engine(model, params, serving_tp=tp,
                          prefix_cache=True)
            outs.append(_run(eng, traffic))
            c = eng.counters()
            gauges.append({k: v for k, v in c.items()
                           if "pages" in k or "prefix" in k})
        _assert_parity(outs[0], outs[1])
        assert gauges[0] == gauges[1]
        assert gauges[0]["serve_prefix_hits"] >= 1

    def test_spec_decode_compose_bitwise(self, tiny_model):
        """Repetitive prompts (the drafter's food) through spec
        verification on both engines: accepted runs and streams
        bitwise, acceptance accounting identical."""
        model, params = tiny_model
        pat = [11, 12, 13, 14] * 8
        traffic = [(pat, 20, False), (list(range(40, 70)), 16, False)]
        e1 = _engine(model, params, spec_decode_k=3)
        e2 = _engine(model, params, spec_decode_k=3, serving_tp=2)
        o1, o2 = _run(e1, traffic), _run(e2, traffic)
        _assert_parity(o1, o2)
        assert e1._spec_rounds > 0
        assert (e1._spec_proposed, e1._spec_accepted) == \
            (e2._spec_proposed, e2._spec_accepted)

    def test_int8_kv_compose_bitwise_streams(self, tiny_model):
        """int8 pools + scale pools sharded together: quantize-at-
        write and in-register dequant run per shard; greedy streams
        stay bitwise vs the single-chip int8 engine."""
        model, params = tiny_model
        traffic = [(list(range(5, 45)), 16, False),
                   ([7, 8, 9, 10, 11, 12], 12, False)]
        o1 = _run(_engine(model, params, kv_dtype="int8", page_size=32,
                          max_context=96, prefill_chunk_tokens=32),
                  traffic)
        o2 = _run(_engine(model, params, kv_dtype="int8", page_size=32,
                          max_context=96, prefill_chunk_tokens=32,
                          serving_tp=2), traffic)
        for (t1, _), (t2, _) in zip(o1, o2):
            assert t1 == t2

    def test_pages_all_return_after_drain(self, tiny_model):
        """Sharded pools never change the free-list contract: after a
        no-cache workload drains, every page is back."""
        model, params = tiny_model
        eng = _engine(model, params, serving_tp=2)
        total = eng.num_pages - 1
        _run(eng, [(list(range(2, 40)), 8, False),
                   ([5, 6, 7], 6, False)])
        assert len(eng._free_pages) == total
        assert eng.counters()["serve_pages_in_use"] == 0

    def test_warmup_traces_on_the_mesh(self, tiny_model):
        """warmup() on a tp2 engine pre-traces every greedy bucket
        under the mesh scope (the compile-stall contract holds on a
        mesh) and traffic after it mints nothing new."""
        from megatron_llm_tpu.analysis.contracts import variants

        model, params = tiny_model
        eng = _engine(model, params, serving_tp=2, spec_decode_k=2)
        eng.warmup()
        n_scan = variants("engine.decode_scan", owner=eng)
        n_mixed = variants("engine.mixed_step", owner=eng)
        _run(eng, [(list(range(5, 30)), 8, False)])
        assert variants("engine.decode_scan", owner=eng) == n_scan
        assert variants("engine.mixed_step", owner=eng) == n_mixed
