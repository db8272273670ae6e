"""Int8 KV pages + weight-only quantized decode matmuls (ISSUE 9).

Convention layer: the ONE symmetric round-to-nearest int8 scheme
(scale = amax/127, error <= scale/2, zero rows round-trip exactly) that
both the KV pools and the weight-only decode matmuls share. The KERNEL
pins for int8 paged attention (dequant-oracle parity, the 32-sublane
gate, scatter-with-scales, decode-row degeneracy) live with the rest of
the paged matrix in tests/test_paged_attention.py since ISSUE 18
collapsed the quantized variants into THE ragged paged kernel's kv
dtype parameter.

Engine layer (tiny fp32 model -> the XLA twins, the engine-suite
pattern): an int8 engine run asserts bounded teacher-forced
prompt-logprob drift vs the bf16 engine, EXACT page accounting, the
serve_kv_* capacity gauges, and the >= 1.5x bytes/token capacity
claim; prefix-cache COW must copy SCALES with pages (int8 prefix-ON ==
prefix-OFF bitwise, including a mid-page divergence); weight-only int8
bounds per-channel round-trip error and runs the engine end to end;
the fp default stays bitwise untouched (prepare_decode_params without
the flag returns the exact old tree — pinned here so the parity suites
keep meaning what they say).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from megatron_llm_tpu.analysis.contracts import get_contract, variants
from megatron_llm_tpu.config import tiny_config
from megatron_llm_tpu.inference.engine import DecodeEngine
from megatron_llm_tpu.models import LlamaModel
from megatron_llm_tpu.ops.quantization import (
    dequantize_rows,
    quantize_rows,
    quantize_weight,
)


# ---------------------------------------------------------------------------
# The quantization convention
# ---------------------------------------------------------------------------


class TestQuantizeRows:
    def test_roundtrip_error_bounded_by_half_scale(self):
        x = jax.random.normal(jax.random.key(0), (5, 3, 64), jnp.float32)
        data, scale = quantize_rows(x)
        assert data.dtype == jnp.int8 and scale.shape == (5, 3)
        err = jnp.abs(dequantize_rows(data, scale) - x)
        # symmetric round-to-nearest: per-element error <= scale/2
        assert bool(jnp.all(err <= scale[..., None] * 0.5 + 1e-7))

    def test_amax_element_exact(self):
        """The row max maps to +-127 exactly (symmetric, no zero
        point)."""
        x = jnp.asarray([[1.0, -2.0, 0.5, 2.0]], jnp.float32)
        data, scale = quantize_rows(x)
        assert int(jnp.max(jnp.abs(data))) == 127
        np.testing.assert_allclose(float(scale[0]), 2.0 / 127.0)

    def test_zero_rows_no_nan(self):
        x = jnp.zeros((2, 8), jnp.float32)
        data, scale = quantize_rows(x)
        assert not bool(jnp.any(jnp.isnan(scale)))
        assert bool(jnp.all(dequantize_rows(data, scale) == 0.0))


# ---------------------------------------------------------------------------
# Weight-only int8
# ---------------------------------------------------------------------------


class TestWeightQuant:
    def test_per_channel_roundtrip_bound(self):
        w = jax.random.normal(jax.random.key(0), (64, 32), jnp.float32)
        qw = quantize_weight(w)
        assert qw["int8_data"].dtype == jnp.int8
        assert qw["scale"].shape == (32,)  # per OUTPUT channel
        deq = qw["int8_data"].astype(jnp.float32) * qw["scale"][None, :]
        assert bool(jnp.all(jnp.abs(deq - w)
                            <= qw["scale"][None, :] * 0.5 + 1e-7))

    def test_quantize_decode_layers_structure(self):
        cfg = tiny_config(compute_dtype=jnp.float32)
        model = LlamaModel(cfg)
        params = model.init(jax.random.key(0))
        dec = model.prepare_decode_params(params)
        qdec = model.prepare_decode_params(params, quantize_int8=True)
        for fp_l, q_l in zip(dec["layers"], qdec["layers"]):
            for path, leaf in (
                    (("attention", "wqkv"), None),
                    (("attention", "wo"), None),
                    (("mlp", "w1"), None),
                    (("mlp", "w2"), None)):
                ref = fp_l[path[0]][path[1]]
                got = q_l[path[0]][path[1]]
                assert got["int8_data"].shape == ref.shape
                # one scale an output channel: wqkv is head-major
                # (heads, head_dim, h), the others (in, out)
                assert got["scale"].shape == (
                    (ref.shape[0] * ref.shape[1],) if ref.ndim == 3
                    else (ref.shape[1],))
            # everything else (norms) untouched, bitwise
            np.testing.assert_array_equal(
                np.asarray(fp_l["input_norm"]["scale"]),
                np.asarray(q_l["input_norm"]["scale"]))
        # contract minted exactly one variant (module-global owner)
        assert get_contract("ops.weight_quant").max_variants == 1
        assert len(variants("ops.weight_quant")) == 1

    def test_fp_default_tree_unchanged(self):
        """prepare_decode_params WITHOUT the flag returns the exact
        pre-ISSUE-9 tree — the bitwise-parity suites rest on this."""
        cfg = tiny_config(compute_dtype=jnp.float32)
        model = LlamaModel(cfg)
        params = model.init(jax.random.key(0))
        dec = model.prepare_decode_params(params)
        for j, layer in enumerate(dec["layers"]):
            assert isinstance(layer["attention"]["wqkv"], jax.Array)
            assert isinstance(layer["mlp"]["w1"], jax.Array)
            # the GLU gate|up-major, (2, f, h): the training leaf's
            # transpose, bit for bit
            np.testing.assert_array_equal(
                np.asarray(layer["mlp"]["w1"]),
                np.asarray(params["layers"]["mlp"]["w1"][j]
                           .transpose(1, 2, 0)))


# ---------------------------------------------------------------------------
# Engine: int8 KV end to end
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def tiny_model():
    cfg = tiny_config(compute_dtype=jnp.float32, use_decode_attn=False)
    model = LlamaModel(cfg)
    params = model.init(jax.random.key(7))
    return model, params


def _engine(model, params, **over):
    kw = dict(slots=2, page_size=16, max_context=64, max_queue=8,
              termination_id=None, vocab_size=256,
              prefill_chunk_tokens=8)
    kw.update(over)
    return DecodeEngine(model, params, **kw)


def _run(eng, prompts, gen=6, **submit_kw):
    reqs = [eng.submit(p, gen, top_k=1, **submit_kw) for p in prompts]
    eng.drain()
    return [r.result() for r in reqs]


class TestEngineInt8:
    def test_bounded_drift_and_exact_page_accounting(self, tiny_model):
        """The acceptance shape: an int8 greedy run completes with
        teacher-forced prompt-logprob drift bounded vs the bf16 engine,
        and EVERY page returns to the free list afterwards."""
        model, params = tiny_model
        rs = np.random.RandomState(3)
        prompts = [list(rs.randint(2, 256, 24)) for _ in range(4)]
        eng_fp = _engine(model, params)
        out_fp = _run(eng_fp, prompts, return_log_probs=True)
        eng_q = _engine(model, params, kv_dtype="int8")
        out_q = _run(eng_q, prompts, return_log_probs=True)
        drift = max(
            abs(a - b)
            for (_, lp0), (_, lp1) in zip(out_fp, out_q)
            for a, b in zip(lp0[:23], lp1[:23]))
        # calibrated: observed ~7e-4 on this seed/model; 0.05 leaves
        # two orders of headroom while still catching a broken scale
        # path (garbage scales blow past 1.0 immediately)
        assert drift < 0.05, drift
        # exact page accounting: nothing leaked, nothing double-freed
        for eng in (eng_fp, eng_q):
            assert sorted(eng._free_pages) == list(
                range(1, eng.num_pages))
            assert all(int(x) == 0 for x in eng._lengths)

    def test_capacity_gauges_and_ratio(self, tiny_model):
        model, params = tiny_model
        eng_fp = _engine(model, params)
        eng_q = _engine(model, params, kv_dtype="int8")
        c = eng_q.counters()
        assert c["serve_kv_dtype"] == "int8"
        assert c["serve_kv_pool_bytes"] == eng_q.kv_pool_bytes()
        assert c["serve_kv_bytes_per_token"] == eng_q.kv_bytes_per_token()
        # the >= 1.5x pages-per-HBM-byte acceptance bar (fp32 compute
        # here -> 3.2x; bf16 compute gives 1.94x on the bench model)
        ratio = eng_fp.kv_bytes_per_token() / eng_q.kv_bytes_per_token()
        assert ratio >= 1.5, ratio
        # scale pools exist and are accounted in the pool bytes
        assert eng_q.kv_pool_bytes() > sum(
            x.size * x.dtype.itemsize
            for x in (*eng_q._pools_k, *eng_q._pools_v))

    def test_whole_prompt_mode_int8(self, tiny_model):
        """The bucketed whole-prompt prefill quantizes at its scatter
        too: chunked and whole-prompt int8 engines emit the same greedy
        stream (same quantized values -> same math)."""
        model, params = tiny_model
        rs = np.random.RandomState(5)
        prompts = [list(rs.randint(2, 256, 20)) for _ in range(3)]
        out_c = _run(_engine(model, params, kv_dtype="int8"), prompts)
        out_w = _run(_engine(model, params, kv_dtype="int8",
                             prefill_chunk_tokens=0), prompts)
        for (t0, _), (t1, _) in zip(out_c, out_w):
            assert t0 == t1

    def test_spec_decode_composes_with_int8(self, tiny_model):
        """Spec verification rides the same quantized chunked stack;
        spec-on == spec-off on an int8 engine (both decide tokens from
        the same quantized-cache logits)."""
        model, params = tiny_model
        rs = np.random.RandomState(6)
        p = list(rs.randint(2, 256, 12))
        prompts = [p + p]  # repetitive: the drafter actually fires
        base = _run(_engine(model, params, kv_dtype="int8"), prompts,
                    gen=8)
        spec = _run(_engine(model, params, kv_dtype="int8",
                            spec_decode_k=2), prompts, gen=8)
        assert base[0][0] == spec[0][0]

    def test_warmup_traces_quantized_buckets(self, tiny_model):
        model, params = tiny_model
        eng = _engine(model, params, kv_dtype="int8")
        eng.warmup()  # all horizon + width buckets through int8 pools
        rs = np.random.RandomState(1)
        out = _run(eng, [list(rs.randint(2, 256, 10))], gen=4)
        assert len(out[0][0]) == 14

    def test_kv_dtype_validated(self, tiny_model):
        model, params = tiny_model
        with pytest.raises(ValueError, match="kv_dtype"):
            _engine(model, params, kv_dtype="fp8")


class TestPrefixCOWWithScales:
    def test_prefix_on_bitwise_matches_off_including_cow(self,
                                                         tiny_model):
        """Int8 + prefix sharing: ON == OFF bitwise, including a
        mid-page divergence that exercises the COW page copy — if the
        copy moved data without SCALES, the divergent request would
        dequantize its shared leading rows against zero/stale scales
        and the streams would split immediately."""
        model, params = tiny_model
        rs = np.random.RandomState(11)
        base = list(rs.randint(2, 256, 40))
        # request B diverges MID-PAGE (page_size 16: token 20 is inside
        # page 1) -> COW path; request C shares the full first page
        prompts = [
            base,
            base[:20] + list(rs.randint(2, 256, 20)),
            base[:16] + list(rs.randint(2, 256, 16)),
        ]
        off = _engine(model, params, kv_dtype="int8", slots=1)
        out_off = _run(off, prompts)
        on = _engine(model, params, kv_dtype="int8", slots=1,
                     prefix_cache=True)
        out_on = _run(on, prompts)
        for (t0, _), (t1, _) in zip(out_off, out_on):
            assert t0 == t1
        assert on._prefix.cow_copies >= 1  # the COW path actually ran
        assert on._prefix.hits >= 1
        # refcounted accounting intact: cached pages retained, the
        # rest back on the free list
        cached = on._prefix.cached_pages
        assert len(on._free_pages) == on.num_pages - 1 - cached


class TestEngineWeightQuant:
    def test_int8_weights_run_with_bounded_drift(self, tiny_model):
        model, params = tiny_model
        rs = np.random.RandomState(13)
        prompts = [list(rs.randint(2, 256, 24)) for _ in range(3)]
        out_fp = _run(_engine(model, params), prompts,
                      return_log_probs=True)
        out_qw = _run(_engine(model, params, kv_dtype="int8",
                              quantize_weights=True), prompts,
                      return_log_probs=True)
        drift = max(
            abs(a - b)
            for (_, lp0), (_, lp1) in zip(out_fp, out_qw)
            for a, b in zip(lp0[:23], lp1[:23]))
        assert drift < 0.1, drift
