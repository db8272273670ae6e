"""Int8 quantization for the serving hot path (ISSUE 9).

Decode is bandwidth-bound: at single-token shapes every matvec and every
cache read streams its operand from HBM once per token, so bytes ARE
latency. This module holds the two quantization schemes the serving
stack uses and the ONE rounding/scale convention they share:

- **Int8 KV pages** (`quantize_rows` over the head dim): the engine's
  global page pools store K/V as int8 with a per-(token, group) fp32
  scale living in a parallel scale pool (num_pages, page_size, g) —
  ~4 bytes of scale per 2 x head_dim bytes of data. Quantization
  happens AT WRITE TIME through the ONE scatter path
  (ops/prefill_attention.scatter_chunk_kv — decode rows are its C == 1
  case since ISSUE 18); the ragged paged kernel dequantizes in-register
  inside its exp2-online-softmax loop (fp32 accumulation unchanged),
  and the XLA gather-pages twin dequantizes the gathered view — the
  same values either way, so the twin stays the CPU oracle.
- **Weight-only int8 decode matmuls** (`quantize_weight` per OUTPUT
  channel, `qdot` at the apply site): a one-shot transform of the fp
  decode param tree (GPTModel.prepare_decode_params(quantize_int8=
  True)) replaces each qkv/dense/MLP weight with
  {"int8_data", "scale"}; the decode GEMVs read half the weight bytes
  and apply the per-channel scale to the (tiny) output row. Activations
  are NOT quantized — at s == 1 they are noise next to the weight
  traffic, and keeping them fp keeps the scheme one-shot (no
  calibration). The fp path stays the default; training never sees
  quantized trees.

Numerics contract: symmetric round-to-nearest int8 (scale = amax/127,
no zero point — K/V and weights are zero-centered), dequantized error
<= scale/2 per element. An all-zero row quantizes to zeros with scale
0 and dequantizes to exact zeros (no NaN path). EQuARX (PAPERS.md)
motivates the "cheap symmetric scheme + fp32 accumulation" choice;
accuracy is measured, not assumed: tests/test_quantization.py bounds the
greedy logprob drift vs the fp path (no cell of `benchmark/` serves int8:
its speed is not measured on the chip), and docs/GUIDE.md "Quantized
serving" states the contract.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from megatron_llm_tpu.analysis.contracts import compile_contract

INT8_MAX = 127.0


def quantize_rows(x: jnp.ndarray, axis: int = -1):
    """Symmetric per-row int8 quantization over `axis`: scale =
    amax/127 (fp32), data = clip(round(x/scale)). Returns (int8 data,
    fp32 scales with `axis` removed). All-zero rows get scale 0 and
    round-trip to exact zeros."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=axis)
    scale = amax / INT8_MAX
    # guarded reciprocal: zero rows multiply by 0 instead of dividing
    # by 0 (dequantization multiplies by scale 0, so the round trip is
    # exact zeros either way)
    inv = jnp.where(scale > 0.0, 1.0 / jnp.maximum(scale, 1e-30), 0.0)
    data = jnp.clip(
        jnp.round(xf * jnp.expand_dims(inv, axis)), -INT8_MAX, INT8_MAX
    ).astype(jnp.int8)
    return data, scale


def dequantize_rows(data: jnp.ndarray, scale: jnp.ndarray,
                    axis: int = -1, dtype=jnp.float32) -> jnp.ndarray:
    """Inverse of quantize_rows: data * scale broadcast over `axis`."""
    return (data.astype(jnp.float32)
            * jnp.expand_dims(scale, axis)).astype(dtype)


def scatter_quantized_rows(data_pool, scale_pool, pages, offs, x):
    """THE quantize-at-write point for int8 KV pools: quantize each
    (..., g, d) row of `x` over the head dim and write the int8 data
    (a token's g heads side by side, the lane-packed pool's row) and
    its g fp32 scales at the SAME [pages, offs] of the paired pools.
    Every scatter path (chunked prefill, the single-token decode
    branch, the whole-prompt bucketed prefill) goes through this one
    definition, so the rounding/scale convention can never fork between
    writers."""
    data, scale = quantize_rows(x)
    return (data_pool.at[pages, offs].set(
                data.reshape(*data.shape[:-2], -1)),
            scale_pool.at[pages, offs].set(scale))


# ---------------------------------------------------------------------------
# Weight-only int8 (the decode matmuls)
# ---------------------------------------------------------------------------


def quantize_weight(w: jnp.ndarray) -> dict:
    """Per-OUTPUT-channel int8 for a (in_dim, out_dim) matmul weight:
    scale over axis 0, so `x @ W ~= (x @ int8) * scale[None, :]` — the
    scale application is a cheap per-column multiply on the GEMV output
    instead of a full dequantized weight materialization. The decode
    tree's head-major `wqkv` (heads, head_dim, in_dim) quantizes over
    its LAST axis: the same channels, their scales in column order."""
    if w.ndim == 3:
        data, scale = quantize_rows(w, axis=-1)
        return {"int8_data": data, "scale": scale.reshape(-1)}
    assert w.ndim == 2, (
        "weight-only quantization expects the 2D decode layout "
        f"(prepare_decode_params flattens GLU first), got {w.shape}")
    data, scale = quantize_rows(w, axis=0)
    return {"int8_data": data, "scale": scale}


def is_quantized_weight(w) -> bool:
    return isinstance(w, dict) and "int8_data" in w


def _wdot(x: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
    """`x @ w` for an (in, out) weight. A rank-3 weight is the decode
    tree's head-major `wqkv` (heads, head_dim, in)
    (GPTModel.prepare_decode_params): the same products summed over
    `in`, the columns coming out in the same order."""
    if w.ndim == 3:
        y = jnp.einsum("...h,ndh->...nd", x, w)
        return y.reshape(*y.shape[:-2], -1)
    return x @ w


def qdot(x: jnp.ndarray, w, dt) -> jnp.ndarray:
    """`x @ w` for a plain fp weight (bitwise-identical to the
    pre-quantization call sites: `x @ w.astype(dt)`) or a weight-only
    int8 dict (int8 operand streamed from HBM, converted in-register by
    the dot fusion, per-channel scale applied to the output in fp32
    then cast back to the compute dtype)."""
    if is_quantized_weight(w):
        y = _wdot(x, w["int8_data"].astype(dt))
        return (y.astype(jnp.float32) * w["scale"]).astype(dt)
    return _wdot(x, w.astype(dt))


@compile_contract(
    "ops.weight_quant",
    max_variants=1,  # ONE builder mint; per-model-shape executables
    # live in the jit call cache (the generate.tokens pattern,
    # jit_cache_size), not the variant store
    collectives={"single": frozenset()},
    tmp_bytes_budget=8 << 20,
    notes="one-shot fp->int8 decode-weight quantization; called once "
          "per engine at construction, never in a hot loop")
def _make_weight_quant_fn():
    """The jitted one-shot weight quantizer: maps the unrolled decode
    layer tuple (prepare_decode_params layout — per-layer standalone
    trees, GLU already flattened) to the weight-only int8 tree. Biases,
    norms, embeddings, and the LM head stay fp: their bytes are noise
    next to the four big GEMV weights, and the head's logit precision
    is exactly what the accuracy contract protects."""

    def quant_layers(layers):
        def one(layer):
            attn = dict(layer["attention"])
            mlp = dict(layer["mlp"])
            attn["wqkv"] = quantize_weight(attn["wqkv"])
            attn["wo"] = quantize_weight(attn["wo"])
            mlp["w1"] = quantize_weight(mlp["w1"])
            mlp["w2"] = quantize_weight(mlp["w2"])
            out = dict(layer)
            out["attention"] = attn
            out["mlp"] = mlp
            return out

        return tuple(one(layer) for layer in layers)

    # graft-contract: ops.weight_quant
    return jax.jit(quant_layers)


_weight_quant_fn = None


def weight_quant_fn():
    """The module-level cached quantizer executable (one jit, traced
    per layer-tree shape like every module-level entry point)."""
    global _weight_quant_fn
    if _weight_quant_fn is None:
        _weight_quant_fn = _make_weight_quant_fn()
    return _weight_quant_fn


def quantize_decode_layers(layers):
    """One-shot quantize of the unrolled decode layer tuple (the
    GPTModel.prepare_decode_params(quantize_int8=True) entry)."""
    return weight_quant_fn()(layers)
