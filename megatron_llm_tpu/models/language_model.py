"""Embedding + transformer + LM head assembly.

Parity target: ref megatron/model/language_model.py (`Embedding` :133,
`TransformerLanguageModel` :329, `parallel_lm_logits` :24). The embedding
table is vocab-sharded over the model axis (ref: VocabParallelEmbedding
layers.py:128-210) — under GSPMD the masked-gather + psum the reference
hand-writes is generated by XLA from the sharding annotation.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from megatron_llm_tpu.models.norms import apply_norm
from megatron_llm_tpu.models.rope import precompute_rope
from megatron_llm_tpu.models.transformer import (
    init_layer_params,
    init_norm_params,
    transformer_stack,
)
from megatron_llm_tpu.ops.flash_attention import LANES
from megatron_llm_tpu.parallel.mesh import shard_activation


def init_language_model_params(cfg, key) -> dict:
    k_emb, k_layers, k_head, k_pos = jax.random.split(key, 4)
    params = {
        "embedding": {
            "word_embeddings": (
                jax.random.normal(
                    k_emb, (cfg.padded_vocab_size, cfg.hidden_size), jnp.float32
                )
                * cfg.init_method_std
            ).astype(cfg.params_dtype)
        },
        "layers": init_layer_params(cfg, k_layers),
        "final_norm": init_norm_params(cfg),
    }
    if cfg.position_embedding_type == "absolute":
        params["embedding"]["position_embeddings"] = (
            jax.random.normal(
                k_pos, (cfg.max_position_embeddings, cfg.hidden_size), jnp.float32
            )
            * cfg.init_method_std
        ).astype(cfg.params_dtype)
    if getattr(cfg, "num_tokentypes", 0) > 0:
        # segment-type embeddings (ref: Embedding.tokentype_embeddings
        # language_model.py:160-170)
        k_tt = jax.random.fold_in(k_pos, 1)
        params["embedding"]["tokentype_embeddings"] = (
            jax.random.normal(
                k_tt, (cfg.num_tokentypes, cfg.hidden_size), jnp.float32
            )
            * cfg.init_method_std
        ).astype(cfg.params_dtype)
    if not cfg.tie_embed_logits:
        # untied lm_head (ref: language_model.py:437-457)
        params["lm_head"] = (
            jax.random.normal(
                k_head, (cfg.hidden_size, cfg.padded_vocab_size), jnp.float32
            )
            * cfg.init_method_std
        ).astype(cfg.params_dtype)
    return params


# a one-hot product reads the table once (Falcon-7B: 591 MB = 0.72 ms on
# a v5e) and is bound by that read up to ~240 rows (197 TFLOP/s over
# 819 GB/s); the gather's re-layout copy reads AND writes it (1.85 ms
# traced), which the product's FLOPs reach near 600 rows
ONE_HOT_MAX_ROWS = 512


def _rows_by_one_hot(params, table_shape, n_rows: int) -> bool:
    """Whether `embed_tokens` takes its rows as a one-hot product against
    the table instead of a gather: for the few rows of a served round
    (a decode-layout tree: prepare_decode_params' tuple of layers)
    against a table the device lays out VOCAB-minor. A TPU's default
    layout of a 2-D array makes the axis that pads least against the
    lane tile the minor one; where that is the vocabulary (Falcon-7B:
    65024 = 508 tiles, hidden 4544 = 35.5) the tied head reads the
    table as it lies and a row gather cannot, so the compiler re-laid
    the whole table out before it: every round, for 8 rows (1.85 ms of a
    25.3 ms decode round, traced on v5e, PR 26). The product reads the
    table in the head's layout. A table that lies hidden-minor (hidden a
    multiple of the tile) is gathered as before; so are the many rows of
    a training step or a whole-prompt prefill."""
    vocab, hidden = table_shape
    return (isinstance(params.get("layers"), (list, tuple))
            and n_rows <= ONE_HOT_MAX_ROWS
            and -hidden % LANES > -vocab % LANES)


@jax.named_scope("embed")
def embed_tokens(params, cfg, tokens, position_ids=None, dropout_rng=None,
                 deterministic=True, tokentype_ids=None):
    """(b, s) int32 -> (b, s, h) compute-dtype (ref: Embedding.forward
    language_model.py:230-260, incl. SP scatter of the dropout region)."""
    emb = params["embedding"]["word_embeddings"]
    if _rows_by_one_hot(params, emb.shape, tokens.size):
        # exact: one 1.0 a row against the table as the head reads it
        rows = jnp.einsum(
            "...v,vh->...h", jax.nn.one_hot(tokens, emb.shape[0],
                                            dtype=emb.dtype), emb,
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)
        hidden = rows.astype(cfg.compute_dtype)
    else:
        hidden = jnp.take(emb, tokens, axis=0).astype(cfg.compute_dtype)
    if cfg.position_embedding_type == "absolute":
        pos = params["embedding"]["position_embeddings"]
        if position_ids is None:
            position_ids = jnp.arange(tokens.shape[1])[None, :]
        hidden = hidden + jnp.take(pos, position_ids, axis=0).astype(cfg.compute_dtype)
    if tokentype_ids is not None:
        tt = params["embedding"]["tokentype_embeddings"]
        hidden = hidden + jnp.take(tt, tokentype_ids, axis=0).astype(
            cfg.compute_dtype
        )
    hidden = shard_activation(hidden, "hidden_seq")
    if not deterministic and cfg.hidden_dropout > 0.0 and dropout_rng is not None:
        keep = jax.random.bernoulli(dropout_rng, 1.0 - cfg.hidden_dropout, hidden.shape)
        hidden = hidden * keep / (1.0 - cfg.hidden_dropout)
    if cfg.fp32_residual_connection:
        hidden = hidden.astype(jnp.float32)
    return hidden


@jax.named_scope("loss")
def chunked_head_cross_entropy(params, cfg, hidden, labels,
                               chunk_size: int = 1024) -> jnp.ndarray:
    """Per-token CE computed chunk-by-chunk over the sequence.

    The (b, s, V) fp32 logits are the single largest activation in a GPT
    training step (1 GB at b=8/s=1024/V=32k before backward copies). Each
    chunk's head matmul + CE is `jax.checkpoint`ed and driven by
    `lax.map`, so at any moment only (b, chunk, V) logits are live, in
    forward AND backward (logits are recomputed per chunk in the
    transpose). The reference gets the same effect from its fused
    vocab-parallel CE kernel that never gathers full logits
    (ref: megatron/mpu/cross_entropy.py:18-40).

    chunk_size 1024 measured best on v5e r4: sequences <= 1024 take the
    direct (unchunked) path — +2.5% end-to-end at the bench shape — and
    longer sequences keep the per-chunk logits buffer at (b, 1024, V).

    Returns (b, s) fp32 per-token losses; numerically identical to
    cross_entropy(lm_logits(...), labels).
    """
    from megatron_llm_tpu.parallel.cross_entropy import (
        vocab_parallel_cross_entropy,
    )

    from megatron_llm_tpu.parallel.mesh import get_context

    ctx = get_context()
    b, s, h = hidden.shape
    # Under context parallelism the seq axis is device-sharded: lax.map's
    # per-chunk dynamic-slice would gather it. The logits buffer is already
    # divided by cp, so the direct vocab-parallel CE is the memory-correct
    # form there.
    if s > chunk_size:
        # chunks must divide s exactly; shrink toward the largest divisor
        # >= 256 so awkward lengths (e.g. 1536) stay chunked instead of
        # silently materializing the full (b, s, V) logits
        while chunk_size >= 256 and s % chunk_size != 0:
            chunk_size //= 2
    if (ctx is not None and ctx.cp > 1) or s % chunk_size != 0 \
            or s <= chunk_size:
        logits = lm_logits(params, cfg, hidden)
        return vocab_parallel_cross_entropy(logits, labels)
    n = s // chunk_size

    def chunk_fn(args):
        hid_c, lbl_c = args  # (b, chunk, h), (b, chunk)
        logits = lm_logits(params, cfg, hid_c)
        return vocab_parallel_cross_entropy(logits, lbl_c)

    chunk_fn = jax.checkpoint(chunk_fn, prevent_cse=False)
    hid = jnp.moveaxis(hidden.reshape(b, n, chunk_size, h), 1, 0)
    lbl = jnp.moveaxis(labels.reshape(b, n, chunk_size), 1, 0)
    losses = jax.lax.map(chunk_fn, (hid, lbl))  # (n, b, chunk)
    return jnp.moveaxis(losses, 0, 1).reshape(b, s)


@jax.named_scope("head")
def lm_logits(params, cfg, hidden) -> jnp.ndarray:
    """Project to (padded) vocab (ref: parallel_lm_logits language_model.py:24-53).

    Tied: x @ E^T; untied: x @ lm_head. Logits stay vocab-sharded
    (gather_output=False in the reference)."""
    if cfg.tie_embed_logits:
        w = params["embedding"]["word_embeddings"].astype(cfg.compute_dtype)
        logits = hidden @ w.T
    else:
        logits = hidden @ params["lm_head"].astype(cfg.compute_dtype)
    return shard_activation(logits, "logits")


def language_model_forward(
    params: dict,
    cfg,
    tokens: jnp.ndarray,
    position_ids: Optional[jnp.ndarray] = None,
    attention_mask: Optional[jnp.ndarray] = None,
    dropout_rng=None,
    deterministic: bool = True,
    kv_caches: Optional[dict] = None,
    return_hidden: bool = False,
) -> Tuple[jnp.ndarray, Optional[dict]]:
    """Full forward to logits (ref: TransformerLanguageModel.forward
    language_model.py:488-555 + GPTModel.forward gpt_model.py:84-100).

    `return_hidden` stops after the final norm and returns the (b, s, h)
    hidden states instead of logits — the training loss path projects to
    the vocab chunk-by-chunk (see chunked_head_cross_entropy) so the full
    (b, s, V) fp32 logits buffer never materialises."""
    if cfg.position_embedding_type == "rotary":
        rope_table = precompute_rope(
            cfg.head_dim,
            cfg.max_position_embeddings,
            cfg.rope_theta,
            cfg.rope_scaling_factor,
        )
    else:
        rope_table = None

    if dropout_rng is not None:
        emb_rng, stack_rng = jax.random.split(dropout_rng)
    else:
        emb_rng = stack_rng = None

    hidden = embed_tokens(params, cfg, tokens, position_ids, emb_rng, deterministic)
    hidden, new_caches = transformer_stack(
        params["layers"], cfg, hidden, rope_table, attention_mask, position_ids,
        stack_rng, deterministic, kv_caches,
    )
    hidden = apply_norm(hidden, params["final_norm"], cfg)
    if return_hidden:
        return hidden, new_caches
    logits = lm_logits(params, cfg, hidden)
    return logits, new_caches
