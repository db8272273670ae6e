"""GQA/MQA self-attention, TPU-first.

Parity target: ref megatron/model/transformer.py:280-537 (`ParallelAttention`
+ `CoreAttention`). Differences by design:

- Layout is (batch, seq, ...) — the TPU-friendly convention — not the
  reference's (seq, batch, ...).
- GQA is computed *grouped*: Q is reshaped to (b, s, groups, q_per_kv, d)
  and contracted against un-expanded K/V of (b, t, groups, d). The
  reference instead broadcast-expands K/V to full head count
  (ref: transformer.py:449-456), which wastes HBM bandwidth; the einsum
  form lets the MXU consume the grouped operand directly.
- The fused-softmax CUDA kernels (ref: fused_kernels/scaled_*_softmax*) are
  unnecessary: the masked-softmax here is fused by XLA; the flash path is a
  Pallas kernel (ops/flash_attention.py).

The fused QKV weight keeps the reference's grouped layout
[group g: q_g(0..q_per_kv-1), k_g, v_g] along the output dim
(ref: transformer.py:316,449-456; weights2megatron.py:82-146) so converted
checkpoints drop in unchanged.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.extend.source_info_util import current_name_stack
from jax.sharding import PartitionSpec as P

from megatron_llm_tpu.models.norms import rms_norm
from megatron_llm_tpu.models.remat import tag as _savepoint
from megatron_llm_tpu.models.rope import apply_rope
from megatron_llm_tpu.ops.quantization import qdot
from megatron_llm_tpu.parallel.mesh import (
    CONTEXT_AXIS,
    DATA_AXIS,
    MODEL_AXIS,
    get_context,
    in_manual_region,
    shard_activation,
    shard_kernel,
)

# how the attention kernels' operands split over the mesh: batch over
# `data`, KV groups (and with them the q heads) over `model`
_Q_SPEC = P(DATA_AXIS, None, MODEL_AXIS, None, None)  # (b, s, g, qpk, d)
_KV_SPEC = P(DATA_AXIS, None, MODEL_AXIS, None)  # (b, t, g, d) "tgd"
_KV_GTD_SPEC = P(DATA_AXIS, MODEL_AXIS, None, None)  # (b, g, T, d)


def _ring_dispatch(q, k, v, doc_start=None):
    """Ring attention over the `context` mesh axis, in a region manual
    over the WHOLE mesh (`shard_kernel`: the per-hop flash kernel must sit
    in a fully manual region) with the sequence over `context`, batch over
    `data` and groups over `model` — the ring body is row- and
    group-independent. Inside the pipeline's manual region `context` is
    already a manual axis of the enclosing shard_map (pipeline.py declares
    it when cp>1) and the operands are the local seq shards: where the hop
    is the Mosaic kernel `shard_kernel` makes the remaining axes manual
    round the same body; where it is XLA (off the TPU, packed documents,
    a shape the flash gate turns away) the body is called as is — it
    needs no more than `context`, and a nested shard_map round XLA hops
    trips jax 0.9.0's residual naming (KNOWN_FAILURES.md). `doc_start`
    (b, s) — global document-start indices — rides along seq-sharded for
    packed-document (--reset_attention_mask) training."""
    from megatron_llm_tpu.ops.flash_attention import flash_reaches_kernel
    from megatron_llm_tpu.parallel.ring_attention import ring_self_attention

    def ring(q, k, v, *ds):
        return ring_self_attention(q, k, v, CONTEXT_AXIS, causal=True,
                                   doc_start=ds[0] if ds else None)

    operands = (q, k, v)
    if doc_start is not None:
        operands += (doc_start.astype(jnp.int32),)
    if in_manual_region() and not (
            doc_start is None and flash_reaches_kernel(q.shape, k.shape[1])):
        return ring(*operands)
    qspec = P(DATA_AXIS, CONTEXT_AXIS, MODEL_AXIS, None, None)
    kspec = P(DATA_AXIS, CONTEXT_AXIS, MODEL_AXIS, None)
    in_specs = (qspec, kspec, kspec, P(DATA_AXIS, CONTEXT_AXIS))
    return shard_kernel(ring, in_specs[:len(operands)], qspec)(*operands)


def _decode_kernel_block(cfg, s: int, t: int, layout: str):
    """Static gate for the Pallas decode-attention kernel on the KV-cache
    paths: returns the cache block size, or None for the XLA fallback.
    Kernel territory is the single-token decode step (s == 1) against a
    cache of at least `decode_attn_min_cache` positions; prefill chunks
    (s > 1) keep the batched-GEMM path, which is compute-bound. A decode
    step the gate turns away is reported (ops/dispatch.py); a cache
    shorter than `decode_attn_min_cache` is the config's own routing and
    is not."""
    if not cfg.use_decode_attn or s != 1:
        return None
    from megatron_llm_tpu.ops.decode_attention import decode_attn_block
    from megatron_llm_tpu.ops.dispatch import report_fallback

    bt = decode_attn_block(
        s, cfg.q_per_kv, cfg.head_dim, t,
        min_cache=cfg.decode_attn_min_cache,
        interpret=cfg.decode_attn_interpret,
    )
    if bt is None and t >= cfg.decode_attn_min_cache:
        report_fallback("decode_attention", "decode_attn_block",
                        qpk=cfg.q_per_kv, d=cfg.head_dim, T=t, layout=layout)
    return bt


def split_qkv(mixed: jnp.ndarray, cfg) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """(b, s, qkv_size) -> q (b,s,g,qpk,d), k (b,s,g,d), v (b,s,g,d).

    Inverse of the reference's grouped view (ref: transformer.py:449-456).
    """
    b, s, _ = mixed.shape
    g, qpk, d = cfg.num_query_groups, cfg.q_per_kv, cfg.head_dim
    qkv = mixed.reshape(b, s, g, qpk + 2, d)
    q = qkv[:, :, :, :qpk]
    k = qkv[:, :, :, qpk]
    v = qkv[:, :, :, qpk + 1]
    return q, k, v


def grouped_attention(
    q: jnp.ndarray,  # (b, s, g, qpk, d)
    k: jnp.ndarray,  # (b, t, g, d)
    v: jnp.ndarray,  # (b, t, g, d)
    mask: Optional[jnp.ndarray],  # (b, 1, s, t) or (s, t); True = masked out
    cfg,
    dropout_rng=None,
    deterministic: bool = True,
) -> jnp.ndarray:
    """Reference (non-flash) attention path (ref: CoreAttention
    transformer.py:144-278) as one fused einsum chain, softmax in fp32
    (ref: attention_softmax_in_fp32 / fused-softmax kernels)."""
    b, s, g, qpk, d = q.shape
    scale = 1.0 / jnp.sqrt(d).astype(jnp.float32)

    # (b, g, qpk, s, t)
    scores = jnp.einsum(
        "bsgqd,btgd->bgqst", q, k, preferred_element_type=jnp.float32
    )
    scores = scores * scale
    if mask is not None:
        if mask.ndim == 2:
            neg = jnp.finfo(scores.dtype).min
            scores = jnp.where(mask[None, None, None], neg, scores)
        else:  # (b, 1, s, t)
            neg = jnp.finfo(scores.dtype).min
            scores = jnp.where(mask[:, :, None], neg, scores)

    probs = jax.nn.softmax(scores, axis=-1)
    if not deterministic and cfg.attention_dropout > 0.0 and dropout_rng is not None:
        keep = jax.random.bernoulli(
            dropout_rng, 1.0 - cfg.attention_dropout, probs.shape
        )
        probs = probs * keep / (1.0 - cfg.attention_dropout)
    probs = probs.astype(v.dtype)

    ctx = jnp.einsum("bgqst,btgd->bsgqd", probs, v)
    return ctx.reshape(b, s, g * qpk * d)


def cross_attention_block(
    attn_params: dict,
    cfg,
    hidden: jnp.ndarray,  # (b, s, h) decoder side
    encoder_output: jnp.ndarray,  # (b, t, h)
    mask: Optional[jnp.ndarray],  # (b, 1, s, t) True = masked out
    dropout_rng=None,
    deterministic: bool = True,
) -> jnp.ndarray:
    """Encoder-decoder cross attention (ref: ParallelAttention with
    attention_type=cross_attn, transformer.py:331-354, 456-470): Q from the
    decoder hidden, fused KV from the encoder output, same grouped einsum
    core as self-attention."""
    b, s, h = hidden.shape
    dt = cfg.compute_dtype
    g, qpk, d = cfg.num_query_groups, cfg.q_per_kv, cfg.head_dim

    q = (hidden @ attn_params["wq"].astype(dt)).reshape(b, s, g, qpk, d)
    kv = encoder_output @ attn_params["wkv"].astype(dt)
    if "bq" in attn_params:
        q = q + attn_params["bq"].astype(dt).reshape(g, qpk, d)
    if "bkv" in attn_params:
        kv = kv + attn_params["bkv"].astype(dt)
    # same named save points as self-attention (models/remat.py): the q and
    # kv projections both carry the "qkv_proj" name
    q = _savepoint(q, "qkv_proj")
    kv = _savepoint(kv, "qkv_proj")
    t = encoder_output.shape[1]
    kv = kv.reshape(b, t, g, 2, d)
    k, v = kv[:, :, :, 0], kv[:, :, :, 1]
    q = shard_activation(q, "groups")
    ctx = grouped_attention(q, k, v, mask, cfg, dropout_rng, deterministic)
    ctx = _savepoint(ctx, "attn_ctx")
    out = ctx @ attn_params["wo"].astype(dt)
    if "bo" in attn_params:
        out = out + attn_params["bo"].astype(dt)
    return _savepoint(out, "attn_dense")


def padding_mask_2d(q_keep: jnp.ndarray,
                    k_keep: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Keep-masks (b, s_q) [x (b, s_k)] {0,1} -> (b, 1, s_q, s_k)
    True-=-masked, the outer-product form (ref:
    bert_extended_attention_mask bert_model.py:21-35 and the enc-dec
    cross mask, t5_dataset.py make_attention_mask)."""
    if k_keep is None:
        k_keep = q_keep
    keep = q_keep.astype(jnp.float32)[:, :, None] * \
        k_keep.astype(jnp.float32)[:, None, :]
    return (keep < 0.5)[:, None]


def causal_mask(s: int, t: Optional[int] = None, offset: int = 0) -> jnp.ndarray:
    """(s, t) boolean mask, True = masked (ref convention:
    utils.py:137-196 builds mask with `< 0.5` => masked True)."""
    t = t if t is not None else s
    rows = jnp.arange(s)[:, None] + offset
    cols = jnp.arange(t)[None, :]
    return cols > rows


def _out_proj(attn_params: dict, ctx: jnp.ndarray, compute_dtype):
    """(b, s, g, q_per_kv * d) attention output -> (b, s, h): the heads
    constraint, the row-parallel `wo` product and its bias. Under tp the
    product's partial sums are exchanged here (all-reduce, or the
    reduce-scatter of sequence parallelism)."""
    with jax.named_scope("out_proj"):
        b, s = ctx.shape[:2]
        ctx = shard_activation(ctx, "heads").reshape(b, s, -1)
        out = qdot(ctx, attn_params["wo"], compute_dtype)
        if "bo" in attn_params:
            out = out + attn_params["bo"].astype(compute_dtype)
    return out


def _attend(statics, q, k, v, pools, page_table, starts, chunk_lens,
            doc_starts=None):
    """THE paged attention call of the paged branch below: `pools` is
    (k_pages, v_pages[, k_scales, v_scales]), `statics` the config's
    (use_decode_attn, decode_attn_min_cache, decode_attn_interpret,
    attention_window_size). Returns (out, *updated pools)."""
    from megatron_llm_tpu.ops.prefill_attention import (
        ragged_paged_attention,
    )

    use_pallas, min_cache, interpret, window = statics
    k_scales, v_scales = pools[2:] or (None, None)
    return ragged_paged_attention(
        q, k, v, pools[0], pools[1], page_table, starts, chunk_lens,
        use_pallas=use_pallas, min_cache=min_cache, interpret=interpret,
        k_scales=k_scales, v_scales=v_scales,
        window_size=window, doc_starts=doc_starts,
    )


@functools.partial(jax.jit, static_argnums=(0, 1))
def _attend_packed(statics, scope, q, k, v, pools, page_table, lengths,
                   chunk_lens, ci):
    """The packed form of a mixed round (`attention_block`,
    "packed_chunk"): q/k/v are (1, width + slots, ...) — slot `ci`'s
    chunk, then one decode row a slot. The chunk goes to the kernel as
    (nc=1, C=width) against its slot's page-table row, then the decode
    rows as (nc=slots, C=1) against the whole table, the decode scan's
    own shape; both scatter into the pools in turn.

    Jitted so that the unrolled layers of a step share ONE trace of it
    and the lowered program holds it once: every width bucket's warm-up
    traces, lowers and hashes the program whatever the compile cache
    holds, and two kernel calls a layer would otherwise make that half
    as long again. A called function's operations do not inherit the
    call site's name stack, so the caller hands over its `scope`."""
    n = lengths.shape[0]
    w = q.shape[1] - n
    with jax.named_scope(scope):
        out_c, *pools = _attend(
            statics, q[:, :w], k[:, :w], v[:, :w], pools,
            *(jax.lax.dynamic_slice_in_dim(x, ci, 1)
              for x in (page_table, lengths, chunk_lens)))
        dec_lens = jnp.where(jnp.arange(n) == ci, 0,
                             jnp.minimum(chunk_lens, 1))
        out_d, *pools = _attend(
            statics, q[0, w:, None], k[0, w:, None], v[0, w:, None],
            pools, page_table, lengths, dec_lens)
        return (jnp.concatenate([out_c, out_d[None, :, 0]], axis=1),
                *pools)


@jax.named_scope("attention")
def attention_block(
    attn_params: dict,
    cfg,
    hidden: jnp.ndarray,  # (b, s, h)
    rope_table: Optional[jnp.ndarray],
    mask: Optional[jnp.ndarray],
    position_ids: Optional[jnp.ndarray],
    dropout_rng=None,
    deterministic: bool = True,
    kv_cache: Optional[dict] = None,
) -> Tuple[jnp.ndarray, Optional[dict]]:
    """Full attention sublayer: fused qkv proj -> RoPE -> (cached) attention
    -> output proj (ref: ParallelAttention.forward transformer.py:412-537).

    `kv_cache` for incremental decode (ref: InferenceParams
    forward_step.py:17, transformer.py:483-496), three forms:
    - stacked (the decode hot path, what transformer_stack passes):
      {"k": (L, b, maxT, g, d), "v": ..., "offset": scalar, "layer": idx}
      — this layer's token column is updated IN PLACE inside the stack;
    - per-layer {"k": (b, maxT, g, d), "v": ..., "offset": scalar} for
      standalone single-layer use;
    - paged (the continuous-batching engine, inference/engine.py):
      {"k_pages": (P, page_size, g * d) (lane-packed: GPTModel.
      init_paged_kv_caches), "v_pages": ..., "page_table":
      (slots, max_pages) int32, "lengths": (slots,) int32, optionally
      "chunk_lens": (slots,) int32} — the batch axis is SLOTS at ragged
      per-slot lengths; slot i contributes a ragged span of
      chunk_lens[i] tokens starting at cache position lengths[i] (s is
      the padded chunk width; 1 == a decode row, 0 == idle), scattered
      into the slot's pages + attended in one ragged pass by THE paged
      kernel (ops/prefill_attention.ragged_paged_attention — ISSUE 18:
      decode scans, mixed rounds, and spec-verify all land here).
      Without "chunk_lens" the form is the engine's single-token decode
      step (s == 1): every slot is a width-1 chunk at its length.
      With "packed_chunk" (a scalar int32 slot index next to
      "chunk_lens": the engine's mixed prefill+decode round) `hidden`
      is ONE packed row axis (1, width + slots, h) instead of (slots,
      width, h): rows 0..width-1 are slot packed_chunk's prefill chunk
      (chunk_lens[packed_chunk] of them valid, at positions
      lengths[packed_chunk] + t), rows width + i are one decode row per
      slot (valid where chunk_lens[i] > 0 and i != packed_chunk). The
      projections around this branch then run on width + slots rows in
      one pass over the weights, and the kernel is called on the two
      shapes it already serves: (1, width) against the admitting
      slot's page-table row, then (slots, 1) against the whole table —
      the decode scan's own shape.

    On a tp serving mesh (DecodeEngine(serving_tp>1), ISSUE 14) BOTH
    paged forms run group-sharded: the pools arrive sharded on their
    lanes, each chip its own heads' (kv_pool_spec), the
    shard_activation("groups"/"heads")
    constraint sites steer q and the attention output onto the same
    split, and the scatter + attention call runs per shard inside
    `shard_kernel` (each chip runs the kernel — or its XLA twin — over
    its own groups against replicated page tables/lengths; Mosaic
    kernels cannot be partitioned by GSPMD). The wo matmul below is the
    step's one collective (row-parallel partial-sum all-reduce, pinned
    by the tp2 audit rows).
    """
    b, s, h = hidden.shape
    compute_dtype = cfg.compute_dtype

    # qdot: `hidden @ wqkv.astype(dt)` for fp weights (bitwise the old
    # call), int8 GEMV + per-channel scale for weight-only quantized
    # decode trees (prepare_decode_params(quantize_int8=True))
    with jax.named_scope("qkv_proj"):
        mixed = qdot(hidden, attn_params["wqkv"], compute_dtype)
        if "bqkv" in attn_params:
            mixed = mixed + attn_params["bqkv"].astype(compute_dtype)
        # named save point: under remat_policy selective/offload the
        # fused QKV projection is kept for backward; q/k/v (incl. RoPE)
        # rebuild from it with elementwise ops only (models/remat.py)
        mixed = _savepoint(mixed, "qkv_proj")
        q, k, v = split_qkv(mixed, cfg)
        if "q_norm" in attn_params:
            # RMSNorm over each head's channels, one scale for the q
            # heads and one for the k heads, before RoPE (qk_layernorm)
            q = rms_norm(q, attn_params["q_norm"], cfg.layernorm_epsilon)
            k = rms_norm(k, attn_params["k_norm"], cfg.layernorm_epsilon)
        q = shard_activation(q, "groups")

    if kv_cache is not None and "k_pages" in kv_cache:
        # THE paged branch (ISSUE 18 — the engine's one attention path):
        # slot i contributes a contiguous span of chunk_lens[i] tokens
        # (<= s, ragged; 0 = idle) starting at cache position
        # lengths[i]. The span's K/V is scattered into the slot's pages
        # and attention runs causally against everything the slot has
        # cached INCLUDING the span itself, in one pass
        # (ops/prefill_attention.ragged_paged_attention). Phase is a
        # shape: the engine's decode scan passes no "chunk_lens" — every
        # slot is then a width-1 chunk at its length, the exact decode
        # semantics (attend positions 0..lengths[i] inclusive of the
        # just-written token; retired slots carry all-null page-table
        # rows, so their writes land on the pool's dead null page 0).
        g, qpk, d = cfg.num_query_groups, cfg.q_per_kv, cfg.head_dim
        lengths = kv_cache["lengths"]
        chunked = "chunk_lens" in kv_cache
        if chunked:
            chunk_lens = kv_cache["chunk_lens"]
        else:
            assert s == 1, \
                "paged KV cache without chunk_lens serves single-token " \
                "decode steps"
            chunk_lens = jnp.ones_like(lengths)
        page_table = kv_cache["page_table"]
        if position_ids is None:
            position_ids = lengths[:, None] + jnp.arange(s)[None, :]
        if rope_table is not None:
            q = apply_rope(q, rope_table, position_ids)
            k = apply_rope(k, rope_table, position_ids)
        # one gate, inside the entry point (ragged_paged_block):
        # use_pallas=True means "kernel if eligible, XLA twin
        # otherwise"; ONE gate means a decode row takes the SAME
        # kernel-vs-XLA path in scan and mixed steps by construction
        quantized = "k_scales" in kv_cache  # int8 pools (ISSUE 9)
        # sliding window (ISSUE 19) rides the model config — static,
        # so every serving trace of a window-enabled model bakes the
        # O(window) clamp in; None leaves the trace byte-identical.
        # "doc_starts" (packed multi-doc prefill floors) is a cache
        # key like "chunk_lens": present only when the caller packs
        # documents, absent from the engine's carries.
        doc_starts = kv_cache.get("doc_starts")
        # "packed_chunk" (the engine's mixed round): b == 1 and the s
        # rows are slot packed_chunk's chunk followed by one decode row
        # a slot, so only that slot is laid out at the chunk's width
        packed_chunk = kv_cache.get("packed_chunk")
        if packed_chunk is not None:
            assert chunked and doc_starts is None and b == 1 \
                and s > lengths.shape[0] and position_ids is not None, \
                "packed_chunk packs one chunk + a decode row per slot " \
                "into a single (1, width + slots) row axis"
        window = getattr(cfg, "attention_window_size", None)

        statics = (cfg.use_decode_attn, cfg.decode_attn_min_cache,
                   cfg.decode_attn_interpret, window)

        def paged(q, k, v, k_pages, v_pages, page_table, lengths,
                  chunk_lens, *rest):
            pools = (k_pages, v_pages) + (tuple(rest[:2]) if quantized
                                          else ())
            if packed_chunk is not None:
                return _attend_packed(
                    statics, str(current_name_stack()), q, k, v, pools,
                    page_table, lengths, chunk_lens, rest[-1])
            return _attend(statics, q, k, v, pools, page_table, lengths,
                           chunk_lens,
                           rest[-1] if doc_starts is not None else None)

        # on a tp serving mesh the pools arrive sharded on their lanes
        # (kv_pool_spec: a chip's slice is its own heads): each chip
        # scatters and attends over its own groups against the
        # replicated page table / lengths
        pool = P(None, None, MODEL_AXIS)
        qspec = P(None, None, MODEL_AXIS, None, None)
        operands = [q, k, v, kv_cache["k_pages"], kv_cache["v_pages"],
                    page_table, lengths, chunk_lens]
        in_specs = [qspec, pool, pool, pool, pool, P(), P(), P()]
        out_specs = [qspec, pool, pool]
        if quantized:
            operands += [kv_cache["k_scales"], kv_cache["v_scales"]]
            in_specs += [pool] * 2
            out_specs += [pool] * 2
        if doc_starts is not None:
            operands.append(doc_starts)
            in_specs.append(P())
        if packed_chunk is not None:
            operands.append(packed_chunk)
            in_specs.append(P())
        # kv_write and page_gather open inside (ops/prefill_attention.py)
        with jax.named_scope("attn_core"):
            res = shard_kernel(paged, in_specs, tuple(out_specs),
                               check_vma=False)(*operands)
        # cache pytree layout is carry-stable: "chunk_lens" stays a key
        # only in the chunked form (the decode scan's carry never grows)
        new_cache = {"page_table": page_table,
                     "lengths": lengths + chunk_lens}
        if chunked:
            new_cache["chunk_lens"] = chunk_lens
        if doc_starts is not None:
            new_cache["doc_starts"] = doc_starts
        if packed_chunk is not None:
            new_cache["packed_chunk"] = packed_chunk
        if quantized:
            (ctx, new_cache["k_pages"], new_cache["v_pages"],
             new_cache["k_scales"], new_cache["v_scales"]) = res
        else:
            ctx, new_cache["k_pages"], new_cache["v_pages"] = res
        return _out_proj(attn_params, ctx.reshape(b, s, g, qpk * d),
                         compute_dtype), new_cache
    if kv_cache is not None:
        offset = kv_cache["offset"]
        if position_ids is None:
            position_ids = offset + jnp.arange(s)[None, :]
        if rope_table is not None:
            q = apply_rope(q, rope_table, position_ids)
            k = apply_rope(k, rope_table, position_ids)
        if "k_gtd" in kv_cache:
            # decode fast path: per-layer standalone (b, g, T, d) caches
            # (init_kv_caches layout="layers") — column updates and
            # attention reads hit a small contiguous buffer in place, no
            # per-layer stack slicing. (A (b, g, d, T) K layout was also
            # measured: the minor-axis column scatter cost more than the
            # sublane-reduce saved.)
            g, qpk, d = cfg.num_query_groups, cfg.q_per_kv, cfg.head_dim
            with jax.named_scope("kv_write"):
                kc = jax.lax.dynamic_update_slice(
                    kv_cache["k_gtd"], k.transpose(0, 2, 1, 3),
                    (0, 0, offset, 0))
                vc = jax.lax.dynamic_update_slice(
                    kv_cache["v_gtd"], v.transpose(0, 2, 1, 3),
                    (0, 0, offset, 0))
            new_cache = {"k_gtd": kc, "v_gtd": vc, "offset": offset + s}
            t = kc.shape[2]
            bt = _decode_kernel_block(cfg, s, t, "gtd")
            if bt is not None:
                # Pallas decode-attention kernel: streams the cache at
                # line rate with in-kernel length masking (the XLA
                # matvecs run far under HBM bandwidth at s == 1)
                from megatron_llm_tpu.ops.decode_attention import (
                    decode_attention,
                )

                with jax.named_scope("attn_core"):
                    ctx = shard_kernel(
                        functools.partial(
                            decode_attention, layout="gtd",
                            use_pallas=True, block_t=bt,
                            interpret=cfg.decode_attn_interpret),
                        (_Q_SPEC, _KV_GTD_SPEC, _KV_GTD_SPEC, P()),
                        _Q_SPEC, check_vma=False,
                    )(q, kc, vc, offset + s)
            else:
                from megatron_llm_tpu.ops.decode_attention import (
                    _xla_decode,
                )

                # the kernel's shapes-and-math twin (batched GEMMs +
                # O(s*t) iota mask) — ONE definition so the exact-match
                # tests pin the kernel against the code that actually
                # serves the fallback
                with jax.named_scope("attn_core"):
                    ctx = _xla_decode(q, kc, vc, offset + s, "gtd")
            return _out_proj(attn_params, ctx.reshape(b, s, g, qpk * d),
                             compute_dtype), new_cache
        if "layer" in kv_cache:
            # stacked-cache form (decode hot path): update THIS layer's
            # token column in place inside the full (L, b, T, g, d) stack
            # and slice the layer back for attention. Updating only the
            # written column (instead of materializing a new per-layer
            # buffer and re-stacking it through scan ys) measured 2.2x
            # faster per decode step at b=8/T=576 on v5e.
            lidx = kv_cache["layer"]
            with jax.named_scope("kv_write"):
                kc = jax.lax.dynamic_update_slice(
                    kv_cache["k"], k[None], (lidx, 0, offset, 0, 0)
                )
                vc = jax.lax.dynamic_update_slice(
                    kv_cache["v"], v[None], (lidx, 0, offset, 0, 0)
                )
                k_full = jax.lax.dynamic_index_in_dim(kc, lidx, 0, False)
                v_full = jax.lax.dynamic_index_in_dim(vc, lidx, 0, False)
            new_cache = {"k": kc, "v": vc, "offset": offset + s,
                         "layer": lidx}
        else:
            with jax.named_scope("kv_write"):
                k_full = jax.lax.dynamic_update_slice_in_dim(
                    kv_cache["k"], k, offset, axis=1)
                v_full = jax.lax.dynamic_update_slice_in_dim(
                    kv_cache["v"], v, offset, axis=1)
            new_cache = {"k": k_full, "v": v_full, "offset": offset + s}
        t = k_full.shape[1]
        bt = _decode_kernel_block(cfg, s, t, "tgd")
        if bt is not None:
            # stage-ring pipelined decode ticks land here (stacked cache,
            # s == 1): stream this layer's (b, T, g, d) cache slice
            # through the decode kernel in place — no transpose, no dense
            # (s, t) mask
            from megatron_llm_tpu.ops.decode_attention import (
                decode_attention,
            )

            with jax.named_scope("attn_core"):
                ctx = shard_kernel(
                    functools.partial(
                        decode_attention, layout="tgd", use_pallas=True,
                        block_t=bt, interpret=cfg.decode_attn_interpret),
                    (_Q_SPEC, _KV_SPEC, _KV_SPEC, P()), _Q_SPEC,
                    check_vma=False,
                )(q, k_full, v_full, offset + s).reshape(b, s, -1)
        else:
            # rows attend to cols <= offset+row
            rows = offset + jnp.arange(s)[:, None]
            cols = jnp.arange(t)[None, :]
            dec_mask = cols > rows  # (s, t)
            with jax.named_scope("attn_core"):
                ctx = grouped_attention(q, k_full, v_full, dec_mask, cfg,
                                        dropout_rng, deterministic=True)
    else:
        if rope_table is not None:
            q = apply_rope(q, rope_table, position_ids)
            k = apply_rope(k, rope_table, position_ids)
        # Packed-document masking (--reset_attention_mask) arrives as
        # {"doc_start": (b, s)} — O(s) instead of a dense (s, s) mask —
        # and stays SEQ-SHARDED through the ring (VERDICT r4 #5).
        doc_start = None
        if isinstance(mask, dict):
            doc_start = mask["doc_start"]
            mask = None
        # flash path has no dropout support: fall back to the grouped path
        # when attention dropout is live (ADVICE r1; the reference's
        # FlashSelfAttention passes dropout to the CUDA kernel instead)
        no_dropout = deterministic or cfg.attention_dropout == 0.0
        pctx = get_context()
        # Context parallelism: when the mesh has a context axis, attention
        # is the ONE op that mixes sequence positions — run the exact ring
        # (scan + ppermute, parallel/ring_attention.py) over seq shards.
        # RoPE was applied above with global position_ids, so q/k enter the
        # ring already rotated.
        ring_ok = (
            pctx is not None and pctx.cp > 1 and mask is None and no_dropout
        )
        if pctx is not None and pctx.cp > 1 and mask is not None:
            # LOUD refusal (was a silent gathered-attention fallback):
            # a dense mask under cp would force a full-sequence gather,
            # quietly losing the memory scaling cp exists for. The CLI
            # path never gets here — args_to_configs rejects BERT/T5
            # (padding-mask models, which have no doc_start form) at
            # config construction; this guard catches direct library
            # callers.
            raise ValueError(
                "cp>1 with a dense attention mask: pass packed-document "
                "masks as {'doc_start': (b, s)} (utils/masks.py "
                "get_document_starts) to keep the sequence sharded, or "
                "disable context parallelism for this model. "
                "BERT/T5-style PADDING masks have no doc_start "
                "equivalent — those model families must run with cp=1 "
                "(rejected at config construction on the CLI path; "
                "docs/GUIDE.md 'Masks')"
            )
        if (pctx is not None and pctx.cp > 1 and doc_start is not None
                and not no_dropout):
            # same loudness for the dropout corner: the ring has no
            # attention-dropout path, and falling back to gathered
            # attention would silently lose cp's memory scaling
            raise ValueError(
                "cp>1 packed-document attention requires "
                "attention_dropout == 0 (ring attention has no dropout "
                "path)"
            )
        if doc_start is not None and not ring_ok:
            # single-device / no-cp path: expand to the dense equivalent
            rows = jnp.arange(s)[None, :, None]
            cols = jnp.arange(s)[None, None, :]
            mask = ((cols > rows) |
                    (cols < doc_start[:, :, None]))[:, None]
        flash_ok = cfg.use_flash_attn and mask is None and no_dropout \
            and doc_start is None
        if ring_ok:
            with jax.named_scope("attn_core"):
                ctx = _ring_dispatch(q, k, v, doc_start=doc_start)
            ctx = _savepoint(ctx, "attn_ctx").reshape(b, s, -1)
        elif flash_ok:
            from megatron_llm_tpu.ops.flash_attention import flash_attention

            # flash output + logsumexp are tagged INSIDE the wrapper
            # ("attn_ctx"/"flash_lse", ops/flash_attention.py) so the
            # selective policy can keep both and the backward never
            # re-runs the forward kernel
            with jax.named_scope("attn_core"):
                ctx = shard_kernel(
                    functools.partial(flash_attention, causal=True),
                    (_Q_SPEC, _KV_SPEC, _KV_SPEC), _Q_SPEC,
                )(q, k, v)
            ctx = ctx.reshape(b, s, -1)
        else:
            if mask is None:
                mask = causal_mask(s)
            # The O(s*t) softmax probabilities are NOT a named save point:
            # under any remat policy but "none" they are recomputed from
            # the saved "qkv_proj" (the reference's selective-granularity
            # behavior, ref: transformer.py:357-401, now expressed by the
            # name policy in models/remat.py rather than a nested
            # jax.checkpoint around the core).
            with jax.named_scope("attn_core"):
                ctx = grouped_attention(q, k, v, mask, cfg, dropout_rng,
                                        deterministic)
            ctx = _savepoint(ctx, "attn_ctx")
        new_cache = None

    out = _out_proj(
        attn_params,
        ctx.reshape(b, s, cfg.num_query_groups, cfg.q_per_kv * cfg.head_dim),
        compute_dtype)
    out = _savepoint(out, "attn_dense")
    return out, new_cache
