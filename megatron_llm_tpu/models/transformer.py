"""Decoder transformer stack — scan-over-layers, remat-aware.

Parity target: ref megatron/model/transformer.py (`ParallelMLP` :77,
`ParallelTransformerLayer` :582, `ParallelTransformer` :897). TPU-first
departures:

- Layer weights are *stacked* along a leading layer axis and the stack is a
  single `jax.lax.scan`, so the whole model compiles once regardless of
  depth (the reference's Python per-layer loop, transformer.py:1236-1242,
  is a CUDA-graph idiom XLA doesn't need).
- Activation recompute is `jax.checkpoint` on the scanned body, driven by
  the named-savepoint policy ladder (models/remat.py;
  ModelConfig.remat_policy full/selective/save_dots/offload/none —
  ref: recompute_granularity arguments.py:606-630, random.py:175-247).
- Residual structure covers pre/post-LN, Falcon parallel-attention and
  parallel-layernorm variants (ref: transformer.py:613-634, 774-806).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from megatron_llm_tpu.config import CapabilityError
from megatron_llm_tpu.models.activations import ACTIVATIONS, GLU_ACTIVATIONS
from megatron_llm_tpu.models.attention import attention_block
from megatron_llm_tpu.models.moe import N_STATS, moe_block
from megatron_llm_tpu.models.norms import apply_norm
from megatron_llm_tpu.models.remat import remat_wrap, tag as _savepoint
from megatron_llm_tpu.models.short_conv import short_conv_block
from megatron_llm_tpu.ops.quantization import is_quantized_weight, qdot
from megatron_llm_tpu.parallel.mesh import shard_activation


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def _normal(key, shape, std, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def init_norm_params(cfg, shape_prefix=()) -> dict:
    p = {"scale": jnp.ones(shape_prefix + (cfg.hidden_size,), cfg.params_dtype)}
    if not cfg.use_rms_norm:
        p["bias"] = jnp.zeros(shape_prefix + (cfg.hidden_size,), cfg.params_dtype)
    return p


def kind_name(kind: tuple) -> str:
    """The name a kind's stack goes by under `params["layers"]` of a
    model whose layers are of several kinds."""
    return "_".join(kind)


def kind_stacks(cfg, layers) -> dict:
    """{kind: that kind's stacked leaves} of `params["layers"]`. A model
    of one kind keeps its stack AS `layers` (the trees and checkpoints
    of the dense families are what they were); with several kinds
    `layers` holds one stack a kind under `kind_name(kind)`."""
    kinds = tuple(dict.fromkeys(cfg.layer_kinds))
    if len(kinds) == 1:
        return {kinds[0]: layers}
    return {kind: layers[kind_name(kind)] for kind in kinds}


def layer_runs(cfg) -> list:
    """The layers as runs of one kind, in layer order: [(kind, the run's
    first entry in its kind's stack, its first layer, its length)]."""
    seen, runs = {}, []
    for i, kind in enumerate(cfg.layer_kinds):
        j = seen.get(kind, 0)
        seen[kind] = j + 1
        if runs and runs[-1][0] == kind:
            runs[-1][3] += 1
        else:
            runs.append([kind, j, i, 1])
    return [tuple(r) for r in runs]


def init_layer_params(cfg, key, num_layers: Optional[int] = None) -> dict:
    """Stacked per-layer weights, leading axis = layer; one stack a kind
    of layer (`kind_stacks`).

    Init distributions follow the reference (ref: model/utils.py:11-24,
    layers.py:79-125): normal(0, std) for inputs projections, and
    normal(0, std/sqrt(2*num_layers)) for the residual-output projections
    (wo, w2) when use_scaled_init_method.
    """
    kinds = cfg.layer_kinds
    if len(set(kinds)) == 1:
        L = num_layers if num_layers is not None else cfg.num_layers
        return _init_kind_params(cfg, key, kinds[0], L)
    assert num_layers is None, "a slice of a stack of several kinds"
    return {kind_name(kind): _init_kind_params(
                cfg, jax.random.fold_in(key, n), kind, kinds.count(kind))
            for n, kind in enumerate(dict.fromkeys(kinds))}


def _init_kind_params(cfg, key, kind: tuple, L: int) -> dict:
    operator, ff = kind
    h = cfg.hidden_size
    std = cfg.init_method_std
    out_std = std / jnp.sqrt(2.0 * cfg.num_layers) if cfg.use_scaled_init_method else std
    keys = jax.random.split(key, 4)
    dt = cfg.params_dtype

    layers = {"input_norm": init_norm_params(cfg, (L,))}
    if operator == "conv":
        assert not cfg.use_bias, "the short convolution has no bias"
        taps = cfg.conv_L_cache
        k_in, k_taps = jax.random.split(keys[0])
        layers["conv"] = {
            "w_in": _normal(k_in, (L, h, 3 * h), std, dt),
            "kernel": _normal(k_taps, (L, h, taps), taps ** -0.5, dt),
            "w_out": _normal(keys[1], (L, h, h), out_std, dt),
        }
    else:
        layers["attention"] = attn = {
            "wqkv": _normal(keys[0], (L, h, cfg.qkv_projection_size), std,
                            dt),
            "wo": _normal(
                keys[1],
                (L, cfg.num_attention_heads * cfg.head_dim, h),
                out_std,
                dt,
            ),
        }
        if cfg.qk_layernorm:
            attn["q_norm"] = jnp.ones((L, cfg.head_dim), dt)
            attn["k_norm"] = jnp.ones((L, cfg.head_dim), dt)
        if cfg.use_bias:
            attn["bqkv"] = jnp.zeros((L, cfg.qkv_projection_size), dt)
            attn["bo"] = jnp.zeros((L, h), dt)
    if ff == "moe":
        assert not cfg.use_bias, "the routed MLP has no bias"
        E, f = cfg.num_experts, cfg.moe_intermediate_size
        k_router, k_gate, k_up = jax.random.split(keys[2], 3)
        layers["moe"] = moe = {
            "router": _normal(k_router, (L, h, E), std, dt),
            "w_gate": _normal(k_gate, (L, E, h, f), std, dt),
            "w_up": _normal(k_up, (L, E, h, f), std, dt),
            "w_down": _normal(keys[3], (L, E, f, h), out_std, dt),
        }
        if cfg.use_expert_bias:
            # a buffer the published training moves, not a trained weight
            moe["expert_bias"] = jnp.zeros((L, E), jnp.float32)
    else:
        # GLU up-projections are stored (L, h, 2, ffn) — the gate/up axis
        # kept separate from the ffn axis — so TP sharding of ffn over the
        # model axis never crosses the gate/up boundary (the reference
        # packs them into one 2*ffn dim, ref: transformer.py:92-102, which
        # forces an interleaved per-rank layout; checkpoint converters
        # reshape (h, 2*ffn) <-> (h, 2, ffn)).
        if cfg.glu_activation:
            w1_shape = (L, h, 2, cfg.ffn_hidden_size)
            b1_shape = (L, 2, cfg.ffn_hidden_size)
        else:
            w1_shape = (L, h, cfg.ffn_hidden_size)
            b1_shape = (L, cfg.ffn_hidden_size)
        layers["mlp"] = mlp = {
            "w1": _normal(keys[2], w1_shape, std, dt),
            "w2": _normal(keys[3], (L, cfg.ffn_hidden_size, h), out_std,
                          dt),
        }
        if cfg.use_bias:
            mlp["b1"] = jnp.zeros(b1_shape, dt)
            mlp["b2"] = jnp.zeros((L, h), dt)
    # post-attention norm exists unless Falcon-style parallel_attn without
    # a dedicated mlp norm (ref: transformer.py:613-634).
    if not cfg.parallel_attn:
        layers["post_attention_norm"] = init_norm_params(cfg, (L,))
    if cfg.parallel_layernorm:
        layers["mlp_norm"] = init_norm_params(cfg, (L,))
    return layers


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


@jax.named_scope("mlp")
def mlp_block(mlp_params, cfg, hidden, dropout_rng, deterministic):
    """ParallelMLP (ref: transformer.py:77-142): h -> [2x]ffn -> act -> h.

    Weight-only int8 decode trees (prepare_decode_params
    (quantize_int8=True), ISSUE 9) arrive with w1/w2 as
    {"int8_data", "scale"} dicts — always in the pre-flattened 2D
    decode layout — and route through `qdot` (int8 GEMV + per-channel
    scale); fp weights take the bitwise-unchanged matmuls."""
    dt = cfg.compute_dtype
    w1 = mlp_params["w1"]
    with jax.named_scope("up"):
        x = _mlp_up(mlp_params, cfg, hidden, w1, dt)
    with jax.named_scope("act"):
        if cfg.glu_activation:
            x = shard_activation(x, "glu_ffn")
            act = GLU_ACTIVATIONS[cfg.glu_activation]
            x = act(x[..., 0, :], x[..., 1, :])
        else:
            x = ACTIVATIONS[cfg.hidden_act](x)
        x = shard_activation(x, "ffn")
    with jax.named_scope("down"):
        x = qdot(x, mlp_params["w2"], dt)
        if "b2" in mlp_params:
            x = x + mlp_params["b2"].astype(dt)
        x = _savepoint(x, "mlp_out")
    return x


def _mlp_up(mlp_params, cfg, hidden, w1, dt):
    """h -> [2x]ffn, bias and the named save point (the `mlp/up` scope)."""
    if cfg.glu_activation:
        if is_quantized_weight(w1) or w1.ndim == 2:
            # Pre-flattened (h, 2f) decode layout (see
            # prepare_decode_params): the (h, 2, f) einsum tiles the
            # 2-sized gate/up axis into sublanes and streams the weight
            # at ~33% of HBM bandwidth at single-token shapes (traced on
            # v5e); the SAME bytes as one flat matvec stream at ~72%
            # like every other GEMV.
            b, s, h = hidden.shape
            x = qdot(hidden, w1, dt).reshape(b, s, 2, -1)
        else:
            # (b,s,h) @ (h,2,f) -> (b,s,2,f); gate/up on their own axis.
            # Also the tp-sharded DECODE path (ISSUE 14): mesh engines
            # keep this layout (prepare_decode_params(flatten_glu=
            # False)) so f shards over `model` and the GLU combine
            # stays elementwise-local per chip — the flat (h, 2f) view
            # concatenates gate|up along exactly the sharded axis.
            x = jnp.einsum("bsh,hcf->bscf", hidden, w1.astype(dt))
        if "b1" in mlp_params:
            x = x + mlp_params["b1"].astype(dt)
        # named save point: the pre-GLU up-projection — what the selective
        # policy keeps so the gate/up GEMM never re-runs in backward (the
        # GLU combine itself is the unnamed-elementwise part it recomputes)
        return _savepoint(x, "mlp_pre_act")
    x = qdot(hidden, w1, dt)
    if "b1" in mlp_params:
        x = x + mlp_params["b1"].astype(dt)
    return _savepoint(x, "mlp_pre_act")


def _dropout(x, rate, rng, deterministic):
    if deterministic or rate == 0.0 or rng is None:
        return x
    keep = jax.random.bernoulli(rng, 1.0 - rate, x.shape)
    return x * keep / (1.0 - rate)


@jax.named_scope("block")
def transformer_layer(
    layer_params: dict,
    cfg,
    hidden: jnp.ndarray,
    rope_table,
    mask,
    position_ids,
    dropout_rng=None,
    deterministic: bool = True,
    kv_cache: Optional[dict] = None,
    hidden_dropout_rate: Optional[float] = None,
    kind: tuple = ("attention", "mlp"),
    row_mask: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, Optional[dict]]:
    """One decoder layer (ref: ParallelTransformerLayer.forward
    transformer.py:695-817), covering:

    - pre-LN (default) / post-LN (cfg.use_post_ln, ref :630-634)
    - Falcon parallel attention: mlp input = same norm output, residual =
      hidden + attn_out + mlp_out (ref :774-806)
    - Falcon-40B parallel layernorm: dedicated mlp_norm (ref :613-629)
    - `kind` = (operator, feed-forward), static: attention or the gated
      short convolution (models/short_conv.py: `kv_cache` then holds the
      slots' "conv_state" in place of page pools), the dense MLP or the
      routed one (models/moe.py; of a served round it sees `row_mask`,
      which rows are real, and leaves its "moe_stats" in the new cache)
    """
    operator, ff = kind
    p_hidden = cfg.hidden_dropout if hidden_dropout_rate is None else hidden_dropout_rate
    if dropout_rng is not None:
        attn_rng, h1_rng, h2_rng = jax.random.split(dropout_rng, 3)
    else:
        attn_rng = h1_rng = h2_rng = None

    residual = hidden
    normed = apply_norm(hidden, layer_params["input_norm"], cfg)
    if operator == "conv":
        if isinstance(mask, dict) or "doc_starts" in (kv_cache or ()):
            raise CapabilityError("packed documents", "the short "
                                  "convolution runs across a boundary")
        attn_out, new_state = short_conv_block(
            layer_params["conv"], cfg, normed, kv_cache)
        new_cache = None if kv_cache is None else {"conv_state": new_state}
    else:
        attn_out, new_cache = attention_block(
            layer_params["attention"], cfg, normed, rope_table, mask,
            position_ids, attn_rng, deterministic, kv_cache,
        )

    def feed_forward(x):
        if ff != "moe":
            return mlp_block(layer_params["mlp"], cfg, x, h2_rng,
                             deterministic)
        out, stats = moe_block(layer_params["moe"], cfg, x, row_mask)
        if new_cache is not None:
            new_cache["moe_stats"] = stats
        return out

    if cfg.parallel_attn:
        if cfg.parallel_layernorm:
            mlp_in = apply_norm(hidden, layer_params["mlp_norm"], cfg)
        else:
            mlp_in = normed
        mlp_out = feed_forward(mlp_in)
        out = residual + _dropout(attn_out + mlp_out, p_hidden, h1_rng, deterministic)
    elif cfg.use_post_ln:
        x = residual + _dropout(attn_out, p_hidden, h1_rng, deterministic)
        x = shard_activation(x, "hidden_seq")
        x = apply_norm(x, layer_params["post_attention_norm"], cfg)
        mlp_out = feed_forward(x)
        out = x + _dropout(mlp_out, p_hidden, h2_rng, deterministic)
        # final norm handled by caller; post-LN applies input_norm after attn
    else:
        x = residual + _dropout(attn_out, p_hidden, h1_rng, deterministic)
        # mid-layer norm/dropout region: seq-sharded under SP (the
        # reduce-scatter after the row-parallel wo, ref: layers.py:225-296)
        x = shard_activation(x, "hidden_seq")
        normed2 = apply_norm(x, layer_params["post_attention_norm"], cfg)
        mlp_out = feed_forward(normed2)
        out = x + _dropout(mlp_out, p_hidden, h2_rng, deterministic)

    # layer boundary = norm/dropout region: under SP the saved residual is
    # seq-sharded over (context, model) — the per-layer memory / tp saving
    # the reference's SP exists for (ref: layers.py:225-296)
    out = shard_activation(out, "hidden_seq")
    return out, new_cache


def _dense_caches_are_one_kinds(several: bool) -> None:
    if several:
        raise CapabilityError(
            "decoding through dense per-layer caches",
            "its layers are of several kinds: a conv layer's state lives "
            "in the engine's cache tree (GPTModel.init_paged_kv_caches)")


def _served_rows(riders: dict, shape: tuple) -> jnp.ndarray:
    """(b, s) bool: which rows of a served round are real, from the
    riders as `attention_block`'s paged branch reads them."""
    b, s = shape
    chunk_lens = riders.get("chunk_lens")
    if chunk_lens is None:  # the decode scan: one row a slot
        return riders.get("active", jnp.ones((b,), bool))[:, None]
    if "packed_chunk" in riders:  # the chunk's rows, then a row a slot
        ci, n = riders["packed_chunk"], chunk_lens.shape[0]
        return jnp.concatenate([
            jnp.arange(s - n) < chunk_lens[ci],
            (chunk_lens > 0) & (jnp.arange(n) != ci)])[None]
    return jnp.arange(s)[None] < chunk_lens[:, None]


@jax.named_scope("layers")
def transformer_stack(
    layer_params: dict,
    cfg,
    hidden: jnp.ndarray,
    rope_table=None,
    mask=None,
    position_ids=None,
    dropout_rng=None,
    deterministic: bool = True,
    kv_caches: Optional[dict] = None,
    layer_offset: int = 0,
) -> Tuple[jnp.ndarray, Optional[dict]]:
    """Scan the stacked layers (ref: ParallelTransformer.forward
    transformer.py:1158-1246).

    `kv_caches` = {"k": (L,b,T,g,d), "v": ..., "offset": scalar} or None.
    `layer_offset` supports pipeline chunks (ref vpp offset math
    transformer.py:1015-1045): layer i's dropout key and LIMA rate use
    global index layer_offset + i.
    """
    unrolled = isinstance(layer_params, (list, tuple))
    kinds = cfg.layer_kinds
    several = len(set(kinds)) > 1
    if unrolled:
        L = len(layer_params)
    elif several:
        L = cfg.num_layers
    else:
        L = jax.tree_util.tree_leaves(layer_params)[0].shape[0]
    if several and (layer_offset or L != cfg.num_layers):
        raise CapabilityError("a slice of the layer stack",
                              "its layers are of several kinds")
    num_total = cfg.num_layers

    def body(carry, xs, kind=kinds[0], row_mask=None):
        hidden, = carry
        params_l, idx, cache_l = xs
        if dropout_rng is not None:
            rng_l = jax.random.fold_in(dropout_rng, idx)
        else:
            rng_l = None
        if cfg.lima_dropout and num_total > 1:
            # linear ramp 0 -> hidden_dropout over depth (ref: transformer.py:964-971)
            p_l = cfg.hidden_dropout * idx.astype(jnp.float32) / (num_total - 1)
        else:
            p_l = None
        out, new_cache_l = transformer_layer(
            params_l, cfg, hidden, rope_table, mask, position_ids,
            rng_l, deterministic, cache_l, hidden_dropout_rate=p_l,
            kind=kind, row_mask=row_mask,
        )
        return (out,), new_cache_l

    # Which remat policy wraps the scan body (models/remat.py): "full"
    # saves only the boundary carry, "selective"/"offload" keep the named
    # matmul outputs (on device / in pinned host), "save_dots" keeps every
    # dot, "none" skips the wrapper. How MANY layers get it follows
    # --recompute_method (ref: arguments.py:616-630): "uniform" remats
    # every layer; "block" remats only the first recompute_num_layers —
    # the rest keep their activations, soaking up whatever HBM is left.
    policy = cfg.resolved_remat_policy
    if policy != "none":
        if cfg.recompute_method == "block":
            n_remat = min(cfg.recompute_num_layers, L)
        else:
            n_remat = L
    else:
        n_remat = 0
    idxs = layer_offset + jnp.arange(L)
    if unrolled:
        # Decode fast path (prepare_decode_params): per-layer standalone
        # weight trees + per-layer (b, g, T, d) caches, layer loop
        # UNROLLED in Python. The scan form dynamic-slices every layer's
        # weights AND cache out of stacked buffers each token — a full
        # extra read+write of the weights and cache per step (traced on
        # v5e); standalone buffers are read in place.
        assert kv_caches is not None and (
            "k_layers" in kv_caches or "k_pages_layers" in kv_caches
        ), "unrolled (tuple) layer params are the decode fast path"
        if "k_pages_layers" in kv_caches:
            # paged serving (continuous-batching engine): per-layer page
            # POOLS with one shared page table + per-slot lengths; each
            # layer scatters its span into the slot's pages and reads
            # back only owned pages through THE ragged paged attention
            # kernel (attention_block's one paged branch, ISSUE 18 —
            # decode rows are width-1 chunks of the same kernel). Same
            # unrolled structure as the dense decode fast path —
            # standalone per-layer buffers, no stack slicing.
            pt = kv_caches["page_table"]
            lens = kv_caches["lengths"]
            # chunked mixed prefill+decode step (ISSUE 4): per-slot
            # ragged chunk lengths ride through every layer (the layer
            # branch scatters + attends the whole span at once); the
            # stack-level length advance is ragged too
            # packed multi-doc prefill (ISSUE 19): per-chunk document
            # floors thread through every layer exactly like chunk_lens;
            # so does the admitting slot's index of a mixed round's
            # packed row axis (attention_block's paged form)
            # and the decode scan's `active`, for the layers that carry
            # a per-slot state or route
            riders = {k: kv_caches[k]
                      for k in ("chunk_lens", "doc_starts", "packed_chunk",
                                "active")
                      if kv_caches.get(k) is not None}
            cl = riders.get("chunk_lens")
            ks = list(kv_caches["k_pages_layers"])
            vs = list(kv_caches["v_pages_layers"])
            # int8 KV pools (ISSUE 9): per-layer fp32 scale pools ride
            # alongside the data pools through every layer
            kss = (list(kv_caches["k_scales_layers"])
                   if "k_scales_layers" in kv_caches else None)
            vss = (list(kv_caches["v_scales_layers"])
                   if kss is not None else None)
            # pools are one an ATTENTION layer, states one a conv layer
            # (GPTModel.init_paged_kv_caches), each in layer order
            states = list(kv_caches.get("conv_state_layers", ()))
            row_mask = _served_rows(riders, hidden.shape[:2]) \
                if any(ff == "moe" for _, ff in kinds) else None
            stats = None
            a = c = 0
            for i in range(L):
                kind = kinds[i if several else 0]
                if kind[0] == "conv":
                    cache_l = {"conv_state": states[c], "lengths": lens,
                               **riders}
                else:
                    cache_l = {"k_pages": ks[a], "v_pages": vs[a],
                               "page_table": pt, "lengths": lens, **riders}
                    if kss is not None:
                        cache_l["k_scales"] = kss[a]
                        cache_l["v_scales"] = vss[a]
                (hidden,), nc = body(
                    (hidden,), (layer_params[i], idxs[i], cache_l),
                    kind, row_mask)
                if kind[0] == "conv":
                    states[c] = nc["conv_state"]
                    c += 1
                else:
                    ks[a], vs[a] = nc["k_pages"], nc["v_pages"]
                    if kss is not None:
                        kss[a], vss[a] = nc["k_scales"], nc["v_scales"]
                    a += 1
                if "moe_stats" in nc:
                    stats = nc["moe_stats"] + (0 if stats is None else stats)
            new_caches = {
                "k_pages_layers": tuple(ks), "v_pages_layers": tuple(vs),
                "page_table": pt,
                "lengths": lens + (cl if cl is not None
                                   else hidden.shape[1]),
                **riders,
            }
            if kss is not None:
                new_caches["k_scales_layers"] = tuple(kss)
                new_caches["v_scales_layers"] = tuple(vss)
            if states:
                new_caches["conv_state_layers"] = tuple(states)
            if stats is not None:
                new_caches["moe_stats"] = stats
            return hidden, new_caches
        _dense_caches_are_one_kinds(several)
        offset = kv_caches["offset"]
        ks = list(kv_caches["k_layers"])
        vs = list(kv_caches["v_layers"])
        for i in range(L):
            cache_l = {"k_gtd": ks[i], "v_gtd": vs[i], "offset": offset}
            (hidden,), nc = body(
                (hidden,), (layer_params[i], idxs[i], cache_l)
            )
            ks[i], vs[i] = nc["k_gtd"], nc["v_gtd"]
        new_caches = {"k_layers": tuple(ks), "v_layers": tuple(vs),
                      "offset": offset + hidden.shape[1]}
        return hidden, new_caches
    if kv_caches is not None:
        # Decode: the FULL (L, b, T, g, d) cache stacks ride the scan
        # CARRY and each layer updates its token column in place
        # (attention_block's stacked-cache form). The previous xs/ys form
        # re-materialized and re-stacked every layer's whole cache per
        # step — 2.2x slower per decode step (see attention.py).
        _dense_caches_are_one_kinds(several)
        offset = kv_caches["offset"]

        def cache_body(carry, xs):
            hidden, kc, vc = carry
            params_l, idx = xs
            cache_l = {"k": kc, "v": vc, "offset": offset,
                       "layer": idx - layer_offset}
            (out,), new_cache_l = body((hidden,), (params_l, idx, cache_l))
            return (out, new_cache_l["k"], new_cache_l["v"]), None

        f = remat_wrap(cache_body, policy) if n_remat == L else cache_body
        (hidden, kc, vc), _ = jax.lax.scan(
            f, (hidden, kv_caches["k"], kv_caches["v"]),
            (layer_params, idxs),
        )
        new_caches = {"k": kc, "v": vc,
                      "offset": kv_caches["offset"] + hidden.shape[1]}
    else:
        take = lambda tree, a, b: jax.tree.map(  # noqa: E731
            lambda x: x[a:b], tree
        )
        # one scan a run of layers of one kind, over that run's entries
        # of its kind's stack; one kind is one run over the whole stack
        runs = [(kinds[0], layer_params, 0, L)]
        if several:
            stacks = kind_stacks(cfg, layer_params)
            runs = [(kind, take(stacks[kind], j, j + n), start, n)
                    for kind, j, start, n in layer_runs(cfg)]
        for kind, stack, start, n in runs:
            run_body = functools.partial(body, kind=kind)
            body_ck = remat_wrap(run_body, policy)
            xs = (stack, idxs[start:start + n], None)
            k = min(max(n_remat - start, 0), n)  # of this run, remat
            if 0 < k < n:
                (hidden,), _ = jax.lax.scan(
                    body_ck, (hidden,), take(xs, 0, k)
                )
                (hidden,), _ = jax.lax.scan(
                    run_body, (hidden,), take(xs, k, n)
                )
            else:
                f = body_ck if k == n else run_body
                (hidden,), _ = jax.lax.scan(f, (hidden,), xs)
        new_caches = None
    return hidden, new_caches
