"""GPT-family model wrapper (ref: megatron/model/gpt_model.py).

A thin stateless class: holds the config, exposes `init` / `forward` /
`loss`. All state lives in the params pytree so the whole object is safe to
close over in jitted functions.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from megatron_llm_tpu.config import ModelConfig
from megatron_llm_tpu.models.language_model import (
    chunked_head_cross_entropy,
    init_language_model_params,
    language_model_forward,
)


class GPTModel:
    """ref: GPTModel gpt_model.py:45-124."""

    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        self._check_config()

    def _check_config(self):
        pass

    def init(self, rng: jax.Array) -> dict:
        return init_language_model_params(self.cfg, rng)

    def forward(
        self,
        params: dict,
        tokens: jnp.ndarray,
        position_ids: Optional[jnp.ndarray] = None,
        attention_mask: Optional[jnp.ndarray] = None,
        dropout_rng=None,
        deterministic: bool = True,
        kv_caches: Optional[dict] = None,
    ) -> Tuple[jnp.ndarray, Optional[dict]]:
        """Returns (logits, new_kv_caches) (ref: gpt_model.py:84-100)."""
        return language_model_forward(
            params, self.cfg, tokens, position_ids, attention_mask,
            dropout_rng, deterministic, kv_caches,
        )

    def loss(
        self,
        params: dict,
        tokens: jnp.ndarray,
        labels: jnp.ndarray,
        loss_mask: Optional[jnp.ndarray] = None,
        position_ids: Optional[jnp.ndarray] = None,
        attention_mask: Optional[jnp.ndarray] = None,
        dropout_rng=None,
        deterministic: bool = True,
    ) -> jnp.ndarray:
        """Mean masked CE (ref: post_language_model_processing
        gpt_model.py:18-42 + loss_func finetune.py:83-89).

        The head + CE run chunked over the sequence so full (b, s, V)
        logits never materialise (see chunked_head_cross_entropy)."""
        hidden, _ = language_model_forward(
            params, self.cfg, tokens, position_ids, attention_mask,
            dropout_rng, deterministic, return_hidden=True,
        )
        losses = chunked_head_cross_entropy(params, self.cfg, hidden, labels)
        if loss_mask is None:
            return jnp.mean(losses)
        loss_mask = loss_mask.astype(jnp.float32)
        return jnp.sum(losses * loss_mask) / jnp.maximum(jnp.sum(loss_mask), 1.0)

    def loss_terms(
        self,
        params: dict,
        tokens: jnp.ndarray,
        labels: jnp.ndarray,
        loss_mask: Optional[jnp.ndarray] = None,
        position_ids: Optional[jnp.ndarray] = None,
        attention_mask: Optional[jnp.ndarray] = None,
        dropout_rng=None,
        deterministic: bool = True,
    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """`loss` decomposed into (numerator, denominator) so a caller
        that holds only a DATA-PARALLEL SLICE of the batch can rebuild
        the global loss exactly: both terms are row-additive, so
        psum(num) / max(psum(den), 1) reproduces `loss`'s op chain
        bitwise (the ZeRO-1 explicit reduce-scatter path,
        optimizer/zero1.py, differentiates num/max(global_den, 1) to
        get the identical backward cotangent). The masked form uses the
        exact expressions of `loss`; the unmasked denominator is the
        token count.

        Implemented AS the composition of `loss_pieces` with one
        full-range layer group — the factored pieces are the single
        source of the op chain, so the backward-interleaved overlap
        path (which vjps the pieces group by group) can never drift
        from this function."""
        embed_fn, group_fn, head_fn = self.loss_pieces(
            tokens, labels, loss_mask, position_ids, attention_mask,
            dropout_rng, deterministic,
        )
        aux_params = {k: v for k, v in params.items() if k != "layers"}
        hidden = group_fn(params["layers"], embed_fn(aux_params), 0)
        return head_fn(aux_params, hidden)

    def loss_pieces(
        self,
        tokens: jnp.ndarray,
        labels: jnp.ndarray,
        loss_mask: Optional[jnp.ndarray] = None,
        position_ids: Optional[jnp.ndarray] = None,
        attention_mask: Optional[jnp.ndarray] = None,
        dropout_rng=None,
        deterministic: bool = True,
    ):
        """`loss_terms` factored at layer-group boundaries so a caller
        can run the backward group by group and issue each group's
        gradient collective as its cotangents materialize (the
        backward-interleaved ZeRO-1 reduce-scatter, optimizer/zero1.py,
        ISSUE 12). Returns

          (embed_fn(aux_params) -> hidden0,
           group_fn(layer_slice, hidden, layer_offset) -> hidden,
           head_fn(aux_params, hidden) -> (numerator, denominator))

        where `aux_params` is the params dict WITHOUT "layers" and
        `layer_slice` is a contiguous [lo:hi] slice of the stacked
        layer tree. Composing the pieces reproduces `loss_terms`'s
        exact op chain — same rope table, same emb/stack dropout-rng
        split, same per-layer fold_in keys via `layer_offset`, same
        head/CE expressions — so vjp-by-pieces is the SAME backward
        ops as value_and_grad of `loss_terms` (fp32 bitwise; pinned in
        tests/test_overlap.py)."""
        from megatron_llm_tpu.models.language_model import (
            chunked_head_cross_entropy,
            embed_tokens,
        )
        from megatron_llm_tpu.models.norms import apply_norm
        from megatron_llm_tpu.models.rope import precompute_rope
        from megatron_llm_tpu.models.transformer import transformer_stack

        cfg = self.cfg
        if cfg.position_embedding_type == "rotary":
            rope_table = precompute_rope(
                cfg.head_dim, cfg.max_position_embeddings, cfg.rope_theta,
                cfg.rope_scaling_factor,
            )
        else:
            rope_table = None
        if dropout_rng is not None:
            emb_rng, stack_rng = jax.random.split(dropout_rng)
        else:
            emb_rng = stack_rng = None

        def embed_fn(aux_params):
            return embed_tokens(aux_params, cfg, tokens, position_ids,
                                emb_rng, deterministic)

        def group_fn(layer_slice, hidden, layer_offset):
            out, _ = transformer_stack(
                layer_slice, cfg, hidden, rope_table, attention_mask,
                position_ids, stack_rng, deterministic,
                layer_offset=layer_offset,
            )
            return out

        def head_fn(aux_params, hidden):
            hidden = apply_norm(hidden, aux_params["final_norm"], cfg)
            losses = chunked_head_cross_entropy(aux_params, cfg, hidden,
                                                labels)
            if loss_mask is None:
                return jnp.sum(losses), jnp.float32(losses.size)
            lm = loss_mask.astype(jnp.float32)
            return jnp.sum(losses * lm), jnp.sum(lm)

        return embed_fn, group_fn, head_fn

    def loss_denominator(self, tokens=None, labels=None, loss_mask=None,
                         **_) -> jnp.ndarray:
        """The `loss_terms` denominator from mask arithmetic alone (no
        forward pass, no params): what the explicit ZeRO-1 path psums
        BEFORE the backward so the local grad target can divide by the
        global count."""
        if loss_mask is None:
            ref = labels if labels is not None else tokens
            return jnp.float32(ref.size)
        return jnp.sum(loss_mask.astype(jnp.float32))

    def prepare_decode_params(self, params: dict,
                              quantize_int8: bool = False,
                              flatten_glu: bool = True) -> dict:
        """Decode-layout view of the params, built ONCE before the token
        loop (called inside generate's jit, ahead of the while_loop):

        - the stacked (L, ...) layer tree is split into a TUPLE of
          per-layer trees of standalone contiguous arrays. Inside the
          decode loop the layer scan would otherwise dynamic-slice every
          layer's weights into fresh buffers each token — a full extra
          read+write of all layer weights per step (traced on v5e:
          ~95us/layer/step, i.e. the GEMVs paid double their weight
          traffic). transformer_stack unrolls over the tuple;
        - the GLU up/gate weight (h, 2, f) is held gate|up-major,
          (2, f, h), its transpose: `_mlp_up` issues one matvec over
          its 2f output columns (`ops/quantization.qdot`'s rank-3
          path), gate | up. Read as (h, 2, f) the 2-sized axis tiles
          into sublanes and the matvec streams at ~33% of HBM
          bandwidth. The flat (h, 2f) view this form replaces lies
          2f-minor by a TPU's default layout wherever h pads no less
          than 2f against the 128-lane tile, while the product reads h
          minor: every program re-laid every layer's weight out
          (Falcon-H1-34B's (5120, 43008): 440 MB read and written a
          layer, 5.7 ms of a ~20 ms device round, traced on v5e). A
          rank-3 array lies row-major, h minor, at any widths;
        - `quantize_int8=True` (ISSUE 9, decode-only — the fp tree is
          untouched and stays the default): the four big per-layer GEMV
          weights (wqkv, wo, w1, w2) are one-shot quantized to
          weight-only int8 with per-output-channel fp32 scales, the
          rank-3 wqkv and w1 over their last axis
          (ops/quantization.quantize_decode_layers); the decode matvecs
          read half the weight bytes. Biases/norms/embeddings/head stay
          fp — see the accuracy contract in docs/GUIDE.md ("Quantized
          serving");
        - `flatten_glu=False` (ISSUE 14, the tp-sharded serving
          engine): keep the GLU weight in the training (h, 2, f)
          layout, which shards f per chip and keeps the GLU
          elementwise-local (parallel/sharding.decode_param_specs).
          `flatten_glu=True` (single-chip engines) holds the (2, f, h)
          form above; the name is that of the flat (h, 2f) view it
          replaced, whose output columns it keeps.

        - `wqkv` (h, qkv) is held HEAD-major, (heads, head_dim, h) with
          heads = groups x (q_per_kv + 2) in `split_qkv`'s order — its
          transpose, cut by head; `ops/quantization.qdot` issues the
          same products from the rank-3 leaf. `split_qkv` cuts the
          projection's columns into heads, so the compiler produces it
          head-major and for that reads the weight with h as the minor
          (lane) axis, while a TPU's default layout of the 2-D leaf
          has qkv there: every layer of every step re-laid its weight
          out (Falcon-7B: 42.5 MB x 32 read and written = 3.3 ms of a
          25.3 ms decode round, traced on v5e, PR 26/27; head 128
          alike, compile-only). The rank-3 shape's DEFAULT layout has h
          minor. (A layout pinned on the 2-D leaf does the same and
          does not survive the persistent compile cache on jax 0.9.0 /
          libtpu 0.0.34: PERF.md §6, PR 34.)

        The tied table's per-step re-layout copy (591 MB for 8 rows at
        Falcon-7B's widths) is cured where the rows are read, not here:
        `models/language_model.embed_tokens`.
        """
        import jax

        if quantize_int8 and not flatten_glu:
            raise ValueError(
                "quantize_int8 requires the flattened GLU decode "
                "layout (flatten_glu=True: quantize_decode_layers "
                "scales the (2, f, h) leaf's 2f output columns); "
                "tp-sharded engines serve the fp decode tree")
        from megatron_llm_tpu.config import CapabilityError
        from megatron_llm_tpu.models.transformer import kind_stacks

        kinds = self.cfg.layer_kinds
        if quantize_int8 and set(kinds) != {("attention", "mlp")}:
            raise CapabilityError(
                "quantize_weights", "the int8 decode tree is cut for "
                "attention + dense-MLP layers only")
        stacks = kind_stacks(self.cfg, params["layers"])
        taken = dict.fromkeys(stacks, 0)

        def layer_slice(kind):
            # the tree of the next layer of its kind, by what it holds:
            # entry j of a kind's stack is that kind's j-th layer
            j = taken[kind]
            taken[kind] = j + 1
            layer = dict(jax.tree.map(lambda x: x[j], stacks[kind]))
            if "attention" in layer:
                attn = dict(layer["attention"])
                wqkv = attn["wqkv"]
                attn["wqkv"] = wqkv.T.reshape(-1, self.cfg.head_dim,
                                              wqkv.shape[0])
                layer["attention"] = attn
            if "mlp" in layer and self.cfg.glu_activation and flatten_glu:
                mlp = dict(layer["mlp"])
                mlp["w1"] = mlp["w1"].transpose(1, 2, 0)
                layer["mlp"] = mlp
            return layer

        params = dict(params)
        params["layers"] = tuple(layer_slice(kind) for kind in kinds)
        if quantize_int8:
            from megatron_llm_tpu.ops.quantization import (
                quantize_decode_layers,
            )

            params["layers"] = quantize_decode_layers(params["layers"])
        return params

    def init_kv_caches(self, batch_size: int, max_len: int,
                       layout: str = "stacked") -> dict:
        """KV cache for incremental decode (ref: InferenceParams
        forward_step.py:17-41).

        layout="stacked": one (L, b, T, g, d) pair — what the layer scan
        (and the pp pipelined decode's per-stage shards) carries.
        layout="layers": per-layer standalone (b, g, T, d) arrays for the
        unrolled decode path (see prepare_decode_params) — each layer's
        column update and attention read hit a small buffer in place with
        no per-layer stack slicing, and the (g, T) order makes the
        QK/PV contractions clean (b*g)-batched GEMMs over the T axis.

        Both layouts feed the Pallas decode-attention kernel in place
        (ops/decode_attention.py: "gtd" = layers, "tgd" = a stacked
        layer's slice); a max_len with a power-of-2 factor >= 16 keeps
        the kernel eligible (otherwise the XLA matvecs serve the cache).
        """
        cfg = self.cfg
        if layout == "layers":
            shape = (batch_size, cfg.num_query_groups, max_len,
                     cfg.head_dim)
            return {
                "k_layers": tuple(jnp.zeros(shape, cfg.compute_dtype)
                                  for _ in range(cfg.num_layers)),
                "v_layers": tuple(jnp.zeros(shape, cfg.compute_dtype)
                                  for _ in range(cfg.num_layers)),
                "offset": jnp.array(0, jnp.int32),
            }
        assert layout == "stacked", layout
        shape = (cfg.num_layers, batch_size, max_len, cfg.num_query_groups,
                 cfg.head_dim)
        return {
            "k": jnp.zeros(shape, cfg.compute_dtype),
            "v": jnp.zeros(shape, cfg.compute_dtype),
            "offset": jnp.array(0, jnp.int32),
        }

    def init_paged_kv_caches(self, slots: int, num_pages: int,
                             page_size: int,
                             max_pages_per_slot: int,
                             kv_dtype=None,
                             mesh_ctx=None) -> dict:
        """Paged KV cache for the continuous-batching engine
        (inference/engine.py): per-layer GLOBAL page pools shared by
        all slots, held LANE-PACKED, (num_pages, page_size, g * d): a
        token's g K/V heads of width d side by side along the lanes,
        head h at lanes h*d..(h+1)*d — the order the scatter writes and
        the paged attention reads (ops/prefill_attention.py, where the
        head-width gate of its kernel is stated), so no program re-lays
        a pool out; one
        (slots, max_pages_per_slot) page table mapping each slot's
        logical pages to pool indices, and per-slot valid lengths.
        Pool page 0 is the NULL page (never allocated): fresh/retired
        slots point every table entry at it, so clamped kernel DMAs and
        inactive-slot writes always land on a real — but dead — page.
        HBM cost per layer: 2 * num_pages * page_size * g * d *
        itemsize; unlike the dense layouts above it is independent of
        slots * max_len, which is the whole point (docs/GUIDE.md,
        "Continuous-batching serving engine").

        `kv_dtype` (default: cfg.compute_dtype) picks the pool storage
        dtype. int8 (ISSUE 9) additionally allocates per-layer fp32
        scale pools `k/v_scales_layers` of (num_pages, page_size, g) —
        one symmetric scale per (token, group), written by the same
        scatter paths that write the data and consumed in-register by
        the ragged paged attention kernel (ops/prefill_attention.py,
        the one paged entry point) — roughly halving the pool's
        bytes/token
        (docs/GUIDE.md, "Quantized serving").

        `mesh_ctx` (ISSUE 14, the tp-sharded engine): a
        ParallelContext whose `model` axis the pools shard over —
        every pool leaf materialises DIRECTLY under its
        kv_pool_spec sharding (the lanes over `model`, each chip its
        own heads', parallel/sharding.py — the per-chip pool is 1/tp
        the bytes,
        never allocated whole on one chip), while the page table and
        lengths stay replicated scalar-prefetch operands.

        Pools are one an ATTENTION layer, in layer order. A layer whose
        operator is the short convolution holds no page: its entry is
        the slots' state, `conv_state_layers` (slots, taps - 1, h), one
        a conv layer (models/short_conv.py). A Mamba-2 layer's are two,
        `mamba_conv_state_layers` (slots, taps - 1, conv channels) and
        `ssm_state_layers` (slots, heads, head_dim, state) in float32
        (models/mamba2.py); a hybrid layer (attention and the mixer side
        by side) holds its pools AND those two states, each list in
        layer order; a routed MLP alone holds nothing."""
        cfg = self.cfg
        n_attention = cfg.count_operator("attention")
        kv_dtype = cfg.compute_dtype if kv_dtype is None else kv_dtype
        g = cfg.num_query_groups
        shape = (num_pages, page_size, g * cfg.head_dim)

        if mesh_ctx is not None:
            import jax
            import numpy as np

            from megatron_llm_tpu.parallel.sharding import kv_pool_spec

            tp = mesh_ctx.tp

            def _sharded_zeros(shape, dtype, sh):
                # per-shard host zeros straight onto each device — no
                # whole-pool materialisation anywhere (the pool is the
                # largest allocation serving makes), and no jit (this
                # is a one-shot allocation, not a compile-contract
                # entry point)
                npdt = np.dtype(dtype)

                def cb(idx):
                    sub = [len(range(*s.indices(n)))
                           for s, n in zip(idx, shape)]
                    return np.zeros(sub, npdt)

                return jax.make_array_from_callback(shape, sh, cb)

            def zeros(shape, dtype):
                return _sharded_zeros(
                    shape, dtype,
                    mesh_ctx.sharding(*kv_pool_spec(shape, tp, g)))

            def zeros_rep(shape, dtype):
                return _sharded_zeros(shape, dtype, mesh_ctx.sharding())
        else:
            def zeros(shape, dtype):
                return jnp.zeros(shape, dtype)

            zeros_rep = zeros

        caches = {
            "k_pages_layers": tuple(zeros(shape, kv_dtype)
                                    for _ in range(n_attention)),
            "v_pages_layers": tuple(zeros(shape, kv_dtype)
                                    for _ in range(n_attention)),
            "page_table": zeros_rep((slots, max_pages_per_slot),
                                    jnp.int32),
            "lengths": zeros_rep((slots,), jnp.int32),
        }
        if jnp.dtype(kv_dtype) == jnp.int8:
            sshape = shape[:2] + (g,)
            caches["k_scales_layers"] = tuple(
                zeros(sshape, jnp.float32)
                for _ in range(n_attention))
            caches["v_scales_layers"] = tuple(
                zeros(sshape, jnp.float32)
                for _ in range(n_attention))
        if cfg.count_operator("conv"):
            caches["conv_state_layers"] = tuple(
                zeros_rep((slots, cfg.conv_L_cache - 1, cfg.hidden_size),
                          cfg.compute_dtype)
                for _ in range(cfg.count_operator("conv")))
        n_mamba = cfg.count_operator("mamba")
        if n_mamba:
            caches["mamba_conv_state_layers"] = tuple(
                zeros_rep((slots, cfg.mamba_conv_kernel - 1,
                           cfg.mamba_conv_size), cfg.compute_dtype)
                for _ in range(n_mamba))
            caches["ssm_state_layers"] = tuple(
                zeros_rep((slots, cfg.mamba_num_heads, cfg.mamba_head_dim,
                           cfg.ssm_state_size), jnp.float32)
                for _ in range(n_mamba))
        return caches
