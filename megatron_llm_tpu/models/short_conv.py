"""Gated short convolution: the operator that takes attention's place in
a layer whose `layer_types` entry is "conv".

  [B, C, X] = split3(u @ w_in)          w_in (h, 3h), in that order
  g = B * X
  v_t = sum_j kernel[:, j] * g_{t - (K-1) + j}     depthwise, causal,
                                                   g_{<0} = 0, no bias
  out = (C * v) @ w_out                 w_out (h, h)

K = `conv_L_cache` taps (3). What a sequence leaves behind for its next
token is its last K-1 gated inputs, `(g_{t-2}, g_{t-1})`: the served
STATE, (slots, K-1, h) a layer, zeros at position 0. The engine carries
one such array a conv layer in its cache tree beside the page pools of
the attention layers (`GPTModel.init_paged_kv_caches`); a slot whose span
starts at cache position 0 reads zeros whatever its state holds, which
is how admission resets it.

Both matrices go through `qdot` like every dense matrix; the taps are
elementwise and run in float32.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from megatron_llm_tpu.models.remat import tag as _savepoint
from megatron_llm_tpu.ops.quantization import qdot


def _taps(kernel, seq, n_out: int):
    """seq (..., n_out + K - 1, h), the K-1 rows of left context first:
    v_t = sum_j kernel[:, j] * seq[t + j], in float32."""
    K = kernel.shape[-1]
    k32 = kernel.astype(jnp.float32)
    s32 = seq.astype(jnp.float32)
    return sum(k32[:, j] * s32[..., j:j + n_out, :] for j in range(K))


def _served(kernel, g, state, cache):
    """g (b, s, h) of a served round, `state` (slots, K-1, h) -> (v, new
    state). Two forms, by the cache's riders as attention's paged branch
    reads them: a mixed round's packed row axis (b = 1: slot
    `packed_chunk`'s chunk at width s - slots, chunk_lens of its rows
    real, then one decode row a slot), and the decode scan's (slots, 1)
    with `active`. A row that is not real writes nothing."""
    lengths = cache["lengths"]
    n = lengths.shape[0]
    keep = state.shape[1]
    if "packed_chunk" in cache:
        ci, chunk_lens = cache["packed_chunk"], cache["chunk_lens"]
        w = g.shape[1] - n
        g_c, g_d = g[0, :w], g[0, w:]
        live = (chunk_lens > 0) & (jnp.arange(n) != ci)
        # the chunk: left context from its slot's state (zeros at
        # position 0), new state = the last K-1 rows of context + real rows
        ctx = jnp.where(lengths[ci] > 0,
                        jax.lax.dynamic_index_in_dim(state, ci, 0, False), 0)
        seq = jnp.concatenate([ctx.astype(g.dtype), g_c], axis=0)
        v_c = _taps(kernel, seq, w)
        new_c = jax.lax.dynamic_slice_in_dim(seq, chunk_lens[ci], keep, 0)
    else:
        assert g.shape[1] == 1 and "chunk_lens" not in cache, \
            "a conv layer serves the packed mixed round and the " \
            "single-token decode scan"
        g_d, live = g[:, 0], cache["active"]
    # decode rows: one step of each slot's own state
    seq_d = jnp.concatenate([state.astype(g.dtype), g_d[:, None]], axis=1)
    v_d = _taps(kernel, seq_d, 1)[:, 0]
    new = jnp.where(live[:, None, None], seq_d[:, 1:], state)
    if "packed_chunk" not in cache:
        return v_d[:, None], new
    new = jax.lax.dynamic_update_index_in_dim(new, new_c.astype(new.dtype),
                                              ci, 0)
    return jnp.concatenate([v_c, v_d], axis=0)[None], new


@jax.named_scope("conv")
def short_conv_block(conv_params: dict, cfg, hidden: jnp.ndarray,
                     cache: Optional[dict] = None
                     ) -> Tuple[jnp.ndarray, Optional[jnp.ndarray]]:
    """hidden (b, s, h) -> (out, new state). Without `cache` the whole-
    sequence form (every row of the batch a sequence from position 0);
    with it (`{"conv_state": ..., **riders}`) a served round."""
    dt = cfg.compute_dtype
    h = cfg.hidden_size
    with jax.named_scope("in_proj"):
        bcx = _savepoint(qdot(hidden, conv_params["w_in"], dt), "qkv_proj")
        B, C, X = bcx[..., :h], bcx[..., h:2 * h], bcx[..., 2 * h:]
    with jax.named_scope("taps"):
        g = B * X
        kernel = conv_params["kernel"]
        if cache is None:
            left = jnp.zeros(g.shape[:1] + (kernel.shape[-1] - 1, h), g.dtype)
            v = _taps(kernel, jnp.concatenate([left, g], axis=1), g.shape[1])
            new_state = None
        else:
            v, new_state = _served(kernel, g, cache["conv_state"], cache)
        y = C * v.astype(dt)
    with jax.named_scope("out_proj"):
        out = qdot(y, conv_params["w_out"], dt)
    return _savepoint(out, "attn_dense"), new_state
