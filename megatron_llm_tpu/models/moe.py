"""Routed MLP: `num_experts` SwiGLU experts of width
`moe_intermediate_size`, `num_experts_per_tok` of them a token.

  s = sigmoid(x @ router)                     float32, (rows, E)
  chosen = top_k(s + expert_bias)             the bias only selects
  w = s[chosen] / (sum + 1e-6) * routed_scaling_factor   (norm_topk_prob)
  out = sum_j w_j * w_down[e_j](silu(x @ w_gate[e_j]) * (x @ w_up[e_j]))

No shared expert, no capacity, no dropped token. EVERY expert multiplies
EVERY row and the unchosen pairs weigh zero: each expert's 22 MB are read
once whatever was picked, and up to ~240 rows (197 TFLOP/s over 819 GB/s)
the products hide under that read. On a v5e 0.97 ms a layer at 32 rows
and 1.02 at 160 (730 GB/s), where sorted pairs + `jax.lax.ragged_dot`
took 1.57 and 1.82 (PERF.md section 6, PR 36): every served round has at
most chunk + slots = 160 rows, so that is the one form. Whole sequences
(the cache-free forward) pay rows x experts x width for it; a grouped
product comes back with the training or long-prefill cell that can show
it wins (ROADMAP.md B-I.2). The products do not go through `qdot`:
`ops/quantization.py` stays the dense matrices' site.

A served round's rows that are not real (`row_mask` False: a chunk's
padding, an idle slot's decode row) are routed nowhere: they weigh
nothing with every expert and are counted nowhere. `stats` is
what the engine's counters sum: [pairs of real rows, experts that got at
least one, experts there are, the fullest expert's pairs].
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from megatron_llm_tpu.models.remat import tag as _savepoint

N_STATS = 4  # pairs, experts touched, expert slots, hottest expert's pairs


def route(moe_params: dict, cfg, x: jnp.ndarray):
    """x (rows, h) -> (chosen (rows, k) int32, weights (rows, k) float32)."""
    scores = jax.nn.sigmoid(jnp.dot(
        x.astype(jnp.float32), moe_params["router"].astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))
    select = scores
    if "expert_bias" in moe_params:
        select = scores + moe_params["expert_bias"].astype(jnp.float32)
    _, chosen = jax.lax.top_k(select, cfg.num_experts_per_tok)
    w = jnp.take_along_axis(scores, chosen, axis=-1)
    if cfg.norm_topk_prob:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-6)
    return chosen, w * cfg.routed_scaling_factor


@jax.named_scope("moe")
def moe_block(moe_params: dict, cfg, hidden: jnp.ndarray,
              row_mask: Optional[jnp.ndarray] = None
              ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """hidden (b, s, h) -> (out (b, s, h), stats (N_STATS,) int32).
    `row_mask` (b, s): which rows are real; None = all."""
    dt = cfg.compute_dtype
    b, s, h = hidden.shape
    E = cfg.num_experts
    x = hidden.reshape(b * s, h)
    with jax.named_scope("router"):
        chosen, w = route(moe_params, cfg, x)
    if row_mask is not None:
        real = row_mask.reshape(-1, 1)
        chosen = jnp.where(real, chosen, E)  # no expert's
        w = jnp.where(real, w, 0.0)
    counts = jnp.bincount(chosen.reshape(-1), length=E).astype(jnp.int32)
    x = x.astype(dt)
    with jax.named_scope("experts"):
        # (rows, E): a pair's weight, zero where the expert was not chosen
        weight = jnp.sum(jax.nn.one_hot(chosen, E, dtype=jnp.float32)
                         * w[..., None], axis=1)

        def up(name):
            return jnp.einsum("th,ehf->tef", x, moe_params[name].astype(dt),
                              preferred_element_type=jnp.float32)

        act = _savepoint(jax.nn.silu(up("w_gate")) * up("w_up"),
                         "mlp_pre_act") * weight[..., None]
    with jax.named_scope("combine"):
        out = jnp.einsum("tef,efh->th", act.astype(dt),
                         moe_params["w_down"].astype(dt),
                         preferred_element_type=jnp.float32)
    out = _savepoint(out.astype(dt).reshape(b, s, h), "mlp_out")
    stats = jnp.stack([jnp.sum(counts), jnp.sum(counts > 0),
                       jnp.int32(E), jnp.max(counts)]).astype(jnp.int32)
    return out, stats
