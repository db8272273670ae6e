"""Rotary position embeddings, Meta-Llama interleaved-pair convention.

Behavioral parity with ref: megatron/model/positional_embeddings.py:7-52 —
freqs 1/theta^(2i/d), positions divided by `scaling_factor` (position
interpolation), and rotation applied to *adjacent* element pairs
(x[2i], x[2i+1]) via complex multiplication. We carry (cos, sin) tables
instead of complex64 (XLA on TPU prefers real arithmetic), computed in fp32.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def precompute_rope(
    head_dim: int,
    max_len: int,
    theta: float = 10000.0,
    scaling_factor: float = 1.0,
) -> jnp.ndarray:
    """Return (max_len, head_dim//2, 2) fp32 table of (cos, sin).

    Equivalent to the reference's complex `freqs_cis` table
    (ref: positional_embeddings.py:7-14).
    """
    inv_freq = 1.0 / (
        theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
    )
    t = jnp.arange(max_len, dtype=jnp.float32) / scaling_factor
    freqs = jnp.outer(t, inv_freq)  # (max_len, head_dim//2)
    return jnp.stack([jnp.cos(freqs), jnp.sin(freqs)], axis=-1)


@jax.named_scope("rope")
def apply_rope(
    x: jnp.ndarray,
    rope: jnp.ndarray,
    position_ids: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """Rotate `x` of shape (batch, seq, *head_dims, head_dim) — seq at axis 1.

    Matches the reference's complex multiply on interleaved pairs
    (ref: positional_embeddings.py:24-52): for each adjacent pair
    (xr, xi): (xr*cos - xi*sin, xr*sin + xi*cos).

    `rope` is the table from `precompute_rope`; `position_ids` (batch, seq)
    selects rows, defaulting to arange(seq) (ref: positional_embeddings.py:36-47).
    """
    seq = x.shape[1]
    n_mid = x.ndim - 3  # head-like dims between seq and head_dim
    if position_ids is None:
        cs = rope[:seq][None]  # (1, seq, d/2, 2)
    else:
        cs = rope[position_ids]  # (batch, seq, d/2, 2)
    # -> (batch, seq, *(1,)*n_mid, d/2, 2)
    cs = cs.reshape(cs.shape[0], seq, *((1,) * n_mid), -1, 2)
    cos, sin = cs[..., 0], cs[..., 1]

    xf = x.astype(jnp.float32).reshape(*x.shape[:-1], -1, 2)
    xr, xi = xf[..., 0], xf[..., 1]
    out_r = xr * cos - xi * sin
    out_i = xr * sin + xi * cos
    out = jnp.stack([out_r, out_i], axis=-1).reshape(x.shape)
    return out.astype(x.dtype)
