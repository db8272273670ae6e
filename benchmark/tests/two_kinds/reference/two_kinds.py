"""Plain float32 reference of the rehearsal's two-kind decoder
(`families/two_kinds.py` has the leaves): grouped K/V heads with rotary
and then, by the configuration's `layer_types`, a SwiGLU MLP under an
RMS norm or a GELU MLP under a LayerNorm; a tied head. `jax.numpy` and
`reference/common.py` only."""

from __future__ import annotations

import sys

import jax
import jax.numpy as jnp

from .common import causal_attention, f32_matmul, layer_norm
from .common import mean_cross_entropy, rotary


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1,
                                      keepdims=True) + eps) * scale


def block(w, x, cfg, positions, matmul=f32_matmul, layer=None):
    """One block on one sequence; its kind is `layer_types[layer]`."""
    T = x.shape[0]
    g, d = cfg["num_key_value_heads"], cfg["head_dim"]
    m = cfg["num_attention_heads"] // g
    glu = cfg["layer_types"][layer] == "glu"
    eps = cfg["norm_eps"]

    def norm(x):
        if glu:
            return rms_norm(x, w["norm_scale"], eps)
        return layer_norm(x, w["norm_scale"], w["norm_bias"], eps)

    qkv = matmul(norm(x), w["wqkv"]).reshape(T, g, m + 2, d)
    q = rotary(qkv[:, :, :m], positions, cfg["rope_theta"])
    k = rotary(qkv[:, :, m], positions, cfg["rope_theta"])
    ctx = causal_attention(q, k, qkv[:, :, m + 1], matmul)
    x = x + matmul(ctx.reshape(T, g * m * d), w["wo"])
    up = matmul(norm(x), w["w_up"])
    if glu:
        up = jax.nn.silu(matmul(norm(x), w["w_gate"])) * up
    else:
        up = jax.nn.gelu(up, approximate=False)
    return x + matmul(up, w["w_down"])


def embed(glob, tokens):
    return jnp.take(glob["embedding"], tokens, axis=0).astype(jnp.float32)


def final_logits(glob, x, cfg, matmul=f32_matmul):
    return matmul(rms_norm(x, glob["lnf_scale"], cfg["norm_eps"]),
                  glob["embedding"].astype(jnp.float32).T)


def mean_loss(params, tokens, labels, cfg, matmul=f32_matmul):
    return mean_cross_entropy(sys.modules[__name__], params, tokens, labels,
                              cfg, matmul)
