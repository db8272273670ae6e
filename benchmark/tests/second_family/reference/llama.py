"""Plain float32 reference of the program's own Llama-form decoder, for
the rehearsal of a SECOND family: RMSNorm, sequential attention then a
SwiGLU MLP, grouped K/V heads, rotary, an untied head. `jax.numpy` and
`reference/common.py` only."""

from __future__ import annotations

import sys

import jax
import jax.numpy as jnp

from .common import causal_attention, f32_matmul, mean_cross_entropy, rotary


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1,
                                      keepdims=True) + eps) * scale


def block(w, x, cfg, positions, matmul=f32_matmul, layer=None):
    del layer  # every block is of one kind
    T, h = x.shape
    g, d = cfg["num_key_value_heads"], cfg["head_dim"]
    m = cfg["num_attention_heads"] // g
    eps = cfg["rms_norm_eps"]
    qkv = matmul(rms_norm(x, w["ln1_scale"], eps),
                 w["wqkv"]).reshape(T, g, m + 2, d)
    q = rotary(qkv[:, :, :m], positions, cfg["rope_theta"])
    k = rotary(qkv[:, :, m], positions, cfg["rope_theta"])
    ctx = causal_attention(q, k, qkv[:, :, m + 1], matmul)
    x = x + matmul(ctx.reshape(T, g * m * d), w["wo"])
    up = matmul(rms_norm(x, w["ln2_scale"], eps),
                w["w1"].reshape(h, -1)).reshape(T, 2, -1)
    return x + matmul(jax.nn.silu(up[:, 0]) * up[:, 1], w["w2"])


def embed(glob, tokens):
    return jnp.take(glob["embedding"], tokens, axis=0).astype(jnp.float32)


def final_logits(glob, x, cfg, matmul=f32_matmul):
    return matmul(rms_norm(x, glob["lnf_scale"], cfg["rms_norm_eps"]),
                  glob["lm_head"])


def mean_loss(params, tokens, labels, cfg, matmul=f32_matmul):
    return mean_cross_entropy(sys.modules[__name__], params, tokens, labels,
                              cfg, matmul)
