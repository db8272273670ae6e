"""Plain float32 reference of the Falcon decoder (7B: multi-query, one
layer norm per block; 40B: grouped K/V heads, two layer norms) and its
loss. `jax.numpy` and `reference/common.py` only: nothing imported from
the program.

Follows the published modelling code (tiiuae/falcon-7b, falcon-40b):
  x -> ln(x) -> [attention(ln x) + mlp(ln x or ln2 x)] + x  (parallel)
  attention: fused qkv, rotary on q and k, causal softmax(q k^T / sqrt d) v
  mlp: gelu (exact, erf) between two matrices, no biases
  head: tied to the embedding; loss: mean token cross-entropy, no other term
Departures, each noted in the configuration files under `assumed`: the
rotary layout (`common.rotary`).
"""

from __future__ import annotations

import sys

import jax
import jax.numpy as jnp

from .common import causal_attention, f32_matmul, layer_norm, rotary
from .common import mean_cross_entropy


def block(w, x, cfg, positions, matmul=f32_matmul, layer=None):
    """One decoder block on one sequence: x (T, h) -> (T, h). Every block
    is of one kind, so `layer` (its index) is not looked at."""
    del layer
    T = x.shape[0]
    g, d = cfg["num_kv_heads"], cfg["head_dim"]
    m = cfg["num_attention_heads"] // g
    eps = cfg["layer_norm_epsilon"]
    a_in = layer_norm(x, w["ln1_scale"], w["ln1_bias"], eps)
    if cfg["new_decoder_architecture"]:
        m_in = layer_norm(x, w["ln2_scale"], w["ln2_bias"], eps)
    else:
        m_in = a_in
    qkv = matmul(a_in, w["wqkv"]).reshape(T, g, m + 2, d)
    q, k, v = qkv[:, :, :m], qkv[:, :, m], qkv[:, :, m + 1]
    q = rotary(q, positions, cfg["rope_theta"])
    k = rotary(k, positions, cfg["rope_theta"])
    ctx = causal_attention(q, k, v, matmul).reshape(T, g * m * d)
    attn = matmul(ctx, w["wo"])
    mlp = matmul(jax.nn.gelu(matmul(m_in, w["w1"]), approximate=False),
                 w["w2"])
    return x + attn + mlp


def embed(glob, tokens):
    return jnp.take(glob["embedding"], tokens, axis=0).astype(jnp.float32)


def final_logits(glob, x, cfg, matmul=f32_matmul):
    """(T, h) -> (T, vocab) through the final norm and the tied head."""
    x = layer_norm(x, glob["lnf_scale"], glob["lnf_bias"],
                   cfg["layer_norm_epsilon"])
    return matmul(x, glob["embedding"].astype(jnp.float32).T)


def mean_loss(params, tokens, labels, cfg, matmul=f32_matmul):
    """The training loss: the mean cross-entropy and nothing beside it."""
    return mean_cross_entropy(sys.modules[__name__], params, tokens, labels,
                              cfg, matmul)
