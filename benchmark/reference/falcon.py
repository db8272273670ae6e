"""Plain float32 reference of the Falcon decoder (7B: multi-query, one
layer norm per block; 40B: grouped K/V heads, two layer norms), its
loss, and AdamW with global-norm clipping. `jax.numpy` only: no kernels,
no cache, no batching, and nothing imported from the program.

Follows the published modelling code (tiiuae/falcon-7b, falcon-40b):
  x -> ln(x) -> [attention(ln x) + mlp(ln x or ln2 x)] + x  (parallel)
  attention: fused qkv, rotary on q and k, causal softmax(q k^T / sqrt d) v
  mlp: gelu (exact, erf) between two matrices, no biases
  head: tied to the embedding; loss: mean token cross-entropy
Departures, each noted in the configuration files under `assumed`:
  rotary rotates adjacent channel pairs (the program's convention; the
  published code rotates halves; a fixed permutation of q/k channels maps
  one onto the other, and the weights here are random).

`matmul` is the one hook: the reference multiplies in float32 at the
highest precision; the control passes a matmul that rounds its operands
to a lower precision first (`check.int8_matmul`). `jax.checkpoint` and the
query-block loop only bound memory; they change no value.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def f32_matmul(a, b):
    return jnp.matmul(a.astype(jnp.float32), b.astype(jnp.float32),
                      precision=HIGHEST)


def layer_norm(x, scale, bias, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * scale + bias


def rotary(x, positions, theta):
    """x (T, ..., d): rotate adjacent pairs (x[2i], x[2i+1]) by
    positions * theta^(-2i/d)."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]  # (T, d/2)
    ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (d // 2,))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    xp = x.reshape(x.shape[:-1] + (d // 2, 2))
    xr, xi = xp[..., 0], xp[..., 1]
    out = jnp.stack([xr * cos - xi * sin, xr * sin + xi * cos], axis=-1)
    return out.reshape(x.shape)


def causal_attention(q, k, v, matmul, q_block=256):
    """q (T, g, m, d), k and v (T, g, d) -> (T, g, m, d). Query rows are
    taken `q_block` at a time so the score matrix stays small."""
    T, g, m, d = q.shape
    scale = 1.0 / math.sqrt(d)
    kt = jnp.transpose(k, (1, 2, 0))  # (g, d, T)
    vt = jnp.transpose(v, (1, 0, 2))  # (g, T, d)
    cols = jnp.arange(T)

    @jax.checkpoint
    def rows(args):
        qb, row0 = args  # (B, g, m, d)
        B = qb.shape[0]
        qg = jnp.transpose(qb, (1, 0, 2, 3)).reshape(g, B * m, d)
        s = matmul(qg, kt).reshape(g, B, m, T) * scale
        keep = cols[None, :] <= (row0 + jnp.arange(B))[:, None]  # (B, T)
        s = jnp.where(keep[None, :, None, :], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        o = matmul(p.reshape(g, B * m, T), vt).reshape(g, B, m, d)
        return jnp.transpose(o, (1, 0, 2, 3))

    if T <= q_block or T % q_block:
        return rows((q, jnp.int32(0)))
    nb = T // q_block
    out = jax.lax.map(rows, (q.reshape(nb, q_block, g, m, d),
                             jnp.arange(nb, dtype=jnp.int32) * q_block))
    return out.reshape(T, g, m, d)


def block(w, x, cfg, positions, matmul=f32_matmul):
    """One decoder block on one sequence: x (T, h) -> (T, h)."""
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


def sequence_loss_sum(params, tokens, labels, cfg, matmul=f32_matmul,
                      loss_chunk=512):
    """Sum of token cross-entropies of ONE sequence (T,)."""
    T = tokens.shape[0]
    positions = jnp.arange(T)
    x = embed(params["globals"], tokens)
    blk = jax.checkpoint(
        lambda w, x: block(w, x, cfg, positions, matmul))
    for w in params["layers"]:
        x = blk(w, x)

    @jax.checkpoint
    def chunk_loss(args):
        xc, lc = args
        logits = final_logits(params["globals"], xc, cfg, matmul)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, lc[:, None], axis=-1)[:, 0]
        return jnp.sum(lse - picked)

    if T <= loss_chunk or T % loss_chunk:
        return chunk_loss((x, labels))
    n = T // loss_chunk
    return jnp.sum(jax.lax.map(
        chunk_loss, (x.reshape(n, loss_chunk, -1),
                     labels.reshape(n, loss_chunk))))


def mean_loss(params, tokens, labels, cfg, matmul=f32_matmul):
    """Mean cross-entropy over all tokens of a batch (rows, T); the rows
    are taken one after another."""
    rows, T = tokens.shape
    total = 0.0
    for r in range(rows):
        total = total + sequence_loss_sum(params, tokens[r], labels[r], cfg,
                                          matmul)
    return total / float(rows * T)


def loss_and_grads(params, tokens, labels, cfg, matmul=f32_matmul):
    return jax.value_and_grad(mean_loss)(params, tokens, labels, cfg, matmul)


def clip_by_global_norm(grads, max_norm):
    norm = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                        for g in jax.tree.leaves(grads)))
    if max_norm <= 0:
        return grads, norm
    coeff = jnp.minimum(max_norm / (norm + 1e-6), 1.0)
    return jax.tree.map(lambda g: g * coeff, grads), norm


def adamw_step(params, grads, m, v, step, opt):
    """Decoupled weight decay on matrices only (vectors are not decayed);
    `step` counts from 1. Returns (params, m, v)."""
    b1, b2, eps = opt["adam_beta1"], opt["adam_beta2"], opt["adam_eps"]
    lr, wd = opt["lr"], opt["weight_decay"]
    bc1 = 1.0 - b1 ** step
    bc2 = 1.0 - b2 ** step
    m = jax.tree.map(lambda m_, g: b1 * m_ + (1 - b1) * g, m, grads)
    v = jax.tree.map(lambda v_, g: b2 * v_ + (1 - b2) * jnp.square(g),
                     v, grads)

    def upd(p, m_, v_):
        u = (m_ / bc1) / (jnp.sqrt(v_ / bc2) + eps)
        decay = wd if p.ndim >= 2 else 0.0
        return p - lr * (u + decay * p)

    return jax.tree.map(upd, params, m, v), m, v
