"""What every family's plain reference shares: float32 arithmetic at the
highest precision, the norms and attention that are plain mathematics,
the chunked cross-entropy over a family's own `embed`, `block` and
`final_logits`, and AdamW with global-norm clipping. `jax.numpy` only: no
kernels, no cache, no batching, and nothing imported from the program or
from a family.

`matmul` is the one hook: the reference multiplies in float32 at the
highest precision; the control passes a matmul that rounds its operands
to a lower precision first (`check.int8_matmul`). `jax.checkpoint` and the
query-block loop only bound memory; they change no value.

Rotary rotates adjacent channel pairs (the program's convention; a
family whose published code rotates halves says so under `assumed` in its
configuration files: a fixed permutation of q/k channels maps one onto
the other, and the weights here are random).
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


def sequence_loss_sum(ref, params, tokens, labels, cfg, matmul=f32_matmul,
                      loss_chunk=512):
    """Sum of token cross-entropies of ONE sequence (T,), through the
    family reference `ref`'s `embed`, `block` (which gets its layer's
    index) and `final_logits`."""
    T = tokens.shape[0]
    positions = jnp.arange(T)
    x = ref.embed(params["globals"], tokens)
    for i, w in enumerate(params["layers"]):
        x = jax.checkpoint(
            lambda w, x, i=i: ref.block(w, x, cfg, positions, matmul,
                                        layer=i))(w, x)

    @jax.checkpoint
    def chunk_loss(args):
        xc, lc = args
        logits = ref.final_logits(params["globals"], xc, cfg, matmul)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, lc[:, None], axis=-1)[:, 0]
        return jnp.sum(lse - picked)

    if T <= loss_chunk or T % loss_chunk:
        return chunk_loss((x, labels))
    n = T // loss_chunk
    return jnp.sum(jax.lax.map(
        chunk_loss, (x.reshape(n, loss_chunk, -1),
                     labels.reshape(n, loss_chunk))))


def mean_cross_entropy(ref, params, tokens, labels, cfg, matmul=f32_matmul):
    """Mean cross-entropy over all tokens of a batch (rows, T); the rows
    are taken one after another."""
    rows, T = tokens.shape
    total = 0.0
    for r in range(rows):
        total = total + sequence_loss_sum(ref, params, tokens[r], labels[r],
                                          cfg, matmul)
    return total / float(rows * T)


def loss_and_grads(ref, params, tokens, labels, cfg, matmul=f32_matmul):
    """`ref.mean_loss` (the cross-entropy and whatever terms the family
    adds to it) with its gradient."""
    return jax.value_and_grad(ref.mean_loss)(params, tokens, labels, cfg,
                                             matmul)


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
