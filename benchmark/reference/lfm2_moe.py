"""Plain float32 reference of the LFM2 mixture-of-experts decoder
(LiquidAI/LFM2-8B-A1B, `model_type` lfm2_moe) and its loss. `jax.numpy`
and `reference/common.py` only: nothing imported from the program, no
kernel, no cache, no batching.

Every norm is an RMSNorm (eps `norm_eps`) with a learned scale; no matrix
has a bias. Block i on one sequence x (T, h):

  x = x + op_i(norm_op(x))         op_i by `layer_types[i]`
  x = x + ff_i(norm_ff(x))         dense for i < `num_dense_layers`, else routed

  op = short convolution ("conv"): [B, C, X] = split3(u @ conv_in), in
       that order; g = B * X; v_t = k_0 g_{t-2} + k_1 g_{t-1} + k_2 g_t
       with `conv_kernel` (h, 3) depthwise, g_{<0} = 0, no bias;
       op(u) = (C * v) @ conv_out
  op = attention ("full_attention"): q heads and fewer k, v heads from
       `wqkv`; RMSNorm over each q head's and each k head's channels (one
       scale for q, one for k), THEN rotary; causal softmax(q k^T /
       sqrt d) v; `wo`
  ff = dense SwiGLU: w2(silu(w1 x) * w3 x)
  ff = routed: s = sigmoid(x @ router) in float32; the chosen experts are
       top_k(s + expert_bias); their weights are s AT the chosen experts
       (without the bias) over (their sum + 1e-6) (`norm_topk_prob`),
       times `routed_scaling_factor`; ff(x) = sum_j w_j * down_e(silu(
       gate_e x) * up_e x). Computed as "every expert on every token,
       masked": no shared expert, no capacity, no dropped token.
  after the last block: the final RMSNorm and the head tied to the
  embedding; loss: mean token cross-entropy and nothing beside it.

Departures from the published modelling code, each also under `assumed`
in the configuration file: q, k and v are one fused matrix `wqkv`, its
columns grouped by K/V head (that head's query heads, its key head, its
value head), and the dense MLP's w1 and w3 are one leaf `w13` (h, 2, f):
fixed permutations of the published leaves, and the weights here are
random; rotary rotates adjacent channel pairs (`common.rotary`).
"""

from __future__ import annotations

import sys

import jax
import jax.numpy as jnp

from .common import causal_attention, f32_matmul, rotary
from .common import mean_cross_entropy


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1,
                                      keepdims=True) + eps) * scale


def is_conv(cfg, layer: int) -> bool:
    return cfg["layer_types"][layer] == "conv"


def is_routed(cfg, layer: int) -> bool:
    return layer >= cfg["num_dense_layers"]


def short_conv(w, u, matmul):
    T, h = u.shape
    bcx = matmul(u, w["conv_in"])
    B, C, X = bcx[:, :h], bcx[:, h:2 * h], bcx[:, 2 * h:]
    g = B * X
    taps = w["conv_kernel"].shape[1]
    padded = jnp.concatenate([jnp.zeros((taps - 1, h), g.dtype), g])
    v = sum(w["conv_kernel"][:, j] * padded[j:j + T] for j in range(taps))
    return matmul(C * v, w["conv_out"])


def attention(w, u, cfg, positions, matmul):
    T = u.shape[0]
    g, d = cfg["num_key_value_heads"], cfg["head_dim"]
    m = cfg["num_attention_heads"] // g
    qkv = matmul(u, w["wqkv"]).reshape(T, g, m + 2, d)
    q, k, v = qkv[:, :, :m], qkv[:, :, m], qkv[:, :, m + 1]
    q = rms_norm(q, w["q_norm_scale"], cfg["norm_eps"])
    k = rms_norm(k, w["k_norm_scale"], cfg["norm_eps"])
    q = rotary(q, positions, cfg["rope_theta"])
    k = rotary(k, positions, cfg["rope_theta"])
    ctx = causal_attention(q, k, v, matmul).reshape(T, g * m * d)
    return matmul(ctx, w["wo"])


def route(w, x, cfg, matmul):
    """(chosen (T, k), their weights (T, k)). The scores are float32; the
    router's product is one of the block's matrix products and goes
    through the `matmul` handed in like the others, so the int8 control
    rounds its operands too (`check.py`: every matrix product)."""
    s = jax.nn.sigmoid(matmul(x, w["router"]))
    _, chosen = jax.lax.top_k(s + w["expert_bias"],
                              cfg["num_experts_per_tok"])
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    if cfg["norm_topk_prob"]:
        picked = picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-6)
    return chosen, picked * cfg["routed_scaling_factor"]


def routed_mlp(w, x, cfg, matmul):
    chosen, picked = route(w, x, cfg, matmul)
    E = cfg["num_experts"]
    # (T, E): an expert's weight for a token, 0 where it was not chosen
    weight = jnp.sum(jax.nn.one_hot(chosen, E) * picked[..., None], axis=1)

    def expert(args):
        gate, up, down, we = args
        return we[:, None] * matmul(
            jax.nn.silu(matmul(x, gate)) * matmul(x, up), down)

    return jnp.sum(jax.lax.map(expert, (w["we_gate"], w["we_up"],
                                        w["we_down"], weight.T)), axis=0)


def dense_mlp(w, x, matmul):
    return matmul(jax.nn.silu(matmul(x, w["w13"][:, 0]))
                  * matmul(x, w["w13"][:, 1]), w["w2"])


def block(w, x, cfg, positions, matmul=f32_matmul, layer=0):
    """One decoder block on one sequence: x (T, h) -> (T, h); its kind by
    `layer`, the block's index among the layers as run."""
    eps = cfg["norm_eps"]
    u = rms_norm(x, w["norm_op_scale"], eps)
    if is_conv(cfg, layer):
        x = x + short_conv(w, u, matmul)
    else:
        x = x + attention(w, u, cfg, positions, matmul)
    u = rms_norm(x, w["norm_ff_scale"], eps)
    if is_routed(cfg, layer):
        return x + routed_mlp(w, u, cfg, matmul)
    return x + dense_mlp(w, u, matmul)


def embed(glob, tokens):
    return jnp.take(glob["embedding"], tokens, axis=0).astype(jnp.float32)


def final_logits(glob, x, cfg, matmul=f32_matmul):
    """(T, h) -> (T, vocab) through the final norm and the tied head."""
    x = rms_norm(x, glob["lnf_scale"], cfg["norm_eps"])
    return matmul(x, glob["embedding"].astype(jnp.float32).T)


def mean_loss(params, tokens, labels, cfg, matmul=f32_matmul):
    """The training loss: the mean cross-entropy and nothing beside it
    (the published training moves `expert_bias` by a rule of its own and
    adds no term)."""
    return mean_cross_entropy(sys.modules[__name__], params, tokens, labels,
                              cfg, matmul)
