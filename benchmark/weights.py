"""Weights from `--seed`, made on the device, in the benchmark's own
neutral layout. The program's adapter (`program.py`) and the plain
reference (`reference/falcon.py`) both draw from here, so neither takes
anything the other has made.

One block's leaves:
  ln1_scale, ln1_bias          (h,)   the block's (attention) layer norm
  ln2_scale, ln2_bias          (h,)   the MLP's own norm (two-norm blocks)
  wqkv  (h, groups * (q_per_kv + 2) * head_dim)   fused, grouped as the
        published code views it: per K/V group its query heads, then its
        key head, then its value head
  wo    (heads * head_dim, h)
  w1    (h, ffn)      w2  (ffn, h)
Globals: embedding (vocab, h), tied to the head; lnf_scale, lnf_bias.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

NORM_JITTER = 0.02


def seed_words(seed: int) -> np.ndarray:
    """`--seed` as two 32-bit words, to hand to a compiled program as an
    ARGUMENT: a seed baked into a program makes every new seed a new
    program to compile. Seeds run a little past 2**31."""
    seed = int(seed)
    return np.array([seed & 0xFFFFFFFF, seed >> 32], np.uint32)


def _key(seed):
    """`seed`: a whole number, or its `seed_words` (traced or not)."""
    words = seed_words(seed) if isinstance(seed, int) else seed
    return jax.random.fold_in(jax.random.key(words[0]), words[1])


def layer_shapes(cfg: dict) -> dict:
    h, f, d = cfg["hidden_size"], cfg["ffn_hidden_size"], cfg["head_dim"]
    heads, groups = cfg["num_attention_heads"], cfg["num_kv_heads"]
    shapes = {
        "ln1_scale": (h,), "ln1_bias": (h,),
        "wqkv": (h, d * (heads + 2 * groups)),
        "wo": (heads * d, h),
        "w1": (h, f), "w2": (f, h),
    }
    if cfg["new_decoder_architecture"]:
        shapes["ln2_scale"] = (h,)
        shapes["ln2_bias"] = (h,)
    return shapes


def _draw(key, name: str, shape, cfg: dict):
    std = cfg["initializer_range"]
    x = jax.random.normal(key, shape, jnp.float32)
    if name.endswith("_scale"):
        return 1.0 + NORM_JITTER * x
    if name.endswith("_bias"):
        return NORM_JITTER * x
    if name in ("wo", "w2"):
        depth = cfg["published"]["num_hidden_layers"]
        return x * (std / math.sqrt(2.0 * depth))
    return x * std


def make_layer(cfg: dict, seed, layer) -> dict:
    """One block's float32 leaves. `layer` may be a traced integer."""
    lkey = jax.random.fold_in(_key(seed), 1 + layer)
    out = {}
    for i, (name, shape) in enumerate(sorted(layer_shapes(cfg).items())):
        out[name] = _draw(jax.random.fold_in(lkey, i), name, shape, cfg)
    return out


def make_globals(cfg: dict, seed) -> dict:
    gkey = jax.random.fold_in(_key(seed), 0)
    h = cfg["hidden_size"]
    shapes = {"embedding": (cfg["vocab_size"], h),
              "lnf_scale": (h,), "lnf_bias": (h,)}
    return {name: _draw(jax.random.fold_in(gkey, i), name, shape, cfg)
            for i, (name, shape) in enumerate(sorted(shapes.items()))}


def make_stacked(cfg: dict, seed, layers: int) -> dict:
    """All blocks with a leading layer axis (what a layer scan reads)."""
    return jax.vmap(lambda i: make_layer(cfg, seed, i))(jnp.arange(layers))
