"""A family whose blocks are of TWO KINDS, for the rehearsal of what the
harness does by kind (`test_two_kinds.py`); added the way a later PR adds
a family, as new files. Every block is `x += attention(norm(x)); x +=
mlp(norm(x))` under ONE norm; the configuration's `layer_types` names
each block's MLP:

  "glu"    norm_scale (h,), wqkv, wo, w_gate (h, f), w_up (h, f),
           w_down (f, h): a SwiGLU MLP under an RMS norm
  "plain"  norm_scale, norm_bias (h,), wqkv, wo, w_up (h, g),
           w_down (g, h): a GELU MLP of ANOTHER width under a LayerNorm

So `w_gate` is only the one kind's and `norm_bias` only the other's,
`w_up` / `w_down` are both kinds' with different shapes, `wqkv` / `wo`
both kinds' with the same. Globals: embedding (vocab, h), tied to the
head; lnf_scale. The program has no model of several kinds yet, so there
is no `model`, `trainer_args` or `engine_args` here and no cell: the
seeded weights, the paths and the reference are what is rehearsed.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

ROW_PARALLEL = {"wo": 0, "w_down": 0}


def layer_kind(cfg: dict, layer: int):
    return cfg["layer_types"][layer]


def layer_shapes(cfg: dict, layer: int) -> dict:
    h, d = cfg["hidden_size"], cfg["head_dim"]
    heads, groups = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    shapes = {"norm_scale": (h,), "wqkv": (h, d * (heads + 2 * groups)),
              "wo": (heads * d, h)}
    if layer_kind(cfg, layer) == "glu":
        f = cfg["glu_size"]
        shapes.update(w_gate=(h, f), w_up=(h, f), w_down=(f, h))
    else:
        g = cfg["plain_size"]
        shapes.update(norm_bias=(h,), w_up=(h, g), w_down=(g, h))
    return shapes


def global_shapes(cfg: dict) -> dict:
    h = cfg["hidden_size"]
    return {"embedding": (cfg["vocab_size"], h), "lnf_scale": (h,)}


def draw(key, name: str, shape, cfg: dict):
    x = jax.random.normal(key, shape, jnp.float32)
    if name.endswith("_scale"):
        return 1.0 + 0.02 * x
    if name.endswith("_bias"):
        return 0.02 * x
    std = cfg["initializer_range"]
    if name in ("wo", "w_down"):
        std /= math.sqrt(2.0 * len(cfg["layer_types"]))
    return x * std


def layer_paths(cfg: dict, kind) -> dict:
    """A kind's leaves -> their paths under the program's `layers`: each
    begins with the name of that kind's stack."""
    paths = {"norm_scale": ("input_norm", "scale"),
             "wqkv": ("attention", "wqkv"), "wo": ("attention", "wo"),
             "w_up": ("mlp", "w_up"), "w_down": ("mlp", "w_down")}
    if kind == "glu":
        paths["w_gate"] = ("mlp", "w_gate")
    else:
        paths["norm_bias"] = ("input_norm", "bias")
    return {name: (kind + "_blocks",) + path for name, path in paths.items()}


def global_paths(cfg: dict) -> dict:
    return {"embedding": ("embedding", "word_embeddings"),
            "lnf_scale": ("final_norm", "scale")}
