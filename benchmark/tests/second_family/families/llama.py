"""A second family for the rehearsal, added the way a later PR adds one:
the program's own Llama-form model (RMSNorm, SwiGLU, grouped K/V heads,
an untied head). Neutral leaves of a block: ln1_scale, ln2_scale (h,),
wqkv (grouped as the program views it), wo, w1 (h, 2, ffn: gate then
up), w2 (ffn, h). Globals: embedding (vocab, h), lnf_scale, lm_head
(h, vocab)."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .. import flops

_DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}
ROW_PARALLEL = {"wo": 0, "w2": 0}


def layer_shapes(cfg: dict, layer=None) -> dict:
    h, f, d = cfg["hidden_size"], cfg["intermediate_size"], cfg["head_dim"]
    heads, groups = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return {"ln1_scale": (h,), "ln2_scale": (h,),
            "wqkv": (h, d * (heads + 2 * groups)), "wo": (heads * d, h),
            "w1": (h, 2, f), "w2": (f, h)}


def layer_kind(cfg: dict, layer: int):
    return 0


def global_shapes(cfg: dict) -> dict:
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    return {"embedding": (v, h), "lnf_scale": (h,), "lm_head": (h, v)}


def draw(key, name: str, shape, cfg: dict):
    x = jax.random.normal(key, shape, jnp.float32)
    if name.endswith("_scale"):
        return 1.0 + 0.02 * x
    std = cfg["initializer_range"]
    if name in ("wo", "w2"):
        std /= math.sqrt(2.0 * cfg["published"]["num_hidden_layers"])
    return x * std


def layer_paths(cfg: dict, kind=None) -> dict:
    return {"ln1_scale": ("input_norm", "scale"),
            "ln2_scale": ("post_attention_norm", "scale"),
            "wqkv": ("attention", "wqkv"), "wo": ("attention", "wo"),
            "w1": ("mlp", "w1"), "w2": ("mlp", "w2")}


def global_paths(cfg: dict) -> dict:
    return {"embedding": ("embedding", "word_embeddings"),
            "lnf_scale": ("final_norm", "scale"), "lm_head": ("lm_head",)}


def model(cfg: dict, use: dict, tp: int = 1):
    from megatron_llm_tpu.config import ModelConfig
    from megatron_llm_tpu.models import LlamaModel

    return LlamaModel(ModelConfig(
        num_layers=use["num_hidden_layers"], hidden_size=cfg["hidden_size"],
        ffn_hidden_size=cfg["intermediate_size"],
        num_attention_heads=cfg["num_attention_heads"],
        num_attention_heads_kv=cfg["num_key_value_heads"],
        kv_channels=cfg["head_dim"],
        max_position_embeddings=cfg["max_position_embeddings"],
        seq_length=use.get("seq_length", use.get("max_context")),
        padded_vocab_size=cfg["vocab_size"],
        layernorm_epsilon=cfg["rms_norm_eps"], use_rms_norm=True,
        use_bias=False, glu_activation="swiglu",
        position_embedding_type="rotary", rope_theta=cfg["rope_theta"],
        parallel_attn=False, tie_embed_logits=False, hidden_dropout=0.0,
        attention_dropout=0.0,
        params_dtype=_DTYPES[use.get("params_dtype",
                                     use.get("weights_dtype", "float32"))],
        compute_dtype=_DTYPES[use["compute_dtype"]],
        init_method_std=cfg["initializer_range"],
        remat_policy=use.get("remat_policy"),
        use_flash_attn=use.get("use_flash_attn", False)))


def trainer_args(cfg: dict, use: dict) -> dict:
    return {}


def engine_args(cfg: dict, use: dict) -> dict:
    return {}


def attn_width(cfg: dict) -> int:
    return cfg["num_attention_heads"] * cfg["head_dim"]


def kv_width(cfg: dict) -> int:
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"]


def head_params(cfg: dict) -> int:
    return cfg["vocab_size"] * cfg["hidden_size"]


def matmul_params(cfg: dict, layers: int, head: bool = True) -> int:
    h, f = cfg["hidden_size"], cfg["intermediate_size"]
    block = h * (attn_width(cfg) + kv_width(cfg)) + attn_width(cfg) * h \
        + 3 * h * f
    return layers * block + (head_params(cfg) if head else 0)


def train_flops_per_token(cfg: dict, layers: int, seq: int) -> float:
    return flops.train_flops_per_token(matmul_params(cfg, layers), layers,
                                       attn_width(cfg), seq)


def train_attention_flops(cfg, layers, seq, tokens) -> float:
    return flops.train_attention_flops(layers, attn_width(cfg), seq, tokens)


def train_attention_bytes(cfg, layers, seq, tokens, itemsize=2) -> float:
    return flops.train_attention_bytes(layers, attn_width(cfg),
                                       kv_width(cfg), tokens, itemsize)


def serve_span_flops(cfg, layers, start, stop, head_tokens) -> float:
    return flops.serve_span_flops(
        matmul_params(cfg, layers, head=False), head_params(cfg), layers,
        attn_width(cfg), start, stop, head_tokens)


def weight_matmul_flops(cfg, layers, tokens, head_tokens) -> float:
    return 2.0 * matmul_params(cfg, layers, head=False) * tokens \
        + 2.0 * head_params(cfg) * head_tokens


def weight_bytes(cfg, layers, itemsize=2) -> float:
    return float(matmul_params(cfg, layers) * itemsize)
