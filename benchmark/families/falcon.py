"""The Falcon family (tiiuae/falcon-7b, falcon-40b): everything the
harness knows about it and about nothing else.

One block's neutral leaves:
  ln1_scale, ln1_bias          (h,)   the block's (attention) layer norm
  ln2_scale, ln2_bias          (h,)   the MLP's own norm (two-norm blocks)
  wqkv  (h, groups * (q_per_kv + 2) * head_dim)   fused, grouped as the
        published code views it: per K/V group its query heads, then its
        key head, then its value head
  wo    (heads * head_dim, h)
  w1    (h, ffn)      w2  (ffn, h)
Globals: embedding (vocab, h), tied to the head; lnf_scale, lnf_bias.
The plain reference is `reference/falcon.py` (`families.find` hands it
out as `.reference`).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .. import flops

NORM_JITTER = 0.02
_DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}

# ---------------------------------------------------------- seeded weights


def layer_shapes(cfg: dict, layer=None) -> dict:
    """Every block is of one kind: `layer` is not looked at."""
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


def layer_kind(cfg: dict, layer: int):
    """Layers of one kind share their compiled reference block."""
    return 0


def global_shapes(cfg: dict) -> dict:
    h = cfg["hidden_size"]
    return {"embedding": (cfg["vocab_size"], h),
            "lnf_scale": (h,), "lnf_bias": (h,)}


def draw(key, name: str, shape, cfg: dict):
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


# ------------------------------------------------------- into the program

# neutral leaves whose product tensor parallelism sums over the ranks,
# with the axis (of one block's leaf) that the ranks split
ROW_PARALLEL = {"wo": 0, "w2": 0}


def layer_paths(cfg: dict, kind=None) -> dict:
    """Neutral block leaf -> its path under the program's `layers`; one
    kind, so `kind` is not looked at."""
    paths = {
        "ln1_scale": ("input_norm", "scale"),
        "ln1_bias": ("input_norm", "bias"),
        "wqkv": ("attention", "wqkv"), "wo": ("attention", "wo"),
        "w1": ("mlp", "w1"), "w2": ("mlp", "w2"),
    }
    if cfg["new_decoder_architecture"]:
        paths["ln2_scale"] = ("mlp_norm", "scale")
        paths["ln2_bias"] = ("mlp_norm", "bias")
    return paths


def global_paths(cfg: dict) -> dict:
    return {"embedding": ("embedding", "word_embeddings"),
            "lnf_scale": ("final_norm", "scale"),
            "lnf_bias": ("final_norm", "bias")}


def model(cfg: dict, use: dict, tp: int = 1):
    """The program's model object from the file's keys; `use` is the
    file's `train` or `serve` section."""
    from megatron_llm_tpu.config import ModelConfig
    from megatron_llm_tpu.models import FalconModel

    if cfg["vocab_size"] % (128 * tp):
        raise ValueError("vocabulary does not divide over the tp ranks")
    seq = use.get("seq_length", use.get("max_context",
                                        cfg["max_position_embeddings"]))
    return FalconModel(ModelConfig(
        num_layers=use["num_hidden_layers"],
        hidden_size=cfg["hidden_size"],
        ffn_hidden_size=cfg["ffn_hidden_size"],
        num_attention_heads=cfg["num_attention_heads"],
        num_attention_heads_kv=cfg["num_kv_heads"],
        kv_channels=cfg["head_dim"],
        max_position_embeddings=cfg["max_position_embeddings"],
        seq_length=seq,
        padded_vocab_size=cfg["vocab_size"],
        layernorm_epsilon=cfg["layer_norm_epsilon"],
        use_rms_norm=False, use_bias=cfg["bias"], glu_activation=None,
        hidden_act=cfg["hidden_act"], position_embedding_type="rotary",
        rope_theta=cfg["rope_theta"], parallel_attn=cfg["parallel_attn"],
        parallel_layernorm=cfg["new_decoder_architecture"],
        tie_embed_logits=cfg["tie_word_embeddings"],
        hidden_dropout=0.0, attention_dropout=0.0,
        params_dtype=_DTYPES[use.get("params_dtype",
                                     use.get("weights_dtype", "float32"))],
        compute_dtype=_DTYPES[use["compute_dtype"]],
        init_method_std=cfg["initializer_range"],
        remat_policy=use.get("remat_policy"),
        use_flash_attn=use.get("use_flash_attn", False),
    ))


def trainer_args(cfg: dict, use: dict) -> dict:
    """`TrainConfig` arguments beyond those the `train` section lists."""
    return {}


def engine_args(cfg: dict, use: dict) -> dict:
    """`DecodeEngine` arguments beyond those the `serve` section lists."""
    return {}


# ---------------------------------------------------------------- its work


def qkv_width(cfg: dict) -> int:
    return cfg["head_dim"] * (cfg["num_attention_heads"]
                              + 2 * cfg["num_kv_heads"])


def attn_width(cfg: dict) -> int:
    """Width of the attention output: query heads x head size."""
    return cfg["num_attention_heads"] * cfg["head_dim"]


def kv_width(cfg: dict) -> int:
    """One token's un-expanded key and value channels, together."""
    return 2 * cfg["num_kv_heads"] * cfg["head_dim"]


def layer_matmul_params(cfg: dict) -> int:
    """Weights of one block that sit in a matrix multiplication."""
    h, f = cfg["hidden_size"], cfg["ffn_hidden_size"]
    return h * qkv_width(cfg) + attn_width(cfg) * h + 2 * h * f


def layer_params(cfg: dict) -> int:
    norms = 2 if cfg["new_decoder_architecture"] else 1
    return layer_matmul_params(cfg) + 2 * cfg["hidden_size"] * norms


def embedding_params(cfg: dict) -> int:
    return cfg["vocab_size"] * cfg["hidden_size"]


def n_params(cfg: dict, layers: int) -> int:
    """All parameters with the tied embedding/head counted once."""
    return (layers * layer_params(cfg) + embedding_params(cfg)
            + 2 * cfg["hidden_size"])


def matmul_params(cfg: dict, layers: int, head: bool = True) -> int:
    """Parameters a token is multiplied by: the blocks' matrices and,
    where `head`, the (tied) output head. The embedding lookup itself
    is a gather and costs no operations. A dense model: every one of
    them is active for every token."""
    return layers * layer_matmul_params(cfg) \
        + (embedding_params(cfg) if head else 0)


def kv_bytes_per_token(cfg: dict, layers: int, itemsize: int = 2) -> int:
    return layers * kv_width(cfg) * itemsize


def train_flops_per_token(cfg: dict, layers: int, seq: int) -> float:
    return flops.train_flops_per_token(matmul_params(cfg, layers), layers,
                                       attn_width(cfg), seq)


def train_attention_flops(cfg: dict, layers: int, seq: int,
                          tokens: int) -> float:
    return flops.train_attention_flops(layers, attn_width(cfg), seq, tokens)


def train_attention_bytes(cfg: dict, layers: int, seq: int, tokens: int,
                          itemsize: int = 2) -> float:
    del seq
    return flops.train_attention_bytes(layers, attn_width(cfg),
                                       kv_width(cfg), tokens, itemsize)


def traced_attention_flops(cfg: dict, use: dict, traced: dict) -> float:
    """For a metric file's `flops_fn`: attention's FLOPs in the traced
    part of a training window, from its counters."""
    return train_attention_flops(cfg, use["num_hidden_layers"],
                                 traced["seq_length"], traced["tokens"])


def traced_attention_bytes(cfg: dict, use: dict, traced: dict) -> float:
    return train_attention_bytes(cfg, use["num_hidden_layers"],
                                 traced["seq_length"], traced["tokens"])


def serve_span_flops(cfg: dict, layers: int, start: int, stop: int,
                     head_tokens: int) -> float:
    """Forward FLOPs of the tokens at cache positions start..stop-1, of
    which `head_tokens` need the head."""
    return flops.serve_span_flops(
        matmul_params(cfg, layers, head=False), embedding_params(cfg),
        layers, attn_width(cfg), start, stop, head_tokens)


def serve_token_flops(cfg: dict, layers: int, position: int,
                      needs_head: bool) -> float:
    return serve_span_flops(cfg, layers, position, position + 1,
                            int(needs_head))


def weight_matmul_flops(cfg: dict, layers: int, tokens: int,
                        head_tokens: int) -> float:
    """FLOPs of the weight matrices alone (no attention) for `tokens`
    rows through the blocks and `head_tokens` through the head."""
    return (2.0 * matmul_params(cfg, layers, head=False) * tokens
            + 2.0 * embedding_params(cfg) * head_tokens)


def weight_bytes(cfg: dict, layers: int, itemsize: int = 2) -> float:
    """Bytes of the matrices one forward pass (one round) reads once."""
    return float(matmul_params(cfg, layers) * itemsize)


def traced_weight_matmul_flops(cfg: dict, use: dict, traced: dict) -> float:
    """For a metric file's `flops_fn`: the weight matrices' FLOPs in the
    traced part of a serving window: every prompt and output token goes
    through the blocks, every output token through the head."""
    return weight_matmul_flops(cfg, use["num_hidden_layers"],
                               traced["tokens"], traced["out_tokens"])


def traced_weight_bytes(cfg: dict, use: dict, traced: dict) -> float:
    """Every round of the traced part reads the matrices once."""
    return traced["steps"] * weight_bytes(cfg, use["num_hidden_layers"])
