"""The LFM2 mixture-of-experts family (LiquidAI/LFM2-8B-A1B): everything
the harness knows about it and about nothing else.

A block's kind is (operator, feed-forward), named as the program names
that kind's stack: `conv_mlp`, `attention_mlp`, `conv_moe`,
`attention_moe`. Neutral leaves, by what the kind has:

  every kind   norm_op_scale, norm_ff_scale (h,)
  conv         conv_in (h, 3h) [B | C | X], conv_kernel (h, taps),
               conv_out (h, h)
  attention    wqkv (h, groups * (q_per_kv + 2) * head_dim) grouped by K/V
               head, wo (heads * head_dim, h), q_norm_scale, k_norm_scale
               (head_dim,)
  mlp          w13 (h, 2, f) [gate, up], w2 (f, h)
  moe          router (h, E), expert_bias (E,), we_gate, we_up (E, h, fe),
               we_down (E, fe, h)
Globals: embedding (vocab, h), tied to the head; lnf_scale.
The plain reference is `reference/lfm2_moe.py`.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .. import flops

NORM_JITTER = 0.02
# the spread of the per-expert selection bias: the published training
# moves this buffer from zero; drawn wide enough that it changes the
# chosen set of at least a tenth of the tokens, so that a program which
# drops it, or weighs WITH it, fails the comparison
BIAS_SPREAD = 0.1
_DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}

# ---------------------------------------------------------- seeded weights


def layer_kind(cfg: dict, layer: int) -> str:
    """The name the program gives the stack of this layer's kind."""
    op = "conv" if cfg["layer_types"][layer] == "conv" else "attention"
    return op + ("_moe" if layer >= cfg["num_dense_layers"] else "_mlp")


def layer_shapes(cfg: dict, layer: int) -> dict:
    h, d = cfg["hidden_size"], cfg["head_dim"]
    op, ff = layer_kind(cfg, layer).split("_")
    shapes = {"norm_op_scale": (h,), "norm_ff_scale": (h,)}
    if op == "conv":
        shapes.update(conv_in=(h, 3 * h),
                      conv_kernel=(h, cfg["conv_L_cache"]),
                      conv_out=(h, h))
    else:
        heads, groups = cfg["num_attention_heads"], \
            cfg["num_key_value_heads"]
        shapes.update(wqkv=(h, d * (heads + 2 * groups)),
                      wo=(heads * d, h),
                      q_norm_scale=(d,), k_norm_scale=(d,))
    if ff == "moe":
        E, fe = cfg["num_experts"], cfg["moe_intermediate_size"]
        shapes.update(router=(h, E), expert_bias=(E,),
                      we_gate=(E, h, fe), we_up=(E, h, fe),
                      we_down=(E, fe, h))
    else:
        f = cfg["intermediate_size"]
        shapes.update(w13=(h, 2, f), w2=(f, h))
    return shapes


def global_shapes(cfg: dict) -> dict:
    h = cfg["hidden_size"]
    return {"embedding": (cfg["vocab_size"], h), "lnf_scale": (h,)}


def draw(key, name: str, shape, cfg: dict):
    std = cfg["initializer_range"]
    x = jax.random.normal(key, shape, jnp.float32)
    if name.endswith("_scale"):
        return 1.0 + NORM_JITTER * x
    if name == "expert_bias":
        return BIAS_SPREAD * x
    if name == "conv_kernel":
        return x / math.sqrt(shape[-1])
    if name in ROW_PARALLEL:
        depth = cfg["published"]["num_hidden_layers"]
        return x * (std / math.sqrt(2.0 * depth))
    return x * std


# ------------------------------------------------------- into the program

# neutral leaves whose product tensor parallelism sums over the ranks,
# with the axis (of one block's leaf) that the ranks would split
ROW_PARALLEL = {"wo": 0, "w2": 0, "conv_out": 0, "we_down": 1}

_PATHS = {
    "norm_op_scale": ("input_norm", "scale"),
    "norm_ff_scale": ("post_attention_norm", "scale"),
    "conv_in": ("conv", "w_in"), "conv_kernel": ("conv", "kernel"),
    "conv_out": ("conv", "w_out"),
    "wqkv": ("attention", "wqkv"), "wo": ("attention", "wo"),
    "q_norm_scale": ("attention", "q_norm"),
    "k_norm_scale": ("attention", "k_norm"),
    "w13": ("mlp", "w1"), "w2": ("mlp", "w2"),
    "router": ("moe", "router"), "expert_bias": ("moe", "expert_bias"),
    "we_gate": ("moe", "w_gate"), "we_up": ("moe", "w_up"),
    "we_down": ("moe", "w_down"),
}


def layer_paths(cfg: dict, kind: str) -> dict:
    """Neutral block leaf -> its path under the program's `layers`: with
    several kinds among the layers as run the program holds one stack a
    kind under the kind's name, with one kind the stack is `layers`."""
    kinds = [layer_kind(cfg, i) for i in range(cfg["num_hidden_layers"])]
    like = kinds.index(kind)
    prefix = (kind,) if len(set(kinds)) > 1 else ()
    return {name: prefix + _PATHS[name] for name in layer_shapes(cfg, like)}


def global_paths(cfg: dict) -> dict:
    return {"embedding": ("embedding", "word_embeddings"),
            "lnf_scale": ("final_norm", "scale")}


def model(cfg: dict, use: dict, tp: int = 1):
    """The program's model object from the file's keys; `use` is the
    file's `train` or `serve` section. The rotary table is as long as
    the cell's context, not the published 128,000 positions."""
    from megatron_llm_tpu.config import ModelConfig
    from megatron_llm_tpu.models import GPTModel

    L = use["num_hidden_layers"]
    seq = use.get("seq_length", use.get("max_context"))
    return GPTModel(ModelConfig(
        num_layers=L, hidden_size=cfg["hidden_size"],
        ffn_hidden_size=cfg["intermediate_size"],
        num_attention_heads=cfg["num_attention_heads"],
        num_attention_heads_kv=cfg["num_key_value_heads"],
        kv_channels=cfg["head_dim"],
        max_position_embeddings=seq, seq_length=seq,
        padded_vocab_size=cfg["vocab_size"],
        layernorm_epsilon=cfg["norm_eps"], use_rms_norm=True,
        use_bias=False, glu_activation="swiglu",
        position_embedding_type="rotary", rope_theta=cfg["rope_theta"],
        tie_embed_logits=cfg["tie_word_embeddings"],
        hidden_dropout=0.0, attention_dropout=0.0,
        params_dtype=_DTYPES[use.get("params_dtype",
                                     use.get("weights_dtype", "float32"))],
        compute_dtype=_DTYPES[use["compute_dtype"]],
        init_method_std=cfg["initializer_range"],
        remat_policy=use.get("remat_policy"),
        use_flash_attn=use.get("use_flash_attn", False),
        layer_types=tuple(cfg["layer_types"][:L]),
        conv_L_cache=cfg["conv_L_cache"], qk_layernorm=True,
        num_experts=cfg["num_experts"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        num_dense_layers=cfg["num_dense_layers"],
        use_expert_bias=cfg["use_expert_bias"],
        norm_topk_prob=cfg["norm_topk_prob"],
        routed_scaling_factor=float(cfg["routed_scaling_factor"]),
    ))


def trainer_args(cfg: dict, use: dict) -> dict:
    return {}


def engine_args(cfg: dict, use: dict) -> dict:
    return {}


# ---------------------------------------------------------------- its work


def attn_width(cfg: dict) -> int:
    return cfg["num_attention_heads"] * cfg["head_dim"]


def kv_width(cfg: dict) -> int:
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"]


def expert_params(cfg: dict) -> int:
    """One expert's three matrices."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def _counts(cfg: dict, layers: int) -> dict:
    kinds = [layer_kind(cfg, i).split("_") for i in range(layers)]
    return {"conv": sum(op == "conv" for op, _ in kinds),
            "attention": sum(op == "attention" for op, _ in kinds),
            "mlp": sum(ff == "mlp" for _, ff in kinds),
            "moe": sum(ff == "moe" for _, ff in kinds)}


def dense_matmul_params(cfg: dict, layers: int) -> int:
    """The blocks' DENSE matrices (operators and dense MLPs: what goes
    through `qdot`), every one active for every token."""
    h, n = cfg["hidden_size"], _counts(cfg, layers)
    return (n["conv"] * 4 * h * h
            + n["attention"] * (h * (attn_width(cfg) + kv_width(cfg))
                                + attn_width(cfg) * h)
            + n["mlp"] * 3 * h * cfg["intermediate_size"])


def active_matmul_params(cfg: dict, layers: int) -> int:
    """Parameters ONE token is multiplied by in the blocks: the dense
    matrices, each routed layer's router and `num_experts_per_tok` of its
    experts, never all of them."""
    n = _counts(cfg, layers)
    return dense_matmul_params(cfg, layers) + n["moe"] * (
        cfg["hidden_size"] * cfg["num_experts"]
        + cfg["num_experts_per_tok"] * expert_params(cfg))


def embedding_params(cfg: dict) -> int:
    return cfg["vocab_size"] * cfg["hidden_size"]


def n_params(cfg: dict, layers: int) -> int:
    """All parameters held, the tied table once."""
    h, n = cfg["hidden_size"], _counts(cfg, layers)
    return (dense_matmul_params(cfg, layers)
            + n["moe"] * (cfg["num_experts"] * (expert_params(cfg) + h + 1))
            + n["conv"] * h * cfg["conv_L_cache"]
            + n["attention"] * 2 * cfg["head_dim"]
            + layers * 2 * h + embedding_params(cfg) + h)


def kv_bytes_per_token(cfg: dict, layers: int, itemsize: int = 2) -> int:
    return _counts(cfg, layers)["attention"] * kv_width(cfg) * itemsize


def conv_state_bytes_per_slot(cfg: dict, layers: int,
                              itemsize: int = 2) -> int:
    return _counts(cfg, layers)["conv"] * (cfg["conv_L_cache"] - 1) \
        * cfg["hidden_size"] * itemsize


def train_flops_per_token(cfg: dict, layers: int, seq: int) -> float:
    return flops.train_flops_per_token(
        active_matmul_params(cfg, layers) + embedding_params(cfg),
        _counts(cfg, layers)["attention"], attn_width(cfg), seq)


def serve_span_flops(cfg: dict, layers: int, start: int, stop: int,
                     head_tokens: int) -> float:
    """Forward FLOPs of the tokens at cache positions start..stop-1 over
    the ACTIVE parameters; attention's keys in the attention layers only."""
    return flops.serve_span_flops(
        active_matmul_params(cfg, layers), embedding_params(cfg),
        _counts(cfg, layers)["attention"], attn_width(cfg), start, stop,
        head_tokens)


def traced_moe_flops(cfg: dict, use: dict, traced: dict) -> float:
    """The experts' products for the token-expert pairs of the traced
    part's real rows: the same work whatever implements it."""
    return 2.0 * expert_params(cfg) * traced["serve_moe_pairs"]


def traced_moe_bytes(cfg: dict, use: dict, traced: dict,
                     itemsize: int = 2) -> float:
    """Each expert that got a real row is read once that round: never
    more than is read, so the share cannot pass 100 %."""
    return float(traced["serve_moe_experts_touched"] * expert_params(cfg)
                 * itemsize)


def traced_head_flops(cfg: dict, use: dict, traced: dict) -> float:
    """The tied head's product for the traced part's output tokens."""
    return 2.0 * embedding_params(cfg) * traced["out_tokens"]


def traced_head_bytes(cfg: dict, use: dict, traced: dict,
                      itemsize: int = 2) -> float:
    """Every round of the traced part reads the table once for the head:
    at 268 MB it is too large to be prefetched under a neighbour's
    products, so its operations' time holds the whole read."""
    return float(traced["steps"] * embedding_params(cfg) * itemsize)
