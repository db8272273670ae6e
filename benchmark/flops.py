"""The yardstick's arithmetic: chip peaks, parameter counts, and the
operations and bytes a configuration's work needs, from its shapes alone.

Nothing here is measured and nothing here imports the program. The two
FLOP formulas are copies of `megatron_llm_tpu/telemetry/chipspec.py`
(`train_flops_per_token`, `decode_flops_per_token`), restated over the
configuration file's own keys; the original is listed in PERF.md for a
later PR to retire. Recomputed (remat) operations never count.
"""

from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def chip_peaks(device_kind: str) -> dict:
    """Published per-chip peaks for a `device_kind` string. A kind that
    is not in `chips.json` is an error, never a default."""
    with open(os.path.join(HERE, "chips.json")) as f:
        table = json.load(f)
    kind = device_kind.lower()
    for chip in table["chips"]:
        if any(pat in kind for pat in chip["device_kind_contains"]):
            return chip
    raise ValueError(f"device_kind {device_kind!r} is not in chips.json; "
                     "add its published peaks with their source")


def qkv_width(cfg: dict) -> int:
    return cfg["head_dim"] * (cfg["num_attention_heads"]
                              + 2 * cfg["num_kv_heads"])


def attn_width(cfg: dict) -> int:
    """Width of the attention output: query heads x head size."""
    return cfg["num_attention_heads"] * cfg["head_dim"]


def norms_per_layer(cfg: dict) -> int:
    return 2 if cfg["new_decoder_architecture"] else 1


def layer_matmul_params(cfg: dict) -> int:
    """Weights of one block that sit in a matrix multiplication."""
    h, f = cfg["hidden_size"], cfg["ffn_hidden_size"]
    return h * qkv_width(cfg) + attn_width(cfg) * h + 2 * h * f


def layer_params(cfg: dict) -> int:
    return layer_matmul_params(cfg) \
        + 2 * cfg["hidden_size"] * norms_per_layer(cfg)


def embedding_params(cfg: dict) -> int:
    return cfg["vocab_size"] * cfg["hidden_size"]


def n_params(cfg: dict, layers: int) -> int:
    """All parameters with a tied embedding/head counted once."""
    return (layers * layer_params(cfg) + embedding_params(cfg)
            + 2 * cfg["hidden_size"])


def matmul_params(cfg: dict, layers: int, head: bool = True) -> int:
    """Parameters a token is multiplied by: the blocks' matrices and,
    where `head`, the (tied) output head. The embedding lookup itself
    is a gather and costs no operations."""
    return layers * layer_matmul_params(cfg) \
        + (embedding_params(cfg) if head else 0)


def kv_bytes_per_token(cfg: dict, layers: int, itemsize: int = 2) -> int:
    return 2 * layers * cfg["num_kv_heads"] * cfg["head_dim"] * itemsize


def train_flops_per_token(cfg: dict, layers: int, seq: int) -> float:
    """Forward + backward model FLOPs of one trained token: 6 per matrix
    weight, plus causal attention (QK^T and PV, 2 FLOPs a multiply-add,
    forward and twice that backward = 12 * width * seq, halved by the
    causal mask = 6 * layers * width * seq)."""
    return 6.0 * matmul_params(cfg, layers) \
        + 6.0 * layers * attn_width(cfg) * seq


def train_attention_flops(cfg: dict, layers: int, seq: int,
                          tokens: int) -> float:
    """Causal attention's own FLOPs (scores and context, fwd + bwd)."""
    return 6.0 * layers * attn_width(cfg) * seq * tokens


def train_attention_bytes(cfg: dict, layers: int, seq: int, tokens: int,
                          itemsize: int = 2) -> float:
    """Bytes attention has to move at the least, forward and backward:
    read q, k, v and write the context going forward; read q, k, v, the
    context and its cotangent and write dq, dk, dv going back. K and V
    are the un-expanded grouped heads. The score matrix is never counted:
    a tiled kernel need not write it."""
    q = attn_width(cfg)
    kv = 2 * cfg["num_kv_heads"] * cfg["head_dim"]
    fwd = q + kv + q
    bwd = (q + kv) + 2 * q + (q + kv)
    del seq
    return float(layers * tokens * (fwd + bwd) * itemsize)


def serve_token_flops(cfg: dict, layers: int, position: int,
                      needs_head: bool) -> float:
    """Forward FLOPs the algorithm needs for one token at cache position
    `position` (it attends to position + 1 keys): 2 per matrix weight
    (the head only where the token's logits are needed: every output
    token and the last prompt token) plus 4 * layers * width per key."""
    return 2.0 * matmul_params(cfg, layers, head=needs_head) \
        + 4.0 * layers * attn_width(cfg) * (position + 1)


def serve_span_flops(cfg: dict, layers: int, start: int, stop: int,
                     head_tokens: int) -> float:
    """Sum of `serve_token_flops` over cache positions start..stop-1, of
    which `head_tokens` need the head."""
    n = max(stop - start, 0)
    keys = (start + 1 + stop) * n / 2.0  # sum of (p + 1)
    return (2.0 * matmul_params(cfg, layers, head=False) * n
            + 2.0 * embedding_params(cfg) * head_tokens
            + 4.0 * layers * attn_width(cfg) * keys)


def weight_bytes(cfg: dict, layers: int, itemsize: int = 2) -> float:
    """Bytes of the matrices one forward pass has to read once."""
    return float(matmul_params(cfg, layers) * itemsize)
