"""The yardstick's arithmetic that no family owns: the chip's published
peaks, and the operations and bytes of a dense decoder's work, from
counts that a family (`families/<model_type>.py`) takes from its own
shapes. A family whose work is not a dense decoder's writes its own.

Nothing here is measured and nothing here imports the program. The two
FLOP formulas are copies of `megatron_llm_tpu/telemetry/chipspec.py`
(`train_flops_per_token`, `decode_flops_per_token`), restated over
counts; the original is listed in PERF.md for a later PR to retire.
Recomputed (remat) operations never count.
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


def train_flops_per_token(matmul_params: int, layers: int, attn_width: int,
                          seq: int) -> float:
    """Forward + backward model FLOPs of one trained token: 6 per matrix
    weight a token is multiplied by, plus causal attention (QK^T and PV,
    2 FLOPs a multiply-add, forward and twice that backward = 12 * width
    * seq, halved by the causal mask = 6 * layers * width * seq).
    `attn_width`: query heads x head size."""
    return 6.0 * matmul_params + 6.0 * layers * attn_width * seq


def train_attention_flops(layers: int, attn_width: int, seq: int,
                          tokens: int) -> float:
    """Causal attention's own FLOPs (scores and context, fwd + bwd)."""
    return 6.0 * layers * attn_width * seq * tokens


def train_attention_bytes(layers: int, attn_width: int, kv_width: int,
                          tokens: int, itemsize: int = 2) -> float:
    """Bytes attention has to move at the least, forward and backward:
    read q, k, v and write the context going forward; read q, k, v, the
    context and its cotangent and write dq, dk, dv going back. K and V
    are the un-expanded grouped heads (`kv_width`: both together). The
    score matrix is never counted: a tiled kernel need not write it."""
    q, kv = attn_width, kv_width
    fwd = q + kv + q
    bwd = (q + kv) + 2 * q + (q + kv)
    return float(layers * tokens * (fwd + bwd) * itemsize)


def serve_span_flops(block_params: int, head_params: int, layers: int,
                     attn_width: int, start: int, stop: int,
                     head_tokens: int) -> float:
    """Forward FLOPs the algorithm needs for the tokens at cache
    positions start..stop-1 (the one at `p` attends to p + 1 keys): 2 per
    matrix weight of the blocks, the head (`head_params`) only for the
    `head_tokens` whose logits are needed (every output token and the
    last prompt token), plus 4 * layers * width per key."""
    n = max(stop - start, 0)
    keys = (start + 1 + stop) * n / 2.0  # sum of (p + 1)
    return (2.0 * block_params * n + 2.0 * head_params * head_tokens
            + 4.0 * layers * attn_width * keys)
