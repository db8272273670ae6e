"""TPU generation spec table: the ONE source of peak-FLOP/s and HBM
numbers for MFU and roofline math (ISSUE 15).

The pjit-TPUv4 paper (PAPERS.md) makes hardware utilization the
headline metric for exactly this class of system; that requires the
peaks to be a runtime fact. The runtime (trainer goodput ledger, engine
dispatch-overhead gauge, CostRegistry roofline math) reads THIS table.
The yardstick keeps its own peaks and FLOP formulas with the benchmark
(`benchmark/flops.py`): the cells' `train_mfu` / `serve_mfu` come from
there, not from here.

Detection reads `jax.devices()[0].device_kind` (lazy jax import — this
module itself stays import-light for the telemetry package). Because
device_kind strings drift across libtpu releases ("TPU v5 lite" vs
"TPU v5e"), matching is substring-based and an explicit `override`
(CLI `--chip_spec`, engine `chip_spec=`, env `MEGATRON_TPU_CHIPSPEC`)
always wins — on the CPU test harness the override is the only way to
get deterministic MFU/roofline numbers at all. A TPU whose kind is not
in the table is an error, never a default: a utilization against a
guessed peak is worse than none.

Peak numbers are the published per-chip figures:
- v5e: 197 TFLOP/s bf16, 394 TOP/s int8, 819 GB/s HBM, 16 GiB
- v5p: 459 TFLOP/s bf16, 918 TOP/s int8, 2765 GB/s HBM, 95 GiB
- v4:  275 TFLOP/s bf16, 275 TOP/s int8, 1228 GB/s HBM, 32 GiB
"""

from __future__ import annotations

import os
from dataclasses import dataclass, replace
from typing import Mapping, Optional, Tuple

__all__ = [
    "ChipSpec",
    "CHIP_SPECS",
    "detect_chip",
    "train_flops_per_token",
    "decode_flops_per_token",
]


@dataclass(frozen=True)
class ChipSpec:
    """Per-chip peaks for one TPU generation.

    `source` records how this spec was chosen ("detected" or
    "override") so every gauge that cites it can state
    whether the denominator was read from the device or asserted by the
    operator.
    """

    name: str
    peak_flops: Mapping[str, float]  # dtype family -> per-chip FLOP/s
    hbm_bytes_s: float  # per-chip HBM bandwidth
    hbm_bytes: int  # per-chip HBM capacity
    source: str = "table"

    def peak_flops_for(self, dtype: str = "bf16") -> float:
        """Peak FLOP/s for a compute dtype. fp32 maps to the bf16 MXU
        peak (the MXU multiplies bf16 with fp32 accumulation; an fp32
        model's matmuls still ride it on these generations), int8 to
        the int8 peak."""
        d = str(dtype).lower()
        if "int8" in d:
            return self.peak_flops.get("int8", self.peak_flops["bf16"])
        return self.peak_flops["bf16"]

    def label(self) -> str:
        return f"{self.name}:{self.source}"


CHIP_SPECS: Mapping[str, ChipSpec] = {
    "v5e": ChipSpec(
        name="v5e",
        peak_flops={"bf16": 197e12, "int8": 394e12},
        hbm_bytes_s=819e9,
        hbm_bytes=16 * 2**30,
    ),
    "v5p": ChipSpec(
        name="v5p",
        peak_flops={"bf16": 459e12, "int8": 918e12},
        hbm_bytes_s=2765e9,
        hbm_bytes=95 * 2**30,
    ),
    "v4": ChipSpec(
        name="v4",
        peak_flops={"bf16": 275e12, "int8": 275e12},
        hbm_bytes_s=1228e9,
        hbm_bytes=32 * 2**30,
    ),
}

# device_kind substring -> table key, first match wins
_KIND_PATTERNS: Tuple[Tuple[str, str], ...] = (
    ("v5 lite", "v5e"),
    ("v5litepod", "v5e"),
    ("v5e", "v5e"),
    ("v5p", "v5p"),
    ("v4", "v4"),
)

_ENV_OVERRIDE = "MEGATRON_TPU_CHIPSPEC"


def detect_chip(devices=None,
                override: Optional[str] = None) -> Optional[ChipSpec]:
    """Resolve the chip spec: explicit `override` (or the
    MEGATRON_TPU_CHIPSPEC env var) wins, then detection from the device
    kind. Off the TPU (CPU harness, no jax) the answer is None — "no
    credible denominator": callers drop their MFU/roofline gauges. A TPU
    kind that is not in the table raises.

    `devices`: the device subset the caller actually computes on (an
    engine pinned to a replica's devices); None = jax.devices()."""
    override = override or os.environ.get(_ENV_OVERRIDE) or None
    if override:
        key = str(override).lower()
        if key not in CHIP_SPECS:
            raise ValueError(
                f"unknown chip spec {override!r} "
                f"(known: {sorted(CHIP_SPECS)}) — extend the table in "
                f"telemetry/chipspec.py for a new generation")
        return replace(CHIP_SPECS[key], source="override")
    if devices is None:
        try:
            import jax
        except ImportError:
            return None
        devices = jax.devices()
    if not devices or devices[0].platform != "tpu":
        return None
    kind = str(devices[0].device_kind).lower()
    for pat, key in _KIND_PATTERNS:
        if pat in kind:
            return replace(CHIP_SPECS[key], source="detected")
    raise ValueError(
        f"TPU device_kind {devices[0].device_kind!r} is not in the chip "
        f"spec table (known: {sorted(CHIP_SPECS)}); add its published "
        f"peaks to telemetry/chipspec.py or pass an explicit override")


def train_flops_per_token(n_params: int, num_layers: int,
                          hidden_size: int, seq_length: int) -> float:
    """fwd+bwd model FLOPs per trained token: 6*N for the matmuls plus
    causal attention (12*L*h*s per token fwd+bwd with the 1/2 causal
    discount = 6*L*h*s). The numerator of the trainer's live MFU gauge
    (the cells' `train_mfu` uses `benchmark/flops.py`)."""
    return 6.0 * n_params + 6.0 * num_layers * hidden_size * seq_length


def decode_flops_per_token(n_params: int, num_layers: int,
                           hidden_size: int, context: int) -> float:
    """fwd-only model FLOPs for one decoded token at cache length
    `context`: 2*N for the matvecs plus attention reading the cache
    (QK^T + PV = 4*L*h*context). The engine's per-request modeled-FLOPs
    record integrates this over the request's context growth."""
    return 2.0 * n_params + 4.0 * num_layers * hidden_size * context
