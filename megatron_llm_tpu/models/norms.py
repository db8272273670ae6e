"""LayerNorm / RMSNorm with fp32 statistics.

Parity targets: ref megatron/model/fused_layer_norm.py —
`MixedFusedLayerNorm` (:64, CUDA kernel with fp32 stats) and pure-python
`RMSNorm` (:125-139, fp32 compute then cast, weight applied after the cast).
On TPU the fused path is a Pallas kernel (ops/rmsnorm.py); these jnp
versions are the always-correct XLA-fused reference implementations.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def rms_norm(x: jnp.ndarray, scale: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    """RMSNorm: fp32 normalize, cast back, then scale (ref: fused_layer_norm.py:133-138)."""
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    normed = (x32 * lax.rsqrt(var + eps)).astype(x.dtype)
    return normed * scale.astype(x.dtype)


def layer_norm(
    x: jnp.ndarray, scale: jnp.ndarray, bias: jnp.ndarray, eps: float = 1e-5
) -> jnp.ndarray:
    """Affine LayerNorm with fp32 statistics (ref: layer_norm_cuda semantics)."""
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mean), axis=-1, keepdims=True)
    normed = ((x32 - mean) * lax.rsqrt(var + eps)).astype(x.dtype)
    return normed * scale.astype(x.dtype) + bias.astype(x.dtype)


@jax.named_scope("norm")
def apply_norm(x: jnp.ndarray, norm_params: dict, cfg) -> jnp.ndarray:
    """Dispatch on config (ref: transformer.py chooses RMSNorm vs LayerNorm).

    use_fused_rmsnorm routes through the Pallas kernel (ops/rmsnorm.py) —
    the analogue of the reference routing norms through apex's fused CUDA
    kernels (fused_layer_norm.py:64)."""
    if cfg.use_rms_norm:
        if getattr(cfg, "use_fused_rmsnorm", False):
            from megatron_llm_tpu.ops.rmsnorm import fused_rms_norm

            return fused_rms_norm(x, norm_params["scale"],
                                  cfg.layernorm_epsilon)
        return rms_norm(x, norm_params["scale"], cfg.layernorm_epsilon)
    return layer_norm(
        x, norm_params["scale"], norm_params["bias"], cfg.layernorm_epsilon
    )
