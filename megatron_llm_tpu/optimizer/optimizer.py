"""Mixed-precision Adam/SGD with clipping, nan-skip and ZeRO-1 sharding.

Parity targets:
- `MegatronOptimizer` / `MixedPrecisionOptimizer` /
  `Float16OptimizerWithFloat16Params` (ref: optimizer/optimizer.py:58-545):
  fp32 master state, global-norm clipping, count-zeros, inf/nan skip.
- apex FusedAdam (adamw-style decoupled weight decay) and FusedSGD
  (ref: optimizer/__init__.py:3-64).
- Distributed (ZeRO-1) optimizer (ref: optimizer/distrib_optimizer.py):
  expressed as sharding of the m/v/master trees over the `data` axis —
  XLA emits the reduce-scatter(grads)/all-gather(params) the reference
  hand-codes (ref: distrib_optimizer.py:522-610).

Functional design: `init_optimizer_state` builds the state pytree,
`optimizer_step` is a pure function (params, grads, state, lr, wd) ->
(params, state, stats) that jits and shards like everything else.
Params are held in fp32 and cast to the compute dtype inside the model
(same numerics as the reference's bf16-params + fp32-master scheme, one
copy fewer).
"""

from __future__ import annotations

from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from megatron_llm_tpu.config import TrainConfig


class OptimizerState(NamedTuple):
    step: jnp.ndarray  # int32 scalar
    m: Any  # first moment (adam) or momentum buffer (sgd); params-shaped
    v: Optional[Any]  # second moment (adam) or None (sgd)
    # fp16 loss-scaler state ({} / scale+trackers dict); None when not fp16
    # (ref: Float16OptimizerWithFloat16Params.grad_scaler optimizer.py:270)
    scaler: Optional[dict] = None


def _tree_cast(tree, dtype):
    return jax.tree.map(lambda x: x.astype(dtype), tree)


def global_grad_norm(grads) -> jnp.ndarray:
    """L2 norm over the full grad pytree in fp32
    (ref: clip_grad_norm_fp32 optimizer/clip_grads.py:16-107; the
    model-parallel allreduce of partial norms is implicit — sharded leaves
    psum under GSPMD)."""
    leaves = jax.tree.leaves(grads)
    return jnp.sqrt(
        sum(jnp.sum(jnp.square(g.astype(jnp.float32))) for g in leaves)
    )


def count_zeros(grads) -> jnp.ndarray:
    """ref: count_zeros_fp32 (optimizer/clip_grads.py:110-150)."""
    leaves = jax.tree.leaves(grads)
    return sum(jnp.sum(g == 0.0) for g in leaves)


def get_grad_scaler(tcfg: TrainConfig):
    """Scaler for fp16 runs, None otherwise (ref: get_megatron_optimizer
    optimizer/__init__.py:68-92: constant when --loss_scale is set, else
    dynamic)."""
    if not tcfg.fp16:
        return None
    from megatron_llm_tpu.optimizer.grad_scaler import (
        ConstantGradScaler,
        DynamicGradScaler,
    )

    if tcfg.loss_scale is not None:
        return ConstantGradScaler(tcfg.loss_scale)
    return DynamicGradScaler(
        initial_scale=tcfg.initial_loss_scale,
        min_scale=tcfg.min_loss_scale,
        growth_interval=tcfg.loss_scale_window,
        hysteresis=tcfg.hysteresis,
    )


def init_optimizer_state(params, tcfg: TrainConfig) -> OptimizerState:
    zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
    scaler = get_grad_scaler(tcfg)
    scaler_state = scaler.init_state() if scaler is not None else None
    if tcfg.optimizer == "adam":
        return OptimizerState(
            step=jnp.zeros((), jnp.int32),
            m=zeros,
            v=jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params),
            scaler=scaler_state,
        )
    elif tcfg.optimizer == "sgd":
        return OptimizerState(step=jnp.zeros((), jnp.int32), m=zeros, v=None,
                              scaler=scaler_state)
    raise ValueError(f"unknown optimizer {tcfg.optimizer}")


@jax.named_scope("optimizer")
def optimizer_step(
    params,
    grads,
    state: OptimizerState,
    tcfg: TrainConfig,
    lr: jnp.ndarray,
    weight_decay: Optional[jnp.ndarray] = None,
    found_inf: Optional[jnp.ndarray] = None,
    scaler=None,
) -> Tuple[Any, OptimizerState, dict]:
    """One update. Mirrors MixedPrecisionOptimizer.step
    (ref: optimizer.py:407-466): unscaled fp32 grads in, global inf/nan
    check, clip by global norm, adamw/sgd update, skipped iteration leaves
    params+state untouched (ref: optimizer.py:418-432).

    When `scaler` is passed (fp16), the grads must arrive ALREADY
    unscaled; the overflow check reuses this function's grad norm (an
    overflowed scaled grad is still inf/nan after unscaling, so one norm
    pass serves both the skip and the scaler update — the reference's
    separate _unscale_main_grads_and_check_for_nan pass, optimizer.py:
    340-365, is folded in here). The returned state carries the updated
    scale; stats gains "loss_scale".
    """
    wd = tcfg.weight_decay if weight_decay is None else weight_decay
    with jax.named_scope("clip"):
        grads = _tree_cast(grads, jnp.float32)

        grad_norm = global_grad_norm(grads)
        finite = jnp.isfinite(grad_norm)
        if found_inf is not None:
            # external skip gate (the loss watchdog's spike/NaN flag): skips
            # the UPDATE only. It must not feed the scaler below — a
            # finite-gradient loss spike is not an fp16 overflow, and
            # backing the scale off for it would ratchet toward underflow.
            finite = finite & ~found_inf

        new_scaler_state = state.scaler
        if scaler is not None:
            # the scaler reacts to GENUINE overflow (non-finite grads) only
            new_scaler_state = scaler.update(state.scaler,
                                             ~jnp.isfinite(grad_norm))

        # clip (ref: clip_grads.py:83-107)
        if tcfg.clip_grad > 0.0:
            clip_coeff = jnp.minimum(tcfg.clip_grad / (grad_norm + 1e-6), 1.0)
            grads = jax.tree.map(lambda g: g * clip_coeff, grads)

    step = state.step + 1
    # `optimizer/adam`, or `optimizer/sgd`
    with jax.named_scope(tcfg.optimizer):
        if tcfg.optimizer == "adam":
            b1, b2, eps = tcfg.adam_beta1, tcfg.adam_beta2, tcfg.adam_eps
            bc1 = 1.0 - b1 ** step.astype(jnp.float32)
            bc2 = 1.0 - b2 ** step.astype(jnp.float32)

            new_m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, state.m, grads)
            new_v = jax.tree.map(
                lambda v, g: b2 * v + (1 - b2) * jnp.square(g), state.v, grads
            )

            def upd(p, m, v):
                # adamw: decoupled weight decay (apex FusedAdam adam_w_mode);
                # 1D params (norm scales, biases) are never decayed
                # (ref: get_param_groups optimizer/__init__.py:28-53)
                u = (m / bc1) / (jnp.sqrt(v / bc2) + eps)
                p32 = p.astype(jnp.float32)
                wd_p = wd if p.ndim >= 2 else 0.0
                return (p32 - lr * (u + wd_p * p32)).astype(p.dtype)

            new_params = jax.tree.map(upd, params, new_m, new_v)
            new_state = OptimizerState(step=step, m=new_m, v=new_v,
                                       scaler=state.scaler)
        else:  # sgd with momentum
            mom = tcfg.sgd_momentum

            def upd_buf(b, g, p):
                wd_p = wd if p.ndim >= 2 else 0.0
                return mom * b + g + wd_p * p.astype(jnp.float32)

            new_m = jax.tree.map(upd_buf, state.m, grads, params)
            new_params = jax.tree.map(
                lambda p, b: (p.astype(jnp.float32) - lr * b).astype(p.dtype),
                params,
                new_m,
            )
            new_state = OptimizerState(step=step, m=new_m, v=state.v,
                                       scaler=state.scaler)

        # skipped iteration on inf/nan (ref: optimizer.py:418-432)
        select = lambda new, old: jax.tree.map(
            lambda n, o: jnp.where(finite, n, o), new, old
        )
        new_params = select(new_params, params)
        new_state = OptimizerState(
            step=jnp.where(finite, step, state.step),
            m=select(new_state.m, state.m),
            v=select(new_state.v, state.v) if state.v is not None else None,
            scaler=new_scaler_state,
        )

    stats = {
        "grad_norm": grad_norm,
        "skipped": (~finite).astype(jnp.int32),
    }
    if scaler is not None:
        stats["loss_scale"] = scaler.scale(state.scaler)
    # ref training_log field set (training.py:452-626): zeros-in-grad and
    # params L2 norm, computed in-step so they ride the same dispatch
    if tcfg.log_num_zeros_in_grad:
        stats["num_zeros"] = count_zeros(grads)
    if tcfg.log_params_norm:
        stats["params_norm"] = global_grad_norm(new_params)
    return new_params, new_state, stats


def get_optimizer(tcfg: TrainConfig):
    """Convenience pair (ref: get_megatron_optimizer optimizer/__init__.py:64)."""
    return (
        lambda params: init_optimizer_state(params, tcfg),
        lambda params, grads, state, lr, **kw: optimizer_step(
            params, grads, state, tcfg, lr, **kw
        ),
    )
