"""The jitted training step.

Parity target: ref training.py:391-450 `train_step` — zero grad buffers,
microbatched fwd/bwd (the no-pipelining schedule,
ref: schedules.py:213-250), grad reduction across DP, clip + Adam, LR step.
On TPU the whole thing is ONE jitted, GSPMD-sharded function:

- gradient accumulation over microbatches is a `lax.scan` (no Python loop,
  no per-microbatch dispatch);
- the DP grad allreduce (ref: distributed.py:202-230) is emitted by XLA
  from the batch-dim sharding of the loss mean;
- the TP/SP collectives come from the parameter/activation shardings;
- the distributed-optimizer reduce-scatter/all-gather
  (ref: distrib_optimizer.py:522-610) comes from optimizer-state sharding.

Loss averaging over microbatches matches ref training.py:442-448.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from megatron_llm_tpu.analysis.contracts import compile_contract
from megatron_llm_tpu.config import ModelConfig, ParallelConfig, TrainConfig
from megatron_llm_tpu.optimizer.optimizer import OptimizerState, optimizer_step


@compile_contract(
    "train.step",
    max_variants=12,  # num_microbatches buckets per trainer; the trainer
    # passes contract_key=num_microbatches so a microbatch-schedule
    # change that re-traces per step fails loudly at mint time. Raised
    # 8 -> 12 with the ZeRO-1 audit specializations (dp2 replicated /
    # zero1 / zero1-quantized, dp2tp2 zero1) minting in the global
    # bucket alongside the original tp2/dp2tp2 pair.
    collectives={
        "single": frozenset(),
        # pinned on the audit reference config (analysis/audit.py):
        # the TP activation/logit reductions lower to all-reduce and
        # the dp grad reduction folds into the same family. jax 0.9.0's
        # partitioner also serves the vocab-parallel embedding lookup
        # by all-reduce (the old build emitted an all-gather there).
        "tp2": frozenset({"all-reduce"}),
        "dp2tp2": frozenset({"all-reduce"}),
        # pure-dp replicated adam: the dp grad reduction + scalar
        # reductions are the only collectives
        "dp2": frozenset({"all-reduce"}),
        # telemetry-on specialization (ISSUE 13): by contract IDENTICAL
        # to dp2 — span/recorder emission is host bookkeeping outside
        # the jit, so the lowered artifact may not change by one op.
        # The audit lowers this row with a live tracer+recorder around
        # the mint and _check_telemetry_parity pins inventory equality
        # + zero host callbacks vs the telemetry-off dp2 row.
        "dp2+telemetry": frozenset({"all-reduce"}),
        # cost-registry-on specialization (ISSUE 15): same contract as
        # +telemetry — mint-time cost capture READS the artifact
        # (lower + cost/memory analysis) and may not change one op;
        # the audit additionally pins compiled-FLOPs equality vs dp2.
        "dp2+costs": frozenset({"all-reduce"}),
        # ZeRO-1 explicit decomposition (optimizer/zero1.py): the ISSUE
        # 10 contract — per-bucket reduce-scatter of grads, all-gather
        # of updated params, all-reduce for loss/denominator/grad-norm
        # scalars and the replicated residue leaves
        "dp2+zero1": frozenset(
            {"all-reduce", "all-gather", "reduce-scatter"}),
        # quantized grad reduction: the bucket exchange is an int8
        # all-to-all (+ fp32 scales) instead of a reduce-scatter
        "dp2+zero1-quant": frozenset(
            {"all-reduce", "all-gather", "all-to-all"}),
        # overlap scheduling (ISSUE 12): the SAME collective inventory
        # as the eager rows — the backward-interleaved reduce-scatter
        # and the explicit per-bucket param all-gather reorder the
        # schedule, they add no collective kind. The interleaving
        # itself is pinned structurally by the audit's overlap report
        # (analysis/overlap.py): reduce-scatters between the per-group
        # backward loops, not after them.
        "dp2+zero1+overlap": frozenset(
            {"all-reduce", "all-gather", "reduce-scatter"}),
        "dp2+zero1-quant+overlap": frozenset(
            {"all-reduce", "all-gather", "all-to-all"}),
        # mixed-mesh zero1 keeps the GSPMD-spec path: no explicit
        # reduce-scatter op on this CPU pipeline (TPU's SPMD partitioner
        # forms one from the steered all-reduce+slice; not witnessable
        # in the CPU audit — GUIDE.md). The constrained grads/update DO
        # lower to real resharding collectives here: all-to-all and
        # collective-permute move the dp-sharded update shards, the
        # all-gather reassembles params — pinned at the audit config.
        "dp2tp2+zero1": frozenset(
            {"all-reduce", "all-gather", "all-to-all",
             "collective-permute"}),
    },
    tmp_bytes_budget=4 << 20,  # raised 2 -> 4 MiB with the ISSUE 12
    # overlap audit rows: they lower a DEEPER (4-layer, 2-microbatch)
    # reference specialization so the interleave pin has group
    # boundaries to witness — measured 3.6 MiB vs the 2-layer rows'
    # 1.8 MiB; the budget still pins relative regressions at the new
    # config set
    notes="the one fused fwd+bwd+optimizer step; audited on tp2/dp2/"
          "dp2x2 CPU meshes at the tiny reference config, zero1 "
          "(explicit + GSPMD-spec + quantized + overlap-scheduled) "
          "specializations included")
def make_train_step(model, tcfg: TrainConfig, pcfg: ParallelConfig,
                    batch_builder=None):
    """Returns train_step(params, opt_state, batch, lr, wd, rng,
    spike_threshold). `batch_builder` is the trainer's raw-batch
    adapter when one is installed — its presence excludes the explicit
    ZeRO-1 path (the builder's batch leaves/kwargs are not the GPT
    loss_terms surface the shard_map body splats).

    `batch` dict of (num_microbatches, batch, seq) arrays with keys
    tokens / labels / loss_mask (loss_mask optional). When
    num_microbatches == 1 a leading axis of 1 is still expected — keeps one
    trace for both cases.

    fp16 runs scale the loss before backward, unscale the accumulated
    grads, skip the step on overflow, and update the dynamic scale — the
    whole Float16OptimizerWithFloat16Params protocol
    (ref: optimizer/optimizer.py:270-466) inside the one jitted step.

    `spike_threshold` (optional TRACED fp32 scalar, the loss watchdog's
    current median + k*sigma, training/watchdog.py): when given, a step
    whose mean loss is non-finite or above it is SKIPPED in-step —
    params/optimizer untouched, stats["skipped"] set — by riding the
    same found_inf machinery the fp16 scaler uses, so bf16 runs get the
    identical no-host-round-trip skip path. Pass +inf for "no spike
    gating, still skip NaN/inf losses".

    ZeRO-1 (`pcfg.use_distributed_optimizer`, ISSUE 10): on pure-dp
    meshes with a loss_terms model (the GPT family) the gradient
    reduction is the EXPLICIT decomposition (optimizer/zero1.py):
    per-bucket reduce-scatter per microbatch into a dp-sharded fp32
    accumulator (opt-in int8-quantized wire via
    `pcfg.quantized_grad_reduce`), shard-local Adam on the dp-sharded
    m/v, then an all-gather of the updated params — bitwise-identical
    to the replicated path when quantization is off (tests/
    test_zero1.py). On mixed meshes (tp/cp > 1) the GSPMD-spec path
    steers the same layout with sharding constraints (all-reduce +
    slice on CPU; TPU forms reduce-scatter from the pattern).
    """
    from megatron_llm_tpu.optimizer.optimizer import get_grad_scaler
    from megatron_llm_tpu.optimizer.zero1 import (
        build_overlap_plan,
        build_zero1_plan,
        explicit_zero1_supported,
        make_explicit_param_gather,
        make_zero1_grad_fn,
    )
    from megatron_llm_tpu.parallel.mesh import get_context

    num_micro = pcfg.num_microbatches
    scaler = get_grad_scaler(tcfg)
    ctx = get_context()
    use_explicit = explicit_zero1_supported(model, pcfg, ctx,
                                            batch_builder=batch_builder)
    if (pcfg.quantized_grad_reduce or pcfg.overlap_grad_reduce
            or pcfg.overlap_param_gather) and not use_explicit:
        # the mesh-SHAPE combinations are rejected at config
        # construction; what remains here: a model without loss_terms
        # (BERT/T5/biencoder), an installed batch_builder, or a
        # missing/mismatched mesh context — falling back would silently
        # train full-precision under a flag that promises int8
        blocker = (
            "no mesh context installed" if ctx is None
            else f"mesh dp={ctx.dp} != configured "
                 f"dp={pcfg.data_parallel_size}"
            if ctx.dp != pcfg.data_parallel_size
            else "a batch_builder is installed (its batch is not the "
                 "loss_terms surface)" if batch_builder is not None
            else f"{type(model).__name__} exposes no loss_terms "
                 f"(GPT-family models do)")
        flags = ", ".join(
            f for f in ("quantized_grad_reduce", "overlap_grad_reduce",
                        "overlap_param_gather")
            if getattr(pcfg, f))
        raise ValueError(
            f"{flags} require(s) the explicit ZeRO-1 path, "
            f"which this run cannot take: {blocker}. Drop the flag or "
            "remove the blocker (docs/GUIDE.md, 'ZeRO-1 distributed "
            "optimizer')")
    zero1_gspmd = (
        not use_explicit
        and ctx is not None
        and pcfg.use_distributed_optimizer
        and pcfg.data_parallel_size > 1
        and pcfg.pipeline_parallel_size == 1
    )

    def loss_on_micro(params, micro, rng, loss_scale):
        # the batch dict's keys ARE the model-loss kwargs: GPT batches
        # carry tokens/labels/loss_mask/position_ids/attention_mask, BERT
        # adds tokentype_ids/sop_labels, T5 uses encoder/decoder fields —
        # one train step serves every model family.
        loss = model.loss(
            params,
            dropout_rng=rng,
            deterministic=rng is None,
            **micro,
        )
        if loss_scale is not None:
            # ref: MegatronOptimizer.scale_loss optimizer.py:116-120
            return loss * loss_scale, loss
        return loss, loss

    def _zero1_constrain(tree, params):
        """Mixed-mesh GSPMD-spec steering: pin each grad leaf to its
        zero1 spec so the m/v update runs shard-wise (the slice happens
        at the reduction, not after a full materialization)."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        from megatron_llm_tpu.parallel.sharding import (
            param_specs,
            zero1_spec,
        )

        specs = param_specs(model.cfg, params)
        flat_t, treedef = jax.tree.flatten(tree)
        flat_s, _ = jax.tree.flatten(
            specs, is_leaf=lambda x: isinstance(x, P))
        out = [
            jax.lax.with_sharding_constraint(
                t, NamedSharding(
                    ctx.mesh, zero1_spec(s, t.shape,
                                         pcfg.data_parallel_size)))
            for t, s in zip(flat_t, flat_s)
        ]
        return jax.tree.unflatten(treedef, out)

    def _gather_params(new_params, params):
        """The all-gather leg of the decomposition: updated params back
        to their dp-replicated (tp/pp-sharded) serving layout."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        from megatron_llm_tpu.parallel.sharding import param_specs

        specs = param_specs(model.cfg, params)
        flat_p, treedef = jax.tree.flatten(new_params)
        flat_s, _ = jax.tree.flatten(
            specs, is_leaf=lambda x: isinstance(x, P))
        return jax.tree.unflatten(treedef, [
            jax.lax.with_sharding_constraint(t, NamedSharding(ctx.mesh, s))
            for t, s in zip(flat_p, flat_s)
        ])

    def train_step(params, opt_state: OptimizerState, batch, lr, wd,
                   rng=None, spike_threshold=None):
        loss_scale = (
            scaler.scale(opt_state.scaler) if scaler is not None else None
        )
        if use_explicit:
            # --overlap_grad_reduce picks the scheduled plan (layer-
            # group issue points threaded through the backward); the
            # eager Zero1Plan stays the bitwise oracle (ISSUE 12)
            if pcfg.overlap_grad_reduce:
                plan = build_overlap_plan(
                    model.cfg, params, pcfg.data_parallel_size,
                    bucket_mb=pcfg.grad_rs_bucket_mb)
            else:
                plan = build_zero1_plan(
                    model.cfg, params, pcfg.data_parallel_size,
                    bucket_mb=pcfg.grad_rs_bucket_mb)
            zgrad = make_zero1_grad_fn(
                model, ctx, plan, num_micro,
                quantized=pcfg.quantized_grad_reduce)
            grads, loss = zgrad(params, batch, rng, loss_scale)
        elif num_micro == 1:
            grad_fn = jax.value_and_grad(loss_on_micro, has_aux=True)
            micro = jax.tree.map(lambda x: x[0], batch)
            (_, loss), grads = grad_fn(params, micro, rng, loss_scale)
        else:
            grad_fn = jax.value_and_grad(loss_on_micro, has_aux=True)
            zero_grads = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params
            )

            def body(carry, xs):
                acc_g, acc_l = carry
                micro, idx = xs
                mrng = jax.random.fold_in(rng, idx) if rng is not None else None
                (_, l), g = grad_fn(params, micro, mrng, loss_scale)
                acc_g = jax.tree.map(
                    lambda a, b: a + b.astype(jnp.float32), acc_g, g
                )
                return (acc_g, acc_l + l), None

            (grads, loss), _ = jax.lax.scan(
                body,
                (zero_grads, jnp.float32(0.0)),
                (batch, jnp.arange(num_micro)),
            )
            grads = jax.tree.map(lambda g: g / num_micro, grads)
            loss = loss / num_micro

        if zero1_gspmd:
            grads = _zero1_constrain(grads, params)

        if scaler is not None:
            # unscale; the overflow check rides optimizer_step's grad norm
            inv = 1.0 / loss_scale
            grads = jax.tree.map(lambda g: g * inv, grads)

        found_inf = None
        if spike_threshold is not None:
            # loss-level gate: NaN/inf losses AND watchdog spikes skip
            # the update exactly like an fp16 overflow skips it (the
            # grad-norm finiteness check inside optimizer_step still
            # applies on top). The fp16 loss SCALE only reacts to
            # genuine overflow, never to this gate — optimizer_step
            # keeps the two signals separate.
            found_inf = ~jnp.isfinite(loss) | (loss > spike_threshold)
        new_params, new_state, stats = optimizer_step(
            params, grads, opt_state, tcfg, lr, weight_decay=wd,
            found_inf=found_inf, scaler=scaler,
        )
        if use_explicit or zero1_gspmd:
            # the all-gather leg: each dp rank computed only its shard
            # of the update (grads + m/v arrive dp-sharded, so GSPMD
            # keeps the elementwise Adam shard-wise); this constraint
            # reassembles the dp-replicated params for the next forward
            if use_explicit and pcfg.overlap_param_gather:
                # explicit per-bucket all-gathers, first-needed-first
                # and double-buffered (ISSUE 12); the constraint after
                # is a no-op re-stamp of the param_specs shardings
                new_params = make_explicit_param_gather(ctx, plan)(
                    new_params)
            new_params = _gather_params(new_params, params)
        stats["loss"] = loss
        return new_params, new_state, stats

    return train_step


@compile_contract(
    "train.eval_step",
    max_variants=4,  # one per eval flavor a trainer can build: plain,
    # pipelined (pp_eval), batch-builder (generic_eval) — the trainer
    # records those variants under the same contract at their jit sites
    collectives=None,  # pp lowering needs a stage-sharded model; the
    # pipeline suites exercise it — variants/markers still audited
    notes="eval is interval-gated, not per-step; the contract exists "
          "so the jit sites are registry-visible (GR007)")
def make_eval_step(model):
    """ref: evaluate (training.py:754-810) inner step."""

    def eval_step(params, batch):
        loss = model.loss(
            params,
            batch["tokens"],
            batch["labels"],
            loss_mask=batch.get("loss_mask"),
            deterministic=True,
        )
        return loss

    return eval_step
