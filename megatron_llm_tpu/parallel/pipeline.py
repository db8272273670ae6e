"""Pipeline parallelism — shard_map over the `stage` axis + ppermute.

Parity target: ref megatron/schedules.py + p2p_communication.py. The
reference drives 1F1B by hand: per-rank Python loops issuing batched
NCCL isend/irecv (p2p_communication.py:204-231), explicit
deallocate_output_tensor/custom_backward memory hacks (schedules.py:36-88),
and a separate embedding-grad allreduce group between first and last stage
(parallel_state.py:172-199, optimizer.py:203-229).

The TPU design collapses all of that into one differentiable program:

- the stacked layer axis (L, ...) is sharded over `stage`, so each stage
  materialises only its L/pp layers;
- a `lax.scan` over num_micro + pp - 1 ticks rotates activations with
  `lax.ppermute` (the XLA collective-permute that rides ICI);
- reverse-mode AD through the scan yields the backward pipeline (transpose
  of ppermute is the reverse ppermute) — no hand-written backward schedule;
- parameters that enter the shard_map replicated over `stage` (embedding,
  final norm, lm head) get their gradients psum'd across stages by the
  shard_map transpose automatically — which IS the reference's tied
  embedding-grad sync, for free;
- `data`/`model` axes stay in GSPMD "auto" mode inside the region, so TP/SP
  sharding of each stage's compute keeps working unchanged.

Schedule note: AD produces a GPipe-style schedule (all-forward then
all-backward per scan transpose) rather than interleaved 1F1B — but the
thing 1F1B exists to bound (per-stage live activation memory,
schedules.py:606-722) is bounded here differently and harder: by default
every tick body is `jax.checkpoint`ed, so the backward keeps ONLY the
(b, s, h) boundary carry per tick and recomputes stage internals.
`ParallelConfig.pipeline_remat` — the shared named-savepoint policy
vocabulary of models/remat.py ("tick"/"full", "selective", "dots"/
"save_dots", "offload", "none") — trades that memory floor back for
1F1B-class FLOPs when per-stage HBM allows — measured in
docs/PIPELINE_MEMORY.md ("dots" hits the FLOP floor at intermediate
memory). 1F1B keeps <=pp
in-flight stashes of a stage's FULL internal activations (~tens of b*s*h
per layer chunk); this design keeps (num_micro + pp - 1) single-boundary
tensors. For any real depth/width the boundary stash is the smaller
footprint, and raising num_micro to shrink the GPipe bubble stays cheap —
which also removes the need for interleaved/vpp scheduling (that exists to
shrink the bubble when 1F1B memory forbids more microbatches).

MEASURED: docs/PIPELINE_MEMORY.md (tools/pipeline_memory_table.py) —
marginal memory per added microbatch is exactly one boundary carry
(1.0 MB measured vs 1.0 MB modeled at b2/s512/h256), vs ~16 boundary
carries per in-flight microbatch under a 1F1B full stash at the same
width.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from megatron_llm_tpu.analysis.contracts import compile_contract
from megatron_llm_tpu.models.norms import apply_norm
from megatron_llm_tpu.models.rope import precompute_rope
from megatron_llm_tpu.models.transformer import transformer_stack
from megatron_llm_tpu.models.language_model import embed_tokens, lm_logits
from megatron_llm_tpu.parallel.cross_entropy import cross_entropy
from megatron_llm_tpu.parallel.mesh import (
    CONTEXT_AXIS,
    STAGE_AXIS,
    ParallelContext,
)


def pipeline_param_specs(cfg, params: dict) -> dict:
    """Param specs with the layer axis sharded over `stage` (the analogue of
    the reference assigning layer ranges to pp ranks,
    ref: transformer.py:845-895 `_get_num_layers` + offset math)."""
    from megatron_llm_tpu.parallel.sharding import param_specs

    specs = param_specs(cfg, params)

    def add_stage(spec: P) -> P:
        parts = list(spec) or [None]
        assert parts[0] is None, "layer axis already sharded"
        parts[0] = STAGE_AXIS
        return P(*parts)

    specs["layers"] = jax.tree.map(
        add_stage, specs["layers"], is_leaf=lambda x: isinstance(x, P)
    )
    return specs


def _boundary_dtype(cfg):
    """Values whose shard_map/pcast transposes emit copy-all-reduces must
    not be bf16 on CPU — XLA-CPU's AllReducePromotion pass crashes cloning
    a copy-bodied all-reduce ("Invalid binary instruction opcode copy").
    TPU keeps bf16 so inter-stage ppermute traffic stays half-width."""
    return jnp.float32 if jax.default_backend() == "cpu" else cfg.compute_dtype


def _mark_varying(cp, aux, rope, batch_ops, layers_local):
    """Mark every operand stage-(and context-)varying up front, while still
    fp32/int32. If a replicated fp32 param is first cast to bf16 and only
    then implicitly pvary'd (by meeting a varying value), the pvary is a
    bf16 copy-bodied all-reduce and XLA-CPU aborts (see _boundary_dtype);
    pcast-then-cast sidesteps it and is a free no-op marker on TPU.

    batch operands enter context-SHARDED when cp>1 (already context-
    varying) — only the stage axis still needs marking on those; stage-
    sharded layer weights are the mirror case (context-invariant)."""
    manual_axes = (STAGE_AXIS, CONTEXT_AXIS) if cp > 1 else (STAGE_AXIS,)
    pcast = jax.lax.pcast
    pv = lambda x: pcast(x, manual_axes, to="varying")  # noqa: E731
    pv_s = lambda x: pcast(x, (STAGE_AXIS,), to="varying")  # noqa: E731
    aux = jax.tree.map(pv, aux)
    rope = pv(rope)
    batch_ops = tuple(map(pv_s if cp > 1 else pv, batch_ops))
    if cp > 1:
        layers_local = jax.tree.map(
            lambda x: pcast(x, (CONTEXT_AXIS,), to="varying"),
            layers_local,
        )
    return manual_axes, aux, rope, batch_ops, layers_local


def _stage_body(cfg, layers_local, hidden, rope_table, mask, position_ids,
                dropout_rng, deterministic, stage, num_stages):
    """Run this stage's layer chunk. layer indices offset by stage
    (ref: vpp/stage offset math transformer.py:1015-1045)."""
    layers_per_stage = jax.tree.leaves(layers_local)[0].shape[0]
    out, _ = transformer_stack(
        layers_local, cfg, hidden, rope_table, mask, position_ids,
        dropout_rng, deterministic,
        layer_offset=stage * layers_per_stage,
    )
    return out


def make_pipelined_loss_fn(model, pcfg, ctx: ParallelContext):
    """loss(params, batch, rng) with the transformer stack pipelined over
    `stage`. `batch` arrays are (num_micro, b, s[, ...]).

    Replaces the reference's forward_backward_pipelining_* schedules
    (schedules.py:253-722): here one jitted function runs the whole
    embed -> stack -> head/CE pipeline INSIDE a scan-over-ticks, and
    jax.grad of it is the pipelined backward.

    Memory design (the reason the reference hand-schedules 1F1B,
    schedules.py:606-722):
    - embedding runs in-tick, so no (num_micro, b, s, h) input buffer —
      only the int32 token batch enters the region;
    - the last stage computes final-norm + logits + CE in-tick under a
      `lax.cond` and banks two SCALARS per microbatch — no
      (num_micro, b, s, V) logits or (num_micro, b, s, h) output buffer;
    - each tick body is `jax.checkpoint`ed: backward keeps only the
      (b, s, h) boundary carry per tick and recomputes stage internals,
      so peak live activations are ticks x b*s*h boundary values — far
      below 1F1B's pp in-flight FULL-chunk stashes for real configs.

    Loss averaging matches the reference: mean over microbatches of each
    microbatch's masked-mean loss (training.py:442-448), not the global
    token-weighted mean.
    """
    cfg = model.cfg
    mesh = ctx.mesh
    num_stages = pcfg.pipeline_parallel_size
    # Async tick dispatch (--async_pipeline_dispatch, ISSUE 12): the
    # stage-ring ppermute decouples from the lockstep tick. The lockstep
    # body is compute -> permute -> carry: the permute's result feeds
    # the very next tick's compute, so XLA must serialize wire and MXU.
    # Async double-buffers the carry: tick T's body issues the permute
    # of tick T-1's OUTPUT (`fly`), which nothing in tick T's compute
    # consumes — the collective-permute and the stage compute are
    # data-independent inside one scan body, exactly what the
    # latency-hiding scheduler needs to overlap them (the MPMD paper's
    # async point-to-point dispatch, still inside the scan-transpose
    # backward — AD of the delayed carry is the same delay in reverse,
    # so the backward ring overlaps too). The price is schedule depth:
    # each hop takes 2 ticks, so fill/drain grows from pp-1 to
    # 2(pp-1) ticks — at num_micro >> pp the bubble cost is small and
    # the per-tick wire hides; at tiny num_micro lockstep wins
    # (docs/GUIDE.md "Collective overlap scheduling"). Per-microbatch
    # math is IDENTICAL (deterministic runs bitwise vs lockstep,
    # tests/test_overlap.py); with dropout the per-tick rng keys map to
    # different ticks — a different but equally valid stream, like the
    # zero1 per-rank dropout note.
    async_dispatch = getattr(pcfg, "async_pipeline_dispatch", False)
    hop = 2 if async_dispatch else 1
    # Context parallelism inside the pipeline: `context` joins `stage` as a
    # manual axis of the SAME shard_map (Shardy rejects a nested manual
    # region whose operands mix free `stage` with manual `context`), the
    # seq dim of every batch operand is context-sharded, and attention runs
    # the ring over the manual axis (models/attention._ring_dispatch: as
    # is for XLA hops, with the remaining axes made manual round it when
    # the hop is the Mosaic flash kernel).
    cp = ctx.cp
    if cp > 1:
        assert cfg.attention_dropout == 0.0, (
            "cp>1 pipelined training: ring attention has no dropout path"
        )

    def loss_fn(params, batch, dropout_rng=None):
        tokens = batch["tokens"]  # (num_micro, b, s)
        labels = batch["labels"]
        loss_mask = batch.get("loss_mask")
        position_ids = batch.get("position_ids")
        num_micro, b, s = tokens.shape
        deterministic = dropout_rng is None

        has_rope = cfg.position_embedding_type == "rotary"
        if has_rope:
            rope_table = precompute_rope(
                cfg.head_dim, cfg.max_position_embeddings, cfg.rope_theta,
                cfg.rope_scaling_factor,
            )
        else:
            rope_table = jnp.zeros((1,), jnp.float32)  # placeholder operand

        if loss_mask is None:
            loss_mask = jnp.ones((num_micro, b, s), jnp.float32)
        else:
            loss_mask = loss_mask.astype(jnp.float32)
        if position_ids is None:
            position_ids = jnp.broadcast_to(
                jnp.arange(s, dtype=jnp.int32)[None, None], (num_micro, b, s)
            )

        # Everything the in-tick embed + head need, entering the region
        # stage-replicated; the shard_map transpose psums their grads over
        # `stage` — which IS the reference's tied embedding-grad allreduce
        # (parallel_state.py:172-199) for free.
        aux_params = {
            "embedding": params["embedding"],
            "final_norm": params["final_norm"],
        }
        if not cfg.tie_embed_logits:
            aux_params["lm_head"] = params["lm_head"]

        boundary_dtype = _boundary_dtype(cfg)

        def stack_shard(layers_local, aux, toks, lbls, lmask, pids, rope):
            # layers_local: (L/pp, ...); toks/lbls/pids: (num_micro, b, s)
            from megatron_llm_tpu.parallel.mesh import manual_region

            with manual_region():
                return _stack_shard_body(
                    layers_local, aux, toks, lbls, lmask, pids, rope
                )

        def _stack_shard_body(layers_local, aux, toks, lbls, lmask, pids,
                              rope):
            stage = jax.lax.axis_index(STAGE_AXIS)
            # async dispatch: each hop takes `hop` ticks (one in-flight
            # slot per boundary), so fill/drain stretches accordingly
            total = num_micro + hop * (num_stages - 1)
            manual_axes, aux, rope, (toks, lbls, lmask, pids), \
                layers_local = _mark_varying(
                    cp, aux, rope, (toks, lbls, lmask, pids), layers_local
                )
            rope_t = rope if has_rope else None
            # decorrelate dropout draws across context shards (each shard
            # holds different global positions)
            rng_base = dropout_rng
            if dropout_rng is not None and cp > 1:
                rng_base = jax.random.fold_in(
                    dropout_rng, jax.lax.axis_index(CONTEXT_AXIS)
                )

            def head_losses(hidden, lbl_t, lm_t):
                # returns LOCAL (this context shard's) sums; the context
                # psum happens outside the banking lax.cond — a collective
                # inside that cond aborts XLA-CPU (same restructure as the
                # score path's unconditional ppermute; ADVICE r4)
                h = apply_norm(
                    hidden.astype(cfg.compute_dtype), aux["final_norm"], cfg
                )
                logits = lm_logits(aux, cfg, h)
                losses = cross_entropy(logits, lbl_t)
                return jnp.sum(losses * lm_t), jnp.sum(lm_t)

            def tick(carry, t):
                if async_dispatch:
                    state, fly, sums, denoms = carry
                else:
                    state, sums, denoms = carry
                m_in = jnp.clip(t, 0, num_micro - 1)
                toks_t = jax.lax.dynamic_index_in_dim(toks, m_in, 0, False)
                pids_t = jax.lax.dynamic_index_in_dim(pids, m_in, 0, False)
                rng_e = rng_t = None
                if rng_base is not None:
                    rng_e = jax.random.fold_in(rng_base, m_in)
                    rng_t = jax.random.fold_in(
                        rng_base, num_micro + 1 + t * num_stages
                    )
                # in-tick embed: every stage computes the (cheap) gather,
                # only stage 0 consumes it — no (num_micro,b,s,h) buffer
                emb = embed_tokens(aux, cfg, toks_t, pids_t, rng_e,
                                   deterministic).astype(boundary_dtype)
                inp = jnp.where(stage == 0, emb, state).astype(
                    cfg.compute_dtype
                )
                # pids_t carries GLOBAL positions (context-sharded when
                # cp>1): RoPE inside the stage must rotate each seq shard
                # by its global angle, and --reset_position_ids streams
                # carry non-arange positions even at cp=1
                out = _stage_body(cfg, layers_local, inp, rope_t, None,
                                  pids_t, rng_t, deterministic, stage,
                                  num_stages)
                out = out.astype(boundary_dtype)

                # last stage runs head + CE for the microbatch leaving the
                # pipe this tick; other stages skip the head FLOPs entirely
                m_out = jnp.clip(t - hop * (num_stages - 1), 0,
                                 num_micro - 1)
                valid = (stage == num_stages - 1) & \
                    (t >= hop * (num_stages - 1))
                lbl_t = jax.lax.dynamic_index_in_dim(lbls, m_out, 0, False)
                lm_t = jax.lax.dynamic_index_in_dim(lmask, m_out, 0, False)
                zero = jax.lax.pcast(
                    jnp.float32(0.0), manual_axes, to="varying"
                )
                sum_t, den_t = jax.lax.cond(
                    valid,
                    lambda h: head_losses(h, lbl_t, lm_t),
                    lambda h: (zero, zero),
                    out,
                )
                if cp > 1:
                    # each context shard holds s/cp tokens of the micro-
                    # batch; `valid` is uniform across context shards at a
                    # given stage, so psum of the selected values equals
                    # the old psum-inside-head_losses — without a
                    # collective inside the cond
                    sum_t = jax.lax.psum(sum_t, CONTEXT_AXIS)
                    den_t = jax.lax.psum(den_t, CONTEXT_AXIS)
                sums = jax.lax.dynamic_update_index_in_dim(
                    sums,
                    jnp.where(
                        valid, sum_t,
                        jax.lax.dynamic_index_in_dim(sums, m_out, 0, False),
                    ),
                    m_out, 0,
                )
                denoms = jax.lax.dynamic_update_index_in_dim(
                    denoms,
                    jnp.where(
                        valid, den_t,
                        jax.lax.dynamic_index_in_dim(denoms, m_out, 0, False),
                    ),
                    m_out, 0,
                )
                # rotate stage s -> s+1 (ref: send_forward
                # p2p_communication.py:292; backward of this ppermute is the
                # reverse rotation = send_backward :311)
                ring = [(i, i + 1) for i in range(num_stages - 1)]
                if async_dispatch:
                    # the DELAYED send: permute last tick's output
                    # (`fly`), which this tick's compute never touches —
                    # wire and MXU are independent inside the body, so
                    # the scheduler can run them concurrently; `out`
                    # rides the carry to be sent next tick
                    arrived = jax.lax.ppermute(fly, STAGE_AXIS, ring)
                    return (arrived, out, sums, denoms), None
                state = jax.lax.ppermute(out, STAGE_AXIS, ring)
                return (state, sums, denoms), None

            # Backward memory policy (ParallelConfig.pipeline_remat) —
            # the SAME named-savepoint vocabulary as the single-mesh stack
            # (models/remat.py): "tick"/"full" keeps only the tick-boundary
            # carries and recomputes stage internals (the TPU answer to
            # deallocate_output_tensor + 1F1B's bounded stash,
            # schedules.py:36-88); "selective" keeps the named matmul
            # outputs; "dots"/"save_dots" keeps every dot (1F1B-class
            # FLOPs, intermediate memory); "offload" parks the selective
            # set in pinned host memory; "none" keeps everything
            # (1F1B-class FLOPs, what the reference's no-remat 1F1B pays
            # in memory). Measured: docs/PIPELINE_MEMORY.md.
            from megatron_llm_tpu.models.remat import remat_wrap

            tick = remat_wrap(tick, pcfg.resolved_pipeline_remat)

            # carries become stage-varying inside the loop; mark the zero
            # initials as varying so the scan carry types are stable
            state = jax.lax.pcast(
                jnp.zeros((b, s // cp, cfg.hidden_size), boundary_dtype),
                manual_axes, to="varying",
            )
            sums0 = jax.lax.pcast(
                jnp.zeros((num_micro,), jnp.float32), (STAGE_AXIS,),
                to="varying",
            )
            denoms0 = jax.lax.pcast(
                jnp.zeros((num_micro,), jnp.float32), (STAGE_AXIS,),
                to="varying",
            )
            if async_dispatch:
                fly0 = jax.lax.pcast(
                    jnp.zeros((b, s // cp, cfg.hidden_size),
                              boundary_dtype),
                    manual_axes, to="varying",
                )
                (_, _, sums, denoms), _ = jax.lax.scan(
                    tick, (state, fly0, sums0, denoms0),
                    jnp.arange(total)
                )
            else:
                (_, sums, denoms), _ = jax.lax.scan(
                    tick, (state, sums0, denoms0), jnp.arange(total)
                )
            # leading stage axis: only the last stage's row is meaningful;
            # the caller slices [-1], one scalar-row transfer from the last
            # stage (the analogue of the last->first stage loss broadcast,
            # ref: text_generation/communication.py:111).
            return sums[None], denoms[None]

        # (num_micro, b, s) batch operands: seq context-sharded when cp>1
        bspec = P(None, None, CONTEXT_AXIS) if cp > 1 else P()
        stack_mapped = jax.shard_map(
            stack_shard,
            mesh=mesh,
            in_specs=(P(STAGE_AXIS), P(), bspec, bspec, bspec, bspec, P()),
            out_specs=(P(STAGE_AXIS), P(STAGE_AXIS)),
            axis_names={STAGE_AXIS, CONTEXT_AXIS} if cp > 1
            else {STAGE_AXIS},
        )
        sums, denoms = stack_mapped(
            params["layers"], aux_params, tokens.astype(jnp.int32),
            labels.astype(jnp.int32), loss_mask,
            position_ids.astype(jnp.int32), rope_table,
        )
        sums, denoms = sums[-1], denoms[-1]  # (num_micro,)
        # reference averaging: mean of per-microbatch masked means
        # (training.py:442-448)
        return jnp.mean(sums / jnp.maximum(denoms, 1.0))

    return loss_fn


def make_pipelined_score_fn(model, pcfg, ctx: ParallelContext):
    """Forward-only pipelined scoring on a stage-sharded mesh: tokens
    (num_micro, b, s) -> per-token target log-probs (num_micro, b, s-1),
    lp[..., i] = log P(tokens[..., i+1] | tokens[..., :i+1]).

    The pp>1 inference path the reference runs as micro-batched pipelined
    forward (ref: text_generation/forward_step.py:61-73,153-204 +
    score_and_return_on_first_stage generation.py:20-86): stage-sharded
    params stay in place, microbatches stream through GPipe ticks, and the
    last stage banks each leaving microbatch's target log-probs. No AD, no
    remat — this is the serving-time scorer for perplexity/reranking from
    a pp-trained checkpoint without resharding it.

    For token-by-token DECODE from a pp-trained checkpoint use
    `reshard_params_for_inference` + the normal generation engine (KV
    caches and a while_loop don't pipeline; the reference keeps its decode
    non-pipelined on the last stage too, generation.py:89-286).
    """
    cfg = model.cfg
    mesh = ctx.mesh
    num_stages = pcfg.pipeline_parallel_size
    cp = ctx.cp

    def score_fn(params, tokens):
        tokens = tokens.astype(jnp.int32)
        num_micro, b, s = tokens.shape

        has_rope = cfg.position_embedding_type == "rotary"
        if has_rope:
            rope_table = precompute_rope(
                cfg.head_dim, cfg.max_position_embeddings, cfg.rope_theta,
                cfg.rope_scaling_factor,
            )
        else:
            rope_table = jnp.zeros((1,), jnp.float32)
        position_ids = jnp.broadcast_to(
            jnp.arange(s, dtype=jnp.int32)[None, None], (num_micro, b, s)
        )

        aux_params = {
            "embedding": params["embedding"],
            "final_norm": params["final_norm"],
        }
        if not cfg.tie_embed_logits:
            aux_params["lm_head"] = params["lm_head"]

        boundary_dtype = _boundary_dtype(cfg)

        def stack_shard(layers_local, aux, toks, pids, rope):
            from megatron_llm_tpu.parallel.mesh import manual_region

            with manual_region():
                return _score_shard_body(layers_local, aux, toks, pids, rope)

        def _score_shard_body(layers_local, aux, toks, pids, rope):
            stage = jax.lax.axis_index(STAGE_AXIS)
            total = num_micro + num_stages - 1
            manual_axes, aux, rope, (toks, pids), layers_local = \
                _mark_varying(cp, aux, rope, (toks, pids), layers_local)
            rope_t = rope if has_rope else None
            s_loc = s // cp

            # targets = tokens shifted left by one; under cp the last local
            # slot needs the NEXT context shard's first token. Computed once
            # here, UNconditionally — a collective inside the banking
            # lax.cond aborts XLA-CPU. The final GLOBAL position has no
            # target (wraparound garbage); the caller drops it.
            if cp > 1:
                first_next = jax.lax.ppermute(
                    toks[:, :, :1], CONTEXT_AXIS,
                    [((i + 1) % cp, i) for i in range(cp)],
                )
                tgts = jnp.concatenate([toks[:, :, 1:], first_next],
                                       axis=-1)
            else:
                tgts = jnp.roll(toks, -1, axis=-1)

            def head_logprobs(hidden, tgt_t):
                h = apply_norm(
                    hidden.astype(cfg.compute_dtype), aux["final_norm"], cfg
                )
                logits = lm_logits(aux, cfg, h)
                lp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
                # position i holds log P(target at i+1)
                return jnp.take_along_axis(
                    lp, tgt_t[..., None], axis=-1
                ).squeeze(-1)  # (b, s_loc)

            def tick(carry, t):
                state, banked = carry
                m_in = jnp.clip(t, 0, num_micro - 1)
                toks_t = jax.lax.dynamic_index_in_dim(toks, m_in, 0, False)
                pids_t = jax.lax.dynamic_index_in_dim(pids, m_in, 0, False)
                emb = embed_tokens(aux, cfg, toks_t, pids_t, None,
                                   True).astype(boundary_dtype)
                inp = jnp.where(stage == 0, emb, state).astype(
                    cfg.compute_dtype
                )
                out = _stage_body(cfg, layers_local, inp, rope_t, None,
                                  pids_t, None, True, stage, num_stages)
                out = out.astype(boundary_dtype)

                m_out = jnp.clip(t - (num_stages - 1), 0, num_micro - 1)
                valid = (stage == num_stages - 1) & (t >= num_stages - 1)
                tgt_t = jax.lax.dynamic_index_in_dim(tgts, m_out, 0, False)
                zero = jax.lax.pcast(
                    jnp.zeros((b, s_loc), jnp.float32), manual_axes,
                    to="varying",
                )
                lp_t = jax.lax.cond(
                    valid,
                    lambda h: head_logprobs(h, tgt_t),
                    lambda h: zero,
                    out,
                )
                banked = jax.lax.dynamic_update_index_in_dim(
                    banked,
                    jnp.where(
                        valid, lp_t,
                        jax.lax.dynamic_index_in_dim(banked, m_out, 0,
                                                     False),
                    ),
                    m_out, 0,
                )
                state = jax.lax.ppermute(
                    out, STAGE_AXIS,
                    [(i, i + 1) for i in range(num_stages - 1)],
                )
                return (state, banked), None

            state = jax.lax.pcast(
                jnp.zeros((b, s_loc, cfg.hidden_size), boundary_dtype),
                manual_axes, to="varying",
            )
            banked0 = jax.lax.pcast(
                jnp.zeros((num_micro, b, s_loc), jnp.float32), manual_axes,
                to="varying",
            )
            (_, banked), _ = jax.lax.scan(
                tick, (state, banked0), jnp.arange(total)
            )
            return banked[None]

        bspec = P(None, None, CONTEXT_AXIS) if cp > 1 else P()
        out_bspec = P(STAGE_AXIS, None, None, CONTEXT_AXIS) if cp > 1 \
            else P(STAGE_AXIS)
        stack_mapped = jax.shard_map(
            stack_shard,
            mesh=mesh,
            in_specs=(P(STAGE_AXIS), P(), bspec, bspec, P()),
            out_specs=out_bspec,
            axis_names={STAGE_AXIS, CONTEXT_AXIS} if cp > 1
            else {STAGE_AXIS},
        )
        banked = stack_mapped(
            params["layers"], aux_params, tokens,
            position_ids, rope_table,
        )
        # only the last stage's bank is real; drop the final position
        # (no target)
        return banked[-1][:, :, :-1]

    return score_fn


def make_pipelined_decode_fn(model, pcfg, ctx: ParallelContext, *,
                             prefill_len: int, max_len: int,
                             num_micro: int | None = None,
                             greedy: bool = True, top_k: int = 0,
                             top_p: float = 0.0, temperature: float = 1.0,
                             vocab_size: int | None = None,
                             termination_id: int | None = None,
                             use_eod_for_early_termination: bool = True,
                             return_log_probs: bool = False):
    """Token-by-token KV-cached decode ON the stage-sharded mesh — no
    `reshard_params_for_inference` pp x param-memory blowup (VERDICT r4
    #4; ref: the pipelined inference forwards of
    text_generation/forward_step.py:153-204).

    Round-robin schedule: the batch is split into `num_micro` (default pp)
    groups; at every tick each stage advances a DIFFERENT group by one
    token, boundaries rotate by `lax.ppermute`, and the last stage samples
    the next token and sends it back to stage 0 — with num_micro == pp the
    returned token arrives exactly when stage 0 next serves that group, so
    steady-state has zero bubble. Each stage holds ONLY its layers' KV
    cache shard: per-device cache AND param memory stay 1/pp.

    Mechanics mirrored from the training/score pipelines: collectives stay
    OUT of lax.conds (XLA-CPU), operands are pcast stage-varying up front,
    and fill/drain garbage ticks write their cache columns into a scratch
    region past max_len (offset redirect) so no per-tick buffer select is
    needed.

    Decode ticks (s == 1) stream each layer's stacked-cache slice through
    the Pallas decode-attention kernel ("tgd" layout, in place — no
    transpose) whenever the scratch-tailed cache length is kernel-
    eligible (models/attention.py routes there; exact-match vs the
    single-mesh engine in tests/test_pp_inference.py), so pp-mesh serving
    gets the same HBM-line-rate attention as the unrolled decode path.
    Prefill chunks (s > 1) keep the batched-GEMM path.

    Returns decode(params, tokens (b, max_len), lengths (b,), rng) ->
    (tokens, gen_lengths, log_probs|None), semantics matching
    `generation.generate_tokens` (greedy path exact).
    """
    from megatron_llm_tpu.inference.generation import select_next_token

    cfg = model.cfg
    mesh = ctx.mesh
    pp = pcfg.pipeline_parallel_size
    assert ctx.cp == 1, "pipelined decode: cp axis unsupported"
    nm = num_micro or pp
    assert nm >= pp, "num_micro must be >= pp (token return latency)"
    steps = max_len - prefill_len - 1  # decode rounds after the seed
    assert steps >= 0
    cache_T = max_len + max(prefill_len, 1)  # scratch tail for garbage ticks
    has_rope = cfg.position_embedding_type == "rotary"

    def decode_fn(params, tokens, lengths, rng=None):
        tokens = tokens.astype(jnp.int32)
        b, _ = tokens.shape
        assert b % nm == 0, (b, nm)
        b_m = b // nm
        toks_g = tokens.reshape(nm, b_m, max_len)
        lens_g = lengths.astype(jnp.int32).reshape(nm, b_m)
        if rng is None:
            rng = jax.random.key(0)
        rng = jax.random.key_data(rng).astype(jnp.uint32)  # pcast-able

        if has_rope:
            rope_table = precompute_rope(
                cfg.head_dim, cfg.max_position_embeddings, cfg.rope_theta,
                cfg.rope_scaling_factor,
            )
        else:
            rope_table = jnp.zeros((1,), jnp.float32)

        aux_params = {
            "embedding": params["embedding"],
            "final_norm": params["final_norm"],
        }
        if not cfg.tie_embed_logits:
            aux_params["lm_head"] = params["lm_head"]

        boundary_dtype = _boundary_dtype(cfg)

        def shard(layers_local, aux, toks, lens, rng_u):
            from megatron_llm_tpu.parallel.mesh import manual_region

            with manual_region():
                return _decode_shard_body(layers_local, aux, toks, lens,
                                          rng_u)

        def _decode_shard_body(layers_local, aux, toks, lens, rng_u):
            stage = jax.lax.axis_index(STAGE_AXIS)
            L_loc = jax.tree.leaves(layers_local)[0].shape[0]
            _, aux, rope, (toks, lens, rng_u), _ = _mark_varying(
                1, aux, rope_table, (toks, lens, rng_u), layers_local
            )
            rope_t = rope if has_rope else None
            base_rng = jax.random.wrap_key_data(rng_u)
            pv = lambda x: jax.lax.pcast(  # noqa: E731
                x, (STAGE_AXIS,), to="varying"
            )

            def head(hidden):  # (b_m, s, h) -> (b_m, s, V) fp32
                h = apply_norm(
                    hidden.astype(cfg.compute_dtype), aux["final_norm"], cfg
                )
                return lm_logits(aux, cfg, h).astype(jnp.float32)

            def run_stage(inp, kc, vc, m, off):
                """One stage pass of (b_m, s) tokens at cache offset
                `off` for microbatch m; returns (out, kc, vc)."""
                kc_m = jax.lax.dynamic_index_in_dim(kc, m, 1, False)
                vc_m = jax.lax.dynamic_index_in_dim(vc, m, 1, False)
                out, new_caches = transformer_stack(
                    layers_local, cfg, inp, rope_t, None, None, None, True,
                    kv_caches={"k": kc_m, "v": vc_m, "offset": off},
                    layer_offset=stage * L_loc,
                )
                kc = jax.lax.dynamic_update_index_in_dim(
                    kc, new_caches["k"], m, 1
                )
                vc = jax.lax.dynamic_update_index_in_dim(
                    vc, new_caches["v"], m, 1
                )
                return out, kc, vc

            kshape = (L_loc, nm, b_m, cache_T, cfg.num_query_groups,
                      cfg.head_dim)
            kc = pv(jnp.zeros(kshape, cfg.compute_dtype))
            vc = pv(jnp.zeros(kshape, cfg.compute_dtype))

            # ---- prefill: GPipe ticks over full-prefix chunks ----------
            pids_prefix = jnp.arange(prefill_len, dtype=jnp.int32)[None]

            def prefill_tick(carry, t):
                state, kc, vc, seeds, lps, toks_b = carry
                m = jnp.clip(t - stage, 0, nm - 1)
                valid = (t >= stage) & (t - stage <= nm - 1)
                chunk = jax.lax.dynamic_index_in_dim(toks, m, 0, False)
                chunk = chunk[:, :prefill_len]
                emb = embed_tokens(aux, cfg, chunk, pids_prefix, None,
                                   True).astype(boundary_dtype)
                inp = jnp.where(stage == 0, emb, state).astype(
                    cfg.compute_dtype
                )
                # garbage ticks redirect their cache writes past max_len
                off = jnp.where(valid, 0, max_len)
                out, kc, vc = run_stage(inp, kc, vc, m, off)
                out = out.astype(boundary_dtype)

                valid_last = (stage == pp - 1) & (t >= pp - 1) & \
                    (t - (pp - 1) <= nm - 1)
                m_out = jnp.clip(t - (pp - 1), 0, nm - 1)
                step_rng = jax.random.fold_in(base_rng, m_out)
                toks_out = jax.lax.dynamic_index_in_dim(toks, m_out, 0,
                                                        False)

                # the head (final norm + full-vocab logits) runs ONLY on
                # the last stage, same lax.cond pattern as the training
                # tick's head_losses — no collectives inside the cond
                def last_stage_work(h):
                    if return_log_probs:
                        logits = head(h)  # (b_m, prefill, V)
                        lp_all = jax.nn.log_softmax(logits, axis=-1)
                        lp_pref = jnp.take_along_axis(
                            lp_all[:, :-1],
                            toks_out[:, 1:prefill_len, None], axis=-1,
                        ).squeeze(-1)  # (b_m, prefill-1)
                        last_logits = logits[:, -1]
                    else:
                        lp_pref = pv(jnp.zeros((b_m, prefill_len - 1),
                                               jnp.float32))
                        last_logits = head(h[:, -1:])[:, 0]
                    # seed token at position prefill_len (teacher-forced
                    # if the row's prompt extends past the prefix)
                    sample = select_next_token(
                        last_logits, toks_out[:, prefill_len - 1],
                        step_rng, jnp.float32(top_p),
                        greedy=greedy, top_k=top_k, top_p=top_p,
                        temperature=temperature, vocab_size=vocab_size,
                    )
                    if prefill_len < max_len:
                        started = jax.lax.dynamic_index_in_dim(
                            lens, m_out, 0, False) <= prefill_len
                        chosen = jnp.where(started, sample,
                                           toks_out[:, prefill_len])
                    else:
                        chosen = sample
                    lp_seed = jnp.take_along_axis(
                        jax.nn.log_softmax(last_logits, -1),
                        chosen[:, None], axis=-1,
                    ).squeeze(-1) if return_log_probs else \
                        pv(jnp.zeros((b_m,), jnp.float32))
                    return chosen, lp_pref, lp_seed

                def skip_stage_work(h):
                    return (pv(jnp.zeros((b_m,), jnp.int32)),
                            pv(jnp.zeros((b_m, prefill_len - 1),
                                         jnp.float32)),
                            pv(jnp.zeros((b_m,), jnp.float32)))

                chosen, lp_pref, lp_seed = jax.lax.cond(
                    valid_last, last_stage_work, skip_stage_work, out
                )
                if return_log_probs:
                    lps = jnp.where(
                        valid_last,
                        jax.lax.dynamic_update_slice(
                            lps, lp_pref[None], (m_out, 0, 0)
                        ),
                        lps,
                    )
                    lps = jnp.where(
                        valid_last,
                        jax.lax.dynamic_update_slice(
                            lps, lp_seed[None, :, None],
                            (m_out, 0, prefill_len - 1),
                        ),
                        lps,
                    )
                seeds = jnp.where(
                    valid_last,
                    jax.lax.dynamic_update_index_in_dim(seeds, chosen,
                                                        m_out, 0),
                    seeds,
                )
                if prefill_len < max_len:
                    toks_b = jnp.where(
                        valid_last,
                        jax.lax.dynamic_update_slice(
                            toks_b, chosen[None, :, None],
                            (m_out, 0, prefill_len),
                        ),
                        toks_b,
                    )
                state = jax.lax.ppermute(
                    out, STAGE_AXIS,
                    [(i, i + 1) for i in range(pp - 1)],
                )
                return (state, kc, vc, seeds, lps, toks_b), None

            state0 = pv(jnp.zeros((b_m, prefill_len, cfg.hidden_size),
                                  boundary_dtype))
            seeds0 = pv(jnp.zeros((nm, b_m), jnp.int32))
            lps0 = pv(jnp.zeros((nm, b_m, max_len - 1), jnp.float32))
            (_, kc, vc, seeds, lps, toks), _ = jax.lax.scan(
                prefill_tick, (state0, kc, vc, seeds0, lps0, toks),
                jnp.arange(nm + pp - 1),
            )
            # ship the seed tokens to stage 0's feed buffer
            next_tok = jax.lax.ppermute(seeds, STAGE_AXIS, [(pp - 1, 0)])

            # ---- decode: round-robin single-token ticks ----------------
            offsets0 = pv(jnp.full((nm,), prefill_len, jnp.int32))
            state0 = pv(jnp.zeros((b_m, 1, cfg.hidden_size),
                                  boundary_dtype))
            # the SEED token (sampled at position prefill_len during
            # prefill) gets the same eod bookkeeping generate_tokens
            # applies to every generated position; seeds are only real on
            # the last stage — the same authority the updates below keep
            if termination_id is not None:
                seed_done = (seeds == termination_id) & \
                    (lens <= prefill_len)
                done0 = seed_done
                glens0 = jnp.where(seed_done, prefill_len + 1, max_len)
            else:
                done0 = pv(jnp.zeros((nm, b_m), bool))
                glens0 = pv(jnp.full((nm, b_m), max_len, jnp.int32))
            total = steps * nm + pp - 1

            def cond(carry):
                t = carry[0]
                all_done = carry[-1]
                keep = t < total
                if termination_id is not None and \
                        use_eod_for_early_termination:
                    keep &= ~all_done
                return keep

            def body(carry):
                (t, state, kc, vc, next_tok, toks_b, lps, done, glens,
                 offsets, _) = carry
                m = jnp.mod(t - stage, nm)
                valid = (t >= stage) & (t - stage < steps * nm)
                off = jax.lax.dynamic_index_in_dim(offsets, m, 0, False)
                tok_in = jax.lax.dynamic_index_in_dim(next_tok, m, 0,
                                                      False)
                emb = embed_tokens(aux, cfg, tok_in[:, None], off[None,
                                   None], None, True).astype(boundary_dtype)
                inp = jnp.where(stage == 0, emb, state).astype(
                    cfg.compute_dtype
                )
                off_w = jnp.where(valid, off, max_len)
                out, kc, vc = run_stage(inp, kc, vc, m, off_w)
                out = out.astype(boundary_dtype)
                offsets = jnp.where(
                    valid,
                    jax.lax.dynamic_update_index_in_dim(offsets, off + 1,
                                                        m, 0),
                    offsets,
                )

                # last stage: sample position off+1's token for its group
                m_l = jnp.mod(t - (pp - 1), nm)
                valid_last = (stage == pp - 1) & (t >= pp - 1) & \
                    (t - (pp - 1) < steps * nm)
                pos = jax.lax.dynamic_index_in_dim(
                    offsets, m_l, 0, False)  # off+1 (just incremented)
                step_rng = jax.random.fold_in(
                    base_rng, pos * nm + m_l
                )
                toks_m = jax.lax.dynamic_index_in_dim(toks_b, m_l, 0,
                                                      False)
                lens_m = jax.lax.dynamic_index_in_dim(lens, m_l, 0, False)
                started = lens_m <= pos

                # full-vocab head + sampling under lax.cond: only the
                # last stage pays the h x V matvec per tick
                def last_stage_work(h):
                    logits = head(h)[:, 0]  # (b_m, V)
                    prev = jnp.take_along_axis(
                        toks_m,
                        jnp.broadcast_to(jnp.maximum(pos - 1, 0),
                                         (b_m,))[:, None],
                        axis=1,
                    ).squeeze(1)
                    sample = select_next_token(
                        logits, prev, step_rng, jnp.float32(top_p),
                        greedy=greedy, top_k=top_k, top_p=top_p,
                        temperature=temperature, vocab_size=vocab_size,
                    )
                    prompt_tok = jnp.take_along_axis(
                        toks_m,
                        jnp.broadcast_to(jnp.minimum(pos, max_len - 1),
                                         (b_m,))[:, None],
                        axis=1,
                    ).squeeze(1)
                    chosen = jnp.where(started, sample, prompt_tok)
                    lp_t = jnp.take_along_axis(
                        jax.nn.log_softmax(logits, -1), chosen[:, None],
                        axis=-1,
                    ).squeeze(-1) if return_log_probs else \
                        pv(jnp.zeros((b_m,), jnp.float32))
                    return chosen, lp_t

                def skip_stage_work(h):
                    return (pv(jnp.zeros((b_m,), jnp.int32)),
                            pv(jnp.zeros((b_m,), jnp.float32)))

                chosen, lp_t = jax.lax.cond(
                    valid_last, last_stage_work, skip_stage_work, out
                )
                new_toks_m = jax.vmap(
                    lambda row, c: jax.lax.dynamic_update_index_in_dim(
                        row, c, jnp.minimum(pos, max_len - 1), 0
                    )
                )(toks_m, chosen)
                toks_b = jnp.where(
                    valid_last,
                    jax.lax.dynamic_update_index_in_dim(
                        toks_b, new_toks_m, m_l, 0
                    ),
                    toks_b,
                )
                if return_log_probs:
                    lps_m = jax.lax.dynamic_index_in_dim(lps, m_l, 0,
                                                         False)
                    new_lps_m = jax.vmap(
                        lambda row, v: jax.lax.dynamic_update_index_in_dim(
                            row, v, jnp.minimum(pos - 1, max_len - 2), 0
                        )
                    )(lps_m, lp_t)
                    lps = jnp.where(
                        valid_last,
                        jax.lax.dynamic_update_index_in_dim(
                            lps, new_lps_m, m_l, 0
                        ),
                        lps,
                    )
                if termination_id is not None:
                    done_m = jax.lax.dynamic_index_in_dim(done, m_l, 0,
                                                          False)
                    glens_m = jax.lax.dynamic_index_in_dim(glens, m_l, 0,
                                                           False)
                    done_token = (chosen == termination_id) & started
                    just = done_token & ~done_m
                    glens_m = jnp.where(just, pos + 1, glens_m)
                    done_m = done_m | done_token
                    done = jnp.where(
                        valid_last,
                        jax.lax.dynamic_update_index_in_dim(done, done_m,
                                                            m_l, 0),
                        done,
                    )
                    glens = jnp.where(
                        valid_last,
                        jax.lax.dynamic_update_index_in_dim(glens, glens_m,
                                                            m_l, 0),
                        glens,
                    )
                    all_done_local = jnp.where(
                        stage == pp - 1, jnp.all(done), False
                    )
                else:
                    all_done_local = jnp.asarray(False)
                # collectives OUTSIDE any cond (XLA-CPU rule)
                all_done = jax.lax.psum(
                    all_done_local.astype(jnp.int32), STAGE_AXIS
                ) > 0
                chosen_bc = jnp.where(valid_last, chosen, 0)
                tok_back = jax.lax.ppermute(chosen_bc, STAGE_AXIS,
                                            [(pp - 1, 0)])
                next_tok = jnp.where(
                    (stage == 0) & (t >= pp - 1),
                    jax.lax.dynamic_update_index_in_dim(
                        next_tok, tok_back, m_l, 0
                    ),
                    next_tok,
                )
                state = jax.lax.ppermute(
                    out, STAGE_AXIS,
                    [(i, i + 1) for i in range(pp - 1)],
                )
                return (t + 1, state, kc, vc, next_tok, toks_b, lps, done,
                        glens, offsets, all_done)

            # all_done comes out of a psum — stage-INVARIANT, so its init
            # must be too
            carry = (jnp.int32(0), state0, kc, vc, next_tok, toks, lps,
                     done0, glens0, offsets0, jnp.asarray(False))
            carry = jax.lax.while_loop(cond, body, carry)
            toks_b, lps, glens = carry[5], carry[6], carry[8]
            return toks_b[None], lps[None], glens[None]

        mapped = jax.shard_map(
            shard,
            mesh=mesh,
            in_specs=(P(STAGE_AXIS), P(), P(), P(), P()),
            out_specs=(P(STAGE_AXIS), P(STAGE_AXIS), P(STAGE_AXIS)),
            axis_names={STAGE_AXIS},
        )
        toks_out, lps_out, glens_out = mapped(
            params["layers"], aux_params, toks_g, lens_g, rng
        )
        # the last stage's bank is authoritative
        out_tokens = toks_out[-1].reshape(b, max_len)
        out_lens = glens_out[-1].reshape(b)
        out_lps = lps_out[-1].reshape(b, max_len - 1) \
            if return_log_probs else None
        return out_tokens, out_lens, out_lps

    return decode_fn


def reshard_params_for_inference(params, ctx: ParallelContext, cfg):
    """Reshard a stage-sharded param tree to stage-REPLICATED (dp/tp/cp
    sharding kept) so the non-pipelined generation engine can serve it on
    the same mesh. The orbax checkpoint layer already reshards across mesh
    shapes on restore; this is the in-memory equivalent for params that
    are live on a pp>1 mesh. Costs pp x the per-device param memory —
    serving a model too big for that needs the pipelined scorer above or a
    smaller serving mesh."""
    from jax.sharding import NamedSharding

    from megatron_llm_tpu.parallel.sharding import param_specs

    specs = param_specs(cfg, params)
    sh = jax.tree.map(lambda sp: NamedSharding(ctx.mesh, sp), specs,
                      is_leaf=lambda x: isinstance(x, P))
    return jax.device_put(params, sh)


@compile_contract(
    "train.pipeline_step",
    max_variants=8,  # num_microbatches buckets per trainer, like
    # train.step — the trainer passes contract_key=num_microbatches
    collectives=None,  # the per-tick stage ring needs a stage-sharded
    # model to lower (collective-permute + tp all-reduces); the pp
    # suites (test_pipeline, test_sp_memory) exercise the lowering —
    # variants and markers are still contract-audited
    notes="the pp>1 per-tick train step; pipeline_remat policies ride "
          "inside one variant (policy is baked at build time)")
def make_pipelined_train_step(model, tcfg, pcfg, ctx: ParallelContext):
    """train_step(params, opt_state, batch, lr, wd, rng) for pp > 1
    (ref: train_step + get_forward_backward_func, training.py:391-431).
    fp16 loss scaling follows the same protocol as the non-pipelined step
    (see training/train_step.py)."""
    from megatron_llm_tpu.optimizer.optimizer import (
        get_grad_scaler,
        optimizer_step,
    )

    loss_fn = make_pipelined_loss_fn(model, pcfg, ctx)
    scaler = get_grad_scaler(tcfg)

    def train_step(params, opt_state, batch, lr, wd, rng=None,
                   spike_threshold=None):
        loss_scale = (
            scaler.scale(opt_state.scaler) if scaler is not None else None
        )

        def scaled_loss(p, b, r):
            loss = loss_fn(p, b, r)
            if loss_scale is not None:
                return loss * loss_scale, loss
            return loss, loss

        (_, loss), grads = jax.value_and_grad(scaled_loss, has_aux=True)(
            params, batch, rng
        )
        if scaler is not None:
            # unscale; the overflow check rides optimizer_step's grad norm
            inv = 1.0 / loss_scale
            grads = jax.tree.map(lambda g: g * inv, grads)
        found_inf = None
        if spike_threshold is not None:
            # the loss watchdog's in-step skip gate — same contract as
            # the non-pipelined step (training/train_step.py): skips
            # the update; never drives the fp16 scale
            found_inf = ~jnp.isfinite(loss) | (loss > spike_threshold)
        params, opt_state, stats = optimizer_step(
            params, grads, opt_state, tcfg, lr, weight_decay=wd,
            found_inf=found_inf, scaler=scaler,
        )
        stats["loss"] = loss
        return params, opt_state, stats

    return train_step
