"""ZeRO-1 distributed optimizer: the explicit reduce-scatter/all-gather
decomposition (ISSUE 10).

The sharding SPECS for the dp-sharded optimizer state have existed since
the first multichip PR (parallel/sharding.py zero1_spec /
optimizer_state_specs) — but specs alone only tell GSPMD where the
m/v/master leaves LIVE. Nothing guaranteed the gradient reduction
actually lowered to the reduce-scatter(grads) -> shard-local Adam ->
all-gather(params) decomposition the reference hand-codes
(ref: distrib_optimizer.py:522-610) and the llama7b-v5p64 forecast
assumes; on the CPU audit meshes GSPMD provably emits
all-reduce + dynamic-slice instead (no reduce-scatter op at all).

This module is the explicit path. `make_zero1_grad_fn` wraps the
fwd/bwd in a `shard_map` manual over the WHOLE mesh (legal only when
every non-`data` axis has size 1 — pure-dp meshes, where the dp
gradient reduction is the entire collective story), so each dp rank
computes its LOCAL microbatch gradients and the reduction is issued by
us, not inferred by GSPMD:

- grads are packed into size-targeted BUCKETS (`grad_rs_bucket_mb`,
  the analogue of the reference's distributed.py grad buffers): each
  leaf is moved so its zero1 axis (parallel/sharding.py zero1_axis —
  the ONE divisibility rule) leads, reshaped to (dp, n) so row r IS
  rank r's shard, and concatenated;
- one `lax.psum_scatter` per bucket per microbatch: the reduce-scatter
  is issued as the backward of each microbatch releases its grads, so
  XLA's latency-hiding scheduler can overlap bucket k's collective
  with the next microbatch's compute, and the fp32 grad ACCUMULATOR
  lives sharded (1/dp of the replicated path's accumulation memory);
- `--overlap_grad_reduce` (ISSUE 12) moves the issue points INSIDE
  each microbatch's backward: the forward runs in layer groups saving
  per-group vjps (model.loss_pieces), the backward walks them
  last-to-first, and each group's bucket collective fires at its group
  boundary and is consumed one group later (OverlapPlan /
  _overlap_one_micro — the double buffer that gives every collective a
  layer group of independent compute). `--overlap_param_gather` makes
  the all-gather leg explicit per-bucket, first-needed-first
  (make_explicit_param_gather). The eager sweep stays the bitwise
  oracle (tests/test_overlap.py);
- leaves with no dp-divisible free axis (norm scales — the documented
  replicated residue of zero1_spec) ride a plain psum, exactly the
  leaves whose optimizer state stays replicated;
- opt-in (`quantized_grad_reduce`), the wire format drops to int8:
  each bucket row is chunk-quantized (symmetric round-to-nearest,
  per-chunk fp32 scales — ops/quantization.quantize_rows, the SAME
  convention as the int8 KV pages), exchanged with `lax.all_to_all`,
  and the dp partials are dequantized and accumulated in fp32
  (EQuARX, PAPERS.md: cheap symmetric scheme + fp32 accumulation).
  ~3.9x less gradient wire traffic; accuracy is MEASURED, not assumed
  (tests/test_zero1.py bounds the loss-trajectory drift).

Numerics contract (pinned by tests/test_zero1.py): with quantization
OFF, the explicit path is BITWISE identical to the replicated-Adam
trainer — per-step losses, grad norms, final params and moments — at
dp2/dp4 in fp32 and bf16, with fp16 scaler and loss-watchdog skip
semantics intact. The local loss mirrors the replicated program's
exact op chain (model.loss_terms numerator/denominator, division by
the psum'd denominator AFTER the local numerator reduction), and
psum/psum_scatter accumulate partials in the same rank order, so no
term is rounded differently.

Mixed meshes (tp/pp/cp > 1) keep the GSPMD-spec path: the explicit
body is written for a FULLY manual region (it skips the activation
sharding constraints tp/sp steer by, parallel/mesh.manual_region), and
pp's train step is its own stage-manual program. Partial-manual
shard_map itself lowers on jax 0.9.0 (the pipeline runs stage-manual
with data/model auto), so a data-manual, model-auto variant is open
work rather than a partitioner limit. There the m/v
sharding still buys the 1/dp state memory and train_step steers the
update shard-wise + gathers params explicitly; on TPU the SPMD
partitioner's reduce-scatter creation applies to the steered
all-reduce+slice, which the CPU audit cannot witness (docs/GUIDE.md
"ZeRO-1 distributed optimizer").
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from megatron_llm_tpu.parallel.mesh import (
    DATA_AXIS,
    ParallelContext,
    manual_region,
)
from megatron_llm_tpu.parallel.sharding import param_specs, zero1_axis

# quantized-reduction chunk: one fp32 scale per this many gradient
# elements (2 KiB of fp32 wire per scale -> 0.2% scale overhead). Small
# enough that one outlier poisons 512 elements, not a whole bucket row.
QUANT_CHUNK = 512


def _bucket_wire_bytes(elems: int, dp: int, quantized: bool) -> int:
    """Wire bytes for one bucket of `elems` fp32 gradient elements:
    fp32, or int8 payload + one fp32 scale per QUANT_CHUNK chunk per
    rank row (the _quantized_bucket_reduce_scatter format)."""
    if not quantized:
        return elems * 4
    n_chunks = -(-elems // (dp * QUANT_CHUNK)) * dp
    return elems * 1 + n_chunks * 4


@dataclass(frozen=True)
class Zero1Plan:
    """The per-leaf reduce-scatter layout + bucket assignment for one
    param tree shape. Built once per train-step trace (pure shape math,
    no arrays held)."""

    dp: int
    # per flat leaf: the axis sharded over `data`, or None (psum residue)
    leaf_axes: Tuple[Optional[int], ...]
    # bucket -> list of flat-leaf indices (only sharded leaves)
    buckets: Tuple[Tuple[int, ...], ...]
    # flat-leaf indices with leaf_axes None
    residue: Tuple[int, ...]
    # per flat leaf: global shape (for the (dp, n) reshape bookkeeping)
    shapes: Tuple[Tuple[int, ...], ...]

    def shard_shape(self, i: int) -> Tuple[int, ...]:
        """Leaf i's per-rank shard shape (full shape for residue)."""
        k = self.leaf_axes[i]
        if k is None:
            return self.shapes[i]
        s = list(self.shapes[i])
        s[k] //= self.dp
        return tuple(s)

    def bucket_comm_bytes(self, quantized: bool) -> Tuple[int, ...]:
        """Per-bucket wire bytes for ONE reduce (one entry per issue
        point) — what bucket sizing is tuned against the overlap window
        with (step-0 gauge `grad-rs-bucket-bytes`, ISSUE 12)."""
        import numpy as np

        out = []
        for b in self.buckets:
            elems = sum(int(np.prod(self.shapes[i])) for i in b)
            out.append(_bucket_wire_bytes(elems, self.dp, quantized))
        return tuple(out)

    def comm_bytes_per_reduce(self, quantized: bool) -> int:
        """Logical gradient bytes on the dp wire for ONE reduce of the
        full tree (per microbatch): fp32 for buckets + residue, or
        int8 + per-chunk fp32 scales for buckets (residue stays fp32)."""
        import numpy as np

        res = sum(int(np.prod(self.shapes[i])) for i in self.residue)
        return sum(self.bucket_comm_bytes(quantized)) + res * 4


def build_zero1_plan(cfg, params_tmpl, dp: int,
                     bucket_mb: float = 4.0) -> Zero1Plan:
    """Partition the grad tree into size-targeted reduce-scatter buckets
    (greedy fill in tree-flatten order, like the reference's
    distributed.py buffer packing). `bucket_mb` targets the fp32 bucket
    payload; a leaf larger than the target gets its own bucket."""
    flat, _ = jax.tree.flatten(params_tmpl)
    specs, _ = jax.tree.flatten(param_specs(cfg, params_tmpl),
                                is_leaf=lambda x: isinstance(x, P))
    target = max(int(bucket_mb * (1 << 20)), 1)
    leaf_axes: List[Optional[int]] = []
    buckets: List[List[int]] = []
    residue: List[int] = []
    cur: List[int] = []
    cur_bytes = 0
    for i, (leaf, spec) in enumerate(zip(flat, specs)):
        k = zero1_axis(spec, leaf.shape, dp)
        leaf_axes.append(k)
        if k is None:
            residue.append(i)
            continue
        nbytes = int(leaf.size) * 4  # grads reduce in fp32
        if cur and cur_bytes + nbytes > target:
            buckets.append(cur)
            cur, cur_bytes = [], 0
        cur.append(i)
        cur_bytes += nbytes
        if cur_bytes >= target:
            buckets.append(cur)
            cur, cur_bytes = [], 0
    if cur:
        buckets.append(cur)
    return Zero1Plan(
        dp=dp,
        leaf_axes=tuple(leaf_axes),
        buckets=tuple(tuple(b) for b in buckets),
        residue=tuple(residue),
        shapes=tuple(tuple(l.shape) for l in flat),
    )


@dataclass(frozen=True)
class OverlapPlan:
    """The backward-interleaved variant of Zero1Plan (ISSUE 12,
    --overlap_grad_reduce): the stacked-layer subtree is cut into
    contiguous layer GROUPS sized so one group's fp32 grads hit the
    `grad_rs_bucket_mb` target, and each group is one reduce-scatter
    ISSUE POINT — its collective fires the moment the group's backward
    releases its cotangents, and is consumed only after the next
    group's backward is emitted (double-buffered).

    Layer leaves shard on a WITHIN-layer axis (zero1_axis skip_leading
    — see parallel/sharding.py for why the layer axis cannot carry the
    shard under per-group scatter); a layer leaf with no dp-divisible
    within-layer axis joins the replicated residue. The non-layer
    leaves (embedding, final norm, lm head) keep the eager plan's
    greedy buckets (`aux`), issued after the embedding's backward —
    the last cotangents to materialize."""

    dp: int
    num_layers: int
    # contiguous (lo, hi) layer ranges, FORWARD order; the backward
    # issues them hi-to-lo
    groups: Tuple[Tuple[int, int], ...]
    # per flat leaf of the "layers" subtree: the within-layer zero1
    # axis, or None (residue); shapes are the FULL stacked shapes
    layer_axes: Tuple[Optional[int], ...]
    layer_shapes: Tuple[Tuple[int, ...], ...]
    # the non-layer subtree's eager plan (greedy buckets + residue)
    aux: Zero1Plan

    def layer_shard_shape(self, i: int) -> Tuple[int, ...]:
        k = self.layer_axes[i]
        if k is None:
            return self.layer_shapes[i]
        s = list(self.layer_shapes[i])
        s[k] //= self.dp
        return tuple(s)

    def _group_elems(self, lo: int, hi: int) -> int:
        import numpy as np

        return sum(
            (hi - lo) * int(np.prod(self.layer_shapes[i][1:]))
            for i, k in enumerate(self.layer_axes) if k is not None)

    def bucket_comm_bytes(self, quantized: bool) -> Tuple[int, ...]:
        """Per-issue-point wire bytes: one entry per layer group
        (forward order) followed by the aux buckets."""
        groups = tuple(
            _bucket_wire_bytes(self._group_elems(lo, hi), self.dp,
                               quantized)
            for lo, hi in self.groups)
        return groups + self.aux.bucket_comm_bytes(quantized)

    def comm_bytes_per_reduce(self, quantized: bool) -> int:
        """Same semantics as Zero1Plan.comm_bytes_per_reduce: one full
        reduce of the tree. The total equals the eager plan's whenever
        the residue sets agree — regrouping moves no bytes."""
        import numpy as np

        res = sum(
            int(np.prod(self.layer_shapes[i]))
            for i, k in enumerate(self.layer_axes) if k is None)
        res += sum(int(np.prod(self.aux.shapes[i]))
                   for i in self.aux.residue)
        return sum(
            _bucket_wire_bytes(self._group_elems(lo, hi), self.dp,
                               quantized)
            for lo, hi in self.groups
        ) + sum(self.aux.bucket_comm_bytes(quantized)) + res * 4


def split_aux_layers(params: dict) -> Tuple[dict, Any]:
    """(non-layer subtree, stacked-layer subtree) of a GPT param dict —
    the split the overlap plan/grad-fn/gather all share."""
    return {k: v for k, v in params.items() if k != "layers"}, \
        params["layers"]


def build_overlap_plan(cfg, params_tmpl, dp: int,
                       bucket_mb: float = 4.0) -> OverlapPlan:
    """Cut the layer stack into reduce-scatter groups of ~`bucket_mb`
    MB of fp32 grads each, and plan the aux subtree with the eager
    greedy packing.

    Groups are AT LEAST 2 LAYERS (the trailing remainder merges into
    its neighbor): a 1-layer group's stack is a trip-count-1 lax.scan,
    which XLA's while-loop simplifier unrolls into straight-line code
    and then re-fuses with its surroundings — FMA formation inside the
    inlined layer differs from the rolled scan body's, and the fp32
    grads drift by last ulps (MEASURED on this CPU backend: 1-layer
    groups break the bitwise-vs-eager contract, >= 2-layer groups — a
    live while op with the IDENTICAL body every schedule compiles —
    keep it)."""
    import numpy as np

    aux_tmpl, layers_tmpl = split_aux_layers(params_tmpl)
    flat_l, _ = jax.tree.flatten(layers_tmpl)
    lspecs, _ = jax.tree.flatten(
        param_specs(cfg, params_tmpl)["layers"],
        is_leaf=lambda x: isinstance(x, P))
    L = int(flat_l[0].shape[0])
    layer_axes: List[Optional[int]] = []
    per_layer_bytes = 0
    for leaf, spec in zip(flat_l, lspecs):
        k = zero1_axis(spec, leaf.shape, dp, skip_leading=True)
        layer_axes.append(k)
        if k is not None:
            per_layer_bytes += int(np.prod(leaf.shape[1:])) * 4
    target = max(int(bucket_mb * (1 << 20)), 1)
    per_group = min(L, max(2, target // max(per_layer_bytes, 1))) \
        if L > 1 else 1
    groups = [
        [lo, min(lo + per_group, L)] for lo in range(0, L, per_group)]
    if len(groups) > 1 and groups[-1][1] - groups[-1][0] < 2:
        groups[-2][1] = groups[-1][1]
        groups.pop()
    groups = tuple(tuple(g) for g in groups)
    return OverlapPlan(
        dp=dp,
        num_layers=L,
        groups=groups,
        layer_axes=tuple(layer_axes),
        layer_shapes=tuple(tuple(l.shape) for l in flat_l),
        aux=build_zero1_plan(cfg, aux_tmpl, dp, bucket_mb=bucket_mb),
    )


def overlap_out_specs(plan: OverlapPlan, params_tmpl) -> Any:
    """shard_map out_specs for the overlap-plan grad tree: `data` on
    each layer leaf's within-layer axis, the aux subtree per its eager
    plan."""
    aux_tmpl, layers_tmpl = split_aux_layers(params_tmpl)
    specs = dict(zero1_out_specs(plan.aux, jax.tree.structure(aux_tmpl)))
    flat_l, td_l = jax.tree.flatten(layers_tmpl)
    out_l = []
    for i, k in enumerate(plan.layer_axes):
        if k is None:
            out_l.append(P())
        else:
            parts = [None] * len(plan.layer_shapes[i])
            parts[k] = DATA_AXIS
            out_l.append(P(*parts))
    specs["layers"] = jax.tree.unflatten(td_l, out_l)
    return specs


def zero1_out_specs(plan: Zero1Plan, treedef) -> Any:
    """shard_map out_specs for the reduced grad tree: `data` on each
    leaf's zero1 axis, replicated residue. (Pure-dp meshes only — the
    specs never mention other axes.)"""
    specs = []
    for i, k in enumerate(plan.leaf_axes):
        if k is None:
            specs.append(P())
        else:
            parts = [None] * len(plan.shapes[i])
            parts[k] = DATA_AXIS
            specs.append(P(*parts))
    return jax.tree.unflatten(treedef, specs)


def _to_dp_matrix(g: jnp.ndarray, k: int, dp: int) -> jnp.ndarray:
    """Move the zero1 axis to the front and reshape to (dp, n): row r is
    exactly rank r's contiguous PartitionSpec block of axis k."""
    g = jnp.moveaxis(g, k, 0)
    return g.reshape(dp, -1).astype(jnp.float32)


def _from_shard_row(row: jnp.ndarray, shape: Tuple[int, ...],
                    k: int, dp: int) -> jnp.ndarray:
    """Inverse of _to_dp_matrix for ONE rank's row: reshape to the local
    shard block (axis k divided by dp) and move the axis back."""
    moved = (shape[k] // dp,) + tuple(
        n for i, n in enumerate(shape) if i != k)
    return jnp.moveaxis(row.reshape(moved), 0, k)


def _from_dp_matrix(mat: jnp.ndarray, shape: Tuple[int, ...],
                    k: int) -> jnp.ndarray:
    """Inverse of _to_dp_matrix for the FULL leaf: a (dp, n) matrix
    whose row r is rank r's axis-k block, reassembled to `shape`."""
    rest = tuple(n for i, n in enumerate(shape) if i != k)
    return jnp.moveaxis(mat.reshape((shape[k],) + rest), 0, k)


def _quantized_bucket_reduce_scatter(mat: jnp.ndarray, dp: int,
                                     axis_name: str = DATA_AXIS
                                     ) -> jnp.ndarray:
    """EQuARX-style int8 reduce-scatter of a (dp, n) bucket matrix of
    LOCAL partials: chunk-quantize each row (symmetric RTN int8,
    per-chunk fp32 scales — the ops/quantization convention), exchange
    row r to rank r with all_to_all (int8 + scales on the wire), then
    dequantize and accumulate the dp partials in fp32. Returns this
    rank's reduced (n,) shard."""
    from megatron_llm_tpu.ops.quantization import quantize_rows

    n = mat.shape[1]
    pad = (-n) % QUANT_CHUNK
    if pad:
        mat = jnp.pad(mat, ((0, 0), (0, pad)))
    nch = mat.shape[1] // QUANT_CHUNK
    data, scale = quantize_rows(mat.reshape(dp, nch, QUANT_CHUNK))
    # tiled all_to_all over axis 0: send row j to rank j, receive every
    # peer's row r (r = this rank) stacked on axis 0 = source rank
    data = jax.lax.all_to_all(data, axis_name, split_axis=0,
                              concat_axis=0, tiled=True)
    scale = jax.lax.all_to_all(scale, axis_name, split_axis=0,
                               concat_axis=0, tiled=True)
    part = data.astype(jnp.float32) * scale[..., None]
    red = jnp.sum(part, axis=0).reshape(-1)  # fp32 accumulation
    return red[:n] if pad else red


def reduce_scatter_grads(grads, plan: Zero1Plan, quantized: bool = False,
                         axis_name: str = DATA_AXIS):
    """Inside a data-manual shard_map body: turn each rank's LOCAL
    partial grad tree into the dp-reduced zero1-sharded tree — one
    reduce-scatter (or quantized all_to_all exchange) per bucket, one
    psum for the replicated residue. Bitwise contract (quantized=False):
    psum_scatter accumulates partials in the same rank order psum does,
    and bucket concatenation is elementwise-transparent, so every
    reduced element equals the replicated all-reduce's."""
    flat, treedef = jax.tree.flatten(grads)
    out: List[Any] = [None] * len(flat)
    dp = plan.dp
    for idx in plan.residue:
        out[idx] = jax.lax.psum(flat[idx].astype(jnp.float32), axis_name)
    for bucket in plan.buckets:
        mats = [_to_dp_matrix(flat[i], plan.leaf_axes[i], dp)
                for i in bucket]
        sizes = [m.shape[1] for m in mats]
        cat = mats[0] if len(mats) == 1 else jnp.concatenate(mats, axis=1)
        if quantized:
            shard = _quantized_bucket_reduce_scatter(cat, dp, axis_name)
        else:
            shard = jax.lax.psum_scatter(
                cat, axis_name, scatter_dimension=0, tiled=True
            ).reshape(-1)
        off = 0
        for i, n in zip(bucket, sizes):
            out[i] = _from_shard_row(
                shard[off:off + n], plan.shapes[i], plan.leaf_axes[i], dp)
            off += n
    return jax.tree.unflatten(treedef, out)


# ---------------------------------------------------------------------------
# The explicit train-step gradient function
# ---------------------------------------------------------------------------


def explicit_zero1_supported(model, pcfg, ctx: Optional[ParallelContext],
                             batch_builder=None) -> bool:
    """Whether the decomposed shard_map path can serve this run: pure-dp
    mesh (every non-data axis size 1 — the body is fully manual, see
    the module docstring), dp > 1, and a model exposing loss_terms (the
    GPT family). Everything else keeps the GSPMD-spec path."""
    return (
        ctx is not None
        and pcfg.use_distributed_optimizer
        and pcfg.data_parallel_size > 1
        and pcfg.pipeline_parallel_size == 1
        and ctx.tp == 1 and ctx.cp == 1 and ctx.pp == 1
        and ctx.dp == pcfg.data_parallel_size
        and batch_builder is None
        and hasattr(model, "loss_terms")
    )


def _overlap_one_micro(model, plan: OverlapPlan, quantized: bool,
                       params, micro, rng, loss_scale, global_den):
    """One microbatch of the SCHEDULED decomposition (ISSUE 12): the
    forward runs group by group saving each group's vjp, the backward
    walks the groups last-to-first, and each group's bucket collective
    is ISSUED at its group boundary and CONSUMED only after the next
    group's backward has been emitted — the double buffer that leaves
    the latency-hiding scheduler a whole layer group of independent
    compute per collective. The math is the eager path's exactly:
    vjp-by-pieces at the factorization boundaries of model.loss_pieces
    is the same op chain value_and_grad(loss_terms) records, psum/
    psum_scatter accumulate in the same rank order, and tied-embedding
    cotangents merge by one fp add (commutative, so bitwise
    order-blind). fp32 bitwise vs eager is pinned in
    tests/test_overlap.py."""
    dp = plan.dp
    aux_params, layers = split_aux_layers(params)
    with manual_region(constraint_barriers=True):
        # same barrier policy as the eager path: shard_activation sites
        # become fusion barriers mirroring the GSPMD program
        embed_fn, group_fn, head_fn = model.loss_pieces(
            dropout_rng=rng, deterministic=rng is None, **micro)
        hidden, embed_vjp = jax.vjp(embed_fn, aux_params)
        group_vjps = []
        for lo, hi in plan.groups:
            sl = jax.tree.map(lambda x, lo=lo, hi=hi: x[lo:hi], layers)
            hidden, vjp_g = jax.vjp(
                lambda p, h, _lo=lo: group_fn(p, h, _lo), sl, hidden)
            group_vjps.append(vjp_g)

        def scaled_head(a, h):
            # the exact scalar chain the eager local_micro_loss
            # differentiates: num / max(global_den, 1) [* loss_scale]
            num, _ = head_fn(a, h)
            loss = num / jnp.maximum(global_den, 1.0)
            if loss_scale is not None:
                loss = loss * loss_scale
            return loss, num

        _, head_vjp, num = jax.vjp(scaled_head, aux_params, hidden,
                                   has_aux=True)

    d_aux, d_h = head_vjp(jnp.float32(1.0))

    G = len(plan.groups)
    group_shards: List[Optional[dict]] = [None] * G
    group_res: List[dict] = [{} for _ in range(G)]
    td_layers_box: List[Any] = [None]

    def issue(gi, d_slice):
        """Pack group gi's sharded-leaf cotangents and fire its
        collective; residue leaves stay local (psum'd once at the
        end)."""
        flat_g, td_layers_box[0] = jax.tree.flatten(d_slice)
        mats, entries = [], []
        for i, g in enumerate(flat_g):
            k = plan.layer_axes[i]
            if k is None:
                group_res[gi][i] = g.astype(jnp.float32)
                continue
            m = _to_dp_matrix(g, k, dp)
            entries.append((i, tuple(g.shape), k, m.shape[1]))
            mats.append(m)
        if not mats:
            # every layer leaf fell to the residue (no within-layer
            # dp-divisible axis at this config) — nothing to scatter
            return gi, None, entries
        cat = mats[0] if len(mats) == 1 else jnp.concatenate(mats, axis=1)
        if quantized:
            sc = _quantized_bucket_reduce_scatter(cat, dp)
        else:
            sc = jax.lax.psum_scatter(
                cat, DATA_AXIS, scatter_dimension=0, tiled=True
            ).reshape(-1)
        return gi, sc, entries

    def consume(pend):
        gi, sc, entries = pend
        out = {}
        if sc is None:
            group_shards[gi] = out
            return
        off = 0
        for i, shp, k, n in entries:
            out[i] = _from_shard_row(sc[off:off + n], shp, k, dp)
            off += n
        group_shards[gi] = out

    pending = None
    for gi in reversed(range(G)):
        d_slice, d_h = group_vjps[gi](d_h)
        issued = issue(gi, d_slice)
        # double buffer: group gi+1's collective is consumed only now,
        # AFTER group gi's backward + issue are in the program — the
        # collective has a group of compute to hide behind
        if pending is not None:
            consume(pending)
        pending = issued
    (d_aux_emb,) = embed_vjp(d_h)
    # tied embeddings: head + embed contributions merge here; fp add is
    # commutative, so the merge order cannot move a bit
    d_aux = jax.tree.map(lambda a, b: a + b, d_aux, d_aux_emb)
    aux_grads = reduce_scatter_grads(d_aux, plan.aux, quantized=quantized)
    consume(pending)

    out_l = []
    for i, k in enumerate(plan.layer_axes):
        if k is None:
            parts = [group_res[g][i] for g in range(G)]
            full = parts[0] if G == 1 else jnp.concatenate(parts, axis=0)
            out_l.append(jax.lax.psum(full, DATA_AXIS))
        else:
            parts = [group_shards[g][i] for g in range(G)]
            out_l.append(
                parts[0] if G == 1 else jnp.concatenate(parts, axis=0))
    grads = dict(aux_grads)
    grads["layers"] = jax.tree.unflatten(td_layers_box[0], out_l)
    loss = jax.lax.psum(num, DATA_AXIS) / jnp.maximum(global_den, 1.0)
    return grads, loss


def make_zero1_grad_fn(model, ctx: ParallelContext, plan,
                       num_micro: int, quantized: bool):
    """Returns grad_fn(params, batch, rng, loss_scale) ->
    (zero1-sharded fp32 grads, mean loss) — the explicit decomposition
    of the replicated train step's accumulation loop. Called inside the
    jitted train step; the shard_map is manual over the whole (pure-dp)
    mesh. `plan` selects the schedule: a Zero1Plan runs the eager
    post-backward sweep (the bitwise oracle), an OverlapPlan the
    backward-interleaved issue points (--overlap_grad_reduce)."""

    mesh = ctx.mesh
    dp = plan.dp
    overlap = isinstance(plan, OverlapPlan)

    def local_micro_loss(params, micro, rng, loss_scale, global_den):
        # mirrors train_step.loss_on_micro's exact op chain: the local
        # numerator over this rank's rows divided by the GLOBAL psum'd
        # denominator gives AD the identical cotangent the replicated
        # backward injects, so the local partials are bitwise the
        # partials GSPMD all-reduces.
        with manual_region(constraint_barriers=True):
            # the whole (pure-dp) mesh is manual inside this body, so
            # shard_activation emits optimization barriers where the
            # replicated program has sharding constraints — mirroring
            # its fusion boundaries is what keeps bf16 rounding (and so
            # the bitwise contract) identical (parallel/mesh.py)
            num, _ = model.loss_terms(
                params, dropout_rng=rng, deterministic=rng is None,
                **micro)
        loss = num / jnp.maximum(global_den, 1.0)
        if loss_scale is not None:
            return loss * loss_scale, num
        return loss, num

    def _shard_zeros(params):
        if not overlap:
            _, treedef = jax.tree.flatten(params)
            return jax.tree.unflatten(treedef, [
                jnp.zeros(plan.shard_shape(i), jnp.float32)
                for i in range(len(plan.shapes))
            ])
        aux_t, layers_t = split_aux_layers(params)
        fa, ta = jax.tree.flatten(aux_t)
        out = dict(jax.tree.unflatten(ta, [
            jnp.zeros(plan.aux.shard_shape(i), jnp.float32)
            for i in range(len(fa))
        ]))
        fl, tl = jax.tree.flatten(layers_t)
        out["layers"] = jax.tree.unflatten(tl, [
            jnp.zeros(plan.layer_shard_shape(i), jnp.float32)
            for i in range(len(fl))
        ])
        return out

    def body(params, batch, rng, loss_scale):
        grad_fn = jax.value_and_grad(local_micro_loss, has_aux=True)

        def one_micro(micro, mrng):
            # the denominator is mask arithmetic only (no forward, no
            # params): psum it up front so the grad target divides by
            # the same global count the replicated loss divides by
            den = model.loss_denominator(**micro)
            global_den = jax.lax.psum(den, DATA_AXIS)
            if overlap:
                return _overlap_one_micro(
                    model, plan, quantized, params, micro, mrng,
                    loss_scale, global_den)
            (_, num), g = grad_fn(params, micro, mrng, loss_scale,
                                  global_den)
            # reported loss: numerator psum'd BEFORE the division, the
            # same order the replicated program reduces it
            loss = jax.lax.psum(num, DATA_AXIS) \
                / jnp.maximum(global_den, 1.0)
            gsh = reduce_scatter_grads(g, plan, quantized=quantized)
            return gsh, loss

        if num_micro == 1:
            micro = jax.tree.map(lambda x: x[0], batch)
            grads, loss = one_micro(micro, rng)
            return grads, loss

        shard_zeros = _shard_zeros(params)

        def scan_body(carry, xs):
            acc_g, acc_l = carry
            micro, idx = xs
            mrng = jax.random.fold_in(rng, idx) if rng is not None else None
            gsh, loss = one_micro(micro, mrng)
            acc_g = jax.tree.map(lambda a, b: a + b, acc_g, gsh)
            return (acc_g, acc_l + loss), None

        (grads, loss), _ = jax.lax.scan(
            scan_body, (shard_zeros, jnp.float32(0.0)),
            (batch, jnp.arange(num_micro)))
        grads = jax.tree.map(lambda g: g / num_micro, grads)
        return grads, loss / num_micro

    def grad_fn(params, batch, rng, loss_scale):
        p_specs = jax.tree.map(lambda _: P(), params)
        b_specs = jax.tree.map(lambda _: P(None, DATA_AXIS), batch)
        g_specs = (overlap_out_specs(plan, params) if overlap
                   else zero1_out_specs(plan, jax.tree.structure(params)))
        args = [params, batch]
        in_specs = [p_specs, b_specs]
        # rng / loss_scale enter replicated only when present (a None
        # stays a static Python None inside the body)
        if rng is not None:
            args.append(rng)
            in_specs.append(P())
        if loss_scale is not None:
            args.append(loss_scale)
            in_specs.append(P())

        def wrapped(params, batch, *rest):
            rest = list(rest)
            r = rest.pop(0) if rng is not None else None
            if r is not None:
                # per-rank dropout stream: the mask layout over rows
                # differs from the replicated program's (documented in
                # GUIDE.md — the replicated path draws one mask over the
                # global batch)
                r = jax.random.fold_in(r, jax.lax.axis_index(DATA_AXIS))
            ls = rest.pop(0) if loss_scale is not None else None
            return body(params, batch, r, ls)

        return jax.shard_map(
            wrapped, mesh=mesh,
            in_specs=tuple(in_specs),
            out_specs=(g_specs, P()),
            check_vma=False,
        )(*args)

    return grad_fn


# ---------------------------------------------------------------------------
# The explicit param all-gather leg (--overlap_param_gather, ISSUE 12)
# ---------------------------------------------------------------------------


def make_explicit_param_gather(ctx: ParallelContext, plan):
    """Returns gather(new_params) -> replicated params: the all-gather
    leg of the decomposition as EXPLICIT per-bucket collectives instead
    of one GSPMD whole-tree constraint. Gathers are issued
    first-needed-first — the aux buckets (embedding leads the aux flat
    order, and the next forward needs the embedding table before any
    layer) and then the layer buckets in FORWARD order — and each
    bucket's gather is consumed only after the next one is issued
    (double-buffered), so bucket N's wire time hides behind bucket
    N+1's issue and, on TPU, behind whatever the scheduler can pull
    over the `-done`. Pure data movement: bitwise vs the GSPMD
    constraint gather (pinned in tests/test_overlap.py). Works with
    either plan flavor (the bucket units follow the active grad
    layout) and composes with --quantized_grad_reduce (the wire format
    of the REDUCE leg is irrelevant here)."""

    mesh = ctx.mesh
    dp = plan.dp
    overlap = isinstance(plan, OverlapPlan)

    def _gather_units(units):
        """units: ordered list of buckets, each a list of
        (tag, full_shape, k, shard_array). One packed all_gather per
        bucket; bucket i is unpacked only after bucket i+1's gather is
        issued. Returns {tag: full array}."""
        results = {}

        def consume(pend):
            unit, g = pend
            off = 0
            for tag, shape, k, a in unit:
                n = int(a.size)
                results[tag] = _from_dp_matrix(
                    g[:, off:off + n], shape, k)
                off += n

        pending = None
        for unit in units:
            rows = [jnp.moveaxis(a, k, 0).reshape(-1)
                    for (_, _, k, a) in unit]
            row = rows[0] if len(rows) == 1 else jnp.concatenate(rows)
            g = jax.lax.all_gather(row, DATA_AXIS, axis=0, tiled=False)
            if pending is not None:
                consume(pending)
            pending = (unit, g)
        if pending is not None:
            consume(pending)
        return results

    def _eager_body(p):
        flat, treedef = jax.tree.flatten(p)
        units = [
            [(i, plan.shapes[i], plan.leaf_axes[i], flat[i])
             for i in bucket]
            for bucket in plan.buckets if bucket
        ]
        results = _gather_units(units)
        out = [results.get(i, flat[i]) for i in range(len(flat))]
        return jax.tree.unflatten(treedef, out)

    def _overlap_body(p):
        aux_t, layers_t = split_aux_layers(p)
        fa, ta = jax.tree.flatten(aux_t)
        fl, tl = jax.tree.flatten(layers_t)
        units = [
            [(("aux", i), plan.aux.shapes[i], plan.aux.leaf_axes[i],
              fa[i]) for i in bucket]
            for bucket in plan.aux.buckets if bucket
        ]
        for gi, (lo, hi) in enumerate(plan.groups):
            unit = []
            for i, k in enumerate(plan.layer_axes):
                if k is None:
                    continue
                shape = (hi - lo,) + plan.layer_shapes[i][1:]
                unit.append((("layer", i, gi), shape, k, fl[i][lo:hi]))
            if unit:
                units.append(unit)
        results = _gather_units(units)
        out_a = [results.get(("aux", i), fa[i]) for i in range(len(fa))]
        out_l = []
        for i, k in enumerate(plan.layer_axes):
            if k is None:
                out_l.append(fl[i])
                continue
            parts = [results[("layer", i, gi)]
                     for gi in range(len(plan.groups))]
            out_l.append(
                parts[0] if len(parts) == 1
                else jnp.concatenate(parts, axis=0))
        out = dict(jax.tree.unflatten(ta, out_a))
        out["layers"] = jax.tree.unflatten(tl, out_l)
        return out

    def gather(new_params):
        in_specs = (
            overlap_out_specs(plan, new_params) if overlap
            else zero1_out_specs(plan, jax.tree.structure(new_params)))
        out_specs = jax.tree.map(lambda _: P(), new_params)
        body = _overlap_body if overlap else _eager_body
        return jax.shard_map(
            body, mesh=mesh, in_specs=(in_specs,), out_specs=out_specs,
            check_vma=False,
        )(new_params)

    return gather
