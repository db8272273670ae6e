"""Flash attention — the flagship Pallas kernel of the build. Fwd + bwd.

Replaces the reference's external FlashAttention-2 dependency
(ref: requirements.txt:3, transformer.py:508-523 — the reference TRAINS
through flash-attn, so the backward here is load-bearing) and the three
fused softmax CUDA kernels (ref: megatron/fused_kernels/scaled_*softmax*).

GQA/MQA-aware: K/V stay at `num_query_groups` heads and are never
broadcast-expanded (the reference expands them, transformer.py:449-456).
Layout: q (b, s, g, qpk, d), k/v (b, t, g, d) — the grouped layout used
throughout megatron_llm_tpu.models.attention. Inside the kernels the
(position, q-head) pair is folded into one row dim (head fastest), so one
MXU matmul serves all q heads of a group.

Backward follows the FlashAttention-2 recomputation scheme: the forward
saves only O and the per-row logsumexp; the backward recomputes the score
blocks and accumulates dq (grid over q blocks) and dk/dv (grid over k
blocks) in fp32 VMEM scratch, with delta = rowsum(dO * O) precomputed.

A head width that is not a multiple of the 128-lane tile (Falcon's 64)
is zero-padded to the next multiple around the kernel calls and the
outputs cut back (`_pad_lanes`): zero columns add nothing to q . k and
come out as zero columns of o, dq, dk, dv, and the softmax scale stays
that of the TRUE width. A lane-aligned head pads by nothing and takes
the same path.

`flash_attention` dispatches to the Pallas kernels on TPU and to a
numerically identical XLA fallback elsewhere; `interpret=True` runs the
real kernels through the Pallas interpreter (used by the CPU test suite).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from megatron_llm_tpu.analysis.contracts import (
    CompileContract,
    register_contract,
)
from megatron_llm_tpu.ops.dispatch import (
    note_kernel,
    report_fallback,
    want_kernel,
)

register_contract(CompileContract(
    name="ops.flash_attention",
    max_variants=None,  # traced per (shape, statics) by jax's jit
    # cache; the model's fixed (b, s, heads, d) keeps the key space to
    # the handful of layouts a config actually runs
    collectives={"single": frozenset()},
    tmp_bytes_budget=2 << 20,  # 32 KB measured at the audit config
    notes="audited on the dense XLA path (use_pallas=False): the "
          "Pallas kernel is TPU-gated and interpret mode IS a host "
          "callback by construction"))

NEG_INF = -1e30
# The kernels run the online softmax in the exp2 domain (scores pre-scaled
# by log2(e)): the TPU transcendental unit computes exp2 natively, so
# exp(x) = exp2(x * log2e) folds one multiply per score cell into the GEMM
# scale. lse crosses the kernel boundary in NATURAL log units.
LOG2E = 1.4426950408889634
LN2 = 0.6931471805599453

# swept on a real v5e (r4, b6/g16/d128 @ seq 4096 and b8 @ 1024):
# 1024/1024 beats 512/1024 by ~10-12% fwd+bwd at both lengths (and
# 256/256 by >2x); _choose_block still shrinks for short sequences and
# many-q-per-kv GQA groups (MAX_ROWS cap), MAX_CELLS bounds VMEM
DEFAULT_BLOCK_Q = 1024
DEFAULT_BLOCK_K = 1024
# cap on folded (position, head) rows per program so fp32 score blocks
# (rows x block_k) and the accumulators fit VMEM (~16 MB)
MAX_ROWS = 2048
# cap on rows*block_k fp32 score cells per program (4 MB per buffer; the
# backward holds two such blocks) — keeps wide-GQA shapes inside VMEM now
# that the default block_k is 1024
MAX_CELLS = 1 << 20
LANES = 128  # the kernels split the lane axis into (head, d): d % LANES == 0


def _pad_lanes(*xs):
    """Zero-pad each operand's head axis (the last) to the next multiple
    of the lane tile; a lane-aligned head comes back as it is."""
    pad = -xs[0].shape[-1] % LANES
    if not pad:
        return xs
    return tuple(jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])
                 for x in xs)


def _xla_reference(q, k, v, causal: bool):
    """Un-tiled reference path; same math, XLA-fused softmax."""
    b, s, g, qpk, d = q.shape
    t = k.shape[1]
    scale = 1.0 / jnp.sqrt(d).astype(jnp.float32)
    scores = jnp.einsum("bsgqd,btgd->bgqst", q, k,
                        preferred_element_type=jnp.float32) * scale
    if causal:
        rows = jnp.arange(s)[:, None]
        cols = jnp.arange(t)[None, :]
        scores = jnp.where(cols > rows, jnp.finfo(jnp.float32).min, scores)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    return jnp.einsum("bgqst,btgd->bsgqd", probs, v)


def _xla_reference_with_lse(q, k, v, causal: bool):
    """Reference path that also returns the per-row logsumexp
    (b, s, g, qpk) fp32 — differentiable through BOTH outputs (autodiff;
    the merge-across-blocks users need d/dlse)."""
    b, s, g, qpk, d = q.shape
    t = k.shape[1]
    scale = 1.0 / jnp.sqrt(d).astype(jnp.float32)
    scores = jnp.einsum("bsgqd,btgd->bgqst", q, k,
                        preferred_element_type=jnp.float32) * scale
    if causal:
        rows = jnp.arange(s)[:, None]
        cols = jnp.arange(t)[None, :]
        scores = jnp.where(cols > rows, NEG_INF, scores)
    lse = jax.scipy.special.logsumexp(scores, axis=-1)  # (b, g, qpk, s)
    probs = jnp.exp(scores - lse[..., None]).astype(v.dtype)
    o = jnp.einsum("bgqst,btgd->bsgqd", probs, v)
    return o, jnp.moveaxis(lse, 3, 1)  # lse -> (b, s, g, qpk)


def _out_struct(shape, dtype, *likes):
    """ShapeDtypeStruct carrying the union of the operands' varying-
    manual-axes sets: inside a shard_map manual region (ring attention's
    per-hop call, the pipelined decode's stage region) the kernel
    outputs must declare how they vary across the manual axes or tracing
    rejects them (check_vma)."""
    vma = frozenset().union(*(jax.typeof(x).vma for x in likes))
    if vma:
        return jax.ShapeDtypeStruct(shape, dtype, vma=vma)
    return jax.ShapeDtypeStruct(shape, dtype)


def _choose_block(size: int, requested: int, qpk: int = 1):
    """Largest power-of-2 block <= requested that divides `size` and keeps
    folded rows (block*qpk) under MAX_ROWS. None if nothing fits (caller
    falls back to the XLA path). Power-of-2 keeps Mosaic tile alignment
    (sublane multiples of 8/16)."""
    b = 1 << (min(requested, size).bit_length() - 1)  # round down to pow2
    while b >= 8 and (size % b or b * qpk > MAX_ROWS):
        b //= 2
    return b if b >= 8 and size % b == 0 else None


# ---------------------------------------------------------------------------
# Shared attention-kernel template (ISSUE 18): the mask / online-softmax /
# fp32-accumulator core that every attention kernel in ops/ instantiates.
# The flash forward (dense training), the dense decode kernel, and the
# unified ragged paged kernel (ops/prefill_attention.py) all run their
# reduction through these helpers, so the exp2-domain running-(m, l, acc)
# scheme and the mask predicate are each ONE definition. The mask is a
# pluggable SHAPE: `_causal_invalid` is the causal family — dense causal
# (pos_base = q-block start), decode row (pos_base = cache offset), and
# ragged chunk (pos_base = slot start + block start, plus the pad-row
# bound `valid_rows`) are all parameterizations of one predicate; a
# sliding-window or packed-doc mask slots in as a new predicate function
# without touching any kernel body.
# ---------------------------------------------------------------------------


def _causal_invalid(rows, block_k, qpk, pos_base, col_base,
                    valid_rows=None, window=None, floor=None):
    """(rows, block_k) bool block, True = masked out. Folded row r (head
    fastest) is token r // qpk at causal position pos_base + r // qpk;
    column c is cache position col_base + c. With `valid_rows` (the
    ragged-chunk pad bound), rows at tokens >= valid_rows mask EVERY
    column. pos_base / valid_rows may be traced scalars.

    The two lower-bound parameterizations (ISSUE 19) are additive
    predicates on the same block, None = off (the trace is then
    bitwise the pre-window one):
    - `window` (static int >= 1): sliding-window attention — a row at
      position p attends only cols in [p - window + 1, p], so the
      window >= context case compares against bounds that never bind
      and stays bitwise-dense.
    - `floor` (traced scalar): packed-doc reset — every row of the
      block additionally masks cols < floor (the chunk's document
      start). Callers must keep floor <= the first row's own position
      or a valid row could mask every column (the finite-NEG_INF
      degenerate case only pad rows are re-masked for)."""
    tok = jax.lax.broadcasted_iota(jnp.int32, (rows, block_k), 0) // qpk
    col = col_base + jax.lax.broadcasted_iota(
        jnp.int32, (rows, block_k), 1
    )
    invalid = col > pos_base + tok
    if window is not None:
        invalid = invalid | (col < pos_base + tok - (window - 1))
    if floor is not None:
        invalid = invalid | (col < floor)
    if valid_rows is not None:
        invalid = invalid | (tok >= valid_rows)
    return invalid


def _softmax_init(m_scr, l_scr, acc_scr):
    """Reset the running (max, sum, acc) VMEM scratch at the first
    reduction step of a grid row."""
    m_scr[:] = jnp.full_like(m_scr, NEG_INF)
    l_scr[:] = jnp.zeros_like(l_scr)
    acc_scr[:] = jnp.zeros_like(acc_scr)


def _softmax_accum(sc, vb, m_scr, l_scr, acc_scr, p_dtype=None):
    """One exp2-domain online-softmax step: fold the (rows, block_k)
    score block `sc` and its value block `vb` into the running fp32
    (m, l, acc) scratch. `p_dtype` casts the probabilities before the PV
    matmul (the fp kernels feed the MXU in the value dtype); the
    int8-dequant epilogue passes None and keeps fp32 — its vb was
    already dequantized in-register."""
    m_prev = m_scr[:]  # (rows, 1)
    m_new = jnp.maximum(m_prev, jnp.max(sc, axis=-1, keepdims=True))
    alpha = jnp.exp2(m_prev - m_new)
    p = jnp.exp2(sc - m_new)  # (rows, block_k)
    l_scr[:] = alpha * l_scr[:] + jnp.sum(p, axis=-1, keepdims=True)
    if p_dtype is not None:
        p = p.astype(p_dtype)
    acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot(
        p, vb, preferred_element_type=jnp.float32,
    )
    m_scr[:] = m_new


def _softmax_finalize(l_scr, acc_scr):
    """Close the reduction: returns (acc / max(l, eps), l) in fp32. The
    eps floor keeps all-masked rows finite; callers re-mask such rows to
    their exact-zero contract where one exists."""
    l = jnp.maximum(l_scr[:], 1e-30)
    return acc_scr[:] / l, l


def _masked_scores(q_ref, k_ref, i, j, *, masked, block_q, block_k, qpk, d,
                   sm_scale):
    """Recompute the scaled score block in the exp2 domain — the ONE
    definition shared by the forward and both backward kernels so fwd
    probabilities and bwd recompute can never desynchronize. `masked` is a
    TRACE-TIME flag: callers split their grid step into interior
    (fully-below-diagonal, no iota/select work) and diagonal-straddling
    branches, so the causal mask costs VPU time only on the ~1/num_blocks
    of blocks that actually straddle the diagonal.
    Returns (rows, block_k) fp32, scaled by sm_scale * log2(e)."""
    rows = block_q * qpk
    qb = q_ref[:].reshape(rows, d)
    kb = k_ref[:].reshape(block_k, d)
    sc = jax.lax.dot_general(
        qb, kb, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * (sm_scale * LOG2E)
    if masked:
        sc = jnp.where(
            _causal_invalid(rows, block_k, qpk, i * block_q, j * block_k),
            NEG_INF, sc,
        )
    return sc


# ---------------------------------------------------------------------------
# Forward kernel
# ---------------------------------------------------------------------------
# Online-softmax tiling: grid over (batch*group, q_block, k_block); running
# (max, sum, acc) in fp32 VMEM scratch; emits O and the logsumexp rows.


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
                *, causal, block_q, block_k, qpk, d, num_k_blocks, sm_scale,
                split_diag=True):
    i = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        _softmax_init(m_scr, l_scr, acc_scr)

    def _accum(masked):
        # rows: (pos, head), head fastest; running stats in exp2 domain
        sc = _masked_scores(
            q_ref, k_ref, i, j, masked=masked, block_q=block_q,
            block_k=block_k, qpk=qpk, d=d, sm_scale=sm_scale,
        )
        _softmax_accum(sc, v_ref[:].reshape(block_k, d), m_scr, l_scr,
                       acc_scr, p_dtype=v_ref.dtype)

    if causal:
        # skip fully-masked K blocks (k block start > last q position);
        # apply the mask only on diagonal-straddling blocks — interior
        # blocks (last col <= first q row) run the maskless branch.
        # (split_diag=False under the interpreter: the two-branch grid
        # step trips a vma check in the Pallas HLO interpreter.)
        run = (j * block_k) <= (i * block_q + block_q - 1)
        if split_diag:
            interior = (j * block_k + block_k - 1) <= (i * block_q)

            @pl.when(run & interior)
            def _compute_interior():
                _accum(False)

            @pl.when(run & ~interior)
            def _compute_diagonal():
                _accum(True)
        else:
            @pl.when(run)
            def _compute():
                _accum(True)
    else:
        @pl.when(j >= 0)  # always true; pl.when so the interpreter's vma
        def _compute():   # unification wraps the body (interpret mode)
            _accum(False)

    @pl.when(j == num_k_blocks - 1)
    def _finalize():
        out, l = _softmax_finalize(l_scr, acc_scr)
        o_ref[:] = out.astype(o_ref.dtype).reshape(
            1, block_q, qpk * d
        )
        # rows-major (rows, 1) layout: Mosaic can't shape-cast the lane dim
        # into sublanes, so lse lives as (bg, s*qpk, 1) end to end.
        # m is in exp2 units; emit NATURAL-log lse (the kernel ABI).
        lse_ref[0] = m_scr[:] * LN2 + jnp.log(l)


def _flash_fwd_pallas(q, k, v, causal, block_q, block_k, interpret=False):
    """q: (b, s, g, qpk, d); k,v: (b, t, g, d).
    Returns (o (b,s,g,qpk,d), lse (b*g, s*qpk, 1) fp32 rows-major)."""
    b, s, g, qpk, d_true = q.shape
    t = k.shape[1]
    sm_scale = 1.0 / (d_true ** 0.5)  # of the true width, not the padded
    assert s % block_q == 0 and t % block_k == 0
    q, k, v = _pad_lanes(q, k, v)
    d = q.shape[-1]

    qf = q.transpose(0, 2, 1, 3, 4).reshape(b * g, s, qpk * d)
    kf = k.transpose(0, 2, 1, 3).reshape(b * g, t, d)
    vf = v.transpose(0, 2, 1, 3).reshape(b * g, t, d)

    num_q_blocks = s // block_q
    num_k_blocks = t // block_k

    kernel = functools.partial(
        _fwd_kernel, causal=causal, block_q=block_q, block_k=block_k,
        qpk=qpk, d=d, num_k_blocks=num_k_blocks, sm_scale=sm_scale,
        split_diag=not interpret,
    )
    grid = (b * g, num_q_blocks, num_k_blocks)

    if causal:
        # skipped above-diagonal blocks clamp their K/V index to the last
        # allowed block: Mosaic detects the repeated block index and skips
        # the DMA, so masked grid steps cost no HBM traffic
        def kv_index(h, i, j):
            return (h, jnp.minimum(j, (i * block_q + block_q - 1)
                                   // block_k), 0)
    else:
        def kv_index(h, i, j):
            return (h, j, 0)

    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, qpk * d), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((1, block_k, d), kv_index),
            pl.BlockSpec((1, block_k, d), kv_index),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, qpk * d), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((1, block_q * qpk, 1), lambda h, i, j: (h, i, 0)),
        ],
        out_shape=[
            _out_struct((b * g, s, qpk * d), q.dtype, qf),
            _out_struct((b * g, s * qpk, 1), jnp.float32, qf),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q * qpk, 1), jnp.float32),
            pltpu.VMEM((block_q * qpk, 1), jnp.float32),
            pltpu.VMEM((block_q * qpk, d), jnp.float32),
        ],
        # (bg, q) grid steps are independent; only the k dim carries the
        # online-softmax accumulator state
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(qf, kf, vf)
    out = out.reshape(b, g, s, qpk, d)[..., :d_true]
    return out.transpose(0, 2, 1, 3, 4), lse


# ---------------------------------------------------------------------------
# Backward kernels (FlashAttention-2 recomputation scheme)
# ---------------------------------------------------------------------------


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   acc_scr, *, causal, block_q, block_k, qpk, d,
                   num_k_blocks, sm_scale, split_diag=True):
    i = pl.program_id(1)  # q block
    j = pl.program_id(2)  # k block

    @pl.when(j == 0)
    def _init():
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def _accum(masked):
        rows = block_q * qpk
        kb = k_ref[:].reshape(block_k, d)
        vb = v_ref[:].reshape(block_k, d)
        dob = do_ref[:].reshape(rows, d)

        sc = _masked_scores(
            q_ref, k_ref, i, j, masked=masked, block_q=block_q,
            block_k=block_k, qpk=qpk, d=d, sm_scale=sm_scale,
        )
        # exact probs via saved logsumexp; sc is exp2-domain, the saved
        # lse is natural-log — rescale the (rows, 1) vector, not the block
        p = jnp.exp2(sc - lse_ref[0] * LOG2E)
        dp = jax.lax.dot_general(
            dob, vb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta_ref[0])
        acc_scr[:] = acc_scr[:] + jax.lax.dot(
            ds.astype(kb.dtype), kb, preferred_element_type=jnp.float32
        )

    if causal:
        run = (j * block_k) <= (i * block_q + block_q - 1)
        if split_diag:
            interior = (j * block_k + block_k - 1) <= (i * block_q)

            @pl.when(run & interior)
            def _compute_interior():
                _accum(False)

            @pl.when(run & ~interior)
            def _compute_diagonal():
                _accum(True)
        else:
            @pl.when(run)
            def _compute():
                _accum(True)
    else:
        @pl.when(j >= 0)
        def _compute():
            _accum(False)

    @pl.when(j == num_k_blocks - 1)
    def _finalize():
        dq_ref[:] = (acc_scr[:] * sm_scale).astype(dq_ref.dtype).reshape(
            1, block_q, qpk * d
        )


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_scr, dv_scr, *, causal, block_q,
                    block_k, qpk, d, num_q_blocks, sm_scale,
                    split_diag=True):
    j = pl.program_id(1)  # k block
    i = pl.program_id(2)  # q block

    @pl.when(i == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def _accum(masked):
        rows = block_q * qpk
        qb = q_ref[:].reshape(rows, d)
        vb = v_ref[:].reshape(block_k, d)
        dob = do_ref[:].reshape(rows, d)

        sc = _masked_scores(
            q_ref, k_ref, i, j, masked=masked, block_q=block_q,
            block_k=block_k, qpk=qpk, d=d, sm_scale=sm_scale,
        )
        p = jnp.exp2(sc - lse_ref[0] * LOG2E)  # (rows, block_k)
        # dv += P^T dO
        dv_scr[:] = dv_scr[:] + jax.lax.dot_general(
            p.astype(dob.dtype), dob, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp = jax.lax.dot_general(
            dob, vb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta_ref[0])
        # dk += dS^T Q
        dk_scr[:] = dk_scr[:] + jax.lax.dot_general(
            ds.astype(qb.dtype), qb, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    if causal:
        # q blocks strictly before this k block contribute nothing
        run = (i * block_q + block_q - 1) >= (j * block_k)
        if split_diag:
            interior = (j * block_k + block_k - 1) <= (i * block_q)

            @pl.when(run & interior)
            def _compute_interior():
                _accum(False)

            @pl.when(run & ~interior)
            def _compute_diagonal():
                _accum(True)
        else:
            @pl.when(run)
            def _compute():
                _accum(True)
    else:
        @pl.when(i >= 0)
        def _compute():
            _accum(False)

    @pl.when(i == num_q_blocks - 1)
    def _finalize():
        dk_ref[:] = (dk_scr[:] * sm_scale).astype(dk_ref.dtype).reshape(
            1, block_k, d
        )
        dv_ref[:] = dv_scr[:].astype(dv_ref.dtype).reshape(1, block_k, d)


def _flash_bwd_pallas(q, k, v, o, lse, do, causal, block_q, block_k,
                      interpret=False, dlse_rows=None):
    b, s, g, qpk, d_true = q.shape
    t = k.shape[1]
    sm_scale = 1.0 / (d_true ** 0.5)
    q, k, v, do_p = _pad_lanes(q, k, v, do)
    d = q.shape[-1]

    qf = q.transpose(0, 2, 1, 3, 4).reshape(b * g, s, qpk * d)
    kf = k.transpose(0, 2, 1, 3).reshape(b * g, t, d)
    vf = v.transpose(0, 2, 1, 3).reshape(b * g, t, d)
    dof = do_p.transpose(0, 2, 1, 3, 4).reshape(b * g, s, qpk * d)
    # delta = rowsum(dO * O) — one fused elementwise reduce, XLA does this
    # as well as a kernel would (ref FA2 preprocess step); rows-major layout
    # matching lse
    delta = jnp.sum(
        do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1
    ).transpose(0, 2, 1, 3).reshape(b * g, s * qpk, 1)
    if dlse_rows is not None:
        # lse as a primal OUTPUT: d lse / d score_ij = p_ij, so the score
        # cotangent gains + g_lse * p — exactly ds = p*(dp - (delta -
        # g_lse)); folding it into delta costs nothing in-kernel
        delta = delta - dlse_rows

    num_q_blocks = s // block_q
    num_k_blocks = t // block_k

    # causal DMA clamps (see _flash_fwd_pallas): masked grid steps re-fetch
    # the previous block index, which Mosaic elides
    if causal:
        def kv_index(h, i, j):
            return (h, jnp.minimum(j, (i * block_q + block_q - 1)
                                   // block_k), 0)

        def q_index_t(h, j, i):
            return (h, jnp.maximum(i, (j * block_k) // block_q), 0)
    else:
        def kv_index(h, i, j):
            return (h, j, 0)

        def q_index_t(h, j, i):
            return (h, i, 0)

    row_specs = [
        pl.BlockSpec((1, block_q, qpk * d), lambda h, i, j: (h, i, 0)),  # q
        pl.BlockSpec((1, block_k, d), kv_index),                         # k
        pl.BlockSpec((1, block_k, d), kv_index),                         # v
        pl.BlockSpec((1, block_q, qpk * d), lambda h, i, j: (h, i, 0)),  # do
        pl.BlockSpec((1, block_q * qpk, 1), lambda h, i, j: (h, i, 0)),  # lse
        pl.BlockSpec((1, block_q * qpk, 1), lambda h, i, j: (h, i, 0)),  # delta
    ]
    dq = pl.pallas_call(
        functools.partial(
            _bwd_dq_kernel, causal=causal, block_q=block_q, block_k=block_k,
            qpk=qpk, d=d, num_k_blocks=num_k_blocks, sm_scale=sm_scale,
            split_diag=not interpret,
        ),
        grid=(b * g, num_q_blocks, num_k_blocks),
        in_specs=row_specs,
        out_specs=pl.BlockSpec((1, block_q, qpk * d), lambda h, i, j: (h, i, 0)),
        out_shape=_out_struct((b * g, s, qpk * d), q.dtype, qf),
        scratch_shapes=[pltpu.VMEM((block_q * qpk, d), jnp.float32)],
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(qf, kf, vf, dof, lse, delta)

    col_specs = [
        pl.BlockSpec((1, block_q, qpk * d), q_index_t),                  # q
        pl.BlockSpec((1, block_k, d), lambda h, j, i: (h, j, 0)),        # k
        pl.BlockSpec((1, block_k, d), lambda h, j, i: (h, j, 0)),        # v
        pl.BlockSpec((1, block_q, qpk * d), q_index_t),                  # do
        pl.BlockSpec((1, block_q * qpk, 1), q_index_t),                  # lse
        pl.BlockSpec((1, block_q * qpk, 1), q_index_t),                  # delta
    ]
    dk, dv = pl.pallas_call(
        functools.partial(
            _bwd_dkv_kernel, causal=causal, block_q=block_q, block_k=block_k,
            qpk=qpk, d=d, num_q_blocks=num_q_blocks, sm_scale=sm_scale,
            split_diag=not interpret,
        ),
        grid=(b * g, num_k_blocks, num_q_blocks),
        in_specs=col_specs,
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda h, j, i: (h, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda h, j, i: (h, j, 0)),
        ],
        out_shape=[
            _out_struct((b * g, t, d), k.dtype, qf),
            _out_struct((b * g, t, d), v.dtype, qf),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(qf, kf, vf, dof, lse, delta)

    dq = dq.reshape(b, g, s, qpk, d)[..., :d_true].transpose(0, 2, 1, 3, 4)
    dk = dk.reshape(b, g, t, d)[..., :d_true].transpose(0, 2, 1, 3)
    dv = dv.reshape(b, g, t, d)[..., :d_true].transpose(0, 2, 1, 3)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# custom_vjp wrapper (ref parity: training THROUGH flash attention,
# transformer.py:508-523)
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _flash(config, q, k, v):
    causal, block_q, block_k, interpret = config
    o, _ = _flash_fwd_pallas(q, k, v, causal, block_q, block_k, interpret)
    return o


def _flash_fwd_rule(config, q, k, v):
    causal, block_q, block_k, interpret = config
    o, lse = _flash_fwd_pallas(q, k, v, causal, block_q, block_k, interpret)
    # Named save points on the residuals (models/remat.py): when an outer
    # jax.checkpoint runs a selective/offload policy, keeping o AND the
    # (tiny, b*s*heads fp32) lse rows means the backward consumes the
    # saved residuals directly — the forward kernel is never re-run; only
    # the bwd kernels (which recompute scores tile-by-tile) execute.
    from jax.ad_checkpoint import checkpoint_name

    return o, (q, k, v, checkpoint_name(o, "attn_ctx"),
               checkpoint_name(lse, "flash_lse"))


def _flash_bwd_rule(config, residuals, g):
    causal, block_q, block_k, interpret = config
    q, k, v, o, lse = residuals
    return _flash_bwd_pallas(
        q, k, v, o, lse, g, causal, block_q, block_k, interpret
    )


_flash.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def _lse_rows_to_bsgq(lse_rows, b, s, g, qpk):
    # (b*g, s*qpk, 1) rows-major (head fastest) -> (b, s, g, qpk)
    return lse_rows.reshape(b, g, s, qpk).transpose(0, 2, 1, 3)


def _lse_bsgq_to_rows(lse, b, s, g, qpk):
    return lse.transpose(0, 2, 1, 3).reshape(b * g, s * qpk, 1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _flash_lse(config, q, k, v):
    causal, block_q, block_k, interpret = config
    b, s, g, qpk, _ = q.shape
    o, lse = _flash_fwd_pallas(q, k, v, causal, block_q, block_k, interpret)
    return o, _lse_rows_to_bsgq(lse, b, s, g, qpk)


def _flash_lse_fwd_rule(config, q, k, v):
    causal, block_q, block_k, interpret = config
    b, s, g, qpk, _ = q.shape
    o, lse = _flash_fwd_pallas(q, k, v, causal, block_q, block_k, interpret)
    return (o, _lse_rows_to_bsgq(lse, b, s, g, qpk)), (q, k, v, o, lse)


def _flash_lse_bwd_rule(config, residuals, cts):
    causal, block_q, block_k, interpret = config
    q, k, v, o, lse = residuals
    do, dlse = cts
    b, s, g, qpk, _ = q.shape
    dlse_rows = _lse_bsgq_to_rows(dlse.astype(jnp.float32), b, s, g, qpk)
    return _flash_bwd_pallas(
        q, k, v, o, lse, do, causal, block_q, block_k, interpret,
        dlse_rows=dlse_rows,
    )


_flash_lse.defvjp(_flash_lse_fwd_rule, _flash_lse_bwd_rule)


def flash_attention_with_lse(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    causal: bool = True,
    use_pallas: bool | None = None,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    interpret: bool = False,
):
    """Like `flash_attention` but ALSO returns the per-row logsumexp
    (b, s, g, qpk) fp32, differentiable through both outputs — the
    building block for merging attention across blocks that live on
    different devices (ring attention's per-hop step)."""
    if want_kernel(use_pallas, interpret):
        blocks = _pick_blocks(q.shape[1], k.shape[1], q.shape[3],
                              block_q, block_k)
        if blocks is not None:
            note_kernel("flash_attention")
            return _flash_lse((causal, *blocks, interpret), q, k, v)
        _report_no_blocks(q, k)
    return _xla_reference_with_lse(q, k, v, causal)


def flash_reaches_kernel(q_shape, t: int) -> bool:
    """Whether a default `flash_attention[_with_lse]` call of q
    (b, s, g, qpk, d) against t keys runs the Pallas kernel — what a call
    site under a mesh must know, since only the Mosaic call needs a fully
    manual region (parallel/mesh.shard_kernel)."""
    return want_kernel(None) and _pick_blocks(
        q_shape[1], t, q_shape[3], DEFAULT_BLOCK_Q,
        DEFAULT_BLOCK_K) is not None


def _report_no_blocks(q, k):
    report_fallback("flash_attention", "_pick_blocks", s=q.shape[1],
                    t=k.shape[1], qpk=q.shape[3], d=q.shape[-1])


def _pick_blocks(s, t, qpk, block_q, block_k):
    """Shared block selection for both entry points: shrink to divisors,
    bound the fp32 score block rows*block_k under VMEM (MAX_CELLS).
    Returns (bq, bk) or None for the XLA fallback. The head width is no
    gate: the kernel wrappers pad it to the lane tile (`_pad_lanes`)."""
    bq = _choose_block(s, block_q, qpk)
    bk = _choose_block(t, block_k)
    while (bq is not None and bk is not None and bk > 128
           and bq * qpk * bk > MAX_CELLS):
        bk = _choose_block(t, bk // 2)
    while (bq is not None and bk is not None
           and bq * qpk * bk > MAX_CELLS and bq * qpk > 256):
        bq = _choose_block(s, bq // 2, qpk)
    if bq is None or bk is None:
        return None
    return bq, bk


# graft-contract: ops.flash_attention
@functools.partial(jax.jit, static_argnames=("causal", "use_pallas",
                                             "block_q", "block_k",
                                             "interpret"))
def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    causal: bool = True,
    use_pallas: bool | None = None,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    interpret: bool = False,
) -> jnp.ndarray:
    """GQA flash attention, differentiable. Returns (b, s, g, qpk, d).

    The output is tagged as the "attn_ctx" named save point (and the
    custom-VJP residuals tag o/lse, see _flash_fwd_rule) so the
    named-savepoint remat policies (models/remat.py) can keep it."""
    from jax.ad_checkpoint import checkpoint_name

    if want_kernel(use_pallas, interpret):
        blocks = _pick_blocks(q.shape[1], k.shape[1], q.shape[3],
                              block_q, block_k)
        if blocks is not None:
            note_kernel("flash_attention")
            return checkpoint_name(
                _flash((causal, *blocks, interpret), q, k, v), "attn_ctx"
            )
        _report_no_blocks(q, k)
    return checkpoint_name(_xla_reference(q, k, v, causal), "attn_ctx")
