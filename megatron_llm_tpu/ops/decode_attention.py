"""Pallas decode-attention kernel — batched KV-cached decode at line rate.

The decode hot loop (inference/generation.py while_loop body) attends ONE
query token per sequence against the growing K/V cache. XLA lowers the
single-token QK/PV contractions to multiply-reduce loops that stream the
cache far below HBM bandwidth (measured r5: b=8 decode at 4.7 ms/step vs a
~3 ms weights+cache streaming floor — VERDICT r5 weak #2). This kernel
streams the cache through VMEM the way ops/flash_attention.py streams K/V
blocks in training, with decode-specific structure:

- grid (batch, group, cache_block): one grid step reads each K/V block
  ONCE per GQA group and serves all `q_per_kv` query heads of the group
  from it (the (position, head) fold of the flash kernel, with s == 1);
- the mask / online-softmax / fp32-accumulator core is the shared
  template of ops/flash_attention.py (`_causal_invalid` +
  `_softmax_init/accum/finalize`, ISSUE 18) instantiated at the dense
  standalone-cache parameterization;
- the VALID cache length rides a scalar-prefetch operand: block index
  maps clamp past-the-end blocks to the last valid block (Mosaic elides
  the repeated DMA, so masked grid steps cost no HBM traffic — the cache
  reads scale with the CURRENT length, not the allocated buffer), and
  in-kernel iota masking covers the straddling block — no dense
  (s, T) mask is ever materialized;
- two cache layouts, matching the two decode engines:
  "gtd" (b, g, T, d) — the per-layer standalone caches of the unrolled
  decode path (models/gpt.py init_kv_caches(layout="layers"));
  "tgd" (b, T, g, d) — the per-layer slice of the stacked (L, b, T, g, d)
  caches the pipelined stage-ring decode carries (parallel/pipeline.py).
  Both are consumed in place; neither is transposed or copied.

`decode_attention` dispatches to the kernel on TPU (or under
`interpret=True` through the Pallas interpreter — the CPU test path) and
to `_xla_decode`, a numerically matching reference, elsewhere.
`decode_attn_block` is the static viability check the model layer gates
on; it returns the chosen cache block size or None (XLA fallback).

This module serves DENSE per-sequence caches only. The continuous-
batching engine's paged pool — every phase of it, decode rows included,
fp and int8 — is served by THE ragged paged attention kernel in
ops/prefill_attention.py (ISSUE 18 collapsed the former paged decode /
ragged prefill / int8-twin fork into that one kernel; a decode step is
its width-1 chunk). `_xla_decode` here is a layout shim over the shared
`_xla_attend` dense core of that module.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from megatron_llm_tpu.ops import dispatch
from megatron_llm_tpu.ops.flash_attention import (
    LOG2E,
    _causal_invalid,
    _out_struct,
    _softmax_accum,
    _softmax_finalize,
    _softmax_init,
    NEG_INF,
)
from megatron_llm_tpu.ops.prefill_attention import _xla_attend

# swept space: 256 balances DMA amortization against the clamp granularity
# (past-the-end traffic is at most one block); _choose_block_t shrinks to
# the largest power-of-2 divisor of the allocated cache length.
DEFAULT_BLOCK_T = 256
# folded (position, head) rows per sequence-group — decode is s == 1 so
# this only bites exotic MQA configs (q_per_kv > 128)
MAX_DECODE_ROWS = 128


def _choose_block_t(T: int, requested: int = DEFAULT_BLOCK_T) -> Optional[int]:
    """Largest power-of-2 block <= requested dividing the allocated cache
    length T. Min 16 keeps bf16 sublane tiling; None -> XLA fallback."""
    b = 1 << (min(requested, T).bit_length() - 1)
    while b >= 16 and T % b:
        b //= 2
    return b if b >= 16 and T % b == 0 else None


def decode_attn_block(s: int, qpk: int, d: int, T: int, *,
                      min_cache: int = 0,
                      requested: int = DEFAULT_BLOCK_T,
                      interpret: bool = False) -> Optional[int]:
    """Static dispatch check for the decode kernel: returns the cache
    block size, or None when the XLA path should serve this shape.

    Kernel territory: single-token steps (s == 1 — prefill chunks keep
    the batched-GEMM path, which is compute- not bandwidth-bound), lane-
    aligned head_dim, an allocated cache at least `min_cache` long (below
    that the matvecs are too small for kernel launch overhead to pay),
    and a power-of-2 block dividing T. On CPU the kernel only runs under
    the interpreter (the test path); otherwise TPU-only, mirroring
    flash_attention's backend dispatch.
    """
    if not (interpret or dispatch.on_tpu()):
        return None
    if s != 1 or s * qpk > MAX_DECODE_ROWS or d % 128 != 0:
        return None
    if T < max(min_cache, 16):
        return None
    return _choose_block_t(T, requested)


# ---------------------------------------------------------------------------
# Kernel
# ---------------------------------------------------------------------------


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr,
                   acc_scr, *, block_t, rows, qpk, d, num_t_blocks,
                   sm_scale, s, split_boundary=True):
    """Grid (b, g, num_t_blocks); the t dim carries the online-softmax
    state in VMEM scratch. Row r of the folded (rows, d) q block is query
    position offset + r // qpk (head fastest), offset = length - s. The
    shared flash template at the dense decode parameterization: causal
    predicate `col <= offset + row`, no pad rows (every row is a live
    query token)."""
    j = pl.program_id(2)
    length = len_ref[0]
    offset = length - s

    @pl.when(j == 0)
    def _init():
        _softmax_init(m_scr, l_scr, acc_scr)

    def _accum(masked):
        # fp32 QK on tiny row counts: decode is cache-bandwidth-bound, so
        # MXU precision costs nothing; scores live in the exp2 domain
        # (sm_scale folded with log2(e), flash kernel convention)
        qb = q_ref[:].reshape(rows, d)
        kb = k_ref[:].reshape(block_t, d).astype(jnp.float32)
        sc = jax.lax.dot_general(
            qb.astype(jnp.float32), kb,
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * (sm_scale * LOG2E)
        if masked:
            # causal-within-step + cache-length mask in one predicate:
            # col c valid for row r iff c <= offset + r//qpk
            sc = jnp.where(
                _causal_invalid(rows, block_t, qpk, offset, j * block_t),
                NEG_INF, sc,
            )
        _softmax_accum(sc, v_ref[:].reshape(block_t, d), m_scr, l_scr,
                       acc_scr, p_dtype=v_ref.dtype)

    # blocks entirely past the valid length skip compute (their DMA was
    # clamped to the last valid block by the index map); interior blocks
    # (fully <= offset, every row) run maskless — only the straddling
    # block pays the iota/select VPU work. split_boundary=False under the
    # interpreter (two-branch grid steps trip its vma unification, same
    # workaround as the flash kernels' split_diag).
    run = (j * block_t) < length
    if split_boundary:
        interior = (j * block_t + block_t - 1) <= offset

        @pl.when(run & interior)
        def _compute_interior():
            _accum(False)

        @pl.when(run & ~interior)
        def _compute_boundary():
            _accum(True)
    else:
        @pl.when(run)
        def _compute():
            _accum(True)

    @pl.when(j == num_t_blocks - 1)
    def _finalize():
        out, _ = _softmax_finalize(l_scr, acc_scr)
        o_ref[:] = out.astype(o_ref.dtype).reshape(o_ref.shape)


def _decode_pallas(q, k, v, length, layout, block_t, interpret):
    """q: (b, s, g, qpk, d); k/v per `layout`; length: scalar int32
    (traced OK) = offset + s valid cache positions. Returns
    (b, s, g, qpk, d) in q's dtype."""
    b, s, g, qpk, d = q.shape
    T = k.shape[2] if layout == "gtd" else k.shape[1]
    rows = s * qpk
    num_t_blocks = T // block_t
    assert T % block_t == 0

    qf = q.transpose(0, 2, 1, 3, 4).reshape(b, g, rows, d)
    # rows below one fp32 sublane tile: launch q/o in fp32 so Mosaic picks
    # a <1x128>-compatible layout for the small memref (the same
    # workaround JAX's paged-attention kernel ships for qpk % 8 != 0)
    out_dtype = q.dtype if rows % 8 == 0 else jnp.float32
    qf = qf.astype(out_dtype)

    kernel = functools.partial(
        _decode_kernel, block_t=block_t, rows=rows, qpk=qpk, d=d,
        num_t_blocks=num_t_blocks, sm_scale=1.0 / (d ** 0.5), s=s,
        split_boundary=not interpret,
    )

    def last_block(len_ref):
        # clamp past-the-end block indices to the last valid block: the
        # repeated index elides the DMA, so cache traffic follows the
        # CURRENT length, not the allocated T
        return jnp.minimum((len_ref[0] - 1) // block_t, num_t_blocks - 1)

    q_spec = pl.BlockSpec((None, None, rows, d),
                          lambda ib, ig, j, len_ref: (ib, ig, 0, 0))
    if layout == "gtd":
        kv_spec = pl.BlockSpec(
            (None, None, block_t, d),
            lambda ib, ig, j, len_ref: (
                ib, ig, jnp.minimum(j, last_block(len_ref)), 0
            ),
        )
    else:  # "tgd"
        # a (block_t, d) block of (b, T, g, d) would squeeze the second-
        # minor group axis, which Mosaic refuses; the free row-major
        # (b, T, g*d) view puts group ig at lane block ig instead
        k = k.reshape(b, T, g * d)
        v = v.reshape(b, T, g * d)
        kv_spec = pl.BlockSpec(
            (None, block_t, d),
            lambda ib, ig, j, len_ref: (
                ib, jnp.minimum(j, last_block(len_ref)), ig
            ),
        )

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, g, num_t_blocks),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((rows, 1), jnp.float32),
            pltpu.VMEM((rows, 1), jnp.float32),
            pltpu.VMEM((rows, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=_out_struct((b, g, rows, d), out_dtype, qf, k, v),
        # (b, g) steps are independent; only the cache dim carries the
        # online-softmax scratch state
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(jnp.asarray(length, jnp.int32).reshape((1,)), qf, k, v)
    return out.reshape(b, g, s, qpk, d).transpose(0, 2, 1, 3, 4) \
        .astype(q.dtype)


# ---------------------------------------------------------------------------
# XLA reference (the pre-kernel decode math, both layouts): a layout shim
# over the shared `_xla_attend` dense core (ops/prefill_attention.py)
# ---------------------------------------------------------------------------


def _xla_decode(q, k, v, length, layout):
    """Batched-GEMM decode attention with the O(s*T) iota mask — the
    shapes-and-math twin of the kernel, used off-TPU and by the exact-
    match tests/bench comparisons."""
    b, s, g, qpk, d = q.shape
    if layout == "tgd":
        k = k.transpose(0, 2, 1, 3)
        v = v.transpose(0, 2, 1, 3)
    row_pos = (length - s) + jnp.arange(s * qpk) // qpk
    return _xla_attend(q, k, v, row_pos)


def decode_attention(
    q: jnp.ndarray,  # (b, s, g, qpk, d)
    k: jnp.ndarray,  # (b, g, T, d) "gtd" | (b, T, g, d) "tgd"
    v: jnp.ndarray,
    length,  # scalar int32 (traced OK): valid cache positions = offset + s
    layout: str = "gtd",
    use_pallas: Optional[bool] = None,
    block_t: int = DEFAULT_BLOCK_T,
    interpret: bool = False,
) -> jnp.ndarray:
    """KV-cached decode attention, (b, s, g, qpk, d) out. Positions
    >= `length` are masked in-kernel; within the step rows are causal
    (row r attends through position length - s + r)."""
    assert layout in ("gtd", "tgd"), layout
    if dispatch.want_kernel(use_pallas, interpret):
        b, s, g, qpk, d = q.shape
        T = k.shape[2] if layout == "gtd" else k.shape[1]
        bt = decode_attn_block(s, qpk, d, T, requested=block_t,
                               interpret=interpret)
        if bt is not None:
            dispatch.note_kernel("decode_attention")
            return _decode_pallas(q, k, v, length, layout, bt, interpret)
        dispatch.report_fallback("decode_attention", "decode_attn_block", s=s,
                        qpk=qpk, d=d, T=T, layout=layout)
    return _xla_decode(q, k, v, length, layout)
