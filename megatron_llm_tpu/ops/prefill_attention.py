"""THE ragged paged attention kernel (ISSUE 18 tentpole, after Ragged
Paged Attention — arxiv 2604.15464): one Pallas kernel serves every
inference phase of the continuous-batching engine.

The paged kernel family used to be a six-way fork — paged decode, ragged
prefill, and int8-quantized twins of both, next to flash (train) and
dense decode — the same exp2-online-softmax inner loop written ~6 ways,
each needing its own parity suite and its own GSPMD check under the tp
serving mesh. This module collapses the paged side to ONE kernel:

- **phase is a shape, not a variant**: a launch serves a batch of
  ragged QUERY CHUNKS — each a contiguous span of one slot's prompt at
  an arbitrary start offset — and a single-token decode row IS the
  width-1 chunk at offset `length` (chunk_lens == 1). The engine's
  decode scan, mixed prefill+decode rounds, and spec-verify steps all
  dispatch here (models/attention.py, ONE paged branch); the retired
  standalone paged decode entry is this kernel at C == 1, pinned
  bitwise by the suites before the fork was deleted.
- **kv dtype is a kernel parameter, not a variant**: fp pools run the
  plain epilogue; int8 pools (per-(token, group) fp32 scale columns in
  parallel scale pools, ISSUE 9) select the in-register dequant
  epilogue — the scale column rides the SAME clamped page index map as
  its data, and the fp32 online-softmax math is unchanged.
- **the mask/accumulator core is the shared template** of
  ops/flash_attention.py (`_causal_invalid` + `_softmax_init/accum/
  finalize`): flash instantiates it for dense training, the dense
  decode kernel for standalone caches, and this kernel for the paged
  pool — mask shapes are pluggable predicates: sliding-window
  attention (`window_size`) and packed-doc floors (`doc_starts`,
  ISSUE 19) are predicate parameterizations of this one body riding
  a double-ended DMA clamp, not new kernels.

Kernel structure:

- grid (chunk, group, q_block, page): each grid step reads one pool
  page ONCE per GQA group and serves all `q_per_kv` query heads of the
  group from it; the page dim carries the online-softmax state in VMEM
  scratch (exp2 domain, fp32 accumulation — the flash forward scheme);
- the per-chunk START OFFSET and VALID LENGTH ride scalar-prefetch
  operands: causal-within-chunk masking is `col <= start + row`, rows
  past the chunk's valid length are pad (exact-zero output), and the
  K/V index map dereferences the page table with past-the-need pages
  clamped to the last needed page — Mosaic elides the repeated DMA, so
  cache traffic follows `start + len`, not the allocated table width;
- interior/boundary split: page blocks fully below the causal diagonal
  and fully inside the valid length run maskless; only straddling
  blocks pay the iota/select VPU work (split_boundary=False under the
  interpreter, the same vma workaround as the flash/decode kernels).

`ragged_paged_attention` is the ONE public paged entry point (a tier-1
guard in tests/test_static_analysis.py holds it at one): it first
SCATTERS the chunk's own K/V into its slot's pages (valid rows only;
pad rows land on the pool's dead null page 0; int8 pools quantize at
write through ops/quantization.scatter_quantized_rows), then attends —
one jitted pass, so the chunk's in-span causal columns are read back
from the pool it just wrote. `_xla_paged_reference` (gather pages to
the dense view, then the `_xla_attend` dense core — also parameterized
by kv dtype) is the numerically matching fallback, the off-TPU serving
path, and the one test oracle; `interpret=True` runs the real kernel
through the Pallas interpreter.
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
    NEG_INF,
    _causal_invalid,
    _out_struct,
    _softmax_accum,
    _softmax_finalize,
    _softmax_init,
)

# folded (token, head) rows per grid program — the flash kernels' VMEM
# bound for the fp32 score block and accumulator
MAX_PAGED_ROWS = 2048


def _choose_block_q(C: int, qpk: int) -> Optional[int]:
    """Largest power-of-2 q block (in TOKENS) dividing the padded chunk
    width C with folded rows (block * qpk) under MAX_PAGED_ROWS.
    Chunks of any width >= 1 are served (the engine's width buckets are
    pow2; C == 1 is the decode row); None only when no divisor fits."""
    b = 1 << (C.bit_length() - 1)
    while b > 1 and (C % b or b * qpk > MAX_PAGED_ROWS):
        b //= 2
    return b if C % b == 0 and b * qpk <= MAX_PAGED_ROWS else None


def ragged_paged_block(s: int, qpk: int, d: int, page_size: int,
                       num_slot_pages: int, *,
                       min_cache: int = 0,
                       kv_dtype=None,
                       interpret: bool = False) -> Optional[int]:
    """Static dispatch check for the unified paged kernel: returns the
    q block size (tokens per grid program) or None for the XLA path.

    Kernel territory: lane-aligned head dim, a page that tiles sublanes
    (the page IS the K/V DMA unit — 16 covers bf16/fp32, int8 pools
    need the 32 int8 sublane tile), TPU-or-interpreter backend, and a
    per-slot reach num_slot_pages * page_size of at least `min_cache`.
    ONE gate for every phase: a decode row (s == 1) takes the same
    kernel-vs-XLA decision it would take as a width-1 chunk of a mixed
    step on the same pool, so a near-tie argmax can never flip when
    admission starts mid-stream.
    """
    if not (interpret or dispatch.on_tpu()):
        return None
    if s < 1 or d % 128 != 0:
        return None
    is_int8 = kv_dtype is not None and jnp.dtype(kv_dtype) == jnp.int8
    sublane = 32 if is_int8 else 16
    if page_size < sublane or page_size % sublane != 0:
        return None
    if num_slot_pages * page_size < max(min_cache, 16):
        return None
    return _choose_block_q(s, qpk)


# ---------------------------------------------------------------------------
# Kernel
# ---------------------------------------------------------------------------


def _paged_kernel(starts_ref, lens_ref, pt_ref, *rest, block_q,
                  page_size, qpk, d, num_pages, sm_scale,
                  split_boundary=True, quantized=False, window=None,
                  has_doc=False):
    """Grid (chunk, group, q_block, page); the page dim carries the
    online-softmax state. Row r of the folded (block_q*qpk, d) q block
    is chunk token i*block_q + r // qpk (head fastest) at global
    position starts[c] + token; rows at tokens >= lens[c] are pad.
    `quantized` selects the int8-KV epilogue (ISSUE 9): k/v arrive int8
    with the page's per-(token, group) fp32 scales as two extra
    (page_size, g) operands — this group's column is picked out
    in-register — and are dequantized before the unchanged fp32
    template math.

    Lower-bound masks (ISSUE 19) are extra parameterizations of the
    SAME body, not new kernels — both default off, and off means the
    emitted program is the pre-window one:
    - `window` (static int): sliding-window attention — row at
      position p attends cols [p - window + 1, p]. Pages wholly below
      the q block's FIRST row's window floor drop out of `run` (and
      the index map clamps them to the first needed page, eliding the
      DMA), pages below the LAST row's floor leave `interior`, so the
      window boundary pays the mask exactly like the causal boundary.
    - `has_doc`: a fourth scalar-prefetch operand doc_starts (nc,)
      gives each chunk an attention FLOOR (its packed document's first
      position); cols below it mask out, resetting causality at doc
      boundaries. Requires doc_starts[c] <= starts[c] so every valid
      row keeps its own diagonal column."""
    if has_doc:
        doc_ref, *rest = rest
    q_ref, k_ref, v_ref, *rest = rest
    if quantized:
        ks_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr = rest
    else:
        o_ref, m_scr, l_scr, acc_scr = rest
    c = pl.program_id(0)
    gi = pl.program_id(1)
    i = pl.program_id(2)
    j = pl.program_id(3)
    rows = block_q * qpk
    start = starts_ref[c]
    clen = lens_ref[c]
    doc0 = doc_ref[c] if has_doc else None

    def _scale_col(s_ref):
        # Mosaic takes a scale block only at the pool's full (page_size,
        # g) trailing dims; a one-hot lane reduce picks this grid step's
        # group column out of it as the (page_size, 1) the dequant needs
        sc = s_ref[:]
        lane = jax.lax.broadcasted_iota(jnp.int32, sc.shape, 1)
        return jnp.sum(jnp.where(lane == gi, sc, 0.0), axis=1,
                       keepdims=True)

    @pl.when(j == 0)
    def _init():
        _softmax_init(m_scr, l_scr, acc_scr)

    def _accum(masked):
        qb = q_ref[:].reshape(rows, d)
        kb = k_ref[:].reshape(page_size, d).astype(jnp.float32)
        if quantized:
            # dequantize in-register against the page's scale column —
            # HBM saw only the int8 bytes
            kb = kb * _scale_col(ks_ref)
        sc = jax.lax.dot_general(
            qb.astype(jnp.float32), kb,
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * (sm_scale * LOG2E)
        if masked:
            # the shared causal predicate at the ragged-chunk
            # parameterization: token t of the chunk sits at position
            # start + t, may see cols <= start + t, and is pad when
            # t >= len (pad rows mask EVERY column -> the finalize
            # clamp emits exact zeros, the empty-slot contract).
            # NEG_INF is a finite constant: a PAD row would degenerate
            # to exp2(0)-everywhere garbage, so the finalize re-masks
            # pad rows; valid rows always have a real max (page 0,
            # col 0 is causal for every row), so their masked cells
            # underflow to exact 0.
            sc = jnp.where(
                _causal_invalid(rows, page_size, qpk,
                                start + i * block_q, j * page_size,
                                valid_rows=clen - i * block_q,
                                window=window, floor=doc0),
                NEG_INF, sc,
            )
        if quantized:
            vb = v_ref[:].reshape(page_size, d).astype(jnp.float32) \
                * _scale_col(vs_ref)
            _softmax_accum(sc, vb, m_scr, l_scr, acc_scr)
        else:
            _softmax_accum(sc, v_ref[:].reshape(page_size, d), m_scr,
                           l_scr, acc_scr, p_dtype=v_ref.dtype)

    # last position this q block's VALID rows can attend: the block's
    # last valid token (or nothing when the block is all pad)
    blk_last_tok = jnp.minimum((i + 1) * block_q, clen) - 1
    run = (i * block_q < clen) & \
        ((j * page_size) <= (start + blk_last_tok))
    if window is not None or has_doc:
        # symmetric lower skip: pages wholly below even the FIRST
        # row's floor serve no row of this q block. For window >=
        # context the floor is never positive and the predicate (like
        # the clamp) never binds — bitwise the dense program.
        first_lo = jnp.int32(0)
        if window is not None:
            first_lo = jnp.maximum(first_lo,
                                   start + i * block_q - (window - 1))
        if has_doc:
            first_lo = jnp.maximum(first_lo, doc0)
        run = run & ((j * page_size + page_size - 1) >= first_lo)
    if split_boundary:
        # maskless when every row is valid AND every column is causal
        # for even the block's FIRST token
        interior = ((i + 1) * block_q <= clen) & \
            ((j * page_size + page_size - 1) <= (start + i * block_q))
        if window is not None:
            # ... AND in-window for even the LAST token's floor
            interior = interior & \
                ((j * page_size) >= (start + (i + 1) * block_q - window))
        if has_doc:
            interior = interior & ((j * page_size) >= doc0)

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

    @pl.when(j == num_pages - 1)
    def _finalize():
        out, _ = _softmax_finalize(l_scr, acc_scr)
        # pad rows accumulated garbage above (see the mask note): pin
        # them to the exact-zero contract of the XLA twin
        row_tok = i * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (rows, d), 0) // qpk
        out = jnp.where(row_tok < clen, out, 0.0)
        o_ref[:] = out.astype(o_ref.dtype).reshape(o_ref.shape)


def _paged_pallas(q, k_pages, v_pages, page_table, starts, chunk_lens,
                  block_q, interpret, k_scales=None, v_scales=None,
                  window=None, doc_starts=None):
    """q: (nc, C, g, qpk, d); k/v_pages: (P, page_size, g, d);
    page_table: (nc, max_pages) int32; starts/chunk_lens: (nc,) int32.
    k/v_scales (int8 pools only): (P, page_size, g) fp32 per-(token,
    group) scales riding the same clamped page index map. `window`
    (static) / `doc_starts` ((nc,) int32, a 4th scalar-prefetch
    operand) add the ISSUE 19 lower bounds: the page index map then
    clamps BOTH ends, so out-of-window / pre-document pages repeat an
    in-bound index and Mosaic elides their DMAs — decode-row traffic
    is O(window), not O(context). Returns (nc, C, g, qpk, d) in q's
    dtype (pad rows exact zero)."""
    nc, C, g, qpk, d = q.shape
    page_size = k_pages.shape[1]
    max_pages = page_table.shape[1]
    rows = block_q * qpk
    num_q_blocks = C // block_q
    quantized = k_scales is not None
    has_doc = doc_starts is not None

    qf = q.transpose(0, 2, 1, 3, 4).reshape(nc, g, C * qpk, d)
    # Mosaic wants a block's last two dims tile-aligned or whole, and a
    # (page_size, d) block of the (P, page_size, g, d) pool would
    # squeeze the second-minor group axis. The row-major (P, page_size,
    # g*d) view is free and puts group gi at lane block gi instead.
    k_pages = k_pages.reshape(*k_pages.shape[:2], g * d)
    v_pages = v_pages.reshape(*v_pages.shape[:2], g * d)
    # rows below one fp32 sublane tile: launch q/o in fp32 (the small-
    # memref Mosaic workaround shared with the dense decode kernel)
    out_dtype = q.dtype if rows % 8 == 0 else jnp.float32
    qf = qf.astype(out_dtype)

    kernel = functools.partial(
        _paged_kernel, block_q=block_q, page_size=page_size, qpk=qpk,
        d=d, num_pages=max_pages, sm_scale=1.0 / (d ** 0.5),
        split_boundary=not interpret, quantized=quantized,
        window=window, has_doc=has_doc,
    )

    def page_index(c, i, j, starts_ref, lens_ref, pt_ref, doc_ref=None):
        # clamp past-the-need page indices to the LAST page this q block
        # attends (repeated index -> elided DMA): traffic follows
        # start + len, not the allocated table width. All-pad blocks and
        # empty chunks clamp to table entry 0 (the slot's null-page
        # parking by engine convention — always a real, dead page).
        last_tok = jnp.minimum((i + 1) * block_q,
                               jnp.maximum(lens_ref[c], 1)) - 1
        last = jnp.clip((starts_ref[c] + last_tok) // page_size,
                        0, max_pages - 1)
        if window is None and doc_ref is None:
            return pt_ref[c, jnp.minimum(j, last)]
        # symmetric LOWER clamp (ISSUE 19): pages wholly before the q
        # block's first row's window floor / the chunk's document
        # start repeat the first needed page — same elision, so the
        # engine may reclaim the pages behind it (the kernel can never
        # dereference a table entry below `first` by construction).
        # window >= context keeps the floor at 0 == bitwise-dense.
        lo = jnp.int32(0)
        if window is not None:
            lo = jnp.maximum(
                lo, starts_ref[c] + i * block_q - (window - 1))
        if doc_ref is not None:
            lo = jnp.maximum(lo, doc_ref[c])
        first = jnp.clip(lo // page_size, 0, max_pages - 1)
        return pt_ref[c, jnp.clip(j, first, last)]

    q_spec = pl.BlockSpec(
        (None, None, rows, d),
        lambda c, gi, i, j, *s_refs: (c, gi, i, 0),
    )
    kv_spec = pl.BlockSpec(
        (None, page_size, d),
        lambda c, gi, i, j, *s_refs: (
            page_index(c, i, j, *s_refs), 0, gi
        ),
    )
    in_specs = [q_spec, kv_spec, kv_spec]
    operands = [qf, k_pages, v_pages]
    if quantized:
        scale_spec = pl.BlockSpec(
            (None, page_size, g),
            lambda c, gi, i, j, *s_refs: (
                page_index(c, i, j, *s_refs), 0, 0
            ),
        )
        in_specs += [scale_spec, scale_spec]
        operands += [k_scales, v_scales]
    scalars = [jnp.asarray(starts, jnp.int32),
               jnp.asarray(chunk_lens, jnp.int32),
               jnp.asarray(page_table, jnp.int32)]
    if has_doc:
        scalars.append(jnp.asarray(doc_starts, jnp.int32))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(nc, g, num_q_blocks, max_pages),
        in_specs=in_specs,
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
        out_shape=_out_struct((nc, g, C * qpk, d), out_dtype, qf, k_pages,
                              v_pages),
        # (chunk, group, q_block) steps are independent; only the page
        # dim carries the online-softmax scratch state
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
        ),
        interpret=interpret,
    )(*scalars, *operands)
    return out.reshape(nc, g, C, qpk, d).transpose(0, 2, 1, 3, 4) \
        .astype(q.dtype)


# ---------------------------------------------------------------------------
# XLA reference: ONE gather-pages-then-dense definition (ISSUE 18
# satellite — the former per-variant oracle twins, paged decode and
# ragged prefill each with a quantized sibling, collapsed)
# ---------------------------------------------------------------------------


def _xla_attend(q, k, v, row_pos, row_valid=None, row_lo=None):
    """The dense masked-softmax core every XLA attention twin shares:
    q (b, s, g, qpk, d) against dense k/v (b, g, T, d). `row_pos` is the
    last attendable cache position per folded row — (rows,) when shared
    across the batch (the dense decode twin), (b, rows) when ragged per
    sequence (the paged twin). `row_valid` (b, rows), optional: rows
    where False pin to exact zero (the pad-row / empty-chunk contract);
    None skips the select entirely so the dense twin's HLO is
    unchanged. `row_lo` (b, rows), optional: the FIRST attendable cache
    position per folded row (the sliding-window / packed-doc lower
    bound, ISSUE 19) — None skips that select the same way. Masked
    columns multiply unwritten (or reclaimed-and-reused) cache by an
    exact fp 0, so the allocated width never leaks into values."""
    b, s, g, qpk, d = q.shape
    T = k.shape[2]
    qb = q.transpose(0, 2, 1, 3, 4).reshape(b, g, s * qpk, d)
    scores = jax.lax.dot_general(
        qb, k, (((3,), (3,)), ((0, 1), (0, 1))),
        preferred_element_type=jnp.float32,
    ) * (1.0 / jnp.sqrt(d).astype(jnp.float32))  # (b, g, s*qpk, T)
    if row_pos.ndim == 1:
        mask = jnp.arange(T)[None, :] > row_pos[:, None]
        scores = jnp.where(mask[None, None], jnp.finfo(jnp.float32).min,
                           scores)
    else:
        mask = jnp.arange(T)[None, None, :] > row_pos[:, :, None]
        scores = jnp.where(mask[:, None], jnp.finfo(jnp.float32).min,
                           scores)
    if row_lo is not None:
        lo_mask = jnp.arange(T)[None, None, :] < row_lo[:, :, None]
        scores = jnp.where(lo_mask[:, None], jnp.finfo(jnp.float32).min,
                           scores)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jax.lax.dot_general(
        probs, v, (((3,), (2,)), ((0, 1), (0, 1))),
    )  # (b, g, s*qpk, d)
    if row_valid is not None:
        out = jnp.where(row_valid[:, None, :, None], out,
                        jnp.zeros((), out.dtype))
    return out.reshape(b, g, s, qpk, d).transpose(0, 2, 1, 3, 4)


def _xla_paged_reference(q, k_pages, v_pages, page_table, starts,
                         chunk_lens, k_scales=None, v_scales=None,
                         window=None, doc_starts=None):
    """Gather each chunk's pages into the dense view, then the
    `_xla_attend` core with ragged per-chunk row positions — the
    shapes-and-math twin of the kernel, the off-TPU serving path, and
    the ONE parity-test oracle. kv dtype is a parameter here too:
    int8 pools pass their scale pools and dequantize to the fp32 view
    first (the quantize-then-dequantize oracle — the same fp32 values
    the kernel's in-register epilogue feeds the same math). Pad rows
    (token >= chunk_lens) pin to the kernel's exact-zero output.
    `window` / `doc_starts` (ISSUE 19) become a per-row lower bound
    row_lo = max(pos - window + 1, doc_starts[c], 0): this path
    GATHERS every table entry (reclaimed entries park on null page 0),
    but the lower mask multiplies those columns by an exact fp 0, so
    mid-flight page reclamation is bitwise-invisible here too."""
    nc, C, g, qpk, d = q.shape
    if k_scales is not None:
        k_pages = k_pages.astype(jnp.float32) * k_scales[..., None]
        v_pages = v_pages.astype(jnp.float32) * v_scales[..., None]
    page_size = k_pages.shape[1]
    max_pages = page_table.shape[1]
    T = max_pages * page_size
    with jax.named_scope("page_gather"):
        k = k_pages[page_table].reshape(nc, T, g, d).transpose(0, 2, 1, 3)
        v = v_pages[page_table].reshape(nc, T, g, d).transpose(0, 2, 1, 3)
    tok = jnp.arange(C * qpk) // qpk  # (rows,)
    row_pos = starts[:, None] + tok[None, :]  # (nc, rows)
    row_valid = tok[None, :] < chunk_lens[:, None]  # (nc, rows)
    row_lo = None
    if window is not None or doc_starts is not None:
        row_lo = jnp.zeros_like(row_pos)
        if window is not None:
            row_lo = jnp.maximum(row_lo, row_pos - (window - 1))
        if doc_starts is not None:
            row_lo = jnp.maximum(row_lo, doc_starts[:, None])
    return _xla_attend(q, k, v, row_pos, row_valid=row_valid,
                       row_lo=row_lo)


@jax.named_scope("kv_write")
def scatter_chunk_kv(k_new, v_new, k_pages, v_pages, page_table, starts,
                     chunk_lens, k_scales=None, v_scales=None):
    """Write a chunk's K/V rows into its slot's pages: token t (valid,
    t < chunk_lens) lands in pool page page_table[c, (starts+t) //
    page_size] at offset (starts+t) % page_size. Pad rows are routed to
    pool page 0 — the dead null page every table parks unowned entries
    on — so they can never touch a live slot's cache. Returns the
    updated pools. The decode scan's single-token write is the C == 1
    case of this one scatter (retired slots carry all-null table rows,
    so their row lands on the null page like a pad row would).

    Int8 pools (k_pages.dtype == int8; pass the matching k/v_scales
    pools): this IS the quantize-at-write point — k_new/v_new arrive fp,
    each (token, group) row quantizes symmetrically over the head dim
    (ops/quantization.quantize_rows), the int8 data lands in the data
    pools and the fp32 scales land at the SAME [page, offset] of the
    scale pools (pad-row scales go to the null page with their data).
    Returns (k_pages, v_pages, k_scales, v_scales)."""
    nc, C = k_new.shape[:2]
    page_size = k_pages.shape[1]
    max_pages = page_table.shape[1]
    quantized = k_pages.dtype == jnp.int8
    pos = starts[:, None] + jnp.arange(C)[None, :]  # (nc, C)
    valid = jnp.arange(C)[None, :] < chunk_lens[:, None]
    logical = jnp.clip(pos // page_size, 0, max_pages - 1)
    pages = jnp.where(
        valid, jnp.take_along_axis(page_table, logical, axis=1), 0)
    offs = pos % page_size
    if quantized:
        from megatron_llm_tpu.ops.quantization import (
            scatter_quantized_rows,
        )

        assert k_scales is not None and v_scales is not None, \
            "int8 KV pools require k_scales/v_scales"
        k_pages, k_scales = scatter_quantized_rows(
            k_pages, k_scales, pages, offs, k_new)
        v_pages, v_scales = scatter_quantized_rows(
            v_pages, v_scales, pages, offs, v_new)
        return k_pages, v_pages, k_scales, v_scales
    k_pages = k_pages.at[pages, offs].set(k_new.astype(k_pages.dtype))
    v_pages = v_pages.at[pages, offs].set(v_new.astype(v_pages.dtype))
    return k_pages, v_pages


def ragged_paged_attention(
    q: jnp.ndarray,  # (nc, C, g, qpk, d) — C = padded chunk width
    k_new: jnp.ndarray,  # (nc, C, g, d) — this chunk's K (RoPE applied)
    v_new: jnp.ndarray,  # (nc, C, g, d)
    k_pages: jnp.ndarray,  # (num_pages, page_size, g, d); int8 OK
    v_pages: jnp.ndarray,
    page_table: jnp.ndarray,  # (nc, max_pages) int32 pool indices
    starts: jnp.ndarray,  # (nc,) int32 — chunk start offset in the slot
    chunk_lens: jnp.ndarray,  # (nc,) int32 valid tokens (<= C; 0 = idle)
    use_pallas: Optional[bool] = None,
    min_cache: int = 0,
    interpret: bool = False,
    k_scales: Optional[jnp.ndarray] = None,  # (num_pages, page_size, g)
    v_scales: Optional[jnp.ndarray] = None,  # fp32; required for int8
    window_size: Optional[int] = None,  # static; None/<=0 = full causal
    doc_starts: Optional[jnp.ndarray] = None,  # (nc,) int32 doc floors
):
    """THE paged attention entry point, one pass for every phase:
    scatter the chunk's own K/V into its slot's pages, then causal
    attention of chunk token t (global position starts + t) over cache
    positions 0..starts+t — served by the Pallas kernel on TPU (or
    under the interpreter) and by the gather-pages twin elsewhere.

    Phase is a shape: a decode row is chunk_lens == 1 at starts ==
    lengths (C == 1 in the engine's decode scan and for the decode
    rows of a mixed round; any C in a spec-verify round), a prefill
    span is chunk_lens in 2..C (a mixed round's one chunk, nc == 1), an
    idle slot is chunk_lens == 0. Returns (out (nc, C, g, qpk, d), k_pages,
    v_pages); pad rows (t >= chunk_lens) are exact zeros.

    kv dtype is a parameter (ISSUE 9): int8 pools pass the fp32 scale
    pools too — the scatter quantizes the chunk's fp K/V at write time,
    attention dequantizes in-register (kernel) or on the gathered view
    (XLA twin), and the return grows to (out, k_pages, v_pages,
    k_scales, v_scales).

    Window is a parameter too (ISSUE 19): `window_size` W restricts
    token t to cache positions [max(0, starts + t - W + 1), starts + t]
    in BOTH paths — the kernel's double-ended DMA clamp makes the read
    O(W), the twin masks the same columns to exact-0 probabilities, and
    W >= starts + chunk_lens (window covers the context) is bitwise the
    W=None program, so the engine may reclaim pages wholly below every
    live window. `doc_starts` (per-chunk floors, doc_starts[c] <=
    starts[c]) packs multiple documents into one ragged launch with
    zero cross-doc attention: give each document its own chunk over the
    same slot pages and its own start, floored at its first position.
    Both default to None == the pre-ISSUE-19 trace, byte-identical."""
    nc, C, g, qpk, d = q.shape
    if window_size is not None and window_size <= 0:
        window_size = None
    quantized = k_pages.dtype == jnp.int8
    if quantized:
        k_pages, v_pages, k_scales, v_scales = scatter_chunk_kv(
            k_new, v_new, k_pages, v_pages, page_table, starts,
            chunk_lens, k_scales=k_scales, v_scales=v_scales)
    else:
        k_pages, v_pages = scatter_chunk_kv(
            k_new, v_new, k_pages, v_pages, page_table, starts,
            chunk_lens)
    if dispatch.want_kernel(use_pallas, interpret):
        bq = ragged_paged_block(C, qpk, d, k_pages.shape[1],
                                page_table.shape[1],
                                min_cache=min_cache,
                                kv_dtype=k_pages.dtype,
                                interpret=interpret)
        if bq is None:
            # a reach below min_cache is the caller's routing, not a
            # refusal
            if page_table.shape[1] * k_pages.shape[1] >= min_cache:
                dispatch.report_fallback(
                    "ragged_paged_attention", "ragged_paged_block", C=C,
                    qpk=qpk, d=d, page_size=k_pages.shape[1],
                    slot_pages=page_table.shape[1], kv=k_pages.dtype.name)
        else:
            dispatch.note_kernel("ragged_paged_attention")
            out = _paged_pallas(q, k_pages, v_pages, page_table,
                                starts, chunk_lens, bq, interpret,
                                k_scales=k_scales, v_scales=v_scales,
                                window=window_size,
                                doc_starts=doc_starts)
            if quantized:
                return out, k_pages, v_pages, k_scales, v_scales
            return out, k_pages, v_pages
    out = _xla_paged_reference(q, k_pages, v_pages, page_table, starts,
                               chunk_lens, k_scales=k_scales,
                               v_scales=v_scales, window=window_size,
                               doc_starts=doc_starts)
    if quantized:
        return out, k_pages, v_pages, k_scales, v_scales
    return out, k_pages, v_pages
