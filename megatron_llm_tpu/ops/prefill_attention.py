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

- **head width is a gate on the shape, not a variant** (ISSUE 38): a
  page pool is held LANE-PACKED, (num_pages, page_size, g * d), the g
  K/V heads of a token side by side along the lanes — the order the
  scatter writes and the kernel and the twin read, so nothing re-lays
  a pool out (a 4-D (..., g, d) pool with 64 in the lane dimension was
  copied whole around every gather and scatter: PERF.md §6, PR 38). A
  grid step serves `hp` neighbouring heads from ONE (page_size, hp * d)
  block of the page: q rides in block-diagonal (head t's rows are zero
  outside lanes t*d..(t+1)*d), so q . k^T over the block's lanes is
  each head's own product plus exact zeros, and of p . v's lanes each
  row keeps its own d — the SAME body at width hp * d, no loop over
  heads. A chunk's step serves one lane tile's heads (one at
  d % 128 == 0, the kernel as it was; two at d = 64 with an even g), a
  decode row's all g, the page read whole (`_heads_a_step`).
  Falcon-7B's g = 1 at d = 64 fills no tile: it keeps the twin, on the
  same pool, and is counted (ops/dispatch.report_fallback).

Kernel structure:

- grid (chunk, head block, q_block, page): each grid step reads one
  pool page ONCE for the `hp` K/V heads it serves and serves all their
  `q_per_kv` query heads from it (a decode row takes all g heads, the
  page read whole; a chunk one lane tile's); the page dim carries the
  online-softmax state in VMEM
  scratch (exp2 domain, fp32 accumulation — the flash forward scheme);
- the per-chunk START OFFSET and VALID LENGTH ride scalar-prefetch
  operands: causal-within-chunk masking is `col <= start + row`, rows
  past the chunk's valid length are pad (exact-zero output), and the
  K/V index map dereferences the page table with past-the-need pages
  clamped to the last needed page — Mosaic elides the repeated DMA, so
  cache traffic follows `start + len`, not the allocated table width;
- ONE masked body for every page a q block attends (pages past its
  last valid row, or below its window / document floor, are skipped
  and their DMAs elided): what the interpreter runs is what Mosaic
  compiles.

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
from jax.extend.source_info_util import current_name_stack

from megatron_llm_tpu.ops import dispatch
from megatron_llm_tpu.ops.flash_attention import (
    LANES,
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
# the width a lone narrower chunk is attended at (ragged_paged_attention):
# a mixed round's widest chunk in every serving cell
LONE_CHUNK_TOKENS = 128
# folded rows x lanes of a grid step up to which the step serves ALL of
# a row's K/V heads from one read of the whole page (`_heads_a_step`)
WHOLE_PAGE_CELLS = 32 * 1024


def _choose_block_q(C: int, qpk: int) -> Optional[int]:
    """Largest power-of-2 q block (in TOKENS) dividing the padded chunk
    width C with folded rows (block * qpk) under MAX_PAGED_ROWS.
    Chunks of any width >= 1 are served (the engine's width buckets are
    pow2; C == 1 is the decode row); None only when no divisor fits."""
    b = 1 << (C.bit_length() - 1)
    while b > 1 and (C % b or b * qpk > MAX_PAGED_ROWS):
        b //= 2
    return b if C % b == 0 and b * qpk <= MAX_PAGED_ROWS else None


def _heads_a_step(C: int, g: int, qpk: int, d: int) -> Optional[int]:
    """K/V heads a grid step serves (`hp`), or None where the heads
    fill no lane tile. A step's K/V block is (page_size, hp * d) of the
    lane-packed pool, so hp * d must be whole 128-lane tiles: any hp at
    d % 128 == 0; at a d that divides 128 (64: two heads a tile) hp a
    multiple of 128 / d, which g must be too (Falcon-7B's g = 1 at
    d = 64: None). A step costs about the same whatever it holds while
    its folded rows x lanes stay under WHOLE_PAGE_CELLS, so rows that
    few (a decode row, C == 1) take ALL g heads a step, the page read
    whole: a slot's pages cost one step each, not g (PERF.md §6, PR 38:
    the decode shape is bound by its grid steps). Wider chunks take one
    lane tile's heads: the block-diagonal q multiplies hp - 1 zeros for
    every product it keeps."""
    if d % LANES == 0:
        tile = 1
    elif LANES % d == 0 and g % (LANES // d) == 0:
        tile = LANES // d
    else:
        return None
    if C * g * qpk * g * d <= WHOLE_PAGE_CELLS:
        return g
    return tile


def ragged_paged_block(s: int, qpk: int, d: int, page_size: int,
                       num_slot_pages: int, *,
                       groups: int = 1,
                       min_cache: int = 0,
                       kv_dtype=None,
                       interpret: bool = False) -> Optional[tuple]:
    """Static dispatch check for the unified paged kernel: returns
    (q block size in tokens, K/V heads a grid step) or None for the XLA
    path.

    Kernel territory: `groups` K/V heads that fill lane tiles
    (`_heads_a_step`: d % 128 == 0, or d = 64 with an even g — what the
    call can see, not a model's name), a page that tiles sublanes
    (the page IS the K/V DMA unit — 16 covers bf16/fp32, int8 pools
    need the 32 int8 sublane tile), a q block Mosaic takes (its folded
    rows the whole row axis or a multiple of 8), TPU-or-interpreter
    backend, and a per-slot reach num_slot_pages * page_size of at
    least `min_cache`.
    ONE gate for every phase: a decode row (s == 1) takes the same
    kernel-vs-XLA decision it would take as a width-1 chunk of a mixed
    step on the same pool, so a near-tie argmax can never flip when
    admission starts mid-stream.
    """
    if not (interpret or dispatch.on_tpu()):
        return None
    if s < 1:
        return None
    hp = _heads_a_step(s, groups, qpk, d)
    if hp is None:
        return None
    is_int8 = kv_dtype is not None and jnp.dtype(kv_dtype) == jnp.int8
    sublane = 32 if is_int8 else 16
    if page_size < sublane or page_size % sublane != 0:
        return None
    if num_slot_pages * page_size < max(min_cache, 16):
        return None
    bq = _choose_block_q(s, hp * qpk)
    if bq is None or (bq != s and (bq * hp * qpk) % 8 != 0):
        return None
    return bq, hp


# ---------------------------------------------------------------------------
# Kernel
# ---------------------------------------------------------------------------


def _paged_kernel(starts_ref, lens_ref, pt_ref, *rest, block_q,
                  page_size, qpk, d, num_pages, sm_scale, hp=1,
                  quantized=False, window=None, has_doc=False):
    """Grid (chunk, head block, q_block, page); the page dim carries
    the online-softmax state. A step serves `hp` K/V heads of width
    d // hp from a (page_size, d) block of the lane-packed page (`d` is
    the BLOCK's lanes, `qpk` the folded rows a token: hp x q_per_kv,
    q block-diagonal — `_paged_pallas`). Row r of the folded
    (block_q*qpk, d) q block
    is chunk token i*block_q + r // qpk (head fastest) at global
    position starts[c] + token; rows at tokens >= lens[c] are pad.
    `quantized` selects the int8-KV epilogue (ISSUE 9): k/v arrive int8
    with the page's per-(token, group) fp32 scales as two extra
    (page_size, g) operands — each served head's column is picked out
    in-register and laid over that head's lanes — and are dequantized
    before the unchanged fp32 template math.

    Lower-bound masks (ISSUE 19) are extra parameterizations of the
    SAME body, not new kernels — both default off, and off means the
    emitted program is the pre-window one:
    - `window` (static int): sliding-window attention — row at
      position p attends cols [p - window + 1, p]. Pages wholly below
      the q block's FIRST row's window floor drop out of `run` (and
      the index map clamps them to the first needed page, eliding the
      DMA).
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
        # g) trailing dims; a one-hot lane reduce picks a served head's
        # column out of it as the (page_size, 1) the dequant needs, and
        # with several heads a step each column goes over its head's
        # lanes of the (page_size, d) block
        sc = s_ref[:]
        group = jax.lax.broadcasted_iota(jnp.int32, sc.shape, 1)

        def col(t):
            return jnp.sum(jnp.where(group == gi * hp + t, sc, 0.0),
                           axis=1, keepdims=True)

        if hp == 1:
            return col(0)
        head = jax.lax.broadcasted_iota(
            jnp.int32, (page_size, d), 1) // (d // hp)
        out = jnp.zeros((page_size, d), jnp.float32)
        for t in range(hp):
            out = jnp.where(head == t, col(t), out)
        return out

    @pl.when(j == 0)
    def _init():
        _softmax_init(m_scr, l_scr, acc_scr)

    def _accum():
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
        # the shared causal predicate at the ragged-chunk
        # parameterization: token t of the chunk sits at position
        # start + t, may see cols <= start + t, and is pad when
        # t >= len (pad rows mask EVERY column -> the finalize
        # clamp emits exact zeros, the empty-slot contract).
        # NEG_INF is a finite constant: a PAD row would degenerate
        # to exp2(0)-everywhere garbage, so the finalize re-masks
        # pad rows; valid rows always have a real max (page 0,
        # col 0 is causal for every row), so their masked cells
        # underflow to exact 0. ONE masked body for every page that
        # runs: a maskless twin of it for pages wholly under the
        # diagonal doubled what every call site's trace and Mosaic
        # lowering cost a warm-up, for an iota and two compares on a
        # (rows, page_size) block beside its exp2 (PERF.md §6, PR 38).
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
    pl.when(run)(_accum)

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
                  block_q, hp, interpret, k_scales=None, v_scales=None,
                  window=None, doc_starts=None):
    """q: (nc, C, g, qpk, d); k/v_pages: (P, page_size, g * d), the
    lane-packed pool (head h at lanes h*d..(h+1)*d);
    page_table: (nc, max_pages) int32; starts/chunk_lens: (nc,) int32.
    k/v_scales (int8 pools only): (P, page_size, g) fp32 per-(token,
    group) scales riding the same clamped page index map. `window`
    (static) / `doc_starts` ((nc,) int32, a 4th scalar-prefetch
    operand) add the ISSUE 19 lower bounds: the page index map then
    clamps BOTH ends, so out-of-window / pre-document pages repeat an
    in-bound index and Mosaic elides their DMAs — decode-row traffic
    is O(window), not O(context). Returns (nc, C, g, qpk, d) in q's
    dtype (pad rows exact zero).

    `hp` K/V heads a grid step (`_heads_a_step`): q goes in as
    (nc, C, g / hp, hp * qpk, hp * d), BLOCK-DIAGONAL — the rows of
    head t zero outside lanes t*d..(t+1)*d — so one product against the
    step's (page_size, hp * d) block of the page is each head's own
    q . k^T (the other heads' lanes add exact zeros), and of p . v's
    hp * d lanes each row keeps its own d. The scale is the TRUE head
    width's. hp == 1 is the plain launch."""
    nc, C, g, qpk, d = q.shape
    heads = (g, qpk, d)
    if hp > 1:
        own = jnp.eye(hp, dtype=bool)[:, None, :, None]
        q = jnp.where(own, q.reshape(nc, C, g // hp, hp, qpk, 1, d), 0)
        g, qpk, d = g // hp, hp * qpk, hp * d
        q = q.reshape(nc, C, g, qpk, d)
    page_size = k_pages.shape[1]
    max_pages = page_table.shape[1]
    rows = block_q * qpk
    num_q_blocks = C // block_q
    quantized = k_scales is not None
    has_doc = doc_starts is not None

    qf = q.transpose(0, 2, 1, 3, 4).reshape(nc, g, C * qpk, d)
    # rows below one fp32 sublane tile: launch q/o in fp32 (the small-
    # memref Mosaic workaround shared with the dense decode kernel)
    out_dtype = q.dtype if rows % 8 == 0 else jnp.float32
    qf = qf.astype(out_dtype)

    kernel = functools.partial(
        _paged_kernel, block_q=block_q, page_size=page_size, qpk=qpk,
        d=d, num_pages=max_pages, sm_scale=1.0 / (heads[2] ** 0.5),
        hp=hp, quantized=quantized, window=window, has_doc=has_doc,
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
            (None, page_size, k_scales.shape[2]),
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
        # (chunk, head block, q_block) steps are independent; only the
        # page dim carries the online-softmax scratch state
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
        ),
        interpret=interpret,
    )(*scalars, *operands)
    out = out.reshape(nc, g, C, qpk, d).transpose(0, 2, 1, 3, 4) \
        .astype(q.dtype)
    if hp > 1:
        # each row's own lanes of the hp * d its step produced
        out = jnp.sum(jnp.where(own, out.reshape(
            nc, C, g, hp, qpk // hp, hp, d // hp), 0), axis=-2)
    return out.reshape(nc, C, *heads)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _paged_call(statics, scope, *operands, **pools):
    """`_paged_pallas` behind a call boundary (`statics` its block_q,
    hp, interpret, window): the attention layers of a step share ONE
    trace of a kernel shape and the lowered program holds its Mosaic
    call once however many layers call it — a step's warm-up traces,
    lowers and hashes its program whatever the compile cache holds
    (PERF.md §7, the set-up row). Only the kernel sits behind it: the
    twin stays inline in its callers, as it was. A called function's
    operations do not inherit the call site's name stack, so the caller
    hands over its `scope`."""
    block_q, hp, interpret, window = statics
    with jax.named_scope(scope):
        return _paged_pallas(*operands, block_q, hp, interpret,
                             window=window, **pools)


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
    the kernel's in-register epilogue feeds the same math). The pools
    are lane-packed, (P, page_size, g * d). Pad rows
    (token >= chunk_lens) pin to the kernel's exact-zero output.
    `window` / `doc_starts` (ISSUE 19) become a per-row lower bound
    row_lo = max(pos - window + 1, doc_starts[c], 0): this path
    GATHERS every table entry (reclaimed entries park on null page 0),
    but the lower mask multiplies those columns by an exact fp 0, so
    mid-flight page reclamation is bitwise-invisible here too."""
    nc, C, g, qpk, d = q.shape
    page_size = k_pages.shape[1]
    max_pages = page_table.shape[1]
    T = max_pages * page_size

    def view(pages, scales):
        # whole lane-packed rows as they lie, cut by head; an int8 pool
        # dequantizes the gathered view, not the pool
        x = pages[page_table].reshape(nc, T, g, d)
        if scales is not None:
            x = x.astype(jnp.float32) \
                * scales[page_table].reshape(nc, T, g, 1)
        return x.transpose(0, 2, 1, 3)

    with jax.named_scope("page_gather"):
        k, v = view(k_pages, k_scales), view(v_pages, v_scales)
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
    """Write a chunk's K/V rows (nc, C, g, d) into its slot's pages of
    the lane-packed pools (P, page_size, g * d), a token's g heads side
    by side as one row: token t (valid,
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
    nc, C, g, d = k_new.shape
    if k_pages.ndim != 3 or k_pages.shape[2] != g * d:
        raise ValueError(
            f"a K/V page pool is lane-packed, (num_pages, page_size, "
            f"g * d) = (..., {g * d}) for {g} heads of width {d} "
            f"(GPTModel.init_paged_kv_caches): got {tuple(k_pages.shape)}")
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
    k_pages = k_pages.at[pages, offs].set(
        k_new.astype(k_pages.dtype).reshape(nc, C, g * d))
    v_pages = v_pages.at[pages, offs].set(
        v_new.astype(v_pages.dtype).reshape(nc, C, g * d))
    return k_pages, v_pages


def ragged_paged_attention(
    q: jnp.ndarray,  # (nc, C, g, qpk, d) — C = padded chunk width
    k_new: jnp.ndarray,  # (nc, C, g, d) — this chunk's K (RoPE applied)
    v_new: jnp.ndarray,  # (nc, C, g, d)
    k_pages: jnp.ndarray,  # (num_pages, page_size, g * d); int8 OK
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

    The pools are LANE-PACKED: (num_pages, page_size, g * d), a token's
    g K/V heads side by side along the lanes, head h at lanes
    h*d..(h+1)*d — written once, in the order both paths read. The
    kernel takes heads that fill 128-lane tiles: d % 128 == 0, or
    d = 64 with an even g, two heads a tile (`ragged_paged_block`);
    any other shape (Falcon-7B's g = 1 at d = 64) takes the twin on
    the same pool and is counted (ops/dispatch.report_fallback).

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
    # (k_pages, v_pages), and an int8 pool's (k_scales, v_scales) after
    pools = scatter_chunk_kv(k_new, v_new, k_pages, v_pages, page_table,
                             starts, chunk_lens, k_scales=k_scales,
                             v_scales=v_scales)
    k_pages, v_pages, k_scales, v_scales = (*pools, None, None)[:4]
    if dispatch.want_kernel(use_pallas, interpret):
        # a lone chunk narrower than LONE_CHUNK_TOKENS goes to the
        # kernel at that width, its tail as pad rows: the engine's
        # mixed rounds come in log2 widths, each its own program, and
        # one kernel shape serves them all from one trace
        # (`_paged_call`). A width-1 call stays a decode row.
        pad = LONE_CHUNK_TOKENS - C if nc == 1 and 1 < C < LONE_CHUNK_TOKENS \
            else 0
        block = ragged_paged_block(C + pad, qpk, d, k_pages.shape[1],
                                   page_table.shape[1], groups=g,
                                   min_cache=min_cache,
                                   kv_dtype=k_pages.dtype,
                                   interpret=interpret)
        if block is not None:
            dispatch.note_kernel("ragged_paged_attention")
            if pad:
                q = jnp.pad(q, ((0, 0), (0, pad)) + ((0, 0),) * 3)
            out = _paged_call(
                (*block, interpret, window_size),
                str(current_name_stack()), q, k_pages, v_pages,
                page_table, starts, chunk_lens, k_scales=k_scales,
                v_scales=v_scales, doc_starts=doc_starts)
            return (out[:, :C], *pools)
        # a reach below min_cache is the caller's routing, not a refusal
        if page_table.shape[1] * k_pages.shape[1] >= min_cache:
            dispatch.report_fallback(
                "ragged_paged_attention", "ragged_paged_block", C=C,
                g=g, qpk=qpk, d=d, page_size=k_pages.shape[1],
                slot_pages=page_table.shape[1], kv=k_pages.dtype.name)
    out = _xla_paged_reference(q, k_pages, v_pages, page_table, starts,
                               chunk_lens, k_scales=k_scales,
                               v_scales=v_scales, window=window_size,
                               doc_starts=doc_starts)
    return (out, *pools)
