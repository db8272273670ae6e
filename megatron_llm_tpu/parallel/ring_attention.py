"""Ring attention — context parallelism over the sequence axis.

The long-context mechanism the reference lacks natively (its answer is
sequence parallelism + selective recompute; ring/context parallelism is
the Megatron-Core successor feature). Design follows the blockwise-ring
formulation (Liu et al., Ring Attention; the public JAX reference
implementations use the same scan+ppermute shape):

- the sequence axis is sharded over a mesh axis (`cp`): each device holds
  its (b, s/cp, ...) slice of Q, K, V;
- cp steps of a `lax.scan`: each step runs the FLASH kernel
  (ops/flash_attention.py — Pallas on TPU, so the per-hop score tile
  lives in VMEM, never HBM) on the currently-resident K/V block and
  merges hops by logsumexp (running row-max m, denominator l,
  accumulator o — the flash recurrence lifted across devices), then
  `ppermute` rotates K/V one hop around the ring, so K/V traffic rides
  neighbour ICI links and overlaps with the block compute;
- causal masking uses each block's ORIGIN index ((idx - t) mod cp) to
  reconstruct global positions, and blocks entirely above the diagonal
  skip both einsums via `lax.cond` (per-device branch in the manual
  region — ~2x causal FLOP saving);
- every step is `jax.checkpoint`ed: the backward keeps only the rotating
  K/V boundary blocks (total = one full K/V per device, N*2*g*d — tiny
  next to the N^2 score matrix this exists to avoid) and recomputes the
  per-block scores, mirroring the flash backward.

GQA layout matches the rest of the stack: q (b, s, g, qpk, d), k/v
(b, s, g, d), K/V never broadcast-expanded.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

NEG_INF = -1e30


def _masked_hop_with_lse(q, k_blk, v_blk, mask):
    """One ring hop with an explicit (b, s_loc, t_loc) mask (True =
    masked): XLA einsum path returning (o, lse) for the logsumexp merge.
    The score block is s_loc x t_loc (per-hop, checkpointed) — the seq^2
    buffer cp exists to avoid never materializes. Packed-document masks
    take this path; a doc-aware Pallas kernel is a future optimization."""
    b, s, g, qpk, d = q.shape
    scale = 1.0 / jnp.sqrt(d).astype(jnp.float32)
    scores = jnp.einsum("bsgqd,btgd->bgqst", q, k_blk,
                        preferred_element_type=jnp.float32) * scale
    scores = jnp.where(mask[:, None, None], NEG_INF, scores)
    lse = jax.scipy.special.logsumexp(scores, axis=-1)  # (b, g, qpk, s)
    # fully-masked rows: lse = -inf -> weight 0 in the merge
    probs = jnp.exp(scores - jnp.maximum(lse, NEG_INF / 2)[..., None])
    o = jnp.einsum("bgqst,btgd->bsgqd", probs.astype(v_blk.dtype), v_blk)
    return o, jnp.moveaxis(lse, 3, 1)  # lse -> (b, s, g, qpk)


def ring_self_attention(q, k, v, axis_name: str, causal: bool = True,
                        use_pallas: bool | None = None,
                        interpret: bool = False,
                        doc_start=None):
    """Inside a shard_map region with the sequence sharded over
    `axis_name`: exact attention over the GLOBAL sequence.

    q: (b, s_loc, g, qpk, d); k, v: (b, s_loc, g, d) — local slices.
    Returns (b, s_loc, g, qpk, d).

    Each hop runs the FLASH kernel on the resident K/V block (Pallas on
    TPU, XLA fallback elsewhere) and merges hop results via their
    logsumexp — so the (s_loc x s_loc) score matrix is only ever tiled in
    VMEM, never materialized in HBM, and the per-hop compute is the same
    tuned kernel the non-ring path uses. Under the causal ring, the
    resident (t=0) hop is the diagonal block (causal inside), later hops
    are either fully visible (owner < idx: causal=False) or fully masked
    (owner > idx: skipped before any compute).

    `doc_start` (b, s_loc) int32 — GLOBAL index of each local query's
    document start — enables --reset_attention_mask packed-document
    training with the sequence still sharded (VERDICT r4 #5): every hop
    builds its small block-diagonal mask from the hop's global key
    offsets (allowed iff doc_start[i] <= j <= i) and runs the masked-hop
    path above; above-diagonal hops are still skipped outright.
    """
    from megatron_llm_tpu.ops.flash_attention import (
        flash_attention_with_lse,
    )

    cp = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    b, s, g, qpk, d = q.shape
    if doc_start is not None:
        assert causal, "packed-document masks imply causal attention"
        # global positions of this shard's queries
        q_pos = idx * s + jnp.arange(s)

    def merge(carry, k_blk, v_blk, diag: bool, owner=None):
        """Flash the hop, fold its (o, lse) into the running (m, l, o)."""
        m, l, o = carry
        if doc_start is not None:
            k_pos = owner * s + jnp.arange(s)  # hop's global key positions
            hop_mask = (k_pos[None, None, :] > q_pos[None, :, None]) | \
                (k_pos[None, None, :] < doc_start[:, :, None])
            o_h, lse_h = _masked_hop_with_lse(q, k_blk, v_blk, hop_mask)
        else:
            o_h, lse_h = flash_attention_with_lse(
                q, k_blk, v_blk, causal=diag, use_pallas=use_pallas,
                interpret=interpret,
            )
        m_new = jnp.maximum(m, lse_h)
        m_safe = jnp.maximum(m_new, NEG_INF / 2)
        corr = jnp.exp(m - m_safe)
        w = jnp.exp(lse_h - m_safe)  # hop weight: sum exp(s - m_safe)
        l = l * corr + w
        o = o * corr[..., None] + o_h.astype(jnp.float32) * w[..., None]
        return m_new, l, o

    def step(carry, t):
        k_blk, v_blk, m, l, o = carry
        # rotate K/V one hop around the ring FIRST (neighbour ICI
        # traffic; rotating at step entry means no wasted final rotation)
        perm = [(i, (i + 1) % cp) for i in range(cp)]
        k_blk = jax.lax.ppermute(k_blk, axis_name, perm)
        v_blk = jax.lax.ppermute(v_blk, axis_name, perm)
        # after t rotations this block originated on (idx - t) mod cp
        owner = (idx - t) % cp
        if causal:
            # blocks entirely above the diagonal (owner strictly after
            # this device in global order) contribute nothing: skip the
            # kernel entirely; visible blocks attend in full
            m, l, o = jax.lax.cond(
                owner > idx,
                lambda kb, vb, c: c,
                lambda kb, vb, c: merge(c, kb, vb, diag=False,
                                        owner=owner),
                k_blk, v_blk, (m, l, o),
            )
        else:
            m, l, o = merge((m, l, o), k_blk, v_blk, diag=False,
                            owner=owner)
        return (k_blk, v_blk, m, l, o), None

    step = jax.checkpoint(step, prevent_cse=False)
    # mark the zero initials device-varying so scan carry types are stable
    pv = lambda x: jax.lax.pcast(x, (axis_name,), to="varying")  # noqa: E731
    m0 = pv(jnp.full((b, s, g, qpk), NEG_INF, jnp.float32))
    l0 = pv(jnp.zeros((b, s, g, qpk), jnp.float32))
    o0 = pv(jnp.zeros((b, s, g, qpk, d), jnp.float32))
    # the resident block (t = 0, owner = idx) is the causal diagonal and
    # merges without any rotation; the scan covers the cp - 1 ring hops
    m1, l1, o1 = merge((m0, l0, o0), k, v, diag=causal, owner=idx)
    (k_f, v_f, m, l, o), _ = jax.lax.scan(
        step, (k, v, m1, l1, o1), jnp.arange(1, cp)
    )
    out = o / jnp.maximum(l, 1e-30)[..., None]
    return out.astype(q.dtype)  # already (b, s, g, qpk, d)


def make_ring_attention(mesh, cp_axis: str, causal: bool = True,
                        batch_axis=None, use_pallas: bool | None = None,
                        interpret: bool = False):
    """Jittable global-array entry: shards the sequence over `cp_axis`
    (and optionally batch over `batch_axis`) and runs the ring.

    q (b, S, g, qpk, d), k/v (b, S, g, d) with S divisible by the cp
    degree. Differentiable; use inside a larger jitted step or alone.
    `use_pallas`/`interpret` reach the per-hop flash kernel (CI runs the
    REAL kernel inside the ring via the Pallas interpreter).
    """
    qspec = P(batch_axis, cp_axis, None, None, None)
    kspec = P(batch_axis, cp_axis, None, None)

    @functools.partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(qspec, kspec, kspec),
        out_specs=qspec,
        axis_names={cp_axis} | ({batch_axis} if batch_axis else set()),
    )
    def ring(q, k, v):
        return ring_self_attention(q, k, v, cp_axis, causal=causal,
                                   use_pallas=use_pallas,
                                   interpret=interpret)

    return ring
