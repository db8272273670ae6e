"""THE ragged paged attention kernel (ISSUE 18): the one parity suite.

ONE parameterized sweep replaces the per-variant case matrices of the
former paged-decode / ragged-prefill / int8-twin suites: phase
(decode-row / ragged-chunk / partial-page) x kv dtype (bf16 / int8,
plus one fp32 exactness pin) x MHA/GQA/MQA x mesh (single / tp2),
every cell against the ONE
gather-pages-then-dense oracle (`_xla_paged_reference`). Kernel runs go
through the REAL Pallas kernel via the shared interpret policy
(conftest.kernel_interpret_mode).

The historical pins ride along as named cases:

- width-1 degeneracy: a width-1 chunk IS the decode path — it matches
  the dense decode math on the gathered view, and the same slot served
  as a decode row of a WIDER (padded) launch agrees;
- null-page containment: empty chunks and pad rows return exact zeros
  and their K/V lands on the pool's null page only;
- DMA-clamp traffic: pool pages beyond each chunk's causal reach are
  inert — garbage there cannot perturb a single output bit;
- the one dispatch gate (lane alignment, page tiling incl. the int8
  32-sublane rule, width blocks, min-cache, backend/interpret), and
  exact-equal XLA fallback for ineligible shapes;
- attention_block's ONE paged branch: kernel vs XLA parity for both
  cache forms (chunked and bare decode), ragged length advance, carry-
  stable cache pytrees, page-table-directed scatter with null-page
  routing for retired slots, chunked == dense prefill per layer;
- transformer_stack plumbing: chunk_lens rides to every layer, ragged
  stack-level length advance, slot-0-solo bitwise logits.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from conftest import kernel_interpret_mode
from megatron_llm_tpu.ops.decode_attention import _xla_decode
from megatron_llm_tpu.ops.prefill_attention import (
    _xla_paged_reference,
    ragged_paged_attention,
    ragged_paged_block,
    scatter_chunk_kv,
)
from megatron_llm_tpu.ops.quantization import (
    dequantize_rows,
    quantize_rows,
)

INTERPRET = kernel_interpret_mode()


@pytest.fixture(scope="module", autouse=True)
def _drop_kernel_caches():
    """Interpret-mode sweeps mint many one-shot executables; drop them
    at module exit so the suites that run after this file don't pay
    growing trace/GC overhead for caches nothing will hit again."""
    yield
    jax.clear_caches()


# (K/V heads, q heads a K/V head, head width). At d = 64 two K/V heads
# fill one 128-lane tile of the lane-packed pool (ISSUE 38): the SAME
# kernel body, q block-diagonal over the heads of a grid step.
HEADS = [
    pytest.param(4, 1, 128, id="mha"),
    pytest.param(2, 2, 128, id="gqa"),
    pytest.param(1, 8, 128, id="mqa"),
    pytest.param(2, 4, 64, id="gqa-2x4-head64"),
    pytest.param(8, 1, 64, id="mha-8x1-head64"),
]

# kv dtype axis: (pool dtype, q dtype, page_size, rtol/atol vs oracle).
# int8 needs the 32-sublane page tile; bf16 kernel-vs-oracle tolerance
# matches the former per-variant suites.
KV_DTYPES = {
    "fp32": (jnp.float32, jnp.float32, 16, 1e-5),
    "bf16": (jnp.bfloat16, jnp.bfloat16, 16, 2e-2),
    "int8": (jnp.int8, jnp.float32, 32, 1e-5),
}

# phase axis: (padded chunk width C, starts(ps), chunk_lens). A decode
# row is starts == the slot's length with chunk_lens 1 — the SAME
# kernel at C == 1, not a variant. Starts are page-size-relative so the
# partial-page phase crosses a page boundary for BOTH the fp (ps=16)
# and int8 (ps=32) tiles at the 2-page-per-slot sweep pool.
PHASES = {
    "decode-row": (1, lambda ps: [7, 2 * ps - 3, 0], [1, 1, 1]),
    "ragged-chunk": (8, lambda ps: [0, ps + 5, 5], [8, 3, 0]),
    "partial-page": (8, lambda ps: [ps - 3, ps + 6, 9], [6, 2, 8]),
}


def _case(nc, C, g, qpk, d, ps, mp, kv="fp32", seed=0):
    """Random chunk batch + pool + a page table of distinct shuffled
    pages per chunk (page 0 reserved as null). The pools are
    lane-packed, (num_pages, ps, g * d): a token's heads side by side.
    int8 pools arrive pre-quantized with their fp32 scale pools
    (num_pages, ps, g) (scales None for fp)."""
    pool_dt, q_dt, _, _ = KV_DTYPES[kv]
    num_pages = 1 + nc * mp
    ks = jax.random.split(jax.random.key(seed), 5)
    q = jax.random.normal(ks[0], (nc, C, g, qpk, d), q_dt)
    k_new = jax.random.normal(ks[1], (nc, C, g, d), q_dt)
    v_new = jax.random.normal(ks[2], (nc, C, g, d), q_dt)
    kp = jax.random.normal(ks[3], (num_pages, ps, g, d), jnp.float32)
    vp = jax.random.normal(ks[4], (num_pages, ps, g, d), jnp.float32)
    rs = np.random.RandomState(seed)
    perm = rs.permutation(num_pages - 1) + 1
    pt = jnp.asarray(perm.reshape(nc, mp), jnp.int32)
    def packed(x):
        return x.reshape(num_pages, ps, g * d)

    if kv == "int8":
        kq, ksc = quantize_rows(kp)
        vq, vsc = quantize_rows(vp)
        return q, k_new, v_new, packed(kq), packed(vq), pt, ksc, vsc
    return q, k_new, v_new, packed(kp.astype(pool_dt)), \
        packed(vp.astype(pool_dt)), pt, None, None


def _both(q, kn, vn, kp, vp, pt, starts, lens, ks=None, vs=None,
          window=None, doc_starts=None):
    """Kernel (interpret policy) + the oracle on the post-scatter
    pools; returns (kernel out, oracle out, kernel pools, scatter-only
    pools)."""
    starts = jnp.asarray(starts, jnp.int32)
    lens = jnp.asarray(lens, jnp.int32)
    if doc_starts is not None:
        doc_starts = jnp.asarray(doc_starts, jnp.int32)
    res = ragged_paged_attention(q, kn, vn, kp, vp, pt, starts, lens,
                                 use_pallas=True, interpret=INTERPRET,
                                 k_scales=ks, v_scales=vs,
                                 window_size=window,
                                 doc_starts=doc_starts)
    sc = scatter_chunk_kv(kn, vn, kp, vp, pt, starts, lens,
                          k_scales=ks, v_scales=vs)
    if ks is not None:
        out_x = _xla_paged_reference(q, sc[0], sc[1], pt, starts, lens,
                                     k_scales=sc[2], v_scales=sc[3],
                                     window=window,
                                     doc_starts=doc_starts)
    else:
        out_x = _xla_paged_reference(q, sc[0], sc[1], pt, starts, lens,
                                     window=window,
                                     doc_starts=doc_starts)
    return res[0], out_x, res[1:], sc


class TestUnifiedKernelSweep:
    """phase x kv dtype x heads, kernel vs the one oracle — the single
    case matrix every former per-variant suite collapsed into."""

    # ISSUE 18's sweep axes are kv in {bf16, int8}; fp32 rides as the
    # single exactness pin below rather than a third full column (single
    # core tier-1 pays ~1.5s per interpret-mode cell).
    @pytest.mark.parametrize("g,qpk,d", HEADS)
    @pytest.mark.parametrize("kv", ["bf16", "int8"])
    @pytest.mark.parametrize("phase", list(PHASES))
    def test_kernel_matches_oracle(self, phase, kv, g, qpk, d):
        pool_dt, _, ps, tol = KV_DTYPES[kv]
        C, starts_fn, lens = PHASES[phase]
        assert ragged_paged_block(C, qpk, d, ps, 2, groups=g,
                                  kv_dtype=pool_dt,
                                  interpret=True) is not None
        q, kn, vn, kp, vp, pt, ks, vs = _case(3, C, g, qpk, d, ps, 2,
                                              kv=kv)
        assert kp.shape == (7, ps, g * d)
        starts = starts_fn(ps)
        out_k, out_x, pools_k, pools_x = _both(q, kn, vn, kp, vp, pt,
                                               starts, lens, ks, vs)
        np.testing.assert_allclose(
            np.asarray(out_k, np.float32), np.asarray(out_x, np.float32),
            rtol=tol, atol=tol, err_msg=f"{phase}/{kv}")
        # the entry point's scatter is bitwise the standalone scatter
        for a, b in zip(pools_k, pools_x):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_fp32_exactness_pin(self):
        """One fp32 cell at tight tolerance: with fp32 pools and fp32
        accumulators the kernel and the gather-then-dense oracle agree
        to 1e-5 on the hardest phase (mid-page start AND end)."""
        C, starts_fn, lens = PHASES["partial-page"]
        q, kn, vn, kp, vp, pt, ks, vs = _case(3, C, 4, 1, 128, 16, 2,
                                              kv="fp32")
        starts = starts_fn(16)
        out_k, out_x, pools_k, pools_x = _both(q, kn, vn, kp, vp, pt,
                                               starts, lens, ks, vs)
        np.testing.assert_allclose(
            np.asarray(out_k), np.asarray(out_x), rtol=1e-5, atol=1e-5)
        for a, b in zip(pools_k, pools_x):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    @pytest.mark.parametrize("kv", ["bf16", "int8"])
    @pytest.mark.parametrize("phase", list(PHASES))
    def test_tp2_group_sharded_bitwise(self, phase, kv):
        """The one entry point under a tp2 GSPMD mesh (pools sharded on
        the group axis per kv_pool_spec, tables/lengths replicated):
        groups are independent, so the sharded run must be BITWISE the
        single-device run — the engine-level tp2 suites pin the full
        serving path; this pins the op's partitioning in isolation."""
        from megatron_llm_tpu.parallel.mesh import MODEL_AXIS
        from megatron_llm_tpu.parallel.sharding import kv_pool_spec

        _, _, ps, _ = KV_DTYPES[kv]
        C, starts_fn, lens = PHASES[phase]
        g, qpk = 2, 2
        q, kn, vn, kp, vp, pt, ks, vs = _case(3, C, g, qpk, 128, ps, 2,
                                              kv=kv, seed=7)
        starts = jnp.asarray(starts_fn(ps), jnp.int32)
        lens = jnp.asarray(lens, jnp.int32)

        def op(q, kn, vn, kp, vp, pt, starts, lens, ks, vs):
            return ragged_paged_attention(
                q, kn, vn, kp, vp, pt, starts, lens,
                use_pallas=False, k_scales=ks, v_scales=vs)

        ref = jax.jit(op)(q, kn, vn, kp, vp, pt, starts, lens, ks, vs)
        mesh = Mesh(np.array(jax.devices()[:2]), (MODEL_AXIS,))

        def put(x, spec):
            return jax.device_put(x, NamedSharding(mesh, spec))

        gax = P(None, None, MODEL_AXIS)
        args = (put(q, P(None, None, MODEL_AXIS, None, None)),
                put(kn, P(None, None, MODEL_AXIS, None)),
                put(vn, P(None, None, MODEL_AXIS, None)),
                put(kp, kv_pool_spec(kp.shape, 2, g)),
                put(vp, kv_pool_spec(vp.shape, 2, g)),
                put(pt, P()), put(starts, P()), put(lens, P()),
                put(ks, kv_pool_spec(ks.shape, 2, g)) if ks is not None
                else None,
                put(vs, kv_pool_spec(vs.shape, 2, g)) if vs is not None
                else None)
        del gax
        got = jax.jit(op)(*args)
        for a, b in zip(got, ref):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


class TestWindowedAndPackedDocs:
    """ISSUE 19: `window_size` / `doc_starts` on the SAME kernel — the
    lower bounds ride the existing interior/boundary mask split and the
    double-ended DMA clamp, so the sweep below is the same phase x kv
    matrix with the window axis added, against the same one oracle."""

    @pytest.mark.parametrize("g,qpk,d", [
        pytest.param(2, 2, 128, id="head128"),
        pytest.param(2, 4, 64, id="head64")])
    @pytest.mark.parametrize("kv", ["bf16", "int8"])
    @pytest.mark.parametrize("phase", list(PHASES))
    def test_window_axis_off_covering_binding(self, phase, kv, g, qpk, d):
        """The three window regimes of one cell: W=None (the base
        trace), W >= context (must be BITWISE the base on both paths —
        the reclamation soundness anchor), and W < context (the mask
        binds: output changes, and kernel still matches the oracle
        under the same window)."""
        _, _, ps, tol = KV_DTYPES[kv]
        C, starts_fn, lens = PHASES[phase]
        q, kn, vn, kp, vp, pt, ks, vs = _case(3, C, g, qpk, d, ps, 2,
                                              kv=kv, seed=13)
        starts = starts_fn(ps)
        base_k, base_x, _, _ = _both(q, kn, vn, kp, vp, pt, starts,
                                     lens, ks, vs)
        # W >= any start + len the pool can reach: bitwise the W=None
        # program — the lower bound never binds, the trace is identical
        ge_k, ge_x, _, _ = _both(q, kn, vn, kp, vp, pt, starts, lens,
                                 ks, vs, window=4 * ps)
        np.testing.assert_array_equal(np.asarray(ge_k),
                                      np.asarray(base_k))
        np.testing.assert_array_equal(np.asarray(ge_x),
                                      np.asarray(base_x))
        # W < context: kernel vs oracle under the same window, and the
        # mask actually bound somewhere (else this cell proves nothing)
        win_k, win_x, _, _ = _both(q, kn, vn, kp, vp, pt, starts, lens,
                                   ks, vs, window=ps)
        np.testing.assert_allclose(
            np.asarray(win_k, np.float32), np.asarray(win_x, np.float32),
            rtol=tol, atol=tol, err_msg=f"{phase}/{kv}/window={ps}")
        assert np.any(np.asarray(win_k, np.float32)
                      != np.asarray(base_k, np.float32)), \
            f"{phase}/{kv}: window={ps} never bound"

    @pytest.mark.parametrize("kv", ["bf16", "int8"])
    def test_tp2_windowed_bitwise(self, kv):
        """Window under the tp2 GSPMD mesh: groups stay independent —
        the sharded windowed run is BITWISE the single-device windowed
        run, and W >= context stays bitwise the dense mesh run."""
        from megatron_llm_tpu.parallel.mesh import MODEL_AXIS
        from megatron_llm_tpu.parallel.sharding import kv_pool_spec

        _, _, ps, _ = KV_DTYPES[kv]
        C, starts_fn, lens = PHASES["partial-page"]
        g = 2
        q, kn, vn, kp, vp, pt, ks, vs = _case(3, C, g, 2, 128, ps, 2,
                                              kv=kv, seed=17)
        starts = jnp.asarray(starts_fn(ps), jnp.int32)
        lens = jnp.asarray(lens, jnp.int32)

        def op(window):
            def f(q, kn, vn, kp, vp, pt, starts, lens, ks, vs):
                return ragged_paged_attention(
                    q, kn, vn, kp, vp, pt, starts, lens,
                    use_pallas=False, k_scales=ks, v_scales=vs,
                    window_size=window)
            return f

        dense1 = jax.jit(op(None))(q, kn, vn, kp, vp, pt, starts, lens,
                                   ks, vs)
        win1 = jax.jit(op(ps))(q, kn, vn, kp, vp, pt, starts, lens,
                               ks, vs)
        mesh = Mesh(np.array(jax.devices()[:2]), (MODEL_AXIS,))

        def put(x, spec):
            return jax.device_put(x, NamedSharding(mesh, spec))

        args = (put(q, P(None, None, MODEL_AXIS, None, None)),
                put(kn, P(None, None, MODEL_AXIS, None)),
                put(vn, P(None, None, MODEL_AXIS, None)),
                put(kp, kv_pool_spec(kp.shape, 2, g)),
                put(vp, kv_pool_spec(vp.shape, 2, g)),
                put(pt, P()), put(starts, P()), put(lens, P()),
                put(ks, kv_pool_spec(ks.shape, 2, g)) if ks is not None
                else None,
                put(vs, kv_pool_spec(vs.shape, 2, g)) if vs is not None
                else None)
        for a, b in zip(jax.jit(op(ps))(*args), win1):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.jit(op(4 * ps))(*args), dense1):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_out_of_window_pages_inert_and_reclaimable(self):
        """The engine's reclamation contract, pinned at the op: pages
        wholly below every live row's window floor may be (a) filled
        with garbage by a reuse and (b) zeroed out of the page table
        (the reclaimed-to-null state) without perturbing one output
        bit on EITHER path — the kernel's double-ended clamp never
        DMAs them, the oracle multiplies them by an exact fp 0."""
        ps, mp = 16, 4
        q, kn, vn, kp, vp, pt, _, _ = _case(2, 1, 2, 2, 128, ps, mp,
                                            seed=19)
        starts = jnp.asarray([40, 55], jnp.int32)
        lens = jnp.asarray([1, 1], jnp.int32)
        W = ps
        base_k, base_x, _, _ = _both(q, kn, vn, kp, vp, pt, starts,
                                     lens, window=W)
        # pages wholly before min row floor start - W + 1 are dead
        ptn = np.asarray(pt)
        kp2, vp2 = np.asarray(kp).copy(), np.asarray(vp).copy()
        pt2 = ptn.copy()
        dead = 0
        for c, s in enumerate([40, 55]):
            lo = s - W + 1
            for j in range(mp):
                if (j + 1) * ps <= lo:
                    kp2[ptn[c, j]] = 1e30  # reused by another slot
                    vp2[ptn[c, j]] = -1e30
                    pt2[c, j] = 0  # reclaimed: table entry nulled
                    dead += 1
        assert dead >= 3  # chunk 0 drops 1 page, chunk 1 drops 2
        got_k, got_x, _, _ = _both(q, kn, vn, jnp.asarray(kp2),
                                   jnp.asarray(vp2), jnp.asarray(pt2),
                                   starts, lens, window=W)
        np.testing.assert_array_equal(np.asarray(got_k),
                                      np.asarray(base_k))
        np.testing.assert_array_equal(np.asarray(got_x),
                                      np.asarray(base_x))

    @pytest.mark.parametrize("d", [128, 64])
    def test_packed_docs_attend_within_doc_only(self, d):
        """Packed multi-doc prefill: two documents as two chunks over
        the SAME slot pages, each floored at its own start — zero
        cross-document attention, so each chunk equals dense causal
        attention over its own document alone, on both paths."""
        from megatron_llm_tpu.models.attention import (
            causal_mask,
            grouped_attention,
        )

        g, qpk, ps, C = 2, 2, 16, 8
        q, kn, vn, kp, vp, pt, _, _ = _case(2, C, g, qpk, d, ps, 2,
                                            seed=23)
        pt = jnp.tile(pt[:1], (2, 1))  # both docs share slot 0's pages
        starts, lens = [0, C], [C, C]
        doc = [0, C]
        out_k, out_x, _, _ = _both(q, kn, vn, kp, vp, pt, starts, lens,
                                   doc_starts=doc)

        class _Cfg:
            attention_dropout = 0.0
            num_query_groups, q_per_kv, head_dim = g, qpk, d

        for c in range(2):
            ref = grouped_attention(q[c:c + 1], kn[c:c + 1],
                                    vn[c:c + 1], causal_mask(C), _Cfg(),
                                    None, True)
            for out in (out_k, out_x):
                np.testing.assert_allclose(
                    np.asarray(out[c]).reshape(1, C, -1),
                    np.asarray(ref), rtol=1e-5, atol=1e-5,
                    err_msg=f"doc {c}")
        # the floor BOUND: without doc_starts, doc 1 sees doc 0's keys
        nof_k, _, _, _ = _both(q, kn, vn, kp, vp, pt, starts, lens)
        assert np.any(np.asarray(out_k[1]) != np.asarray(nof_k[1]))
        # degenerate floor == start is the plain causal program
        zf_k, zf_x, _, _ = _both(q, kn, vn, kp, vp, pt, starts, lens,
                                 doc_starts=[0, 0])
        np.testing.assert_array_equal(np.asarray(zf_k),
                                      np.asarray(nof_k))


class TestHead64:
    """ISSUE 38: the serving cell's own shapes at head 64 (LFM2-8B-A1B:
    8 K/V heads x 4, page 64) on the SAME kernel body: a grid step
    serves the heads of whole 128-lane tiles of the lane-packed page,
    q block-diagonal, the scale the true width's."""

    @pytest.mark.parametrize("nc,C,lens", [
        pytest.param(32, 1, [1] * 30 + [0, 0], id="decode-32-slots"),
        pytest.param(1, 1, [1], id="chunk-1"),
        pytest.param(1, 2, [1], id="chunk-2-tail-1"),
        pytest.param(2, 3, [3, 2], id="chunk-3-tail-2"),
        pytest.param(1, 128, [77], id="chunk-128-tail-77"),
        pytest.param(2, 8, [0, 0], id="idle-and-all-pad"),
    ])
    def test_cell_shapes_match_oracle(self, nc, C, lens):
        g, qpk, d, ps, mp = 8, 4, 64, 64, 4
        q, kn, vn, kp, vp, pt, _, _ = _case(nc, C, g, qpk, d, ps, mp,
                                            kv="bf16", seed=61)
        rs = np.random.RandomState(61)
        starts = rs.randint(0, ps * mp - C + 1, nc)
        out_k, out_x, pools_k, pools_x = _both(q, kn, vn, kp, vp, pt,
                                               starts, lens)
        np.testing.assert_allclose(
            np.asarray(out_k, np.float32), np.asarray(out_x, np.float32),
            rtol=2e-2, atol=2e-2)
        for a, b in zip(pools_k, pools_x):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        # pad rows and idle chunks: exact zeros, both paths
        pad = np.arange(C)[None, :] >= np.asarray(lens)[:, None]
        for out in (out_k, out_x):
            assert not np.any(np.asarray(out, np.float32)[pad])

    def test_fp32_exactness_and_true_width_scale(self):
        """fp32 pools: 1e-5 against the oracle on the hardest phase; a
        scale of 1 / sqrt(128) in place of 1 / sqrt(64) would miss by
        far more."""
        C, starts_fn, lens = PHASES["partial-page"]
        q, kn, vn, kp, vp, pt, _, _ = _case(3, C, 4, 2, 64, 16, 2,
                                            seed=37)
        out_k, out_x, _, _ = _both(q, kn, vn, kp, vp, pt, starts_fn(16),
                                   lens)
        np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_x),
                                   rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("phase", ["decode-row", "partial-page"])
    def test_other_heads_of_a_step_never_reach_an_output(self, phase):
        """Huge K/V planted in head 1 (lanes 64..127 of the one tile),
        cached and new: head 0's rows come out bit for bit as before —
        q's zero lanes multiply them by an exact 0 and their lanes of
        p . v are cut — and head 1's change."""
        C, starts_fn, lens = PHASES[phase]
        g, qpk, d = 2, 2, 64
        q, kn, vn, kp, vp, pt, _, _ = _case(3, C, g, qpk, d, 16, 2,
                                            seed=41)
        starts = starts_fn(16)
        base, base_x, _, _ = _both(q, kn, vn, kp, vp, pt, starts, lens)
        big = jnp.float32(3e4)
        kn2, vn2 = kn.at[:, :, 1].set(big), vn.at[:, :, 1].set(-big)
        kp2, vp2 = kp.at[:, :, d:].set(-big), vp.at[:, :, d:].set(big)
        got, got_x, _, _ = _both(q, kn2, vn2, kp2, vp2, pt, starts, lens)
        for a, b in ((got, base), (got_x, base_x)):
            np.testing.assert_array_equal(np.asarray(a[:, :, 0]),
                                          np.asarray(b[:, :, 0]))
        assert np.any(np.asarray(got[:, :, 1]) != np.asarray(base[:, :, 1]))

    @pytest.mark.parametrize("phase", ["decode-row", "ragged-chunk"])
    def test_tp2_each_chip_its_own_heads(self, phase):
        """Four heads of 64 over two chips: the lanes are the sharded
        axis (kv_pool_spec), each chip's slice one tile of its own two
        heads, and the kernel runs per shard (shard_kernel, as
        attention_block calls it): bit for bit the one-chip run, output
        and pools."""
        from megatron_llm_tpu.parallel.mesh import (
            MODEL_AXIS,
            ParallelContext,
            build_mesh,
            shard_kernel,
            use_mesh,
        )
        from megatron_llm_tpu.parallel.sharding import kv_pool_spec

        C, starts_fn, lens = PHASES[phase]
        g, qpk, d = 4, 2, 64
        q, kn, vn, kp, vp, pt, _, _ = _case(3, C, g, qpk, d, 16, 2,
                                            seed=53)
        starts = jnp.asarray(starts_fn(16), jnp.int32)
        lens = jnp.asarray(lens, jnp.int32)

        def op(q, kn, vn, kp, vp, pt, starts, lens):
            return ragged_paged_attention(
                q, kn, vn, kp, vp, pt, starts, lens, use_pallas=True,
                interpret=INTERPRET)

        ref = jax.jit(op)(q, kn, vn, kp, vp, pt, starts, lens)
        ctx = ParallelContext(build_mesh(tp=2, devices=jax.devices()[:2]))
        pool = kv_pool_spec(kp.shape, 2, g)
        assert pool == P(None, None, MODEL_AXIS)
        qs, ns = P(None, None, MODEL_AXIS, None, None), \
            P(None, None, MODEL_AXIS, None)
        with use_mesh(ctx):
            got = jax.jit(shard_kernel(
                op, (qs, ns, ns, pool, pool, P(), P(), P()),
                (qs, pool, pool), check_vma=False))(
                q, kn, vn, kp, vp, pt, starts, lens)
        for a, b in zip(got, ref):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    @pytest.mark.parametrize("g,qpk,d", [
        pytest.param(1, 8, 64, id="falcon7b-mqa-half-a-tile"),
        pytest.param(3, 1, 64, id="a-tile-and-a-half"),
    ])
    def test_what_fills_no_tile_takes_the_twin_and_is_counted(
            self, monkeypatch, g, qpk, d):
        """Falcon-7B's one K/V head of 64 is half a tile: the gate says
        None, the twin serves the SAME lane-packed pool (g * d lanes,
        never rounded up) exactly, and on a TPU backend the refusal is
        counted (`kernel_fallbacks.batch/.chat` stay 8), not hidden."""
        from megatron_llm_tpu.ops import dispatch

        q, kn, vn, kp, vp, pt, _, _ = _case(2, 4, g, qpk, d, 16, 4,
                                            seed=59)
        assert kp.shape[2] == g * d
        starts = jnp.asarray([0, 5], jnp.int32)
        lens = jnp.asarray([4, 3], jnp.int32)
        monkeypatch.setattr(dispatch, "on_tpu", lambda: True)
        before = set(dispatch.fallbacks())
        out, kp2, vp2 = ragged_paged_attention(
            q, kn, vn, kp, vp, pt, starts, lens, use_pallas=True)
        new, = set(dispatch.fallbacks()) - before
        assert f"g={g}, qpk={qpk}, d={d}" in new \
            and "gate=ragged_paged_block" in new
        np.testing.assert_array_equal(
            np.asarray(out), np.asarray(_xla_paged_reference(
                q, kp2, vp2, pt, starts, lens)))

    def test_a_pool_that_is_not_lane_packed_is_refused(self):
        q, kn, vn, kp, vp, pt, _, _ = _case(2, 1, 2, 2, 64, 16, 2)
        starts = jnp.asarray([3, 4], jnp.int32)
        four_d = kp.reshape(5, 16, 2, 64)
        with pytest.raises(ValueError, match="lane-packed"):
            ragged_paged_attention(q, kn, vn, four_d, four_d, pt, starts,
                                   jnp.ones_like(starts))


class TestHistoricalPins:
    def test_width_one_chunk_is_the_decode_path(self):
        """The former test suites pinned a width-1 chunk bitwise-equal
        to the paged decode kernel; ISSUE 18 promoted that degeneracy
        from test to dispatch (the decode kernel IS the width-1 chunk).
        What remains to pin: (a) a width-1 chunk matches the DENSE
        decode math on the gathered view — the page indirection is
        pure data movement; (b) the same slot state served as a padded
        width-8 launch with chunk_lens 1 agrees — mixed-round decode
        rows and scan decode rows are the same math."""
        slots, g, qpk, d, ps, mp = 2, 2, 2, 128, 16, 4
        q, kn, vn, kp, vp, pt, _, _ = _case(slots, 1, g, qpk, d, ps, mp,
                                            seed=3)
        lengths = jnp.asarray([7, 33], jnp.int32)
        ones = jnp.ones_like(lengths)
        out, kpn, vpn = ragged_paged_attention(
            q, kn, vn, kp, vp, pt, lengths, ones,
            use_pallas=True, interpret=INTERPRET)
        # (a) dense decode on the gathered per-slot view
        kd = kpn[pt].reshape(slots, mp * ps, g, d)
        vd = vpn[pt].reshape(slots, mp * ps, g, d)
        for i in range(slots):
            ref = _xla_decode(q[i:i + 1], kd[i:i + 1], vd[i:i + 1],
                              lengths[i] + 1, "tgd")
            np.testing.assert_allclose(
                np.asarray(out[i:i + 1]), np.asarray(ref),
                rtol=1e-5, atol=1e-5, err_msg=f"slot {i}")
        # (b) the same rows as width-1 rows of a padded width-8 launch
        C = 8
        q8 = jnp.zeros((slots, C, g, qpk, d), q.dtype).at[:, :1].set(q)
        kn8 = jnp.zeros((slots, C, g, d), kn.dtype).at[:, :1].set(kn)
        vn8 = jnp.zeros((slots, C, g, d), vn.dtype).at[:, :1].set(vn)
        out8 = ragged_paged_attention(
            q8, kn8, vn8, kp, vp, pt, lengths, ones,
            use_pallas=True, interpret=INTERPRET)[0]
        np.testing.assert_allclose(
            np.asarray(out8[:, 0]), np.asarray(out[:, 0]),
            rtol=1e-6, atol=1e-6)

    def test_window_boundary_exact_cover_is_dense(self):
        """The reclamation bound at its tightest: a decode row at
        position p with W == p + 1 has lower bound exactly 0 — still
        bitwise the dense program; W == p drops exactly position 0 and
        must change the output. Off-by-one here silently breaks either
        the fast path (too wide) or correctness (too narrow)."""
        slots = 2
        q, kn, vn, kp, vp, pt, _, _ = _case(slots, 1, 2, 2, 128, 16, 4,
                                            seed=29)
        lengths = jnp.asarray([7, 33], jnp.int32)
        ones = jnp.ones_like(lengths)
        args = (q, kn, vn, kp, vp, pt, lengths, ones)
        kw = dict(use_pallas=True, interpret=INTERPRET)
        base = ragged_paged_attention(*args, **kw)[0]
        cover = ragged_paged_attention(*args, window_size=34, **kw)[0]
        np.testing.assert_array_equal(np.asarray(cover),
                                      np.asarray(base))
        clipped = ragged_paged_attention(*args, window_size=33, **kw)[0]
        assert np.any(np.asarray(clipped[1]) != np.asarray(base[1]))
        # slot 0 (position 7 < W) is untouched by the clip
        np.testing.assert_array_equal(np.asarray(clipped[0]),
                                      np.asarray(base[0]))

    def test_empty_and_pad_chunks_are_exact_zero(self):
        """Length-0 chunks (idle slots of a mixed step) and the pad
        rows of ragged chunks return exact zeros on both paths, and
        their K/V lands on the null page only."""
        q, kn, vn, kp, vp, pt, _, _ = _case(2, 8, 2, 1, 128, 16, 2,
                                            seed=1)
        starts, lens = [0, 9], [0, 3]
        out_k, out_x, (kpk, _), _ = _both(q, kn, vn, kp, vp, pt, starts,
                                          lens)
        for out in (out_k, out_x):
            assert not np.any(np.asarray(out[0]))  # empty chunk
            assert not np.any(np.asarray(out[1, 3:]))  # pad rows
            assert np.all(np.isfinite(np.asarray(out)))
        # pad/idle K/V never touches a live page: only the null page
        # and chunk 1's written positions may differ from the original
        before = np.asarray(kp)
        after = np.asarray(kpk)
        changed = {int(p) for p in np.argwhere(
            np.any(after != before, axis=(1, 2)))[:, 0]}
        live = {int(np.asarray(pt)[1, (9 + t) // 16]) for t in range(3)}
        assert changed <= ({0} | live)

    def test_dma_clamp_out_of_reach_pages_inert(self):
        """The kernel clamps past-the-need page indices to the last
        needed page (traffic follows start + len, not the table width)
        and the oracle's masked columns multiply by an exact fp 0:
        huge garbage planted in every page beyond each chunk's causal
        reach must leave BOTH outputs bitwise unchanged."""
        q, kn, vn, kp, vp, pt, _, _ = _case(2, 8, 2, 2, 128, 16, 4,
                                            seed=5)
        starts = jnp.asarray([0, 17], jnp.int32)
        lens = jnp.asarray([8, 5], jnp.int32)
        base_k, base_x, _, _ = _both(q, kn, vn, kp, vp, pt, starts, lens)
        # poison pages past each chunk's reach (start + len)
        ptn = np.asarray(pt)
        reach = [int(s + l) for s, l in ((0, 8), (17, 5))]
        kp2, vp2 = np.asarray(kp).copy(), np.asarray(vp).copy()
        for c in range(2):
            first_dead = (reach[c] + 15) // 16
            for j in range(first_dead, 4):
                kp2[ptn[c, j]] = 1e30
                vp2[ptn[c, j]] = 1e30
        got_k, got_x, _, _ = _both(q, kn, vn, jnp.asarray(kp2),
                                   jnp.asarray(vp2), pt, starts, lens)
        np.testing.assert_array_equal(np.asarray(got_k),
                                      np.asarray(base_k))
        np.testing.assert_array_equal(np.asarray(got_x),
                                      np.asarray(base_x))

    def test_chunk_reads_its_own_kv(self):
        """Causal columns INSIDE the chunk span come from the K/V
        scattered in the same pass: attending with start=0 over a pool
        that held garbage in the span's pages must equal dense causal
        attention over k_new/v_new alone."""
        nc, C, g, qpk, d = 1, 8, 2, 2, 128
        q, kn, vn, kp, vp, pt, _, _ = _case(nc, C, g, qpk, d, 16, 2,
                                            seed=2)
        out_k, out_x, _, _ = _both(q, kn, vn, kp, vp, pt, [0], [C])
        from megatron_llm_tpu.models.attention import (
            causal_mask,
            grouped_attention,
        )

        class _Cfg:
            attention_dropout = 0.0
            num_query_groups, q_per_kv, head_dim = g, qpk, d

        ref = grouped_attention(q, kn, vn, causal_mask(C), _Cfg(),
                                None, True)
        for out in (out_k, out_x):
            np.testing.assert_allclose(
                np.asarray(out).reshape(nc, C, -1), np.asarray(ref),
                rtol=1e-5, atol=1e-5)

    def test_scatter_quantizes_with_scales_in_place(self):
        """The int8 scatter writes data AND scales at the same
        [page, offset]; rows round-trip within scale/2; pad rows land
        on the null page (data + scale both) and no foreign page is
        touched."""
        g, qpk, d, ps = 2, 1, 128, 32
        num_pages = 1 + 2 * 2
        keys = jax.random.split(jax.random.key(11), 3)
        kp = jnp.zeros((num_pages, ps, g * d), jnp.int8)
        vp = jnp.zeros_like(kp)
        kps = jnp.zeros((num_pages, ps, g), jnp.float32)
        vps = jnp.zeros_like(kps)
        rs = np.random.RandomState(11)
        pt = jnp.asarray((rs.permutation(num_pages - 1) + 1)
                         .reshape(2, 2), jnp.int32)
        C = 8
        kn = jax.random.normal(keys[1], (2, C, g, d), jnp.float32)
        vn = jax.random.normal(keys[2], (2, C, g, d), jnp.float32)
        starts = jnp.asarray([0, 3], jnp.int32)
        lens = jnp.asarray([8, 5], jnp.int32)  # chunk 1: 3 pad rows
        kp2, vp2, kps2, vps2 = scatter_chunk_kv(
            kn, vn, kp, vp, pt, starts, lens, k_scales=kps,
            v_scales=vps)
        deq = dequantize_rows(kp2[pt[0, 0]].reshape(ps, g, d),
                              kps2[pt[0, 0]])
        err = jnp.abs(deq[:8] - kn[0])
        assert bool(jnp.all(err <= kps2[pt[0, 0], :8, :, None] * 0.5
                            + 1e-7))
        # pad rows of chunk 1 (tokens 5..7) went to the null page
        assert bool(jnp.any(kp2[0] != 0)) and bool(jnp.any(kps2[0] != 0))
        # untouched foreign slot pages stay zero past chunk 1's reach
        own = {int(pt[1, 0])}
        other = [p for p in range(1, kp2.shape[0])
                 if p not in own | {int(pt[0, 0])}]
        assert bool(jnp.all(kps2[jnp.asarray(other)] == 0))

    def test_traced_operands_under_jit(self):
        """starts/lens/page table are TRACED in the engine's step fns;
        the scalar-prefetch operands must accept them."""
        q, kn, vn, kp, vp, pt, _, _ = _case(2, 4, 2, 1, 128, 16, 2,
                                            seed=5)

        @jax.jit
        def f(q, kn, vn, kp, vp, pt, starts, lens):
            return ragged_paged_attention(q, kn, vn, kp, vp, pt, starts,
                                          lens, use_pallas=True,
                                          interpret=INTERPRET)[0]

        for starts, lens in (([0, 8], [4, 4]), ([3, 15], [2, 4])):
            starts = jnp.asarray(starts, jnp.int32)
            lens = jnp.asarray(lens, jnp.int32)
            kpx, vpx = scatter_chunk_kv(kn, vn, kp, vp, pt, starts,
                                        lens)
            np.testing.assert_allclose(
                np.asarray(f(q, kn, vn, kp, vp, pt, starts, lens)),
                np.asarray(_xla_paged_reference(q, kpx, vpx, pt, starts,
                                                lens)),
                rtol=1e-5, atol=1e-5)


class TestDispatchGate:
    def test_gate(self):
        """ONE gate for every phase: the decode-row values ride the
        same rules as chunk widths (s == 1 is just the narrowest
        chunk), so a near-tie can never flip paths between the scan and
        mixed steps."""
        ok = dict(interpret=True)
        # (q block in tokens, K/V heads a grid step)
        assert ragged_paged_block(8, 1, 128, 16, 4, **ok) == (8, 1)
        assert ragged_paged_block(1, 8, 128, 16, 4, **ok) == (1, 1)
        # the decode row: width 1 is kernel territory
        assert ragged_paged_block(1, 1, 128, 64, 8, **ok) == (1, 1)
        assert ragged_paged_block(256, 1, 128, 64, 8, **ok) == (256, 1)
        # wide GQA folds shrink the q block under the VMEM row cap
        assert ragged_paged_block(2048, 8, 128, 16, 4, **ok) == (256, 1)
        # page must tile sublanes
        assert ragged_paged_block(8, 1, 128, 8, 4, **ok) is None
        assert ragged_paged_block(8, 1, 128, 24, 4, **ok) is None
        # int8 pools need the 32 int8 sublane tile
        assert ragged_paged_block(8, 1, 128, 16, 4, kv_dtype=jnp.int8,
                                  **ok) is None
        assert ragged_paged_block(8, 1, 128, 32, 4, kv_dtype=jnp.int8,
                                  **ok) is not None
        assert ragged_paged_block(1, 2, 128, 16, 4, kv_dtype=jnp.int8,
                                  **ok) is None
        assert ragged_paged_block(1, 2, 128, 32, 4, kv_dtype=jnp.int8,
                                  **ok) is not None
        # min-cache threshold measured against the per-slot reach
        assert ragged_paged_block(8, 1, 128, 16, 4, min_cache=128,
                                  interpret=True) is None
        assert ragged_paged_block(8, 1, 128, 16, 8, min_cache=128,
                                  interpret=True) == (8, 1)
        assert ragged_paged_block(1, 1, 128, 16, 4, min_cache=128,
                                  interpret=True) is None
        assert ragged_paged_block(1, 1, 128, 16, 8, min_cache=128,
                                  interpret=True) == (1, 1)
        if jax.default_backend() != "tpu":
            assert ragged_paged_block(8, 1, 128, 16, 4,
                                      interpret=False) is None

    @pytest.mark.parametrize("s,qpk,d,page,groups,kv,want", [
        # heads that fill 128-lane tiles take the kernel (ISSUE 38): a
        # decode row all of its heads a grid step, a chunk one tile's
        pytest.param(1, 4, 64, 64, 8, None, (1, 8), id="lfm2-decode-row"),
        pytest.param(128, 4, 64, 64, 8, None, (128, 2), id="lfm2-chunk"),
        pytest.param(2, 4, 64, 64, 8, None, (2, 8), id="lfm2-width-2"),
        pytest.param(1, 16, 64, 64, 2, None, (1, 2),
                     id="falcon40b-tp4-chip-decode-row"),
        pytest.param(1, 4, 64, 64, 8, jnp.int8, (1, 8), id="lfm2-int8"),
        pytest.param(1, 4, 128, 64, 8, None, (1, 8),
                     id="head128-decode-row-whole-page"),
        pytest.param(8, 2, 32, 16, 4, None, (8, 4), id="four-heads-of-32"),
        # ... and these fill none: the twin, on the same pool
        pytest.param(1, 71, 64, 64, 1, None, None, id="falcon7b-mqa-head64"),
        pytest.param(128, 71, 64, 64, 1, None, None,
                     id="falcon7b-mqa-head64-chunk"),
        pytest.param(8, 2, 64, 16, 3, None, None, id="a-tile-and-a-half"),
        pytest.param(8, 2, 96, 16, 4, None, None, id="96-divides-no-tile"),
        # a q block Mosaic takes: folded rows the whole axis or a
        # multiple of 8 (a speculative width 5 at qpk 4 would be blocks
        # of 4 rows of 20: PERF.md §7 had it as found and not cured)
        pytest.param(5, 4, 128, 16, 1, None, None, id="spec-5-rows-of-4"),
        pytest.param(5, 8, 128, 16, 1, None, (1, 1), id="spec-5-rows-of-8"),
    ])
    def test_head_width_gate(self, s, qpk, d, page, groups, kv, want):
        """What the gate can see decides: the K/V heads of the call
        fill lane tiles or they do not — no model's name, no option."""
        assert ragged_paged_block(s, qpk, d, page, 32, groups=groups,
                                  kv_dtype=kv, interpret=True) == want

    def test_ineligible_page_size_falls_back_exact(self):
        """Shapes the gate refuses are served by the XLA twin — for
        BOTH kv dtypes (fp: ps below the 16-sublane tile; int8: ps 16
        below the 32 int8 tile)."""
        q, kn, vn, kp, vp, pt, _, _ = _case(2, 4, 2, 1, 128, 8, 4,
                                            seed=6)
        starts = jnp.asarray([0, 5], jnp.int32)
        lens = jnp.asarray([4, 3], jnp.int32)
        out, kpn, vpn = ragged_paged_attention(
            q, kn, vn, kp, vp, pt, starts, lens, use_pallas=True,
            interpret=INTERPRET)
        np.testing.assert_array_equal(
            np.asarray(out),
            np.asarray(_xla_paged_reference(q, kpn, vpn, pt, starts,
                                            lens)))
        q, kn, vn, kq, vq, pt, ks, vs = _case(2, 1, 2, 2, 128, 16, 4,
                                              kv="int8", seed=6)
        lens1 = jnp.asarray([1, 1], jnp.int32)
        starts1 = jnp.asarray([5, 20], jnp.int32)
        out, kq2, vq2, ks2, vs2 = ragged_paged_attention(
            q, kn, vn, kq, vq, pt, starts1, lens1, use_pallas=True,
            interpret=INTERPRET, k_scales=ks, v_scales=vs)
        np.testing.assert_array_equal(
            np.asarray(out),
            np.asarray(_xla_paged_reference(q, kq2, vq2, pt, starts1,
                                            lens1, k_scales=ks2,
                                            v_scales=vs2)))

    def test_scales_required_for_int8(self):
        q, kn, vn, kq, vq, pt, _, _ = _case(2, 1, 2, 2, 128, 32, 2,
                                            kv="int8", seed=6)
        with pytest.raises(AssertionError, match="k_scales"):
            ragged_paged_attention(q, kn, vn, kq, vq, pt,
                                   jnp.asarray([1, 1], jnp.int32),
                                   jnp.asarray([1, 1], jnp.int32))


class TestAttentionBlockPaged:
    """attention_block's ONE paged branch: kernel vs XLA parity for
    both cache forms, carry-stable pytrees, the ragged length advance,
    the page-table-directed scatter, and chunked == dense prefill."""

    def _cfg(self, **over):
        from megatron_llm_tpu.config import ModelConfig

        base = dict(
            num_layers=1, hidden_size=256, num_attention_heads=2,
            num_attention_heads_kv=1, kv_channels=128,
            max_position_embeddings=64, seq_length=64,
            compute_dtype=jnp.float32, params_dtype=jnp.float32,
            use_bias=False, attention_dropout=0.0, hidden_dropout=0.0,
            use_decode_attn=True, decode_attn_interpret=INTERPRET,
            decode_attn_min_cache=0,
        )
        base.update(over)
        return ModelConfig(**base)

    def _params(self, cfg, seed=0):
        ks = jax.random.split(jax.random.key(seed), 2)
        h = cfg.hidden_size
        return {
            "wqkv": jax.random.normal(
                ks[0], (h, cfg.qkv_projection_size), jnp.float32) * 0.05,
            "wo": jax.random.normal(
                ks[1], (cfg.num_attention_heads * cfg.head_dim, h),
                jnp.float32) * 0.05,
        }

    def _cache(self, cfg, slots, ps, mp, lengths, chunk_lens=None,
               random_pool=False, seed=6):
        g, d = cfg.num_query_groups, cfg.head_dim
        num_pages = 1 + slots * mp
        pt = np.zeros((slots, mp), np.int32)
        for i in range(slots):
            pt[i] = np.arange(1 + i * mp, 1 + (i + 1) * mp)
        if random_pool:
            ks = jax.random.split(jax.random.key(seed), 2)
            kp = jax.random.normal(ks[0], (num_pages, ps, g * d),
                                   jnp.float32)
            vp = jax.random.normal(ks[1], (num_pages, ps, g * d),
                                   jnp.float32)
        else:
            kp = jnp.zeros((num_pages, ps, g * d), jnp.float32)
            vp = jnp.zeros_like(kp)
        cache = {
            "k_pages": kp, "v_pages": vp,
            "page_table": jnp.asarray(pt),
            "lengths": jnp.asarray(lengths, jnp.int32),
        }
        if chunk_lens is not None:
            cache["chunk_lens"] = jnp.asarray(chunk_lens, jnp.int32)
        return cache

    def test_chunked_kernel_vs_xla_and_length_advance(self):
        from megatron_llm_tpu.models.attention import attention_block

        cfg_on = self._cfg()
        cfg_off = dataclasses.replace(cfg_on, use_decode_attn=False)
        params = self._params(cfg_on)
        slots, ps, mp, w = 2, 16, 4, 8
        hidden = jax.random.normal(jax.random.key(5), (slots, w, 256),
                                   jnp.float32)
        outs = {}
        for name, cfg in (("on", cfg_on), ("off", cfg_off)):
            outs[name] = attention_block(
                params, cfg, hidden, None, None, None,
                kv_cache=self._cache(cfg, slots, ps, mp, [0, 21],
                                     chunk_lens=[8, 3]))
        np.testing.assert_allclose(
            np.asarray(outs["on"][0]), np.asarray(outs["off"][0]),
            rtol=1e-5, atol=1e-6)
        np.testing.assert_array_equal(
            np.asarray(outs["on"][1]["lengths"]), [8, 24])
        for key in ("k_pages", "v_pages"):
            np.testing.assert_array_equal(
                np.asarray(outs["on"][1][key]),
                np.asarray(outs["off"][1][key]))

    def test_decode_form_kernel_vs_xla_and_carry_shape(self):
        """The bare paged form (no chunk_lens — the decode scan's
        carry) takes the same unified path: kernel vs XLA parity at
        the layer level, lengths advance by one, and the returned
        cache pytree has NO chunk_lens key (scan carries must be
        structure-stable)."""
        from megatron_llm_tpu.models.attention import attention_block

        cfg_on = self._cfg()
        cfg_off = dataclasses.replace(cfg_on, use_decode_attn=False)
        params = self._params(cfg_on)
        slots, ps, mp = 2, 16, 4
        hidden = jax.random.normal(jax.random.key(5), (slots, 1, 256),
                                   jnp.float32)
        out_on, cache_on = attention_block(
            params, cfg_on, hidden, None, None, None,
            kv_cache=self._cache(cfg_on, slots, ps, mp, [7, 33],
                                 random_pool=True))
        out_off, cache_off = attention_block(
            params, cfg_off, hidden, None, None, None,
            kv_cache=self._cache(cfg_off, slots, ps, mp, [7, 33],
                                 random_pool=True))
        np.testing.assert_allclose(
            np.asarray(out_on), np.asarray(out_off), rtol=1e-5,
            atol=1e-6)
        assert "chunk_lens" not in cache_on
        np.testing.assert_array_equal(np.asarray(cache_on["lengths"]),
                                      [8, 34])
        for key in cache_on:
            np.testing.assert_array_equal(np.asarray(cache_on[key]),
                                          np.asarray(cache_off[key]))

    def test_scatter_targets_owned_page(self):
        """The decode step's K/V lands at page_table[slot, len // ps]
        offset len % ps, and ONLY there; lengths advance by one."""
        from megatron_llm_tpu.models.attention import attention_block

        cfg = self._cfg(use_decode_attn=False)
        params = self._params(cfg)
        slots, ps, mp = 2, 16, 4
        cache = self._cache(cfg, slots, ps, mp, [7, 33],
                            random_pool=True)
        before_k = np.asarray(cache["k_pages"]).copy()
        hidden = jax.random.normal(jax.random.key(8), (slots, 1, 256),
                                   jnp.float32)
        _, new_cache = attention_block(
            params, cfg, hidden, None, None, None, kv_cache=cache)
        after_k = np.asarray(new_cache["k_pages"])
        np.testing.assert_array_equal(np.asarray(new_cache["lengths"]),
                                      [8, 34])
        pt = np.asarray(cache["page_table"])
        changed = np.argwhere(
            np.any(after_k != before_k, axis=2))  # (page, off)
        expect = {(int(pt[0, 7 // ps]), 7 % ps),
                  (int(pt[1, 33 // ps]), 33 % ps)}
        assert {tuple(map(int, rc)) for rc in changed} == expect

    def test_retired_slot_writes_null_page(self):
        """A slot with an all-zero page-table row (the engine's retired
        state) scatters into pool page 0 and corrupts nothing else."""
        from megatron_llm_tpu.models.attention import attention_block

        cfg = self._cfg(use_decode_attn=False)
        params = self._params(cfg)
        slots, ps, mp = 2, 16, 2
        cache = self._cache(cfg, slots, ps, mp, [5, 0],
                            random_pool=True)
        pt = np.array(cache["page_table"])
        pt[1] = 0  # slot 1 retired
        cache["page_table"] = jnp.asarray(pt)
        before_k = np.asarray(cache["k_pages"]).copy()
        hidden = jax.random.normal(jax.random.key(9), (slots, 1, 256),
                                   jnp.float32)
        _, new_cache = attention_block(
            params, cfg, hidden, None, None, None, kv_cache=cache)
        after_k = np.asarray(new_cache["k_pages"])
        changed_pages = set(
            int(p) for p in
            np.argwhere(np.any(after_k != before_k,
                               axis=(1, 2)))[:, 0]
        )
        assert changed_pages <= {0, int(pt[0, 5 // ps])}

    def test_chunked_equals_dense_prefill_per_layer(self):
        """Feeding a prompt through the chunked branch in two ragged
        spans reproduces the dense per-layer prefill — the layer-level
        form of the engine's exact-match guarantee. Numerically tight
        (not bitwise) HERE: at this width XLA's CPU thread partitioning
        blocks the h-reduction differently per matmul M-dim; the
        BITWISE pin lives at the engine level (tests/test_engine.py),
        where it holds across chunk placements."""
        from megatron_llm_tpu.models.attention import attention_block
        from megatron_llm_tpu.models.rope import precompute_rope

        cfg = self._cfg(use_decode_attn=False)
        params = self._params(cfg)
        rope = precompute_rope(cfg.head_dim, 64, cfg.rope_theta, 1.0)
        s = 11
        hidden = jax.random.normal(jax.random.key(8), (1, s, 256),
                                   jnp.float32)
        dense_cache = {
            "k": jnp.zeros((1, 16, cfg.num_query_groups, cfg.head_dim)),
            "v": jnp.zeros((1, 16, cfg.num_query_groups, cfg.head_dim)),
            "offset": jnp.array(0, jnp.int32),
        }
        ref, _ = attention_block(params, cfg, hidden, rope, None, None,
                                 kv_cache=dense_cache)
        got = np.zeros_like(np.asarray(ref))
        cache = self._cache(cfg, 1, 16, 2, [0], chunk_lens=[0])
        for a, b in ((0, 7), (7, 11)):
            w = 8
            h_c = jnp.zeros((1, w, 256), jnp.float32)
            h_c = h_c.at[:, :b - a].set(hidden[:, a:b])
            cache["chunk_lens"] = jnp.asarray([b - a], jnp.int32)
            out, cache = attention_block(params, cfg, h_c, rope, None,
                                         None, kv_cache=cache)
            got[:, a:b] = np.asarray(out[:, :b - a])
        np.testing.assert_allclose(got, np.asarray(ref), rtol=1e-5,
                                   atol=5e-6)


def test_transformer_stack_chunk_plumbing():
    """chunk_lens rides through the unrolled paged stack to every
    layer, the stack-level lengths advance is ragged, and the result
    matches the same stack fed slot-by-slot."""
    from megatron_llm_tpu.config import tiny_config
    from megatron_llm_tpu.models import LlamaModel

    cfg = tiny_config(compute_dtype=jnp.float32, use_decode_attn=False)
    model = LlamaModel(cfg)
    params = model.prepare_decode_params(model.init(jax.random.key(0)))
    slots, ps, mp, w = 2, 16, 2, 4
    caches = model.init_paged_kv_caches(slots, 1 + slots * mp, ps, mp)
    pt = np.zeros((slots, mp), np.int32)
    for i in range(slots):
        pt[i] = np.arange(1 + i * mp, 1 + (i + 1) * mp)
    toks = jnp.asarray(np.arange(2, 2 + slots * w).reshape(slots, w))
    lengths = jnp.asarray([0, 5], jnp.int32)
    chunk_lens = jnp.asarray([4, 2], jnp.int32)
    kvc = dict(caches, page_table=jnp.asarray(pt), lengths=lengths,
               chunk_lens=chunk_lens)
    pos = lengths[:, None] + jnp.arange(w)[None, :]
    logits, out_c = model.forward(params, toks, kv_caches=kvc,
                                  position_ids=pos)
    np.testing.assert_array_equal(np.asarray(out_c["lengths"]), [4, 7])
    assert len(out_c["k_pages_layers"]) == cfg.num_layers
    # slot 0 alone through its own single-slot stack: identical logits
    solo = model.init_paged_kv_caches(1, 1 + mp, ps, mp)
    solo = dict(solo, page_table=jnp.asarray(np.arange(1, 1 + mp)[None]),
                lengths=lengths[:1], chunk_lens=chunk_lens[:1])
    logits_solo, _ = model.forward(params, toks[:1], kv_caches=solo,
                                   position_ids=pos[:1])
    np.testing.assert_array_equal(np.asarray(logits[0, :4]),
                                  np.asarray(logits_solo[0, :4]))
