"""ZeRO-1 distributed optimizer: the explicit reduce-scatter/all-gather
decomposition (ISSUE 10, optimizer/zero1.py + training/train_step.py).

The claims pinned here:
- zero1 ON is BITWISE identical to replicated adam on the same dp mesh —
  per-step losses, final params AND moments — at dp2/dp4 in fp32, and
  with the fp16 dynamic scaler. The grad-norm SCALAR is pinned to its
  last ulp, not bitwise: it is reduced shard-wise + psum vs whole-leaf,
  and the two groupings can round differently (on jax 0.9.0's CPU
  compiler they do at dp2 fp32, one step in three) — the clip
  coefficient and skip decisions still agree, which bitwise params and
  moments prove.
- bf16 compute: the same contract to a last-ulps tolerance. The local
  shard_map program and the GSPMD program compile the bf16 softmax
  BACKWARD with different elementwise fusions (measured: the forward
  was made bitwise by mirroring constraint sites as fusion barriers —
  parallel/mesh.py manual_region(constraint_barriers=True) — but the
  d_logits chain still rounds differently on the CPU backend), so bf16
  is pinned tight-but-not-bitwise, plus run-to-run determinism.
- the bucketed reduce-scatter primitive in isolation: fp reduction is
  bitwise the rank-ordered partial sum; the int8-quantized exchange
  respects the per-chunk scale/2 error bound; degenerate buckets
  (all-zero, all-equal) behave; the DEFAULT train step lowers with no
  quantization ops and no all-to-all (HLO text), the zero1 step lowers
  WITH reduce-scatter, the quantized step WITH all-to-all + s8.
- dp-sharded optimizer state round-trips through checkpoints across
  mesh shapes (zero1 dp4 -> zero1 dp2 -> replicated, and back).
- grad-clip and found_inf/watchdog skip semantics are intact under
  sharded state.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from megatron_llm_tpu.config import ParallelConfig, TrainConfig, tiny_config
from megatron_llm_tpu.models import LlamaModel
from megatron_llm_tpu.optimizer.zero1 import (
    QUANT_CHUNK,
    build_zero1_plan,
    reduce_scatter_grads,
    zero1_out_specs,
)
from megatron_llm_tpu.parallel.mesh import (
    destroy_parallel,
    initialize_parallel,
)
from megatron_llm_tpu.training.trainer import Trainer

SEQ = 32
VOCAB = 256


def _cfg(**over):
    base = dict(
        seq_length=SEQ, max_position_embeddings=SEQ,
        compute_dtype=jnp.float32, params_dtype=jnp.float32,
    )
    base.update(over)
    return tiny_config(**base)


def _run(dp, zero1, steps=3, compute=jnp.float32, fp16=False, quant=False,
         num_micro=2, dropout=0.0, seed=0, with_hlo=False):
    """Train `steps` steps on a pure-dp mesh; returns (losses, gnorms,
    params, m, v, step_hlo_text). `with_hlo` costs a FULL extra compile
    (.lower().compile() does not reuse the jit call cache) — only the
    inventory test pays it."""
    cfg = _cfg(compute_dtype=compute, hidden_dropout=dropout,
               attention_dropout=dropout)
    mbs = 2
    rows = mbs * dp
    tcfg = TrainConfig(
        micro_batch_size=mbs, global_batch_size=num_micro * rows,
        lr=1e-3, clip_grad=1.0, train_iters=steps,
        bf16=not fp16, fp16=fp16)
    pcfg = ParallelConfig(
        data_parallel_size=dp, num_microbatches=num_micro,
        use_distributed_optimizer=zero1, quantized_grad_reduce=quant)
    ctx = initialize_parallel(dp=dp, pp=1, tp=1)
    try:
        trainer = Trainer(LlamaModel(cfg), tcfg, pcfg)
        state = trainer.setup()
        rs = np.random.RandomState(seed)
        losses, gnorms = [], []
        rng = jax.random.key(7) if dropout > 0 else None
        for i in range(steps):
            text = rs.randint(
                0, VOCAB, (num_micro, rows, SEQ + 1)).astype(np.int32)
            step_rng = jax.random.fold_in(rng, i) if rng is not None \
                else None
            stats = trainer.train_step(state, text, step_rng)
            losses.append(float(stats["loss"]))
            gnorms.append(float(stats["grad_norm"]))
        params = jax.tree.map(np.asarray, state.params)
        m = jax.tree.map(np.asarray, state.opt_state.m)
        v = jax.tree.map(np.asarray, state.opt_state.v)
        txt = None
        if with_hlo:
            from megatron_llm_tpu.training.trainer import get_batch

            text = rs.randint(0, VOCAB,
                              (num_micro, rows, SEQ + 1)).astype(np.int32)
            batch = get_batch(text, None)
            txt = trainer._get_step_fn(num_micro).lower(
                state.params, state.opt_state, batch,
                jnp.float32(1e-3), jnp.float32(0.01),
                jax.random.fold_in(rng, 99) if rng is not None else None,
                jnp.float32(np.inf)).compile().as_text()
        return losses, gnorms, params, m, v, txt
    finally:
        destroy_parallel()


def _trees_equal(a, b):
    return all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def _trees_close(a, b, rtol, atol):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_allclose(np.asarray(x, np.float32),
                                   np.asarray(y, np.float32),
                                   rtol=rtol, atol=atol)


class TestZero1BitwiseParity:
    """zero1 ON == replicated adam, trainer end to end."""

    @pytest.fixture(scope="class")
    def dp2_fp32(self):
        rep = _run(2, zero1=False, with_hlo=True)
        z1 = _run(2, zero1=True, with_hlo=True)
        return rep, z1

    def test_dp2_fp32_bitwise(self, dp2_fp32):
        (l_r, g_r, p_r, m_r, v_r, _), (l_z, g_z, p_z, m_z, v_z, _) = \
            dp2_fp32
        assert l_r == l_z, (l_r, l_z)
        np.testing.assert_array_max_ulp(
            np.float32(g_r), np.float32(g_z), maxulp=1)
        assert _trees_equal(p_r, p_z)
        assert _trees_equal(m_r, m_z)
        assert _trees_equal(v_r, v_z)

    def test_dp2_hlo_inventory(self, dp2_fp32):
        """The decomposition is in the compiled artifact: replicated has
        NO reduce-scatter / all-to-all / int8; zero1 HAS reduce-scatter
        and an all-gather, still no quantization ops (the default-OFF
        guard of the quantized reduction)."""
        (_, _, _, _, _, t_rep), (_, _, _, _, _, t_z1) = dp2_fp32
        assert "reduce-scatter" not in t_rep
        assert "all-to-all" not in t_rep
        assert "s8[" not in t_rep
        assert "reduce-scatter" in t_z1
        assert "all-gather" in t_z1
        assert "all-to-all" not in t_z1
        assert "s8[" not in t_z1

    def test_dp4_fp32_bitwise(self):
        """dp4: losses/params/moments bitwise. The grad-norm SCALAR can
        round one ulp apart at dp4 (the sharded path reduces each leaf
        as 4 shard partials combined in rank order; the replicated
        whole-leaf fp32 reduce uses XLA's pairwise tree — at dp2 the
        two groupings coincide, at dp4 they need not). The clip
        coefficient saturates at 1 below clip_grad either way, so the
        update stays bitwise; under ACTIVE clipping the coefficient —
        and then params — could differ in the same last ulp."""
        l_r, g_r, p_r, m_r, v_r, _ = _run(4, zero1=False)
        l_z, g_z, p_z, m_z, v_z, _ = _run(4, zero1=True)
        assert l_r == l_z, (l_r, l_z)
        np.testing.assert_allclose(g_r, g_z, rtol=1e-6)
        assert _trees_equal(p_r, p_z)
        assert _trees_equal(m_r, m_z)
        assert _trees_equal(v_r, v_z)

    def test_dp2_fp16_scaler_semantics(self):
        """fp16 dynamic-scaler runs: losses/params/moments bitwise; the
        scaler state (scale, growth trackers) identical — the skip and
        backoff machinery is layout-blind. The grad-norm scalar may
        round differently (shard-wise + psum vs whole-leaf reduction of
        fp16-scaled grads) — pinned to its fp32 neighborhood."""
        l_r, g_r, p_r, m_r, v_r, _ = _run(2, zero1=False, fp16=True,
                                          compute=jnp.float16)
        l_z, g_z, p_z, m_z, v_z, _ = _run(2, zero1=True, fp16=True,
                                          compute=jnp.float16)
        assert l_r == l_z, (l_r, l_z)
        assert _trees_equal(p_r, p_z)
        assert _trees_equal(m_r, m_z)
        assert _trees_equal(v_r, v_z)
        np.testing.assert_allclose(g_r, g_z, rtol=1e-6)

    def test_dp2_bf16_last_ulp(self):
        """bf16 compute: tight-but-not-bitwise (see module docstring for
        the measured mechanism), plus zero1 self-determinism BITWISE."""
        l_r, g_r, p_r, m_r, v_r, _ = _run(2, zero1=False,
                                          compute=jnp.bfloat16)
        l_z, g_z, p_z, m_z, v_z, _ = _run(2, zero1=True,
                                          compute=jnp.bfloat16)
        np.testing.assert_allclose(l_r, l_z, rtol=3e-5)
        np.testing.assert_allclose(g_r, g_z, rtol=1e-3)
        # a last-ulp bf16 grad difference can flip an early Adam
        # update's direction where v is still tiny, so the honest bound
        # on params is ABSOLUTE at the update scale (3 steps x lr=1e-3
        # with |u| <= ~1+wd), not relative
        _trees_close(p_r, p_z, rtol=0.0, atol=5e-3)
        _trees_close(m_r, m_z, rtol=0.0, atol=5e-3)
    def test_dp2_quantized_bounded_drift(self, dp2_fp32):
        """--quantized_grad_reduce on the eager zero1 step: the int8
        exchange is in the compiled step (all-to-all + s8, no
        reduce-scatter) and the loss trajectory drifts from the fp
        path only within the int8 bound (fp is the bitwise contract;
        quantized is bounded, never bitwise)."""
        _, (l_fp, _, _, _, _, _) = dp2_fp32
        l_q, _, _, _, _, txt = _run(2, zero1=True, quant=True,
                                    with_hlo=True)
        assert all(np.isfinite(l_q)), l_q
        drift = max(abs(a - b) / max(abs(a), 1e-9)
                    for a, b in zip(l_fp, l_q))
        assert drift < 0.05, (drift, l_fp, l_q)
        assert "all-to-all" in txt
        assert "s8[" in txt
        assert "reduce-scatter" not in txt

    def test_dropout_rng_smoke(self):
        """The explicit path with dropout: the per-rank rng fold runs
        and trains (the stream deviates from replicated by design —
        documented in GUIDE.md)."""
        l_z, _, p_z, _, _, _ = _run(2, zero1=True, steps=2, dropout=0.1)
        assert all(np.isfinite(l_z)), l_z

    @pytest.mark.slow
    def test_bf16_self_determinism(self):
        """The explicit bf16 path reproduces itself bitwise run to run
        (the non-bitwise delta vs replicated is cross-PROGRAM fusion,
        not nondeterminism)."""
        a = _run(2, zero1=True, compute=jnp.bfloat16)
        b = _run(2, zero1=True, compute=jnp.bfloat16)
        assert a[0] == b[0] and a[1] == b[1]
        assert _trees_equal(a[2], b[2])
        assert _trees_equal(a[3], b[3])


class TestQuantizedGates:
    def test_quantized_requires_zero1(self):
        with pytest.raises(ValueError, match="use_distributed_optimizer"):
            ParallelConfig(data_parallel_size=2,
                           quantized_grad_reduce=True)

    def test_quantized_rejects_mixed_mesh(self):
        with pytest.raises(ValueError, match="pure-dp"):
            ParallelConfig(data_parallel_size=2, tensor_parallel_size=2,
                           use_distributed_optimizer=True,
                           quantized_grad_reduce=True)

    def test_quantized_rejects_model_without_loss_terms(self):
        """A loss_terms-less model under --quantized_grad_reduce fails
        LOUDLY at step construction instead of silently training
        full-precision."""
        from megatron_llm_tpu.models.bert import BertModel
        from megatron_llm_tpu.training.train_step import make_train_step

        cfg = _cfg(num_tokentypes=2, add_binary_head=True,
                   position_embedding_type="absolute", use_bias=True,
                   glu_activation=None, use_rms_norm=False,
                   tie_embed_logits=True)
        pcfg = ParallelConfig(data_parallel_size=2, num_microbatches=1,
                              use_distributed_optimizer=True,
                              quantized_grad_reduce=True)
        ctx = initialize_parallel(dp=2, pp=1, tp=1)
        try:
            with pytest.raises(ValueError, match="loss_terms"):
                make_train_step(BertModel(cfg), TrainConfig(lr=1e-3),
                                pcfg)
        finally:
            destroy_parallel()


class TestZero1SkipSemantics:
    def test_watchdog_spike_skip_identical(self):
        """A spike-threshold skip under zero1: params/opt untouched
        BITWISE (the found_inf gate rides the sharded update's select),
        exactly as the replicated path skips."""
        from megatron_llm_tpu.training.train_step import make_train_step
        from megatron_llm_tpu.training.trainer import get_batch

        cfg = _cfg()
        dp, num_micro, mbs = 2, 2, 2
        rows = mbs * dp
        tcfg = TrainConfig(micro_batch_size=mbs,
                           global_batch_size=num_micro * rows, lr=1e-3)
        pcfg = ParallelConfig(data_parallel_size=dp,
                              num_microbatches=num_micro,
                              use_distributed_optimizer=True)
        ctx = initialize_parallel(dp=dp, pp=1, tp=1)
        try:
            model = LlamaModel(cfg)
            trainer = Trainer(model, tcfg, pcfg)
            state = trainer.setup()
            text = np.random.RandomState(0).randint(
                0, VOCAB, (num_micro, rows, SEQ + 1)).astype(np.int32)
            batch = get_batch(text, None)
            step = trainer._get_step_fn(num_micro)
            p0 = jax.tree.map(np.asarray, state.params)
            m0 = jax.tree.map(np.asarray, state.opt_state.m)
            # threshold far below any real loss -> the step must skip
            new_p, new_s, stats = step(
                state.params, state.opt_state, batch, jnp.float32(1e-3),
                jnp.float32(0.0), None, jnp.float32(1e-6))
            assert int(stats["skipped"]) == 1
            assert _trees_equal(p0, jax.tree.map(np.asarray, new_p))
            assert _trees_equal(m0, jax.tree.map(np.asarray, new_s.m))
            assert int(new_s.step) == 0
        finally:
            destroy_parallel()


# ---------------------------------------------------------------------------
# The reduce-scatter primitive in isolation (satellite: quantized
# all-reduce tests)
# ---------------------------------------------------------------------------


def _leaf_tree(rs, dp):
    """A grad-shaped tree covering the plan's cases: big 2D (own
    bucket), small leaves (shared bucket), a (L, h) leaf whose dp axis
    is NOT axis 0, and a residue leaf with no dp-divisible axis."""
    return {
        "w_big": jnp.asarray(rs.randn(16 * dp, 64), jnp.float32),
        "w_small": jnp.asarray(rs.randn(dp, 8), jnp.float32),
        "norm": jnp.asarray(rs.randn(3, 8 * dp), jnp.float32),
        "residue": jnp.asarray(rs.randn(3, 5), jnp.float32),
    }


def _plan_for(tree, dp, bucket_mb):
    # build_zero1_plan reads param_specs(cfg, tree); this tree is not a
    # transformer layer tree, so every leaf gets the replicated default
    # spec and zero1_axis picks the first dp-divisible axis — exactly
    # what the primitive test wants.
    return build_zero1_plan(_cfg(), tree, dp, bucket_mb=bucket_mb)


def _reduce_on_mesh(tree, dp, quantized, bucket_mb=0.001):
    """Drive reduce_scatter_grads with DISTINCT per-rank partials: the
    input carries a leading (dp,) axis sharded over data; the body
    peels its own slice as the local partial."""
    plan = _plan_for(jax.tree.map(lambda x: x[0], tree), dp, bucket_mb)
    ctx = initialize_parallel(dp=dp, pp=1, tp=1)
    try:
        mesh = ctx.mesh
        stacked = jax.device_put(
            tree, jax.tree.map(
                lambda x: NamedSharding(
                    mesh, P(*(["data"] + [None] * (x.ndim - 1)))), tree))
        g_specs = zero1_out_specs(
            plan, jax.tree.structure(jax.tree.map(lambda x: x[0], tree)))

        def body(t):
            local = jax.tree.map(lambda x: x[0], t)
            return reduce_scatter_grads(local, plan, quantized=quantized)

        fn = jax.jit(jax.shard_map(
            body, mesh=mesh,
            in_specs=(jax.tree.map(
                lambda x: P(*(["data"] + [None] * (x.ndim - 1))), tree),),
            out_specs=g_specs, check_vma=False))
        out = fn(stacked)
        txt = fn.lower(stacked).compile().as_text()
        return jax.tree.map(np.asarray, out), plan, txt
    finally:
        destroy_parallel()


def _rank_order_sum(stacked):
    """numpy reference: partials accumulated in rank order (the
    documented collective order)."""
    out = np.asarray(stacked[0], np.float32).copy()
    for r in range(1, stacked.shape[0]):
        out = out + np.asarray(stacked[r], np.float32)
    return out


class TestReduceScatterPrimitive:
    @pytest.mark.parametrize("dp", [2, 4])
    def test_fp_bitwise_vs_rank_order_sum(self, dp):
        rs = np.random.RandomState(0)
        tree = jax.tree.map(
            lambda x: jnp.stack([x + i for i in range(dp)]),
            _leaf_tree(rs, dp))
        out, plan, txt = _reduce_on_mesh(tree, dp, quantized=False)
        for k in tree:
            ref = _rank_order_sum(np.asarray(tree[k]))
            assert np.array_equal(out[k], ref), k
        # the sharded leaves went through a real reduce-scatter; the
        # residue through all-reduce; nothing quantized
        assert "reduce-scatter" in txt
        assert "all-to-all" not in txt
        assert "s8[" not in txt
        # bucket targeting: the big leaf exceeds the tiny target, so
        # more than one bucket exists; the residue leaf stays out
        assert len(plan.buckets) >= 2
        assert len(plan.residue) == 1

    @pytest.mark.parametrize("dp", [2, 4])
    def test_quantized_error_bound(self, dp):
        rs = np.random.RandomState(1)
        tree = jax.tree.map(
            lambda x: jnp.stack([x * (1 + 0.1 * i) for i in range(dp)]),
            _leaf_tree(rs, dp))
        out, plan, txt = _reduce_on_mesh(tree, dp, quantized=True)
        assert "all-to-all" in txt
        assert "s8[" in txt
        flat_ref = {k: _rank_order_sum(np.asarray(tree[k])) for k in tree}
        # residue leaves are NOT quantized: bitwise
        assert np.array_equal(out["residue"], flat_ref["residue"])
        # sharded leaves: |err| <= sum_r scale_r/2 per element, where
        # scale_r is the rank's per-chunk amax/127. Bound it leaf-wide
        # with the max per-rank amax (chunks only tighten it).
        for k in ("w_big", "w_small", "norm"):
            stacked = np.asarray(tree[k], np.float32)
            bound = sum(
                np.abs(stacked[r]).max() / 127.0 / 2.0
                for r in range(dp)) + 1e-6
            err = np.abs(out[k] - flat_ref[k]).max()
            assert err <= bound, (k, err, bound)

    def test_quantized_degenerate_zero_and_equal(self):
        dp = 2
        z = jnp.zeros((dp, 4 * dp, QUANT_CHUNK // 4), jnp.float32)
        eq = jnp.full((dp, 4 * dp, 8), 0.375, jnp.float32)
        tree = {"zero": z, "equal": eq}
        out, _, _ = _reduce_on_mesh(tree, dp, quantized=True)
        # all-zero bucket: exact zeros (scale-0 guarded reciprocal)
        assert np.array_equal(out["zero"], np.zeros(z.shape[1:])), \
            np.abs(out["zero"]).max()
        # all-equal values quantize to exactly +/-127 steps: the
        # round-trip is within one fp32 ulp of dp * value
        np.testing.assert_allclose(out["equal"], dp * 0.375, rtol=1e-6)

    def test_bucket_partitioning(self):
        """Size-targeted greedy packing: a leaf above the target gets
        its own bucket, small leaves share, residue leaves (no
        dp-divisible axis) are excluded from every bucket."""
        rs = np.random.RandomState(2)
        tree = _leaf_tree(rs, 2)
        plan = _plan_for(tree, 2, bucket_mb=0.001)  # 1 KiB target
        flat, _ = jax.tree.flatten(tree)
        all_bucketed = sorted(i for b in plan.buckets for i in b)
        assert all_bucketed == sorted(
            i for i in range(len(flat)) if plan.leaf_axes[i] is not None)
        assert len(plan.residue) == 1
        sizes = [sum(int(flat[i].size) * 4 for i in b)
                 for b in plan.buckets]
        assert max(sizes) >= 1024  # the big leaf alone busts the target
        # one-bucket regime: a huge target packs everything together
        plan_big = _plan_for(tree, 2, bucket_mb=64)
        assert len(plan_big.buckets) == 1

    def test_comm_bytes_accounting(self):
        rs = np.random.RandomState(3)
        tree = _leaf_tree(rs, 2)
        plan = _plan_for(tree, 2, bucket_mb=64)
        flat, _ = jax.tree.flatten(tree)
        sharded = sum(int(flat[i].size)
                      for b in plan.buckets for i in b)
        residue = sum(int(flat[i].size) for i in plan.residue)
        fp = plan.comm_bytes_per_reduce(quantized=False)
        q = plan.comm_bytes_per_reduce(quantized=True)
        assert fp == (sharded + residue) * 4
        assert q < fp  # int8 + scales beats fp32
        assert q >= sharded * 1 + residue * 4  # data floor


# ---------------------------------------------------------------------------
# dp-sharded optimizer-state checkpoint round trip (satellite)
# ---------------------------------------------------------------------------


class TestShardedStateCheckpoint:
    def _sharded_state(self, dp):
        from megatron_llm_tpu.optimizer.optimizer import (
            OptimizerState,
            init_optimizer_state,
        )
        from megatron_llm_tpu.parallel.sharding import (
            optimizer_state_specs,
            param_specs,
        )

        cfg = _cfg()
        model = LlamaModel(cfg)
        ctx = initialize_parallel(dp=dp, pp=1, tp=1)
        mesh = ctx.mesh
        tmpl = jax.eval_shape(model.init, jax.random.key(0))
        pspecs = param_specs(cfg, tmpl)
        psh = jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs,
                           is_leaf=lambda x: isinstance(x, P))
        params = jax.jit(model.init, out_shardings=psh)(jax.random.key(3))
        tcfg = TrainConfig(lr=1e-3)
        ospecs = optimizer_state_specs(cfg, tmpl, dp, True,
                                       base_specs=pspecs)
        osh = jax.tree.map(lambda s: NamedSharding(mesh, s), ospecs,
                           is_leaf=lambda x: isinstance(x, P))
        opt = jax.jit(
            lambda p: init_optimizer_state(p, tcfg),
            out_shardings=OptimizerState(
                step=NamedSharding(mesh, P()), m=osh, v=osh,
                scaler=None))(params)
        # make the moments non-trivial so a resharding bug is visible
        key = jax.random.key(11)
        opt = opt._replace(
            m=jax.tree.map(
                lambda x: x + jax.random.normal(key, x.shape, x.dtype),
                opt.m))
        return cfg, params, opt

    def test_zero1_dp4_restores_under_dp2_and_replicated(self, tmp_path):
        """Save under zero1 dp4; restore under zero1 dp2 AND with no
        mesh at all — tensorstore reshards on load, values bitwise."""
        from megatron_llm_tpu.training.checkpointing import (
            load_checkpoint,
            save_checkpoint,
        )

        cfg, params, opt = self._sharded_state(4)
        ref_m = jax.tree.map(np.asarray, opt.m)
        ref_p = jax.tree.map(np.asarray, params)
        save_checkpoint(str(tmp_path), 1, params, opt, cfg)
        destroy_parallel()

        # restore under zero1 dp2 (different shard boundaries)
        cfg2, params2, opt2 = self._sharded_state(2)
        loaded = load_checkpoint(str(tmp_path), params2, opt2, cfg2)
        assert loaded is not None
        r_params, r_opt, _, it = loaded
        assert it == 1
        assert _trees_equal(ref_p, jax.tree.map(np.asarray, r_params))
        assert _trees_equal(ref_m, jax.tree.map(np.asarray, r_opt.m))
        # the restored leaves carry the dp2 TEMPLATE's shardings
        some = jax.tree.leaves(r_opt.m)[0]
        tpl = jax.tree.leaves(opt2.m)[0]
        assert some.sharding == tpl.sharding
        destroy_parallel()

        # restore with NO mesh (replicated single-process template)
        model = LlamaModel(cfg)
        params_r = model.init(jax.random.key(0))
        from megatron_llm_tpu.optimizer.optimizer import (
            init_optimizer_state,
        )

        opt_r = init_optimizer_state(params_r, TrainConfig(lr=1e-3))
        loaded = load_checkpoint(str(tmp_path), params_r, opt_r, cfg)
        assert loaded is not None
        assert _trees_equal(ref_m, jax.tree.map(np.asarray, loaded[1].m))

    def test_replicated_restores_under_zero1_dp4(self, tmp_path):
        """The reverse direction: a replicated checkpoint restores into
        dp4-sharded optimizer-state templates."""
        from megatron_llm_tpu.optimizer.optimizer import (
            init_optimizer_state,
        )
        from megatron_llm_tpu.training.checkpointing import (
            load_checkpoint,
            save_checkpoint,
        )

        cfg = _cfg()
        model = LlamaModel(cfg)
        params = model.init(jax.random.key(5))
        opt = init_optimizer_state(params, TrainConfig(lr=1e-3))
        key = jax.random.key(13)
        opt = opt._replace(
            v=jax.tree.map(
                lambda x: x + jnp.abs(
                    jax.random.normal(key, x.shape, x.dtype)), opt.v))
        ref_v = jax.tree.map(np.asarray, opt.v)
        save_checkpoint(str(tmp_path), 2, params, opt, cfg)

        cfg2, params2, opt2 = self._sharded_state(4)
        try:
            loaded = load_checkpoint(str(tmp_path), params2, opt2, cfg2)
            assert loaded is not None
            r_opt = loaded[1]
            assert _trees_equal(ref_v, jax.tree.map(np.asarray, r_opt.v))
            assert loaded[3] == 2
        finally:
            destroy_parallel()
