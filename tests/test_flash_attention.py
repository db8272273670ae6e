"""Flash-attention kernel correctness: forward AND backward vs the XLA
reference, GQA/MQA/MHA, causal and full (VERDICT r1 missing #4 / weak #3).

Interpret mode comes from the ONE shared conftest policy
(`kernel_interpret_mode` / MEGATRON_TPU_KERNEL_INTERPRET): on CPU the
real Pallas kernels run through the interpreter; the same kernels
compile natively on TPU (both training cells of `benchmark/` run them;
`chip_smoke.py` compares each with its XLA twin on the chip). Ref parity target: training through
flash-attn (ref transformer.py:508-523) with the external flash_attn
package's numerics.
"""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import kernel_interpret_mode

from megatron_llm_tpu.ops.flash_attention import (
    _choose_block,
    _xla_reference,
    _xla_reference_with_lse,
    flash_attention,
    flash_attention_with_lse,
)

# `megatron_llm_tpu.ops.flash_attention` as an attribute is the FUNCTION
# (the package re-exports it); this is the module
flash_module = importlib.import_module("megatron_llm_tpu.ops.flash_attention")

INTERPRET = kernel_interpret_mode()


def _rand_qkv(b, s, g, qpk, d, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.key(seed), 3)
    q = jax.random.normal(ks[0], (b, s, g, qpk, d), dtype)
    k = jax.random.normal(ks[1], (b, s, g, d), dtype)
    v = jax.random.normal(ks[2], (b, s, g, d), dtype)
    return q, k, v


def _flash_interp(q, k, v, causal=True, block_q=64, block_k=64):
    return flash_attention(
        q, k, v, causal=causal, use_pallas=True, interpret=INTERPRET,
        block_q=block_q, block_k=block_k,
    )


# d=128: the lane-aligned head, nothing padded (TestNarrowHead has 64 and 80)
CASES = [
    # (g, qpk) : MHA, GQA, MQA
    pytest.param(4, 1, id="mha"),
    pytest.param(2, 4, id="gqa"),
    pytest.param(1, 8, id="mqa"),
]


class TestForward:
    @pytest.mark.parametrize("g,qpk", CASES)
    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_xla(self, g, qpk, causal):
        q, k, v = _rand_qkv(2, 128, g, qpk, 128)
        ref = _xla_reference(q, k, v, causal)
        out = _flash_interp(q, k, v, causal)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5
        )

    def test_uneven_blocks(self):
        """seq not a multiple of the default block: _choose_block shrinks."""
        q, k, v = _rand_qkv(1, 192, 2, 2, 128)
        ref = _xla_reference(q, k, v, True)
        out = flash_attention(
            q, k, v, causal=True, use_pallas=True, interpret=INTERPRET,
            block_q=64, block_k=64,
        )
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5
        )


class TestBackward:
    @pytest.mark.parametrize("g,qpk", CASES)
    def test_grads_match_xla(self, g, qpk):
        """d(loss)/d(q,k,v) through the Pallas bwd kernels == XLA autodiff
        (the reference trains through flash-attn; grads are the product)."""
        q, k, v = _rand_qkv(2, 128, g, qpk, 128, seed=1)

        def loss_ref(q, k, v):
            return jnp.sum(jnp.square(_xla_reference(q, k, v, True)))

        def loss_flash(q, k, v):
            return jnp.sum(jnp.square(_flash_interp(q, k, v, True)))

        ref_grads = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        flash_grads = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        for rg, fg, name in zip(ref_grads, flash_grads, "qkv"):
            np.testing.assert_allclose(
                np.asarray(fg), np.asarray(rg), rtol=1e-4, atol=1e-4,
                err_msg=f"d{name}",
            )

    def test_grads_noncausal(self):
        q, k, v = _rand_qkv(1, 64, 2, 2, 128, seed=2)
        ref = jax.grad(
            lambda q: jnp.sum(jnp.square(_xla_reference(q, k, v, False)))
        )(q)
        got = jax.grad(
            lambda q: jnp.sum(
                jnp.square(_flash_interp(q, k, v, causal=False))
            )
        )(q)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(ref), rtol=1e-4, atol=1e-4
        )

    def test_bf16_grads_close(self):
        """bf16 inputs (production dtype): grads within bf16 tolerance."""
        q, k, v = _rand_qkv(1, 128, 2, 2, 128, dtype=jnp.bfloat16, seed=3)
        ref = jax.grad(
            lambda q: jnp.sum(
                jnp.square(_xla_reference(q, k, v, True).astype(jnp.float32))
            )
        )(q)
        got = jax.grad(
            lambda q: jnp.sum(
                jnp.square(_flash_interp(q, k, v).astype(jnp.float32))
            )
        )(q)
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(ref, np.float32),
            rtol=0.1, atol=0.5,
        )


class TestNarrowHead:
    """A head that is not a multiple of the 128-lane tile (Falcon's 64,
    an odd 80) is zero-padded around the kernels and cut back: outputs,
    all three gradients and the lse equal the XLA reference's, whose
    softmax scale is that of the TRUE width."""

    @pytest.mark.parametrize("d", [64, 80])
    @pytest.mark.parametrize("g,qpk", [pytest.param(1, 8, id="mqa"),
                                       pytest.param(2, 4, id="gqa")])
    def test_output_and_grads_match_xla(self, g, qpk, d):
        q, k, v = _rand_qkv(2, 128, g, qpk, d, seed=4)
        np.testing.assert_allclose(
            np.asarray(_flash_interp(q, k, v)),
            np.asarray(_xla_reference(q, k, v, True)), rtol=1e-5, atol=1e-5)

        def loss(attend):
            return lambda q, k, v: jnp.sum(jnp.square(attend(q, k, v)))

        ref = jax.grad(loss(lambda q, k, v: _xla_reference(q, k, v, True)),
                       argnums=(0, 1, 2))(q, k, v)
        got = jax.grad(loss(_flash_interp), argnums=(0, 1, 2))(q, k, v)
        for r, f, name in zip(ref, got, "qkv"):
            assert f.shape == r.shape
            np.testing.assert_allclose(np.asarray(f), np.asarray(r),
                                       rtol=1e-4, atol=1e-4,
                                       err_msg=f"d{name}")

    @pytest.mark.parametrize("d", [64, 80])
    @pytest.mark.parametrize("g,qpk", [pytest.param(1, 8, id="mqa"),
                                       pytest.param(2, 4, id="gqa")])
    def test_lse_and_grads_through_it(self, g, qpk, d):
        q, k, v = _rand_qkv(1, 128, g, qpk, d, seed=5)

        def kernel(q, k, v):
            return flash_attention_with_lse(
                q, k, v, causal=True, use_pallas=True, interpret=INTERPRET,
                block_q=64, block_k=64)

        def xla(q, k, v):
            return _xla_reference_with_lse(q, k, v, True)

        (o1, l1), (o2, l2) = kernel(q, k, v), xla(q, k, v)
        np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                                   rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(np.asarray(l1), np.asarray(l2),
                                   rtol=1e-5, atol=1e-5)

        def obj(impl):
            def f(q, k, v):
                o, lse = impl(q, k, v)
                return (o ** 2).sum() + jnp.sin(lse).sum()
            return f

        g1 = jax.grad(obj(kernel), argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(obj(xla), argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-5)

    def test_lane_aligned_head_is_not_padded(self, monkeypatch):
        """A 128-wide call takes the path it took: nothing is padded in
        the forward or the backward, and the result is bitwise the
        kernel's own on the same operands. (The shapes are this test's
        alone: `flash_attention` is jitted, a cached trace pads nothing.)"""
        pads = []
        real_pad = jnp.pad
        monkeypatch.setattr(
            flash_module.jnp, "pad",
            lambda x, *a, **kw: pads.append(x.shape) or real_pad(x, *a, **kw))
        q, k, v = _rand_qkv(1, 128, 2, 2, 128, seed=6)
        jax.grad(lambda q: jnp.sum(_flash_interp(q, k, v)))(q)
        assert not pads
        direct, _ = flash_module._flash_fwd_pallas(
            q, k, v, True, 64, 64, interpret=INTERPRET)
        np.testing.assert_array_equal(
            np.asarray(_flash_interp(q, k, v)), np.asarray(direct))
        q64, k64, v64 = _rand_qkv(1, 128, 2, 2, 64, seed=6)
        _flash_interp(q64, k64, v64)
        assert pads == [(1, 128, 2, 2, 64), (1, 128, 2, 64), (1, 128, 2, 64)]


class TestBlockChooser:
    def test_divisor_and_row_cap(self):
        assert _choose_block(4096, 256, 1) == 256
        assert _choose_block(4096, 256, 71) == 16  # MQA falcon-7b rows cap
        assert _choose_block(192, 64) == 64
        assert _choose_block(100, 64) is None  # no pow2 divisor >= 8


class TestModelIntegration:
    def test_attention_block_uses_flash(self):
        """use_flash_attn config path produces the same logits as the
        grouped path (interpret mode, fp32)."""
        import dataclasses

        from megatron_llm_tpu.config import tiny_config
        from megatron_llm_tpu.models import LlamaModel

        base = tiny_config(
            hidden_size=512, num_attention_heads=4, num_attention_heads_kv=2,
            kv_channels=128, ffn_hidden_size=256, seq_length=64,
            max_position_embeddings=64, compute_dtype=jnp.float32,
        )
        model = LlamaModel(base)
        params = model.init(jax.random.key(0))
        tokens = jax.random.randint(jax.random.key(1), (2, 64), 0, 256)

        ref_logits, _ = model.forward(params, tokens)
        flash_cfg = dataclasses.replace(base, use_flash_attn=True)
        flash_logits, _ = LlamaModel(flash_cfg).forward(params, tokens)
        np.testing.assert_allclose(
            np.asarray(flash_logits), np.asarray(ref_logits),
            rtol=1e-5, atol=1e-5,
        )


class TestFlashWithLse:
    """The (o, lse) variant that ring attention merges across hops —
    both outputs and the d/dlse path must match the XLA reference
    (the score cotangent gains + g_lse * p, folded into delta)."""

    @pytest.mark.parametrize("causal", [True, False])
    def test_values_and_lse(self, causal):
        from megatron_llm_tpu.ops.flash_attention import (
            _xla_reference_with_lse,
            flash_attention_with_lse,
        )

        q, k, v = _rand_qkv(2, 128, 2, 2, 128)
        o1, l1 = flash_attention_with_lse(
            q, k, v, causal=causal, use_pallas=True, interpret=INTERPRET,
            block_q=64, block_k=64,
        )
        o2, l2 = _xla_reference_with_lse(q, k, v, causal)
        np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                                   rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(np.asarray(l1), np.asarray(l2),
                                   rtol=1e-5, atol=1e-5)

    def test_grads_through_both_outputs(self):
        from megatron_llm_tpu.ops.flash_attention import (
            _xla_reference_with_lse,
            flash_attention_with_lse,
        )

        q, k, v = _rand_qkv(1, 128, 2, 1, 128, seed=3)

        def obj(impl):
            def f(q, k, v):
                o, lse = impl(q, k, v)
                # nontrivial cotangents on BOTH outputs
                return (o.astype(jnp.float32) ** 2).sum() \
                    + jnp.sin(lse).sum()
            return f

        g1 = jax.grad(obj(lambda q, k, v: flash_attention_with_lse(
            q, k, v, causal=True, use_pallas=True, interpret=INTERPRET,
            block_q=64, block_k=64)), argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(obj(lambda q, k, v: _xla_reference_with_lse(
            q, k, v, True)), argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-5)
