"""Single-device model correctness (analogue of ref tests/test_basic.py +
megatron/mpu/tests/test_layers.py dense-reference checks)."""

import jax
import jax.numpy as jnp
import numpy as np

from megatron_llm_tpu.config import tiny_config
from megatron_llm_tpu.models import FalconModel, GPTModel, LlamaModel


def test_llama_forward_shapes():
    cfg = tiny_config()
    model = LlamaModel(cfg)
    params = model.init(jax.random.key(0))
    tokens = jnp.zeros((2, 16), jnp.int32)
    logits, _ = model.forward(params, tokens)
    assert logits.shape == (2, 16, cfg.padded_vocab_size)
    assert jnp.isfinite(logits.astype(jnp.float32)).all()


def test_gpt_forward_absolute_pos():
    cfg = tiny_config(
        position_embedding_type="absolute",
        glu_activation=None,
        use_rms_norm=False,
        use_bias=True,
        tie_embed_logits=True,
    )
    model = GPTModel(cfg)
    params = model.init(jax.random.key(0))
    tokens = jnp.zeros((2, 16), jnp.int32)
    logits, _ = model.forward(params, tokens)
    assert logits.shape == (2, 16, cfg.padded_vocab_size)


def test_falcon_forward_mqa_parallel_attn():
    cfg = tiny_config(
        glu_activation=None,
        use_rms_norm=False,
        parallel_attn=True,
        parallel_layernorm=True,
        num_attention_heads_kv=1,
        tie_embed_logits=True,
    )
    model = FalconModel(cfg)
    params = model.init(jax.random.key(0))
    tokens = jnp.arange(32, dtype=jnp.int32).reshape(2, 16)
    logits, _ = model.forward(params, tokens)
    assert logits.shape == (2, 16, cfg.padded_vocab_size)


def test_causality():
    """Changing a future token must not change past logits."""
    cfg = tiny_config()
    model = LlamaModel(cfg)
    params = model.init(jax.random.key(0))
    t1 = jnp.arange(16, dtype=jnp.int32)[None, :] % 256
    t2 = t1.at[0, 10].set(99)
    l1, _ = model.forward(params, t1)
    l2, _ = model.forward(params, t2)
    np.testing.assert_allclose(
        np.asarray(l1[0, :10], np.float32), np.asarray(l2[0, :10], np.float32),
        rtol=1e-5, atol=1e-5,
    )
    assert not np.allclose(
        np.asarray(l1[0, 10], np.float32), np.asarray(l2[0, 10], np.float32)
    )


def test_loss_finite_and_decreases_with_sgd():
    cfg = tiny_config()
    model = LlamaModel(cfg)
    params = model.init(jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (4, 32), 0, 256)
    labels = jnp.roll(tokens, -1, axis=1)

    loss_fn = jax.jit(lambda p: model.loss(p, tokens, labels))
    grad_fn = jax.jit(jax.value_and_grad(lambda p: model.loss(p, tokens, labels)))
    l0 = float(loss_fn(params))
    assert np.isfinite(l0)
    for _ in range(5):
        l, g = grad_fn(params)
        params = jax.tree.map(lambda p, gr: p - 0.5 * gr.astype(p.dtype), params, g)
    l5 = float(loss_fn(params))
    assert l5 < l0


def test_kv_cache_decode_matches_full_forward():
    """Incremental decode with KV cache == full forward (ref: InferenceParams
    semantics, forward_step.py:17)."""
    cfg = tiny_config()
    model = LlamaModel(cfg)
    params = model.init(jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(2), (2, 12), 0, 256)

    full_logits, _ = model.forward(params, tokens)

    caches = model.init_kv_caches(batch_size=2, max_len=32)
    # prefill 8, then decode 4 one at a time
    logits_p, caches = model.forward(params, tokens[:, :8], kv_caches=caches)
    step_logits = [logits_p[:, -1]]
    for i in range(8, 12):
        lg, caches = model.forward(params, tokens[:, i : i + 1], kv_caches=caches)
        step_logits.append(lg[:, 0])
    np.testing.assert_allclose(
        np.asarray(jnp.stack(step_logits, axis=1), np.float32),
        np.asarray(full_logits[:, 7:12], np.float32),
        rtol=2e-2, atol=2e-2,
    )
