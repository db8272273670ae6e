"""The plain reference against the program's own Falcon model, on the CPU
in float32 at a tiny shape, for both block forms: 7B (multi-query, one
norm) and 40B (grouped K/V heads, two norms). So the chip comparison
starts from a reference known to agree."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark import harness, program, weights
from benchmark.reference import falcon as ref


@pytest.mark.parametrize("name", ["tiny-falcon", "tiny-falcon40"])
def test_forward_loss_and_gradient_agree(name):
    from megatron_llm_tpu.models import FalconModel

    cfg = harness.load_json(harness.HERE, "tests", "tiny", "configs",
                            name + ".json")
    use = dict(cfg["train"], compute_dtype="float32", remat_policy=None,
               use_flash_attn=False)
    model = FalconModel(program.model_config(cfg, use))
    seed, L, T = 11, use["num_hidden_layers"], 64
    params = program.program_tree(weights.make_stacked(cfg, seed, L),
                                  weights.make_globals(cfg, seed))
    rng = np.random.default_rng(3)
    text = rng.integers(0, cfg["vocab_size"], (2, T + 1), dtype=np.int32)
    tokens, labels = jnp.asarray(text[:, :-1]), jnp.asarray(text[:, 1:])

    with jax.default_matmul_precision("highest"):
        logits, _ = model.forward(params, tokens)
        loss_p, grads_p = jax.value_and_grad(model.loss)(params, tokens,
                                                         labels)
    plain = {"layers": [weights.make_layer(cfg, seed, i) for i in range(L)],
             "globals": weights.make_globals(cfg, seed)}
    x = ref.embed(plain["globals"], tokens[0])
    for w in plain["layers"]:
        x = ref.block(w, x, cfg, jnp.arange(T))
    logits_r = ref.final_logits(plain["globals"], x, cfg)
    assert np.allclose(np.asarray(logits[0]), np.asarray(logits_r),
                       atol=2e-4)
    loss_r, grads_r = ref.loss_and_grads(plain, tokens, labels, cfg)
    assert float(loss_p) == pytest.approx(float(loss_r), rel=1e-5)
    got = program.neutral_leaves(grads_p, cfg["new_decoder_architecture"])
    for k, g in got.items():
        if k in program.LAYER_PATHS:
            want = np.stack([np.asarray(l[k]) for l in grads_r["layers"]])
        else:
            want = np.asarray(grads_r["globals"][k])
        scale = np.abs(want).max() + 1e-12
        assert np.abs(np.asarray(g) - want).max() / scale < 2e-3, k
