"""Each family's plain reference against the program's own model of that
family, on the CPU in float32 at a tiny shape: both Falcon block forms
(7B: multi-query, one norm; 40B: grouped K/V heads, two norms) and the
rehearsal's second family (`second_family/`, found in the copy). So the
chip comparison starts from a reference known to agree."""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark import families, harness, program, weights
from benchmark.reference import common


@pytest.mark.parametrize("name", ["tiny-falcon", "tiny-falcon40", "tiny2"])
def test_forward_loss_and_gradient_agree(tiny_base, name):
    cfg = harness.load_json(tiny_base, "configs", name + ".json")
    fam = families.find(cfg, tiny_base)
    ref = fam.reference
    use = dict(cfg["train"], compute_dtype="float32", remat_policy=None,
               use_flash_attn=False)
    model = fam.model(cfg, use)
    seed, L, T = 11, use["num_hidden_layers"], 64
    params = program.program_tree(cfg, weights.make_stacked(cfg, seed, L),
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
    for i, w in enumerate(plain["layers"]):
        x = ref.block(w, x, cfg, jnp.arange(T), layer=i)
    logits_r = ref.final_logits(plain["globals"], x, cfg)
    assert np.allclose(np.asarray(logits[0]), np.asarray(logits_r),
                       atol=2e-4)
    loss_r, grads_r = common.loss_and_grads(ref, plain, tokens, labels, cfg)
    assert float(loss_p) == pytest.approx(float(loss_r), rel=1e-5)
    stacks, got = program.neutral_leaves(cfg, grads_p, L)
    (stack,) = stacks.values()  # these families have one kind of block
    assert set(stack) == set(plain["layers"][0])
    assert set(got) == set(plain["globals"])
    for k, g in {**stack, **got}.items():
        if k in stack:
            want = np.stack([np.asarray(l[k]) for l in grads_r["layers"]])
        else:
            want = np.asarray(grads_r["globals"][k])
        scale = np.abs(want).max() + 1e-12
        assert np.abs(np.asarray(g) - want).max() / scale < 2e-3, k


def test_a_family_is_found_by_model_type_alone(tiny_base):
    """The lookup: `model_type` -> families/<it>.py + reference/<it>.py
    under the cell's own base; an unknown one ends the run."""
    cfg = harness.load_json(tiny_base, "configs", "tiny2.json")
    fam = families.find(cfg, tiny_base)
    # a copy's modules: the name an import would give, and the copy's mark
    assert fam.__name__.startswith(
        "benchmark.families." + cfg["model_type"] + "__")
    assert fam.reference.__name__.startswith(
        "benchmark.reference." + cfg["model_type"] + "__")
    assert os.path.dirname(fam.__file__) == os.path.join(tiny_base,
                                                         "families")
    with pytest.raises(SystemExit):
        families.find({"model_type": "no_such_family"}, tiny_base)
