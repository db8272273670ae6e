"""A family whose blocks are of TWO KINDS with different leaves
(`two_kinds/`, copied into the rehearsal's copy as new files): what the
harness does by kind. Seeded weights, stacks, the way into the program's
tree and back, the training reference with its control and faults, the
serving reference's compiled programs.

`TrainProgram` and `ServeProgram` with two kinds cannot be rehearsed: the
program has no model whose layer stack is more than one stacked tree.
They hold no branch on the number of kinds, so the Falcon cells (one
kind: the same loops, gone round once) cover their code; their weights
are held to the parent's bits below."""

import hashlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark import check, families, harness, program, traffic, weights

BIG = 2**31 + 12345
KINDS = {"glu": [0, 2], "plain": [1, 3, 4]}
ONLY = {"glu": "w_gate", "plain": "norm_bias"}  # a leaf the other lacks
# CPU readings at toy sizes (the float32 reference against itself reads
# 0): the control and each fault have to come out over them
LIMITS = {"loss_gap_step1": 1e-4, "loss_gap_step2": 1e-4,
          "loss_gap_step3": 1e-4, "grad_norm_gap": 0.01,
          "change_norm_gap": 0.01}


@pytest.fixture(scope="module")
def cfg(tiny_base):
    cfg = harness.load_json(tiny_base, "configs", "tiny-two-kinds.json")
    families.find(cfg, tiny_base)
    return cfg


def test_layers_are_grouped_by_kind_in_layer_order(cfg):
    assert weights.by_kind(cfg, 5) == KINDS
    assert list(weights.by_kind(cfg, 5)) == ["glu", "plain"]
    assert weights.by_kind(cfg, 1) == {"glu": [0]}


def test_a_blocks_leaves_are_its_kinds_and_hang_on_seed_and_layer(cfg):
    fam = families.find(cfg)
    makers = weights.layer_makers(cfg, 5)
    assert set(makers) == set(KINDS)
    for kind, idx in KINDS.items():
        for i in idx:
            one = weights.make_layer(cfg, BIG, i)
            assert {k: v.shape for k, v in one.items()} == \
                fam.layer_shapes(cfg, i)
            assert ONLY[kind] in one
            assert not (set(ONLY.values()) - {ONLY[kind]}) & set(one)
            # the compiled maker of the kind, its index an argument
            traced = makers[kind](weights.seed_words(BIG), jnp.int32(i))
            # ... and a cell of another depth draw the same block
            alone = weights.layer_makers(cfg, i + 1)[kind](
                weights.seed_words(BIG), jnp.int32(i))
            for k in one:
                # (compiled, a multiply-add is fused: the last bit may go)
                assert np.allclose(np.asarray(one[k]), np.asarray(traced[k]),
                                   rtol=1e-6, atol=0), (i, k)
                assert np.array_equal(np.asarray(traced[k]),
                                      np.asarray(alone[k])), (i, k)
    a, b = weights.make_layer(cfg, BIG, 1), weights.make_layer(cfg, BIG, 3)
    other = weights.make_layer(cfg, BIG + 1, 1)
    for k in a:  # same kind: another layer or another seed, other numbers
        assert not np.array_equal(np.asarray(a[k]), np.asarray(b[k])), k
        assert not np.array_equal(np.asarray(a[k]), np.asarray(other[k])), k


def test_a_kinds_stack_is_its_blocks_drawn_one_by_one(cfg):
    stacks = weights.make_stacked(cfg, BIG, 5)
    assert list(stacks) == ["glu", "plain"]
    for kind, idx in KINDS.items():
        for j, i in enumerate(idx):
            one = weights.make_layer(cfg, BIG, i)
            assert set(one) == set(stacks[kind])
            for k in one:
                assert np.array_equal(np.asarray(stacks[kind][k][j]),
                                      np.asarray(one[k])), (kind, i, k)


def test_tree_round_trip_gives_one_entry_a_block_that_has_the_leaf(cfg):
    stacks = weights.make_stacked(cfg, 7, 5)
    glob = weights.make_globals(cfg, 7)
    tree = program.program_tree(cfg, stacks, glob)
    # one stack a kind, under the name the family's paths begin with
    assert set(tree["layers"]) == {"glu_blocks", "plain_blocks"}
    assert tree["layers"]["glu_blocks"]["mlp"]["w_up"].shape == (2, 64, 96)
    assert tree["layers"]["plain_blocks"]["mlp"]["w_up"].shape == \
        (3, 64, 160)
    back, back_glob = program.neutral_leaves(cfg, tree, 5)
    assert jax.tree.structure(back) == jax.tree.structure(stacks)
    assert all(a is b for a, b in zip(jax.tree.leaves((back, back_glob)),
                                      jax.tree.leaves((stacks, glob))))
    got = program._leaf_norms(cfg, tree, 5)
    blocks = [weights.make_layer(cfg, 7, i) for i in range(5)]
    want = check._stack(
        [{k: jnp.linalg.norm(v.ravel()) for k, v in b.items()}
         for b in blocks],
        {k: jnp.linalg.norm(v.ravel()) for k, v in glob.items()})
    assert set(got) == set(want)
    assert {k: len(v) for k, v in got.items()} == {
        "norm_scale": 5, "wqkv": 5, "wo": 5, "w_up": 5, "w_down": 5,
        "w_gate": 2, "norm_bias": 3, "embedding": 1, "lnf_scale": 1}
    for k in want:  # in layer order, the two kinds' entries interleaved
        assert np.allclose(got[k], want[k], rtol=1e-6), k


@pytest.fixture(scope="module")
def followed(cfg, tiny_base):
    mix = harness.load_json(tiny_base, "traffic", "tiny-train.json")
    texts = traffic.train_batches(mix, 5, 3, cfg["vocab_size"])
    return texts, check.train_reference(cfg, 5, texts)


def test_the_reference_follows_three_steps_of_two_kinds(cfg, followed):
    texts, want = followed
    assert len(want["loss"]) == 3 and all(np.isfinite(want["loss"]))
    for norms in (want["grad_norms"], want["change_norms"]):
        assert len(norms["w_gate"]) == 2 and len(norms["norm_bias"]) == 3
        assert len(norms["w_up"]) == 5 and len(norms["embedding"]) == 1
        assert all((v > 0).all() for v in norms.values())
    again = check.train_reference(cfg, 5, texts)
    ok, compared = check.verdict(check.train_numbers(again, want), LIMITS)
    assert ok and all(c["value"] == 0 for c in compared.values())


@pytest.mark.parametrize("how", [{"precision": "int8"},
                                 {"fault": "half_batch"},
                                 {"fault": "no_exchange"}])
def test_control_and_faults_fail_against_the_reference(cfg, followed, how):
    texts, want = followed
    got = check.train_reference(cfg, 5, texts, **how)
    ok, _ = check.verdict(check.train_numbers(got, want), LIMITS)
    assert not ok


def test_serving_reference_compiles_one_block_and_one_maker_a_kind(
        cfg, monkeypatch):
    fam = families.find(cfg)
    traced = {"block": [], "maker": []}
    block, shapes = fam.reference.block, fam.layer_shapes

    def counted_block(w, x, cfg, positions, matmul, layer=None):
        traced["block"].append(layer)
        return block(w, x, cfg, positions, matmul, layer=layer)

    def counted_shapes(cfg, layer):
        assert isinstance(layer, int)  # never a traced index
        traced["maker"].append(layer)
        return shapes(cfg, layer)

    monkeypatch.setattr(fam.reference, "block", counted_block)
    monkeypatch.setattr(fam, "layer_shapes", counted_shapes)
    rng = np.random.default_rng(3)
    samples = [{"tokens": rng.integers(0, 512, n).tolist(), "prompt_len": 4}
               for n in (40, 64, 21)]
    logits = check.serve_reference_logits(cfg, 9, samples)
    # the Python body of a jitted function runs only while it is traced:
    # five layers, three samples, and ONE trace a kind of each program
    assert traced == {"block": [0, 1], "maker": [0, 1]}
    assert [l.shape for l in logits] == [(36, 512), (60, 512), (17, 512)]
    # the same logits as the blocks applied one by one, uncompiled
    monkeypatch.undo()
    s = samples[0]
    toks = np.zeros(256, np.int32)
    toks[:40] = s["tokens"]
    rnd = lambda t: jax.tree.map(  # noqa: E731
        lambda x: x.astype(jnp.bfloat16).astype(jnp.float32), t)
    glob = rnd(weights.make_globals(cfg, 9))
    x = fam.reference.embed(glob, jnp.asarray(toks))
    for i in range(5):
        x = fam.reference.block(rnd(weights.make_layer(cfg, 9, i)), x, cfg,
                                jnp.arange(256), layer=i)
    want = fam.reference.final_logits(glob, x[3:39], cfg)
    assert np.allclose(logits[0], np.asarray(want), atol=1e-5)


# sha256 over the stack's leaves (name, then float32 bytes, names sorted)
# as `weights.make_stacked(cfg, seed, 2)` gave them on the PARENT commit
# 6823b94, where a stack was one `vmap` over all the layers: what the
# trainer gets is the same to the bit
PINNED_STACK = {
    ("tiny-falcon", 11):
        "1a532745408f98802d0e779099ae86cab0fec341e6f3d9a90c2ec5d285cc07a6",
    ("tiny-falcon", 2147495993):
        "e3369daa5486e16a8f9223269725019230b4b8708eea4d41e0c6ff365219285a",
    ("tiny-falcon40", 11):
        "ff9946508b9497af1da70657801b1bd2c433933808338e19d1042b34683fe28c",
    ("tiny-falcon40", 2147495993):
        "8ffc539a376a8e8dc60dc5793a1a6748d1886fd1398871af727f82b59c2d6438",
}


@pytest.mark.parametrize("name,seed", sorted(PINNED_STACK))
def test_one_kind_stacks_to_the_parents_bits(name, seed):
    cfg = harness.load_json(harness.HERE, "tests", "tiny", "configs",
                            name + ".json")
    (stack,) = weights.make_stacked(cfg, seed, 2).values()
    h = hashlib.sha256()
    for k in sorted(stack):
        leaf = np.asarray(stack[k])
        assert leaf.dtype == np.float32
        h.update(k.encode())
        h.update(leaf.tobytes())
    assert h.hexdigest() == PINNED_STACK[(name, seed)]
