"""A model whose layers are of three kinds (gated short convolution +
dense MLP, attention with q/k norms + routed MLP, convolution + routed
MLP) against its plain reference, `benchmark/reference/lfm2_moe.py`, at
tiny widths on the CPU: seeded weights, float32, matmul precision
"highest" (conftest pins it). `GPTModel.forward` / `loss`, the engine
(chunked admission at ragged chunk tails, mixed rounds, decoding, a slot
reused), the routed MLP alone against "every expert on every token,
masked", and the engine features refused for a model with per-slot
state."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import families, program, weights
from megatron_llm_tpu.config import CapabilityError
from megatron_llm_tpu.inference.engine import MOE_COUNTERS, DecodeEngine
from megatron_llm_tpu.models import moe

CFG = {
    "model_type": "lfm2_moe", "conv_L_cache": 3, "conv_bias": False,
    "hidden_size": 64, "intermediate_size": 96, "head_dim": 16,
    "layer_types": ["conv", "full_attention", "conv", "conv",
                    "full_attention", "conv"],
    "moe_intermediate_size": 32, "norm_eps": 1e-5, "norm_topk_prob": True,
    "num_attention_heads": 4, "num_key_value_heads": 2,
    "num_dense_layers": 1, "num_experts": 8, "num_experts_per_tok": 2,
    "num_hidden_layers": 6, "rope_theta": 1000000,
    "routed_scaling_factor": 1, "use_expert_bias": True, "vocab_size": 256,
    "tie_word_embeddings": True, "initializer_range": 0.1,
    "published": {"num_hidden_layers": 6},
}
USE = {"num_hidden_layers": 6, "max_context": 64,
       "compute_dtype": "float32", "weights_dtype": "float32"}
SEED = 11


@pytest.fixture(scope="module")
def made():
    """(family, model, the program's tree, the reference's params) over
    the same seeded leaves."""
    fam = families.find(CFG)
    L = USE["num_hidden_layers"]
    glob = weights.make_globals(CFG, SEED)
    tree = program.program_tree(CFG, weights.make_stacked(CFG, SEED, L), glob)
    ref = {"globals": glob,
           "layers": [weights.make_layer(CFG, SEED, i) for i in range(L)]}
    return fam, fam.model(CFG, USE), tree, ref


def reference_logits(fam, ref, tokens):
    x = fam.reference.embed(ref["globals"], jnp.asarray(tokens))
    for i, w in enumerate(ref["layers"]):
        x = fam.reference.block(w, x, CFG, jnp.arange(len(tokens)), layer=i)
    return fam.reference.final_logits(ref["globals"], x, CFG)


def test_three_kinds_in_six_layers(made):
    _, model, tree, _ = made
    assert sorted(tree["layers"]) == ["attention_moe", "conv_mlp", "conv_moe"]
    assert len(set(model.cfg.layer_kinds)) == 3
    shapes = jax.eval_shape(model.init, jax.random.key(0))
    assert jax.tree.map(lambda x: x.shape, shapes) \
        == jax.tree.map(lambda x: x.shape, tree)


def test_forward_logits_equal_the_reference(made):
    """(a) float32 on both sides: the sums differ in their order only.
    1e-4 on logits of size ~1 is a hundred float32 roundings of a sum over
    64 to 96 terms through 6 blocks."""
    fam, model, tree, ref = made
    tokens = np.random.RandomState(0).randint(0, 256, (2, 24))
    logits, _ = model.forward(tree, jnp.asarray(tokens))
    for row in range(2):
        want = reference_logits(fam, ref, tokens[row])
        np.testing.assert_allclose(logits[row], want, atol=1e-4, rtol=1e-4)


def test_loss_gradient_equals_the_reference_leaf_by_leaf(made):
    """(a) `jax.grad` of `loss` against the reference's gradient, every
    leaf by its norm-relative distance. 1e-4: float32 sums in another
    order, through the backward of 6 blocks; a wrong term (a weight taken
    WITH the bias, a missing q/k norm) reads 1e-2 or more."""
    fam, model, tree, ref = made
    rs = np.random.RandomState(1)
    tokens, labels = rs.randint(0, 256, (2, 2, 16))
    got = jax.grad(model.loss)(tree, jnp.asarray(tokens), jnp.asarray(labels))
    want = jax.grad(fam.reference.mean_loss)(
        ref, jnp.asarray(tokens), jnp.asarray(labels), CFG)
    stacks, glob = program.neutral_leaves(CFG, got, USE["num_hidden_layers"])
    groups = weights.by_kind(CFG, USE["num_hidden_layers"])
    checked = 0
    for kind, stack in stacks.items():
        for j, layer in enumerate(groups[kind]):
            for name, leaf in stack.items():
                w = want["layers"][layer][name]
                gap = jnp.linalg.norm(leaf[j] - w) \
                    / (jnp.linalg.norm(w) + 1e-12)
                if name != "expert_bias":  # selects only: no gradient
                    assert float(jnp.linalg.norm(w)) > 0, (layer, name)
                assert float(gap) < 1e-4, (layer, name, float(gap))
                checked += 1
    for name, leaf in glob.items():
        w = want["globals"][name]
        assert float(jnp.linalg.norm(leaf - w) / jnp.linalg.norm(w)) < 1e-4
    assert checked == sum(len(s) * len(groups[k]) for k, s in stacks.items())


def engine_of(model, tree, **kw):
    args = dict(slots=3, page_size=16, max_context=64,
                prefill_chunk_tokens=4, step_horizon=1, termination_id=None,
                vocab_size=256)
    return DecodeEngine(model, tree, **{**args, **kw})


def served_gaps(fam, ref, prompt, result):
    """Largest |served log-probability - the reference's full-pass one|
    over every position of prompt + output, and whether each output token
    is the reference's first."""
    tokens, lps = result[:2]
    want = jax.nn.log_softmax(reference_logits(fam, ref, tokens), axis=-1)
    picked = np.take_along_axis(np.asarray(want[:-1]),
                                np.asarray(tokens)[1:, None], axis=-1)[:, 0]
    greedy = list(np.argmax(np.asarray(want), axis=-1)[len(prompt) - 1:-1])
    return np.abs(picked - np.asarray(lps)).max(), greedy == tokens[
        len(prompt):]


def test_engine_serves_the_reference_at_every_position(made):
    """(b) prompts whose lengths are no multiples of the chunk (4): chunk
    tails of 1, 2 and 3 tokens, a prompt of 1 and of 2 tokens, whole
    chunks; three slots at once, so chunks ride mixed rounds beside
    decode rows, and seven requests, so slots are reused. The served
    log-probability of every prompt and output token is the reference's
    full-pass one to 2e-5 (float32: the engine sums a chunk's attention
    over pages and the convolution's taps over a carried state, in
    another order), and every output token is the reference's first."""
    fam, model, tree, ref = made
    rs = np.random.RandomState(2)
    prompts = [list(rs.randint(2, 256, n)) for n in (5, 6, 7, 1, 2, 9, 12)]
    eng = engine_of(model, tree)
    reqs = [eng.submit(p, 6, top_k=1, return_log_probs=True) for p in prompts]
    eng.drain()
    for p, r in zip(prompts, reqs):
        gap, same = served_gaps(fam, ref, p, r.result(5))
        assert gap < 2e-5 and same, (len(p), gap, same)
    c = eng.counters()
    assert c["serve_admitted"] == c["serve_retired"] == len(prompts)
    # the routing counters, booked by the loop from each round's integers:
    # every real row gives k pairs in each of the 5 routed layers
    rows = c["serve_rows_useful"]
    k, routed = CFG["num_experts_per_tok"], 5
    assert c["serve_moe_pairs"] == rows * k * routed
    rounds = sum(c["serve_rounds_" + kind] for kind in ("mixed", "decode"))
    assert c["serve_moe_expert_slots"] % (routed * CFG["num_experts"]) == 0
    assert c["serve_moe_expert_slots"] >= rounds * routed * CFG["num_experts"]
    assert 0 < c["serve_moe_experts_touched"] <= c["serve_moe_expert_slots"]
    assert c["serve_moe_pairs"] / CFG["num_experts"] \
        <= c["serve_moe_hottest_pairs"] <= c["serve_moe_pairs"]
    assert list(c)[-4:] == list(MOE_COUNTERS)


def test_a_round_fetches_its_routing_integers_beside_its_tokens(made,
                                                                 tmp_path):
    """Every round copies back two arrays, the tokens and the four routing
    integers, in its `engine.fetch.copy` span, after its
    `engine.fetch.wait`; and copies up its eleven (decode) or twelve
    (mixed) operands in its `engine.upload` span."""
    _, model, tree, _ = made
    eng = engine_of(model, tree, trace_dir=str(tmp_path))
    reqs = [eng.submit([5, 6, 7, 8, 9], 4, top_k=1),
            eng.submit([40, 41], 4, top_k=1)]
    eng.drain()
    for r in reqs:
        r.result(5)
    evs = [e for e in eng.tracer.events() if e["ph"] == "X"]
    copies = [e["args"]["copies"] for e in evs
              if e["name"] == "engine.fetch.copy"]
    uploads = [e["args"]["copies"] for e in evs
               if e["name"] == "engine.upload"]
    kinds = [e["args"]["kind"] for e in evs
             if e["name"] == "engine.dispatch"]
    assert copies == [2] * len(kinds) and "decode" in kinds
    assert uploads == [{"mixed": 12, "decode": 11}[k] for k in kinds]
    assert sum(e["name"] == "engine.fetch.wait" for e in evs) == len(kinds)


def test_engine_serves_the_reference_through_the_paged_kernel(monkeypatch):
    """(b) again at the published head width, 64: two K/V heads fill one
    128-lane tile of the lane-packed pools (ISSUE 38) and every
    attention call of the engine runs the paged kernel itself, under the
    Pallas interpreter: chunks with ragged tails, decode rows beside
    them and reused slots against the reference's full pass, 2e-5 as on
    the XLA twin."""
    import dataclasses

    from megatron_llm_tpu.models import GPTModel
    from megatron_llm_tpu.ops import prefill_attention

    cfg = dict(CFG, head_dim=64)
    fam = families.find(cfg)
    L = USE["num_hidden_layers"]
    glob = weights.make_globals(cfg, SEED)
    tree = program.program_tree(cfg, weights.make_stacked(cfg, SEED, L), glob)
    ref = {"globals": glob,
           "layers": [weights.make_layer(cfg, SEED, i) for i in range(L)]}
    model = GPTModel(dataclasses.replace(
        fam.model(cfg, USE).cfg, use_decode_attn=True,
        decode_attn_min_cache=0, decode_attn_interpret=True))
    calls = []
    kernel = prefill_attention._paged_pallas
    monkeypatch.setattr(
        prefill_attention, "_paged_pallas",
        lambda q, *a, **kw: calls.append(q.shape) or kernel(q, *a, **kw))

    def logits_of(tokens):
        x = fam.reference.embed(ref["globals"], jnp.asarray(tokens))
        for i, w in enumerate(ref["layers"]):
            x = fam.reference.block(w, x, cfg, jnp.arange(len(tokens)),
                                    layer=i)
        return fam.reference.final_logits(ref["globals"], x, cfg)

    rs = np.random.RandomState(4)
    prompts = [list(rs.randint(2, 256, n)) for n in (5, 7, 2, 9)]
    eng = engine_of(model, tree, slots=2)
    assert eng._pools_k[0].shape == (eng.num_pages, 16, 2 * 64)
    reqs = [eng.submit(p, 4, top_k=1, return_log_probs=True) for p in prompts]
    eng.drain()
    for p, r in zip(prompts, reqs):
        tokens, lps = r.result(5)[:2]
        want = np.asarray(jax.nn.log_softmax(logits_of(tokens), axis=-1))
        picked = np.take_along_axis(
            want[:-1], np.asarray(tokens)[1:, None], axis=-1)[:, 0]
        assert np.abs(picked - np.asarray(lps)).max() < 2e-5, len(p)
        assert list(np.argmax(want, -1)[len(p) - 1:-1]) == tokens[len(p):]
    # every traced kernel shape: the decode rows (slots, 1), the
    # width-1 chunk, and every wider chunk at the ONE lone-chunk width
    # (its tail pad rows); 2 K/V heads x 2 of 64
    assert calls and {s[2:] for s in calls} == {(2, 2, 64)}
    assert {s[:2] for s in calls} == {(2, 1), (1, 1), (1, 128)}


def test_a_reused_slot_answers_as_a_fresh_engine_does(made):
    """(b) one slot, two requests one after the other: the second finds
    the first's convolution state and K/V in its slot and must answer as
    an engine that never saw the first, bit for bit (the same programs
    on the same values: the state is read as zeros at position 0)."""
    fam, model, tree, ref = made
    rs = np.random.RandomState(3)
    first, second = list(rs.randint(2, 256, 9)), list(rs.randint(2, 256, 7))
    used = engine_of(model, tree, slots=1)
    for p in (first, second):
        got = used.submit(p, 5, top_k=1, return_log_probs=True)
        used.drain()
    fresh = engine_of(model, tree, slots=1)
    want = fresh.submit(second, 5, top_k=1, return_log_probs=True)
    fresh.drain()
    assert got.result(5)[0] == want.result(5)[0]
    np.testing.assert_array_equal(got.result(5)[1], want.result(5)[1])
    assert served_gaps(fam, ref, second, got.result(5))[0] < 2e-5


# ------------------------------------------------------ the routed MLP alone


def every_expert_masked(p, cfg, x, row_mask=None):
    """The oracle: every expert on every token, the unchosen masked; rows
    that are not real give zeros."""
    s = jax.nn.sigmoid(x @ p["router"])
    _, chosen = jax.lax.top_k(s + p["expert_bias"], cfg.num_experts_per_tok)
    w = jnp.take_along_axis(s, chosen, axis=-1)
    w = w / (w.sum(-1, keepdims=True) + 1e-6)
    full = jnp.zeros_like(s).at[jnp.arange(x.shape[0])[:, None], chosen].set(w)
    if row_mask is not None:
        full = full * row_mask[:, None]
    out = 0.0
    for e in range(cfg.num_experts):
        y = (jax.nn.silu(x @ p["w_gate"][e]) * (x @ p["w_up"][e])) \
            @ p["w_down"][e]
        out = out + full[:, e:e + 1] * y
    return out, chosen, full


@pytest.fixture(scope="module")
def routed(made):
    _, model, tree, _ = made
    p = jax.tree.map(lambda x: x[0], tree["layers"]["conv_moe"]["moe"])
    return model.cfg, p


@pytest.mark.parametrize("rows", [12, 160], ids=["24rows", "320rows"])
@pytest.mark.parametrize("load", ["even", "one_expert", "padded"])
def test_routed_mlp_equals_every_expert_masked(routed, load, rows):
    """(c) the experts' products against the oracle, 1e-5 (float32, a sum
    over 2 experts of products over 32 to 64 terms), at a served round's
    few rows and at more rows than any served round has: under the seeded
    load, with every token sent to ONE expert pair (a bias that dwarfs
    the scores: nothing is dropped, the hottest expert holds every row),
    and with rows that are not real present (they weigh nothing and are
    counted nowhere); the four integers are exact."""
    cfg, p = routed
    rs = np.random.RandomState(4)
    x = jnp.asarray(rs.randn(2, rows, 64), jnp.float32)
    mask = None
    if load == "one_expert":
        p = dict(p, expert_bias=jnp.zeros(8).at[jnp.array([3, 5])].set(10.0))
    if load == "padded":
        mask = jnp.asarray(rs.rand(2, rows) < 0.6)
    out, stats = moe.moe_block(p, cfg, x, mask)
    flat_mask = None if mask is None else mask.reshape(-1)
    want, chosen, full = every_expert_masked(p, cfg, x.reshape(-1, 64),
                                             flat_mask)
    np.testing.assert_allclose(out.reshape(-1, 64), want, atol=1e-5)
    real = np.ones(2 * rows, bool) if mask is None \
        else np.asarray(flat_mask)
    counts = np.bincount(np.asarray(chosen)[real].reshape(-1), minlength=8)
    assert list(np.asarray(stats)) == [2 * real.sum(), (counts > 0).sum(), 8,
                                       counts.max()]
    if load == "one_expert":
        assert list(np.asarray(stats)) == [4 * rows, 2, 8, 2 * rows]
    if load == "padded":
        assert np.all(np.asarray(out.reshape(-1, 64))[~real] == 0)


def test_no_real_row_routes_nothing(routed):
    """(c) a round whose rows are all padding: zeros out, and every
    counter but the experts there are reads nought."""
    cfg, p = routed
    x = jnp.asarray(np.random.RandomState(6).randn(1, 8, 64), jnp.float32)
    out, stats = moe.moe_block(p, cfg, x, jnp.zeros((1, 8), bool))
    assert np.all(np.asarray(out) == 0)
    assert list(np.asarray(stats)) == [0, 0, 8, 0]


def test_the_bias_selects_and_does_not_weigh(routed):
    """(c) over 512 tokens the seeded bias changes the chosen set of at
    least a tenth of them (so a program that drops it fails the
    comparison), and the weights are the scores at the chosen experts
    WITHOUT it."""
    cfg, p = routed
    x = jnp.asarray(np.random.RandomState(5).randn(512, 64), jnp.float32)
    chosen, w = moe.route(p, cfg, x)
    unbiased, _ = moe.route({k: v for k, v in p.items()
                             if k != "expert_bias"}, cfg, x)
    moved = np.mean(np.any(np.sort(chosen, -1) != np.sort(unbiased, -1), -1))
    assert moved >= 0.10, moved
    s = jax.nn.sigmoid(x @ p["router"])
    at = jnp.take_along_axis(s, chosen, axis=-1)
    np.testing.assert_allclose(w, at / (at.sum(-1, keepdims=True) + 1e-6),
                               rtol=1e-6)


# ------------------------------------------------------------ what is refused


@pytest.mark.parametrize("feature,kw", [
    ("prefix_cache", {"prefix_cache": True}),
    ("spec_decode_k", {"spec_decode_k": 2}),
    ("whole-prompt admission", {"prefill_chunk_tokens": 0}),
    ("serving_tp", {"serving_tp": 2}),
    ("quantize_weights", {"quantize_weights": True}),
])
def test_engine_refuses_by_name(made, feature, kw):
    """(d) what copies, shares or rolls back pages, and what knows one
    kind of layer, is refused at construction, by name."""
    _, model, tree, _ = made
    with pytest.raises(CapabilityError, match=feature) as err:
        engine_of(model, tree, **kw)
    assert feature in err.value.feature


def test_page_transfer_and_packed_documents_are_refused(made):
    _, model, tree, _ = made
    eng = engine_of(model, tree)
    with pytest.raises(CapabilityError, match="page export"):
        eng.export_prefix([1, 2, 3])
    with pytest.raises(CapabilityError, match="page import"):
        eng.import_prefix({})
    tokens = jnp.zeros((1, 8), jnp.int32)
    with pytest.raises(CapabilityError, match="packed documents"):
        model.forward(tree, tokens, attention_mask={
            "doc_start": jnp.zeros((1, 8), jnp.int32)})
    with pytest.raises(CapabilityError, match="dense per-layer caches"):
        model.forward(tree, tokens, kv_caches=model.init_kv_caches(1, 8))


def test_one_kind_keeps_its_tree_and_its_pools():
    """A model of one kind is the case n = 1: its stack is `layers`
    itself, it has a pool for every layer and no slot state."""
    from megatron_llm_tpu.config import tiny_config
    from megatron_llm_tpu.models import GPTModel

    model = GPTModel(tiny_config())
    tree = jax.eval_shape(model.init, jax.random.key(0))
    assert sorted(tree["layers"]) == ["attention", "input_norm", "mlp",
                                      "post_attention_norm"]
    caches = jax.eval_shape(lambda: model.init_paged_kv_caches(2, 5, 16, 4))
    assert len(caches["k_pages_layers"]) == model.cfg.num_layers
    assert "conv_state_layers" not in caches
