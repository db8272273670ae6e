"""Decode-tree layout (ISSUE 34): a served round reads every weight as
it lies on the device. A TPU's default layout of a 2-D array makes the
axis that pads least against the 128-lane tile the minor one, which is
not what two uses read; a layout PINNED on the leaf does not survive the
persistent compile cache on jax 0.9.0 / libtpu 0.0.34 (PERF.md §6, PR
34), so the decode tree holds shapes whose DEFAULT layout is the wanted
one.

Pinned here:
- `prepare_decode_params` holds `wqkv` head-major, (heads, head_dim,
  hidden): the (hidden, qkv) leaf's transpose cut by head, fp and int8
  (one scale an output channel, in column order), and `qdot` issues the
  same products from it; under serving_tp the heads axis is sharded and
  every chip holds the columns it held;
- `embed_tokens` takes the few rows of a served round (a decode-layout
  tree) as a one-hot product against a table that lies vocab-minor, and
  gathers everywhere else: the choice as a pure function of (tree form,
  table shape, rows), and the rows equal to the gather's, bit for bit;
- an engine built on a Falcon-form toy serves the tokens
  `generate_tokens` emits; on a committed tree (a restored
  checkpoint's) warm-up compiles the programs the rounds then run;
- compile-only, for a described v5e (nothing runs): the engine's own
  `decode_scan` and `mixed_step` at Falcon-7B's widths hold no
  weight-sized `copy` / `transpose` outside a fusion; handed the
  (hidden, qkv) leaf and a gathered table they hold the table's and one
  a layer for `wqkv` (the guard guards something); a head-128 GQA model
  loses its `wqkv` copies and gains none;
- the same for the K/V page pools (ISSUE 38): held lane-packed,
  (num_pages, page_size, g * d), a pool is written and read as it lies:
  neither program at LFM2-8B-A1B's attention widths (32 slots, 8 K/V
  heads of 64: the paged kernel) holds a `copy` / `transpose` of a
  pool's size outside a fusion.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, SingleDeviceSharding

from megatron_llm_tpu.config import ModelConfig, tiny_config
from megatron_llm_tpu.inference import engine as engine_mod
from megatron_llm_tpu.inference.engine import DecodeEngine
from megatron_llm_tpu.inference.generation import (
    bucket_prefill_len,
    generate_tokens,
)
from megatron_llm_tpu.models import FalconModel, GPTModel, LlamaModel
from megatron_llm_tpu.models import language_model
from megatron_llm_tpu.models.attention import split_qkv
from megatron_llm_tpu.models.language_model import (
    ONE_HOT_MAX_ROWS,
    _rows_by_one_hot,
    embed_tokens,
)
from megatron_llm_tpu.ops import dispatch
from megatron_llm_tpu.ops.quantization import qdot, quantize_weight
from megatron_llm_tpu.parallel.mesh import ParallelContext, build_mesh
from megatron_llm_tpu.parallel.sharding import (
    decode_param_shardings,
    decode_param_specs,
)

BF16 = jnp.bfloat16


def falcon7b(layers=2, **over):
    """tiiuae/falcon-7b's widths: MQA at head 64, tied 65k table,
    parallel block, no GLU."""
    kw = dict(
        num_layers=layers, hidden_size=4544, ffn_hidden_size=18176,
        num_attention_heads=71, num_attention_heads_kv=1, kv_channels=64,
        max_position_embeddings=2048, seq_length=2048,
        padded_vocab_size=65024, use_rms_norm=False, use_bias=False,
        glu_activation=None, position_embedding_type="rotary",
        parallel_attn=True, tie_embed_logits=True, hidden_dropout=0.0,
        attention_dropout=0.0, params_dtype=BF16, compute_dtype=BF16)
    kw.update(over)
    return FalconModel(ModelConfig(**kw))


def gqa_head128(layers=2, **over):
    """A Llama-3-8B-shaped model: 32 heads of 128 over 8 K/V groups,
    SwiGLU, untied head."""
    kw = dict(
        num_layers=layers, hidden_size=4096, ffn_hidden_size=14336,
        num_attention_heads=32, num_attention_heads_kv=8, kv_channels=128,
        max_position_embeddings=2048, seq_length=2048,
        padded_vocab_size=128256, use_rms_norm=True, use_bias=False,
        glu_activation="swiglu", position_embedding_type="rotary",
        tie_embed_logits=False, hidden_dropout=0.0, attention_dropout=0.0,
        params_dtype=BF16, compute_dtype=BF16)
    kw.update(over)
    return LlamaModel(ModelConfig(**kw))


def _toy_falcon(**over):
    """MQA, head 64, tied table, parallel block, at toy widths."""
    kw = dict(glu_activation=None, use_rms_norm=False, parallel_attn=True,
              num_attention_heads_kv=1, kv_channels=64, hidden_size=256,
              num_attention_heads=4, ffn_hidden_size=512,
              tie_embed_logits=True, compute_dtype=jnp.float32,
              use_decode_attn=False)
    kw.update(over)
    return FalconModel(tiny_config(**kw))


def decode_shapes(model, **kw):
    """The decode tree's shapes, nothing allocated."""
    return jax.eval_shape(lambda: model.prepare_decode_params(
        model.init(jax.random.key(0)), **kw))


# ------------------------------------------------- wqkv, head-major


@pytest.mark.parametrize("make,want", [
    (falcon7b, (73, 64, 4544)),        # 71 q + k + v heads of 64
    (gqa_head128, (48, 128, 4096)),    # 8 groups x (4 q + k + v) of 128
])
def test_wqkv_is_held_head_major(make, want):
    model = make(layers=3)
    dec = decode_shapes(model)
    assert len(dec["layers"]) == 3
    for layer in dec["layers"]:
        assert layer["attention"]["wqkv"].shape == want
        assert layer["attention"]["wo"].ndim == 2
        assert layer["mlp"]["w1"].ndim == 2 and layer["mlp"]["w2"].ndim == 2


def test_wqkv_head_major_is_the_transpose_cut_by_head():
    model = _toy_falcon(num_layers=2)
    params = model.init(jax.random.key(3))
    dec = model.prepare_decode_params(params)
    cfg = model.cfg
    for i, layer in enumerate(dec["layers"]):
        w2d = np.asarray(params["layers"]["attention"]["wqkv"][i])
        w3d = np.asarray(layer["attention"]["wqkv"])
        assert w3d.shape == (cfg.num_query_groups * (cfg.q_per_kv + 2),
                             cfg.head_dim, cfg.hidden_size)
        np.testing.assert_array_equal(
            w3d.reshape(-1, cfg.hidden_size).T, w2d)


@pytest.mark.parametrize("rows", [(1, 1), (2, 5), (1, 24)])
def test_qdot_head_major_equals_the_2d_product(rows):
    """The same products summed over hidden, the columns in the same
    order: q, k, v cut from it are those of the (hidden, qkv) leaf."""
    model = _toy_falcon(num_attention_heads_kv=2)
    cfg = model.cfg
    k1, k2 = jax.random.split(jax.random.key(5))
    w2d = jax.random.normal(k1, (cfg.hidden_size, cfg.qkv_projection_size))
    w3d = w2d.T.reshape(-1, cfg.head_dim, cfg.hidden_size)
    x = jax.random.normal(k2, rows + (cfg.hidden_size,))
    want, got = qdot(x, w2d, jnp.float32), qdot(x, w3d, jnp.float32)
    assert got.shape == want.shape == rows + (cfg.qkv_projection_size,)
    # fp32 sums of 256 products in another order: rounding, not terms
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-4)
    for a, b in zip(split_qkv(got, cfg), split_qkv(want, cfg)):
        assert a.shape == b.shape


def test_int8_tree_keeps_one_scale_an_output_channel():
    """Weight-only int8 of the head-major leaf: the same int8 values
    and the same scales as the (hidden, qkv) leaf's, transposed."""
    model = _toy_falcon(num_layers=1)
    params = model.init(jax.random.key(2))
    w2d = params["layers"]["attention"]["wqkv"][0]
    qdec = model.prepare_decode_params(params, quantize_int8=True)
    got = qdec["layers"][0]["attention"]["wqkv"]
    want = quantize_weight(w2d)
    h = model.cfg.hidden_size
    assert got["int8_data"].shape == (w2d.shape[1] // 64, 64, h)
    assert got["scale"].shape == (w2d.shape[1],)
    # (the tree is quantized inside one jitted program, `want` eagerly:
    # a scale may differ in its last bit, a value by one step)
    np.testing.assert_allclose(np.asarray(got["scale"]),
                               np.asarray(want["scale"]), rtol=1e-6)
    steps = np.abs(
        np.asarray(got["int8_data"], np.int32).reshape(-1, h).T
        - np.asarray(want["int8_data"], np.int32))
    assert steps.max() <= 1 and (steps > 0).mean() < 1e-3
    x = jax.random.normal(jax.random.key(4), (3, h))
    np.testing.assert_allclose(
        np.asarray(qdot(x, got, jnp.float32)),
        np.asarray(qdot(x, want, jnp.float32)), rtol=1e-4, atol=1e-4)


def test_tp_tree_shards_the_heads_axis_every_chip_its_columns():
    """Every leaf of a tp decode tree goes where decode_param_shardings
    says; the head-major `wqkv`'s shard on a chip is the columns the
    (hidden, qkv) leaf's column split gave it."""
    model = _toy_falcon(num_attention_heads_kv=2)
    ctx = ParallelContext(build_mesh(tp=2, devices=jax.devices()[:2]))
    params = model.init(jax.random.key(1))
    dec = model.prepare_decode_params(params, flatten_glu=False)
    specs = decode_param_specs(model.cfg, dec)
    shardings = decode_param_shardings(ctx, model.cfg, dec)
    placed = jax.device_put(dec, shardings)
    for leaf, sh in zip(jax.tree.leaves(placed), jax.tree.leaves(shardings)):
        assert isinstance(sh, NamedSharding) and leaf.sharding == sh
    assert specs["layers"][0]["attention"]["wqkv"] == \
        jax.sharding.PartitionSpec("model", None, None)
    h = model.cfg.hidden_size
    w2d = np.asarray(params["layers"]["attention"]["wqkv"][0])
    cols = w2d.shape[1] // 2
    for shard in placed["layers"][0]["attention"]["wqkv"].addressable_shards:
        r = list(ctx.mesh.devices.flat).index(shard.device)
        np.testing.assert_array_equal(
            np.asarray(shard.data).reshape(-1, h).T,
            w2d[:, r * cols:(r + 1) * cols])


# ------------------------------------------- the table's rows, one-hot


DECODE_TREE, STACKED_TREE = {"layers": ()}, {"layers": {}}


@pytest.mark.parametrize("tree,table,rows,want", [
    # Falcon-7B: the vocabulary is whole lane tiles, hidden is not
    (DECODE_TREE, (65024, 4544), 8, True),
    (DECODE_TREE, (65024, 4544), 8 + 128, True),
    (DECODE_TREE, (65024, 4544), ONE_HOT_MAX_ROWS, True),
    (DECODE_TREE, (65024, 4544), ONE_HOT_MAX_ROWS + 1, False),
    (DECODE_TREE, (65024, 4544), 2048, False),       # whole-prompt prefill
    (STACKED_TREE, (65024, 4544), 8, False),         # not a served round
    (STACKED_TREE, (65024, 4544), 2 * 2048, False),  # a training step
    # hidden is whole lane tiles: the table lies hidden-minor, gather
    (DECODE_TREE, (65024, 8192), 8, False),          # Falcon-40B
    (DECODE_TREE, (128256, 4096), 8, False),         # Llama-3-8B
    (DECODE_TREE, (32000, 4096), 136, False),        # Llama-2-7B
    (DECODE_TREE, (50304, 1600), 8, True),           # GPT-2 XL, padded
    (DECODE_TREE, (50257, 4480), 8, False),          # hidden pads least
])
def test_rows_by_one_hot_rule(tree, table, rows, want):
    assert _rows_by_one_hot(tree, table, rows) is want


@pytest.mark.parametrize("dtype", [jnp.float32, BF16])
def test_one_hot_rows_are_the_gathered_rows(dtype):
    """One 1.0 a row, accumulated in fp32: exact, in the table's own
    type and through the compute type."""
    model = _toy_falcon(params_dtype=dtype, compute_dtype=dtype)
    params = model.init(jax.random.key(9))
    dec = model.prepare_decode_params(params)
    tokens = jnp.asarray(
        np.random.RandomState(0).randint(0, 256, (3, 7)), jnp.int32)
    assert _rows_by_one_hot(dec, (256, 256), tokens.size) is False
    model = _toy_falcon(hidden_size=192, num_attention_heads=3,
                        params_dtype=dtype, compute_dtype=dtype)
    params = model.init(jax.random.key(9))
    dec = model.prepare_decode_params(params)
    assert _rows_by_one_hot(dec, (256, 192), tokens.size) is True
    got = embed_tokens(dec, model.cfg, tokens)      # the one-hot product
    want = embed_tokens(params, model.cfg, tokens)  # stacked tree: gather
    assert got.dtype == want.dtype == dtype
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))


# ------------------------------------------------------------ the engine


def test_engine_on_falcon_form_toy_serves_generate_tokens_tokens():
    """MQA, head 64, tied table, parallel block: the engine's tree goes
    through the placement at construction; what it serves is what the
    dense path (which never places anything) emits."""
    model = _toy_falcon()
    params = model.init(jax.random.key(7))
    rs = np.random.RandomState(3)
    prompts = [list(rs.randint(2, 256, n)) for n in (5, 11, 3, 18)]
    gen = 6
    eng = DecodeEngine(model, params, slots=2, page_size=16, max_context=64,
                       prefill_chunk_tokens=8, termination_id=None,
                       vocab_size=256)
    assert eng._dec_params["embedding"]["word_embeddings"] is \
        params["embedding"]["word_embeddings"]
    reqs = [eng.submit(p, gen, top_k=1) for p in prompts]
    eng.drain()
    for p, req in zip(prompts, reqs):
        buf = np.zeros((1, len(p) + gen), np.int32)
        buf[0, :len(p)] = p
        ref = generate_tokens(
            model, params, jnp.asarray(buf),
            jnp.asarray([len(p)], np.int32),
            prefill_len=bucket_prefill_len(len(p)), rng=None, top_k=1,
            termination_id=None, use_eod_for_early_termination=False,
            vocab_size=256)
        assert req.result(5)[0] == list(np.asarray(ref.tokens)[0])


def test_committed_tree_warmup_compiles_what_traffic_runs():
    """One committed argument commits every output of a jitted step,
    and a committed argument is another program than an uncommitted
    one. A placed leaf is committed (so is a restored checkpoint's):
    pools and carried logits start out committed beside it, and the
    rounds after warm-up find every program they call."""
    model = _toy_falcon()
    params = jax.device_put(model.init(jax.random.key(7)),
                            jax.devices()[0])
    eng = DecodeEngine(model, params, slots=2, page_size=16, max_context=64,
                       prefill_chunk_tokens=8, termination_id=None,
                       vocab_size=256)
    assert eng._last_logits.committed
    assert all(p.committed for p in eng._pools_k + eng._pools_v)
    eng.warmup()
    fns = [eng._step_fn(1, True)] + [
        eng._mixed_fn(w, True)
        for w in engine_mod.mixed_width_buckets(eng.prefill_chunk_tokens)]
    assert [f._cache_size() for f in fns] == [1] * len(fns)
    rs = np.random.RandomState(5)
    reqs = [eng.submit(list(rs.randint(2, 256, n)), 5, top_k=1)
            for n in (5, 11, 3, 18)]
    eng.drain()
    assert all(len(r.result(5)[0]) == n + 5
               for r, n in zip(reqs, (5, 11, 3, 18)))
    assert [f._cache_size() for f in fns] == [1] * len(fns)


# ------------------------------------------- compile-only, described v5e


@pytest.fixture(scope="module")
def one_chip():
    try:
        from jax.experimental import topologies

        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no libtpu, or one that
        # cannot build the client: nothing to ask
        pytest.skip(f"compile-only TPU topology unavailable: {e!r}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def lowering_for_tpu(monkeypatch):
    """The dispatch sites ask `on_tpu()`; conftest.py pins matmul
    precision to "highest" for the CPU numerics suites, no entry point
    does."""
    monkeypatch.setattr(dispatch, "on_tpu", lambda: True)
    with jax.default_matmul_precision("default"):
        yield


def round_programs(model, dev, as_before: bool = False, slots=8,
                   chunk=128, page_size=64, max_context=2048):
    """{name: compiled text} of the engine's own decode_scan (horizon
    1) and mixed_step (the chunk's width), lowered as
    `benchmark/sizing.py size_serve` lowers them, on the decode tree's
    shapes. `as_before`: the tree as it was held before ISSUE 34 (the
    (hidden, qkv) leaf; the caller also turns the one-hot rows off)."""
    cfg = model.cfg
    L, V = cfg.num_layers, cfg.padded_vocab_size

    def arr(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=dev)

    def tree():
        dec = model.prepare_decode_params(model.init(jax.random.key(0)))
        if as_before:
            for layer in dec["layers"]:
                w = layer["attention"]["wqkv"]
                layer["attention"]["wqkv"] = w.reshape(-1, w.shape[-1]).T
        return dec

    dec = jax.tree.map(lambda x: arr(x.shape, x.dtype), jax.eval_shape(tree))
    pages = 1 + slots * max_context // page_size
    pool = tuple(arr((pages, page_size, cfg.num_query_groups,
                      cfg.head_dim), BF16) for _ in range(L))
    n = slots
    pt = arr((n, max_context // page_size), jnp.int32)
    i32 = arr((n,), jnp.int32)
    tail = (arr((n,), bool), arr((n,), jnp.float32), i32,
            arr((n,), jnp.float32), arr((n,), jnp.uint32), i32)
    logits = arr((n, V), jnp.float32)
    key = ("test_decode_layout", cfg.hidden_size, slots, as_before)
    scan = engine_mod._make_step_fn(model, V, 1, True, contract_key=key,
                                    contract_owner=None)
    mixed = engine_mod._make_mixed_step_fn(
        model, V, chunk, True, contract_key=key, contract_owner=None)
    lowered = {
        "decode_scan": scan.lower(
            dec, pool, pool, (), (), pt, i32, logits, arr((n,), bool),
            arr((n, 1), jnp.int32), arr((n, 1), bool), *tail),
        "mixed_step": mixed.lower(
            dec, pool, pool, (), (), pt, i32, logits,
            arr((chunk,), jnp.int32), i32, arr((n,), bool),
            arr((), jnp.int32), *tail),
    }
    return {name: low.compile().as_text() for name, low in lowered.items()}


_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) .*\{$")
_INSTRUCTION = re.compile(
    r"^\s+(?:ROOT )?%?[\w.\-]+ = \w+\[([\d,]*)\]\S* (copy|transpose)\(")


def weight_sized_copies(text: str, floor: int) -> list:
    """Lines of `copy` / `transpose` instructions OUTSIDE a fusion whose
    result has `floor` elements or more (an asynchronous `copy-start` /
    `copy-done` pair is the compiler's prefetch, not a pass of its
    own)."""
    out, comp = [], None
    for line in text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            comp = m.group(1)
            continue
        if comp is None or "fused_computation" in comp:
            continue
        m = _INSTRUCTION.match(line)
        if m and np.prod([int(d) for d in m.group(1).split(",") if d],
                         dtype=np.int64) >= floor:
            out.append(line.strip()[:160])
    return out


def test_no_weight_sized_copy_v5e(one_chip, lowering_for_tpu, monkeypatch):
    """Falcon-7B's widths, 2 layers, 8 slots x 128-token chunks (the
    serving cells' shape): neither program re-lays a weight out. Held
    as before (the (hidden, qkv) leaf, the rows gathered) each holds the
    table's copy and one a layer for `wqkv`."""
    model = falcon7b(layers=2)
    floor = model.cfg.hidden_size ** 2
    for name, text in round_programs(model, one_chip).items():
        assert weight_sized_copies(text, floor) == [], name
    monkeypatch.setattr(language_model, "_rows_by_one_hot",
                        lambda *a: False)
    for name, text in round_programs(model, one_chip, True).items():
        found = weight_sized_copies(text, floor)
        assert len(found) == 3, (name, found)
        assert sum("[65024,4544]" in line for line in found) == 1
        assert sum("[4544,4672]" in line for line in found) == 2


def test_head128_gqa_gains_no_weight_sized_copy_v5e(one_chip,
                                                    lowering_for_tpu):
    """A Llama-3-8B-shaped model: its table lies hidden-minor and is
    gathered as before; the head-major `wqkv` loses its copy a layer.
    What is left is the flat GLU `w1`'s, one a layer, as before (its
    columns are cut into gate | up the same way: PERF.md §7)."""
    model = gqa_head128(layers=2)
    floor = model.cfg.hidden_size ** 2
    for name, text in round_programs(model, one_chip).items():
        found = weight_sized_copies(text, floor)
        assert len(found) == 2, (name, found)
        assert all("[28672,4096]" in line for line in found), (name, found)
    for name, text in round_programs(model, one_chip, True).items():
        found = weight_sized_copies(text, floor)
        assert len(found) == 4, (name, found)
        assert sum("[4096,6144]" in line for line in found) == 2


# ------------------------------------------- the K/V page pools (ISSUE 38)


def lfm2_attention_widths(layers=3):
    """LiquidAI/LFM2-8B-A1B's attention: hidden 2048, 32 heads of 64 over
    8 K/V heads, RMSNorm on each q and k head, 65,536 rows; its dense
    MLP's width for every layer (the routed MLP and the convolutions
    touch no pool)."""
    return GPTModel(ModelConfig(
        num_layers=layers, hidden_size=2048, ffn_hidden_size=7168,
        num_attention_heads=32, num_attention_heads_kv=8, kv_channels=64,
        max_position_embeddings=2048, seq_length=2048,
        padded_vocab_size=65536, use_rms_norm=True, use_bias=False,
        glu_activation="swiglu", position_embedding_type="rotary",
        tie_embed_logits=True, qk_layernorm=True, hidden_dropout=0.0,
        attention_dropout=0.0, params_dtype=BF16, compute_dtype=BF16))


@pytest.mark.parametrize("make,slots,pool,kernel,copies_a_layer", [
    # 8 x 64 lanes = four tiles of two heads: the paged kernel
    pytest.param(lfm2_attention_widths, 32, (1025, 64, 512), True, 0,
                 id="lfm2-32-slots-kernel"),
    # 1 x 64 lanes fill no tile: the twin, whose scatter and gather
    # still want the pool's 64 lanes in two orders, K and V: four copies
    # a layer a round, as on the (257, 64, 1, 64) pool before (1.9 ms a
    # round, PERF.md §7) — pinned as it is, Falcon-7B's cells must not
    # move with this layout
    pytest.param(lambda: falcon7b(layers=3), 8, (257, 64, 64), False, 4,
                 id="falcon7b-8-slots-twin"),
])
def test_pools_are_written_and_read_as_they_lie_v5e(
        one_chip, lowering_for_tpu, make, slots, pool, kernel,
        copies_a_layer):
    """The engine's own decode_scan and mixed_step at a serving cell's
    attention shape, three attention layers. `lfm2moe-serve-batch`'s
    lane-packed pool (1025, 64, 512) goes through the scatter and the
    paged kernel with no copy or transpose of its size outside a fusion
    (on the 4-D pool `bf16[1025,64,8,64]` there were four a layer a
    round, 5 ms: PERF.md §6, PR 38), and each program holds the Mosaic
    call."""
    model = make()
    shapes = jax.eval_shape(
        lambda: model.init_paged_kv_caches(slots, pool[0], 64, 32))
    assert {x.shape for x in shapes["k_pages_layers"]
            + shapes["v_pages_layers"]} == {pool}
    for name, text in round_programs(model, one_chip, slots=slots).items():
        found = weight_sized_copies(text, int(np.prod(pool)))
        assert len(found) == 3 * copies_a_layer, (name, found)
        assert ("tpu_custom_call" in text) is kernel, name
