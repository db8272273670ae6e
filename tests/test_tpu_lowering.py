"""Ask the TPU compiler from the sandbox.

The installed libtpu builds a COMPILE-ONLY client for a topology with no
chip attached (`jax.experimental.topologies`), so every Pallas entry
point can be lowered through Mosaic and XLA:TPU with interpret=False
here. The interpreter the rest of the CPU suite runs kernels under takes
any block shape and partitions freely, so it cannot see the two failures
this suite exists for: a block spec Mosaic refuses, and a Mosaic call
outside a shard_map under a multi-device mesh. These are compiler
verdicts only — nothing runs; numbers come from `chip_smoke.py`.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from megatron_llm_tpu.ops import dispatch
from megatron_llm_tpu.ops.decode_attention import decode_attention
from megatron_llm_tpu.ops.flash_attention import flash_attention
from megatron_llm_tpu.ops.prefill_attention import ragged_paged_attention
from megatron_llm_tpu.ops.rmsnorm import fused_rms_norm

BF16 = jnp.bfloat16
D = 128


@pytest.fixture(scope="module")
def topo():
    try:
        from jax.experimental import topologies

        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no libtpu, or one that
        # cannot build the client: nothing to ask
        pytest.skip(f"compile-only TPU topology unavailable: {e!r}")


@pytest.fixture(autouse=True)
def kernels_on(monkeypatch):
    """The dispatch sites ask `on_tpu()`; the lowering target here is
    the TPU although the default backend is the CPU. conftest.py pins
    matmul precision to "highest" for the CPU numerics suites; no entry
    point does, and Mosaic refuses a bf16 matmul at fp32 precision."""
    monkeypatch.setattr(dispatch, "on_tpu", lambda: True)
    with jax.default_matmul_precision("default"):
        yield


def compile_on(topo, fn, *shapes):
    """Compile fn for one v5e chip; returns the Mosaic call count."""
    sh = SingleDeviceSharding(topo.devices[0])
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sh) for s, dt in shapes]
    lowered = jax.jit(fn).lower(*args)
    lowered.compile()
    return lowered.as_text().count("tpu_custom_call")


@pytest.mark.parametrize("b,s,g,qpk,d", [
    (1, 1024, 4, 1, D), (1, 1024, 2, 4, D),
    # the two training cells' shapes a chip: head 64 is zero-padded to
    # the lane tile inside ops/flash_attention.py
    pytest.param(2, 2048, 1, 71, 64, id="falcon7b-mqa-head64"),
    pytest.param(2, 2048, 2, 16, 64, id="falcon40b-tp4-gqa-head64")])
def test_flash_fwd_bwd(topo, reported, b, s, g, qpk, d):
    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True).astype(
            jnp.float32).sum()

    n = compile_on(topo, jax.grad(loss, argnums=(0, 1, 2)),
                   ((b, s, g, qpk, d), BF16), ((b, s, g, d), BF16),
                   ((b, s, g, d), BF16))
    assert n == 3  # forward, dq, dk/dv
    assert not reported()


def test_flash_with_lse_head64(topo, reported):
    """A ring hop at head 64 (cp > 1) reaches the kernel too, so
    `_ring_dispatch` wraps it as it does a 128-wide one."""
    from megatron_llm_tpu.ops.flash_attention import (
        flash_attention_with_lse,
        flash_reaches_kernel,
    )

    def loss(q, k, v):
        o, lse = flash_attention_with_lse(q, k, v, causal=False)
        return o.astype(jnp.float32).sum() + lse.sum()

    q = (1, 1024, 2, 16, 64)
    assert flash_reaches_kernel(q, 1024)
    n = compile_on(topo, jax.grad(loss, argnums=(0, 1, 2)), (q, BF16),
                   ((1, 1024, 2, 64), BF16), ((1, 1024, 2, 64), BF16))
    assert n == 3
    assert not reported()


@pytest.mark.parametrize("g,qpk", [(32, 1), (8, 4), (1, 8)])
@pytest.mark.parametrize("variant", ["row", "chunk", "int8-row",
                                     "int8-chunk-window", "window-row"])
def test_paged(topo, g, qpk, variant):
    C = 256 if "chunk" in variant else 1
    int8 = "int8" in variant
    page, slots, max_pages = (32 if int8 else 16), 4, 16
    assert compile_on(topo, *paged_call(
        slots, C, g, qpk, D, page, max_pages, int8,
        window=100 if "window" in variant else None)) == 1


def paged_call(nc, C, g, qpk, d, page, max_pages, int8, window=None):
    """(fn, *shapes) of one `ragged_paged_attention` call on lane-packed
    pools of nc slots."""
    pools = ((nc * max_pages + 1, page, g * d), jnp.int8 if int8 else BF16)
    shapes = [((nc, C, g, qpk, d), BF16), ((nc, C, g, d), BF16),
              ((nc, C, g, d), BF16), pools, pools,
              ((nc, max_pages), jnp.int32), ((nc,), jnp.int32),
              ((nc,), jnp.int32)]
    if int8:
        shapes += [(pools[0][:2] + (g,), jnp.float32)] * 2

    def fn(q, kn, vn, kp, vp, pt, starts, lens, *scales):
        kw = dict(k_scales=scales[0], v_scales=scales[1]) if scales else {}
        return ragged_paged_attention(
            q, kn, vn, kp, vp, pt, starts, lens, window_size=window, **kw)

    return (fn, *shapes)


@pytest.mark.parametrize("int8", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("nc,C", [
    pytest.param(32, 1, id="decode-32x1"),
    pytest.param(1, 128, id="chunk-1x128")])
def test_paged_head64_cell_shapes(topo, reported, nc, C, int8):
    """The two kernel shapes of `lfm2moe-serve-batch` (8 K/V heads of 64
    x 4, page 64, 32 pages a slot: two heads fill a 128-lane tile of the
    lane-packed pool, ISSUE 38) compile through Mosaic for a v5e."""
    assert compile_on(topo, *paged_call(nc, C, 8, 4, 64, 64, 32, int8)) == 1
    assert not reported()


def test_paged_falcon7b_head64_takes_the_twin_and_is_counted(topo, reported):
    """One K/V head of 64 fills no lane tile: the twin on the same
    lane-packed pool, counted."""
    assert compile_on(topo, *paged_call(8, 1, 1, 71, 64, 64, 32, False)) == 0
    assert len(reported()) == 1 and "g=1, qpk=71, d=64" in list(reported())[0]


@pytest.mark.parametrize("layer_types", [
    pytest.param(["conv", "full_attention", "conv"], id="one-attention"),
    pytest.param(["conv", "full_attention", "conv", "full_attention"],
                 id="two-attention")])
def test_a_program_holds_each_paged_kernel_once(topo, reported, layer_types):
    """The guard of the set-up budget (ISSUE 38): a step's warm-up
    traces, lowers and hashes its program whatever the compile cache
    holds, and a Mosaic call is lowered once per call site. The kernel
    sits behind one call boundary (`_paged_call`), so the decode step of
    a tiny three-kind model holds ONE `tpu_custom_call` and its mixed
    step TWO (the chunk's shape and the decode rows'), the same with two
    attention layers as with one. Counts, not seconds."""
    from benchmark import families
    from megatron_llm_tpu.inference import engine as eng

    L = len(layer_types)
    cfg = {
        "model_type": "lfm2_moe", "conv_L_cache": 3, "conv_bias": False,
        "hidden_size": 256, "intermediate_size": 256, "head_dim": 64,
        "layer_types": layer_types, "moe_intermediate_size": 128,
        "norm_eps": 1e-5, "norm_topk_prob": True,
        "num_attention_heads": 4, "num_key_value_heads": 2,
        "num_dense_layers": 1, "num_experts": 4, "num_experts_per_tok": 2,
        "num_hidden_layers": L, "rope_theta": 1000000,
        "routed_scaling_factor": 1, "use_expert_bias": True,
        "vocab_size": 512, "tie_word_embeddings": True,
        "initializer_range": 0.1}
    use = {"num_hidden_layers": L, "max_context": 256,
           "compute_dtype": "bfloat16", "weights_dtype": "bfloat16"}
    model = families.find(cfg).model(cfg, use)
    assert len(set(model.cfg.layer_kinds)) == 3
    sh = SingleDeviceSharding(topo.devices[0])

    def like(tree):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sh),
            tree)

    def arr(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=sh)

    n, page, chunk = 4, 64, 16
    dec = like(jax.eval_shape(
        lambda: model.prepare_decode_params(model.init(jax.random.key(0)))))
    cache = like({k: v for k, v in jax.eval_shape(
        lambda: model.init_paged_kv_caches(n, 1 + n * 4, page, 4)).items()
        if k not in ("page_table", "lengths")})
    assert len(cache["k_pages_layers"]) == layer_types.count("full_attention")
    pt, i32 = arr((n, 4), jnp.int32), arr((n,), jnp.int32)
    tail = (arr((n,), bool), arr((n,), jnp.float32), i32,
            arr((n,), jnp.float32), arr((n,), jnp.uint32), i32)
    logits = arr((n, 512), jnp.float32)
    key = ("test_tpu_lowering", L)
    scan = eng._make_step_fn(model, 512, 1, True, contract_key=key,
                             contract_owner=None)
    mixed = eng._make_mixed_step_fn(model, 512, chunk, True,
                                    contract_key=key, contract_owner=None)
    decode_text = scan.lower(
        dec, cache, pt, i32, logits, arr((n,), bool),
        arr((n, 1), jnp.int32), arr((n, 1), bool), *tail).as_text()
    mixed_text = mixed.lower(
        dec, cache, pt, i32, logits, arr((chunk,), jnp.int32), i32,
        arr((n,), bool), arr((), jnp.int32), *tail).as_text()
    assert decode_text.count("tpu_custom_call") == 1
    assert mixed_text.count("tpu_custom_call") == 2
    assert not reported()


@pytest.mark.parametrize("layout", ["gtd", "tgd"])
@pytest.mark.parametrize("g,qpk", [(32, 1), (8, 4)])
def test_dense_decode(topo, layout, g, qpk):
    cache = (2, g, 512, D) if layout == "gtd" else (2, 512, g, D)
    n = compile_on(
        topo, functools.partial(decode_attention, layout=layout),
        ((2, 1, g, qpk, D), BF16), (cache, BF16), (cache, BF16),
        ((), jnp.int32))
    assert n == 1


def test_fused_rmsnorm_fwd_bwd(topo):
    def loss(x, scale):
        return fused_rms_norm(x, scale).astype(jnp.float32).sum()

    n = compile_on(topo, jax.grad(loss, argnums=(0, 1)),
                   ((1024, 512), BF16), ((512,), jnp.float32))
    assert n == 2


# ---------------------------------------------------------------------------
# Under a multi-device mesh: Mosaic calls must sit in a shard_map
# (parallel/mesh.shard_kernel)
# ---------------------------------------------------------------------------


def _abstract(tree, shardings):
    return jax.tree.map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
        tree, shardings)


def compile_train_step(topo, dp=1, pp=1, cp=1, tp=1, sp=False,
                       zero1=False, **cfg_over):
    """The production train step (flash on, full remat, bf16) compiled
    for a mesh over the topology's chips; returns (Mosaic calls, bytes
    per device)."""
    from megatron_llm_tpu.config import (
        ParallelConfig,
        TrainConfig,
        llama_config,
    )
    from megatron_llm_tpu.models import LlamaModel
    from megatron_llm_tpu.optimizer.optimizer import (
        OptimizerState,
        init_optimizer_state,
    )
    from megatron_llm_tpu.parallel.mesh import (
        destroy_parallel,
        initialize_parallel,
    )
    from megatron_llm_tpu.parallel.sharding import (
        optimizer_state_specs,
        param_specs,
    )

    cfg = dict(num_layers=2, hidden_size=512, num_attention_heads=4,
               num_attention_heads_kv=4, ffn_hidden_size=1024,
               seq_length=1024, vocab_size=1024,
               recompute_granularity="full")
    cfg.update(cfg_over)
    model = LlamaModel(llama_config(7, **cfg))
    cfg = model.cfg
    ctx = initialize_parallel(
        dp=dp, pp=pp, tp=tp, cp=cp, sequence_parallel=sp,
        devices=topo.devices[:dp * pp * cp * tp])
    try:
        mesh = ctx.mesh
        tmpl = jax.eval_shape(model.init, jax.random.key(0))
        if pp > 1:
            from megatron_llm_tpu.parallel.pipeline import (
                make_pipelined_train_step,
                pipeline_param_specs,
            )

            pspecs = pipeline_param_specs(cfg, tmpl)
        else:
            from megatron_llm_tpu.training.train_step import make_train_step

            pspecs = param_specs(cfg, tmpl)

        def named(specs):
            return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                                is_leaf=lambda x: isinstance(x, P))

        rep = NamedSharding(mesh, P())
        num_micro = 2 if pp > 1 else 1
        tcfg = TrainConfig(micro_batch_size=1,
                           global_batch_size=num_micro * dp, lr=1e-4)
        pcfg = ParallelConfig(
            num_microbatches=num_micro, data_parallel_size=dp,
            pipeline_parallel_size=pp, context_parallel_size=cp,
            tensor_parallel_size=tp, sequence_parallel=sp,
            use_distributed_optimizer=zero1)
        osh = named(optimizer_state_specs(cfg, tmpl, dp, zero1,
                                          base_specs=pspecs))
        opt = _abstract(
            jax.eval_shape(lambda p: init_optimizer_state(p, tcfg), tmpl),
            OptimizerState(step=rep, m=osh, v=osh, scaler=None))
        key = ("tpu-lowering", dp, pp, cp, tp, sp, zero1,
               tuple(sorted(cfg_over.items())))
        if pp > 1:
            fn = make_pipelined_train_step(model, tcfg, pcfg, ctx,
                                           contract_key=key,
                                           contract_owner=None)
        else:
            fn = make_train_step(model, tcfg, pcfg, contract_key=key,
                                 contract_owner=None)
        tok = jax.ShapeDtypeStruct(
            (num_micro, dp, cfg.seq_length), jnp.int32,
            sharding=NamedSharding(mesh, P(None, "data", None)))

        def scalar():
            return jax.ShapeDtypeStruct((), jnp.float32, sharding=rep)

        # graft-contract: train.step
        lowered = jax.jit(fn, donate_argnums=(0, 1)).lower(
            _abstract(tmpl, named(pspecs)), opt,
            {"tokens": tok, "labels": tok}, scalar(), scalar(), None,
            scalar())
        mem = lowered.compile().memory_analysis()
        return (lowered.as_text().count("tpu_custom_call"),
                mem.argument_size_in_bytes + mem.temp_size_in_bytes)
    finally:
        destroy_parallel()


@pytest.mark.parametrize("heads", [
    pytest.param({}, id="head128"),
    pytest.param(dict(num_attention_heads=8, num_attention_heads_kv=4),
                 id="head64-gqa")])
def test_train_step_tp4_sp_flash(topo, reported, heads):
    """The layout in finetune.py's docstring, with the flash kernel in
    the step: GSPMD refuses a bare Mosaic call under this mesh. At head
    64 (the four-chip cell's form: grouped K/V, one group a chip) the
    kernel is in the step as well."""
    n, _ = compile_train_step(topo, tp=4, sp=True, **heads)
    assert n >= 3
    assert not reported()


@pytest.mark.slow
@pytest.mark.parametrize("layout", [
    dict(dp=4), dict(dp=2, tp=2, zero1=True), dict(pp=2, tp=2),
    dict(cp=2, tp=2), dict(pp=2, cp=2)],
    ids=["dp4", "dp2tp2zero1", "pp2tp2", "cp2tp2", "pp2cp2"])
def test_train_step_other_layouts(topo, layout):
    """pp2cp2: the ring's per-hop flash kernel inside the pipeline's
    stage+context-manual region needs the remaining axes manual too
    (models/attention._ring_dispatch)."""
    n, _ = compile_train_step(topo, **layout)
    assert n >= 3


@pytest.mark.slow
@pytest.mark.parametrize("layout,depth", [(dict(), 2),
                                          (dict(tp=4, sp=True), 12)],
                         ids=["one-chip", "tp4sp"])
def test_train_step_llama2_7b_widths_fit(topo, layout, depth):
    """chip_smoke.py's two training shapes at the full Llama-2-7B widths
    fit one v5e chip's 16 GB by the compiler's own accounting."""
    n, per_device = compile_train_step(
        topo, num_layers=depth, hidden_size=4096, num_attention_heads=32,
        num_attention_heads_kv=32, ffn_hidden_size=11008, seq_length=4096,
        vocab_size=32000, **layout)
    assert n >= 3
    assert per_device < 15 * 2**30, per_device


@pytest.mark.parametrize("int8", [False, True], ids=["bf16", "int8"])
def test_paged_attention_block_tp4(topo, int8):
    """The serving attention sublayer on a tp4 mesh with group-sharded
    pools (the --serving_tp 4 program's kernel call)."""
    from megatron_llm_tpu.config import llama_config
    from megatron_llm_tpu.models.attention import attention_block
    from megatron_llm_tpu.models.rope import precompute_rope
    from megatron_llm_tpu.parallel.mesh import (
        ParallelContext,
        build_mesh,
        use_mesh,
    )
    from megatron_llm_tpu.parallel.sharding import kv_pool_spec

    cfg = llama_config(7, num_layers=1, hidden_size=1024,
                       num_attention_heads=8, num_attention_heads_kv=8,
                       vocab_size=1024)
    ctx = ParallelContext(build_mesh(tp=4, devices=topo.devices))
    slots, C, page, max_pages, g = 4, 16, 32, 8, 8
    pool = (slots * max_pages + 1, page, g * D)  # lane-packed

    def arg(shape, dtype, spec=P()):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=ctx.sharding(*spec))

    cache = {
        "k_pages": arg(pool, jnp.int8 if int8 else BF16,
                       kv_pool_spec(pool, 4, g)),
        "v_pages": arg(pool, jnp.int8 if int8 else BF16,
                       kv_pool_spec(pool, 4, g)),
        "page_table": arg((slots, max_pages), jnp.int32),
        "lengths": arg((slots,), jnp.int32),
        "chunk_lens": arg((slots,), jnp.int32),
    }
    if int8:
        cache["k_scales"] = arg(pool[:2] + (g,), jnp.float32,
                                kv_pool_spec(pool[:2] + (g,), 4, g))
        cache["v_scales"] = cache["k_scales"]
    params = {"wqkv": arg((1024, 3 * 1024), jnp.float32, P(None, "model")),
              "wo": arg((1024, 1024), jnp.float32, P("model", None))}
    rope = np.asarray(precompute_rope(D, 4096, 10000.0, 1.0))

    def fn(params, hidden, cache):
        return attention_block(params, cfg, hidden, jnp.asarray(rope), None,
                               None, kv_cache=cache)

    with use_mesh(ctx):
        lowered = jax.jit(fn).lower(params, arg((slots, C, 1024), BF16),
                                    cache)
    lowered.compile()
    assert lowered.as_text().count("tpu_custom_call") == 1


# ---------------------------------------------------------------------------
# What is reported as a fallback on a TPU backend (ops/dispatch.py): trace-
# time decisions, no compiler needed
# ---------------------------------------------------------------------------


@pytest.fixture
def reported(monkeypatch):
    monkeypatch.setattr(dispatch, "_FALLBACKS", {})
    return dispatch.fallbacks


def test_min_cache_routing_is_not_a_fallback(reported):
    """A decode cache below `decode_attn_min_cache` is the config's own
    routing; a cache in kernel territory the block gate refuses is a
    fallback."""
    from megatron_llm_tpu.config import llama_config
    from megatron_llm_tpu.models.attention import _decode_kernel_block

    cfg = llama_config(7, num_layers=1)
    assert cfg.decode_attn_min_cache == 128
    assert _decode_kernel_block(cfg, 1, 49, "gtd") is None
    assert _decode_kernel_block(cfg, 1, 384, "gtd") == 128
    assert not reported()
    assert _decode_kernel_block(cfg, 1, 312, "gtd") is None  # 8 * 39
    assert list(reported()) == [
        "decode_attention[qpk=1, d=128, T=312, layout=gtd] "
        "gate=decode_attn_block"]


def test_paged_refusals(reported):
    def paged(page, min_cache):
        pools = jax.ShapeDtypeStruct((9, page, 2 * D), BF16)
        new = jax.ShapeDtypeStruct((2, 1, 2, D), BF16)
        i32 = jax.ShapeDtypeStruct((2,), jnp.int32)
        jax.eval_shape(
            functools.partial(ragged_paged_attention, min_cache=min_cache),
            jax.ShapeDtypeStruct((2, 1, 2, 1, D), BF16), new, new, pools,
            pools, jax.ShapeDtypeStruct((2, 4), jnp.int32), i32, i32)

    paged(8, 128)  # reach 4 x 8 = 32 < min_cache: routed, silent
    assert not reported()
    paged(8, 0)  # in territory; a page of 8 does not tile bf16 sublanes
    assert len(reported()) == 1 and "page_size=8" in list(reported())[0]


def test_shard_kernel_reports_an_axis_it_cannot_split(reported):
    """MQA's single KV group under tp: every model shard runs the whole
    kernel call, and says so."""
    from megatron_llm_tpu.parallel.mesh import (
        ParallelContext,
        build_mesh,
        shard_kernel,
        use_mesh,
    )

    def attend(q):
        return q

    spec = P("data", None, "model", None, None)
    ctx = ParallelContext(build_mesh(tp=4, devices=jax.devices()[:4]))
    with use_mesh(ctx):
        jax.eval_shape(shard_kernel(attend, (spec,), spec),
                       jax.ShapeDtypeStruct((2, 8, 4, 1, D), BF16))
        assert not reported()
        jax.eval_shape(shard_kernel(attend, (spec,), spec),
                       jax.ShapeDtypeStruct((2, 8, 1, 8, D), BF16))
    assert list(reported()) == [
        "attend[repeated_over=model, shapes=2x8x1x8x128] gate=shard_kernel"]
