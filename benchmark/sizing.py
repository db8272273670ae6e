#!/usr/bin/env python3
"""Compile-only sizing: each cell's main programs, at their real sizes,
compiled for a described `v5e:2x2` (no chip attached), and the bytes the
compiler accounts per device. Nothing runs; a compile that passes is not
a chip run. Run here, before a chip call:

  JAX_PLATFORMS=cpu python3 benchmark/sizing.py train falcon-7b
  JAX_PLATFORMS=cpu python3 benchmark/sizing.py train falcon-40b
  JAX_PLATFORMS=cpu python3 benchmark/sizing.py serve falcon-7b [slots chunk]
"""

import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from benchmark import families, harness, program, weights  # noqa: E402


def _topo():
    from jax.experimental import topologies

    return topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")


def _report(name, lowered):
    try:
        compiled = lowered.compile()
    except Exception as e:  # noqa: BLE001 the compiler's refusal IS the result
        msg = str(e)
        cut = msg.find("Used ")
        row = {"program": name, "refused": msg[cut:cut + 80].split(". ")[0]
               if cut >= 0 else msg[:200]}
        print(json.dumps(row), flush=True)
        return row
    mem = compiled.memory_analysis()
    row = {"program": name,
           "argument_gb": mem.argument_size_in_bytes / 1e9,
           "output_gb": mem.output_size_in_bytes / 1e9,
           "alias_gb": mem.alias_size_in_bytes / 1e9,
           "temp_gb": mem.temp_size_in_bytes / 1e9}
    row["live_gb"] = row["argument_gb"] + row["temp_gb"] \
        + row["output_gb"] - row["alias_gb"]
    print(json.dumps(row), flush=True)
    return row


def size_train(cfg: dict):
    from megatron_llm_tpu.config import ParallelConfig, TrainConfig
    from megatron_llm_tpu.ops import dispatch
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
    from megatron_llm_tpu.training.train_step import make_train_step

    dispatch.on_tpu = lambda: True
    use = dict(cfg["train"])
    use.update(json.loads(os.environ.get("SIZING_OVERRIDE", "{}")))
    tp, sp = use["tensor_parallel"], use["sequence_parallel"]
    topo = _topo()
    model = families.find(cfg).model(cfg, use, tp)
    ctx = initialize_parallel(tp=tp, sequence_parallel=sp,
                              devices=topo.devices[:tp])
    try:
        mesh = ctx.mesh
        tmpl = jax.eval_shape(model.init, jax.random.key(0))
        pspecs = param_specs(model.cfg, tmpl)

        def named(specs):
            return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                                is_leaf=lambda x: isinstance(x, P))

        def abstract(tree, sh):
            return jax.tree.map(
                lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                                  sharding=s), tree, sh)

        rep = NamedSharding(mesh, P())
        tcfg = TrainConfig(micro_batch_size=use["micro_batch_size"],
                           global_batch_size=use["global_batch_size"],
                           lr=use["lr"], clip_grad=use["clip_grad"])
        micro = use["global_batch_size"] // use["micro_batch_size"]
        pcfg = ParallelConfig(num_microbatches=micro, tensor_parallel_size=tp,
                              sequence_parallel=sp)
        osh = named(optimizer_state_specs(model.cfg, tmpl, 1, False,
                                          base_specs=pspecs))
        opt = abstract(
            jax.eval_shape(lambda p: init_optimizer_state(p, tcfg), tmpl),
            OptimizerState(step=rep, m=osh, v=osh, scaler=None))
        fn = make_train_step(model, tcfg, pcfg,
                             contract_key=("sizing", cfg["name"]),
                             contract_owner=None)
        rows, seq = use["micro_batch_size"], use["seq_length"]
        bsh = NamedSharding(mesh, P(None, "data", None))
        tok = jax.ShapeDtypeStruct((micro, rows, seq), jnp.int32, sharding=bsh)
        msk = jax.ShapeDtypeStruct((micro, rows, seq), jnp.float32, sharding=bsh)
        sc = jax.ShapeDtypeStruct((), jnp.float32, sharding=rep)
        lowered = jax.jit(fn, donate_argnums=(0, 1)).lower(
            abstract(tmpl, named(pspecs)), opt,
            {"tokens": tok, "labels": tok, "loss_mask": msk,
             "position_ids": tok}, sc, sc, None, sc)
        return _report(f"train_step {cfg['name']} L{use['num_hidden_layers']}"
                       f" tp{tp}", lowered)
    finally:
        destroy_parallel()


def size_serve(cfg: dict, slots=None, chunk=None):
    from megatron_llm_tpu.inference import engine as eng
    from megatron_llm_tpu.ops import dispatch

    dispatch.on_tpu = lambda: True
    use = dict(cfg["serve"])
    use.update(json.loads(os.environ.get("SIZING_OVERRIDE", "{}")))
    slots = int(slots or use["slots"])
    chunk = int(chunk or use["prefill_chunk_tokens"])
    dev = SingleDeviceSharding(_topo().devices[0])
    model = families.find(cfg).model(cfg, use)
    mc = model.cfg
    L, V = mc.num_layers, mc.padded_vocab_size
    bf = jnp.bfloat16

    def arr(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=dev)

    # the decode tree's shapes: the family's seeded leaves (one stack a
    # kind of block) laid out as the program lays them out, then as the
    # engine keeps them
    dec = jax.eval_shape(lambda: model.prepare_decode_params(
        program.program_tree(cfg, weights.make_stacked(cfg, 0, L),
                             weights.make_globals(cfg, 0))))
    dec = jax.tree.map(lambda x: arr(x.shape, bf), dec)
    pages = 1 + slots * use["max_context"] // use["page_size"]
    pool = tuple(arr((pages, use["page_size"], mc.num_query_groups,
                      mc.head_dim), bf) for _ in range(L))
    n = slots
    pt = arr((n, use["max_context"] // use["page_size"]), jnp.int32)
    i32 = arr((n,), jnp.int32)
    common_tail = (arr((n,), bool), arr((n,), jnp.float32), i32,
                   arr((n,), jnp.float32), arr((n,), jnp.uint32), i32)
    logits = arr((n, V), jnp.float32)
    out = []
    for hor in sorted({1, use["step_horizon"]}):
        scan = eng._make_step_fn(model, cfg["vocab_size"], hor, True,
                                 contract_key=("sizing", hor, slots),
                                 contract_owner=None)
        out.append(_report(
            f"decode_scan slots{slots} h{hor}",
            scan.lower(dec, pool, pool, (), (), pt, i32, logits,
                       arr((n,), bool), arr((n, hor), jnp.int32),
                       arr((n, hor), bool), *common_tail)))
    for w in sorted({1, chunk}):
        mixed = eng._make_mixed_step_fn(model, cfg["vocab_size"], w, True,
                                        contract_key=("sizing", w, slots),
                                        contract_owner=None)
        out.append(_report(
            f"mixed_step slots{slots} w{w}",
            mixed.lower(dec, pool, pool, (), (), pt, i32, logits,
                        arr((w,), jnp.int32), i32, arr((n,), bool),
                        arr((), jnp.int32), *common_tail)))
    return out


if __name__ == "__main__":
    kind, name = sys.argv[1], sys.argv[2]
    cfg = harness.load_json(name) if name.endswith(".json") else \
        harness.load_json(harness.HERE, "configs", name + ".json")
    if kind == "train":
        size_train(cfg)
    else:
        size_serve(cfg, *sys.argv[3:5])
