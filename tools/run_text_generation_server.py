#!/usr/bin/env python
"""Start the REST text-generation server on a checkpoint.

The rebuild of ref tools/run_text_generation_server.py: load a native
checkpoint (trained or converter-written "release"), build the tokenizer,
serve PUT /api.

    python tools/run_text_generation_server.py --load /path/ckpt \
        --model llama --tokenizer_type SentencePieceTokenizer \
        --tokenizer_model tok.model --port 5000

SIGTERM / Ctrl-C stop accepting requests, drain the engine and exit 0.
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import sys
import threading

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def load_served_model(load: str, family: str = "llama", tp: int = 1,
                      **overrides):
    """(model, params, checkpoint dir) of a native checkpoint: the
    architecture comes from its meta.json (plus config `overrides`),
    and the weights are restored straight into the serving layout on
    the first `tp` devices — tp-sharded when tp > 1. The template's
    explicit shardings keep orbax from resurrecting the SAVING
    topology, so a checkpoint trained on four chips loads on one and
    the reverse."""
    import jax

    from megatron_llm_tpu.config import (
        falcon_config,
        gpt_config,
        llama_config,
    )
    from megatron_llm_tpu.models import FalconModel, GPTModel, LlamaModel
    from megatron_llm_tpu.parallel.mesh import ParallelContext, build_mesh
    from megatron_llm_tpu.parallel.sharding import param_shardings
    from megatron_llm_tpu.training.checkpointing import (
        checkpoint_dir,
        load_checkpoint,
        read_tracker,
    )

    iteration, release = read_tracker(load)
    path = checkpoint_dir(load, iteration or 0, release=release)
    with open(os.path.join(path, "meta.json")) as f:
        saved = json.load(f)["config"]

    common = {k: saved[k] for k in (
        "num_layers", "hidden_size", "num_attention_heads",
        "num_attention_heads_kv", "ffn_hidden_size", "seq_length",
        "max_position_embeddings", "padded_vocab_size", "rope_theta",
        "rope_scaling_factor", "layernorm_epsilon",
    ) if k in saved}
    common.update(overrides)
    if family == "llama":
        cfg = llama_config(7, vocab_size=saved["padded_vocab_size"], **common)
        model = LlamaModel(cfg)
    elif family == "falcon":
        cfg = falcon_config(
            7, vocab_size=saved["padded_vocab_size"],
            parallel_layernorm=saved.get("parallel_layernorm", False),
            **common,
        )
        model = FalconModel(cfg)
    else:
        cfg = gpt_config(vocab_size=saved["padded_vocab_size"], **common)
        model = GPTModel(cfg)

    load_ctx = ParallelContext(build_mesh(tp=tp, devices=jax.devices()[:tp]))
    tmpl = jax.eval_shape(model.init, jax.random.key(0))
    tmpl = jax.tree.map(
        lambda x, sh: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sh),
        tmpl, param_shardings(load_ctx, cfg, tmpl))
    loaded = load_checkpoint(load, tmpl)
    if loaded is None:
        raise SystemExit(f"no loadable checkpoint under {load}")
    return model, loaded[0], path


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--load", required=True)
    p.add_argument("--model", choices=["llama", "falcon", "gpt"],
                   default="llama")
    p.add_argument("--tokenizer_type", default="SentencePieceTokenizer")
    p.add_argument("--vocab_file", default=None)
    p.add_argument("--merges_file", "--merge_file", default=None)
    p.add_argument("--tokenizer_model", default=None)
    p.add_argument("--null_vocab_size", type=int, default=None,
                   help="NullTokenizer: vocabulary size without the eod "
                        "id (prompts and answers are space-separated "
                        "token ids)")
    p.add_argument("--host", default="0.0.0.0")
    p.add_argument("--port", type=int, default=5000)
    # continuous-batching engine knobs (inference/engine.py; docs/GUIDE.md
    # "Continuous-batching serving engine"). --serving_slots 0 disables
    # the engine: every request takes the whole-batch path under the
    # device lock (single-shot batch eval behavior).
    p.add_argument("--serving_slots", type=int, default=8)
    p.add_argument("--page_size", type=int, default=64)
    p.add_argument("--max_context", type=int, default=2048)
    p.add_argument("--page_budget", type=int, default=None,
                   help="total pooled KV positions; default "
                        "slots*max_context (full reservation)")
    p.add_argument("--max_queue", type=int, default=64)
    p.add_argument("--step_horizon", type=int, default=8,
                   help="decode steps per host round-trip (dispatch "
                        "amortizer; admission latency quantum)")
    p.add_argument("--prefill_chunk_tokens", type=int, default=256,
                   help="per-round prompt-token budget of chunked "
                        "admission (mixed prefill+decode steps): a long "
                        "prompt delays each in-flight decode token by at "
                        "most one chunk forward; 0 = whole-prompt "
                        "prefill at admission (single-tenant short-"
                        "prompt mode)")
    p.add_argument("--warmup_compile", action="store_true",
                   help="pre-trace the mixed-step/decode-scan "
                        "executables for the configured buckets before "
                        "serving, so the first request never eats the "
                        "compile stall")
    p.add_argument("--request_deadline_s", type=float, default=None,
                   help="per-request wall-clock budget: an engine "
                        "request past it fails with a timeout and its "
                        "slot's KV pages return to the pool (ISSUE 5 "
                        "serving robustness; default: no deadline)")
    # ISSUE 6 serving features (docs/GUIDE.md "Prefix caching,
    # streaming, and speculative decoding")
    p.add_argument("--prefix_cache", action=argparse.BooleanOptionalAction,
                   default=None,
                   help="share prompt-prefix KV pages across requests "
                        "(refcounted page-aligned cache, COW on mid-page "
                        "divergence, LRU eviction under pool pressure). "
                        "Default: on whenever chunked admission is on "
                        "(--prefill_chunk_tokens > 0 is required); pass "
                        "--prefix_cache with --prefill_chunk_tokens 0 to "
                        "get the loud incompatibility error instead of a "
                        "silent downgrade")
    p.add_argument("--spec_decode_k", type=int, default=0,
                   help="speculative decoding: prompt-lookup n-gram "
                        "drafts of up to K tokens per greedy slot, "
                        "verified in one width-(K+1) ragged chunk; "
                        "greedy token streams stay bitwise. 0 disables "
                        "(the right call for short generations or "
                        "non-repetitive traffic — see GUIDE)")
    p.add_argument("--stream", action=argparse.BooleanOptionalAction,
                   default=True,
                   help="serve SSE token streaming for {\"stream\": "
                        "true} PUTs (one data: event per generated "
                        "token); --no_stream turns the surface off "
                        "(e.g. behind a buffering proxy)")
    # ISSUE 9 quantized serving (docs/GUIDE.md "Quantized serving")
    p.add_argument("--kv_dtype", choices=["bf16", "int8"], default="bf16",
                   help="paged KV pool storage dtype: bf16 (default; "
                        "bitwise greedy parity with generate_tokens) or "
                        "int8 (per-token/group fp32 scales — ~half the "
                        "pool bytes/token and half the decode kernels' "
                        "cache traffic at a measured logprob drift; "
                        "bench extra.quant reports the bound)")
    p.add_argument("--quantize_weights", action="store_true",
                   help="weight-only int8 decode matmuls: one-shot "
                        "per-output-channel quantization of the decode "
                        "qkv/dense/MLP weights (halves decode weight "
                        "traffic; fp checkpoint untouched; decode-only)")
    # ISSUE 13 observability (docs/GUIDE.md "Observability"): host span
    # tracing, the flight-recorder crash artifact, and the jax.profiler
    # capture hook (POST /profile). GET /metrics always serves both the
    # legacy JSON and — under Accept: text/plain / ?format=prometheus —
    # the Prometheus text exposition with real latency histograms.
    p.add_argument("--trace_dir", type=str, default=None,
                   help="enable the engine's host span tracer; Chrome "
                        "trace-event JSON (Perfetto) exports here on "
                        "shutdown, and POST /profile captures default "
                        "here")
    p.add_argument("--record_dir", type=str, default=".",
                   help="where the flight recorder dumps its crash "
                        "artifact when the serve loop dies poisoned "
                        "(default: the working directory; the live "
                        "snapshot is always at GET /flight_record)")
    p.add_argument("--flight_recorder_size", type=int, default=4096,
                   help="bounded ring of recent structured engine "
                        "events (rounds, admissions, retirements) the "
                        "flight recorder keeps")
    # ISSUE 15 goodput & device-cost accounting (docs/GUIDE.md
    # "Goodput & device-cost accounting")
    p.add_argument("--cost_registry", action="store_true",
                   help="capture each minted executable's compiled "
                        "cost (cost_analysis FLOPs/bytes + "
                        "memory_analysis temp/args) at mint time: "
                        "unlocks the per-request device-cost record on "
                        "retire events, serve_modeled_gflops/"
                        "serve_page_rounds aggregates, the "
                        "serve_dispatch_overhead_pct gauge, and the "
                        "labeled cost_* Prometheus samples on "
                        "/metrics. One extra AOT compile per minted "
                        "executable (pair with --warmup_compile so it "
                        "all happens before traffic)")
    p.add_argument("--chip_spec", type=str, default=None,
                   choices=["v5e", "v5p", "v4"],
                   help="override TPU-generation detection for the "
                        "roofline denominators (telemetry/chipspec.py; "
                        "default: detect from the engine's devices)")
    p.add_argument("--perf_sentinel_ksigma", type=float, default=0.0,
                   help="arm the decode-round perf-regression "
                        "sentinel: patience consecutive rounds above "
                        "median + ksigma * 1.4826*MAD of the recent "
                        "per-token-advance latency trip it — flight-"
                        "recorder trail, serve_perf_regressions "
                        "counter, ring auto-dump into --record_dir. "
                        "0 disables (default)")
    p.add_argument("--perf_sentinel_window", type=int, default=64,
                   help="sentinel baseline window (good rounds)")
    p.add_argument("--perf_sentinel_patience", type=int, default=8,
                   help="consecutive bad rounds that trip the sentinel")
    # ISSUE 14: serve from a mesh, not a chip (docs/GUIDE.md "Serving
    # on a tp mesh & replica routing")
    p.add_argument("--serving_tp", type=int, default=1,
                   help="tensor-parallel degree of EACH engine's "
                        "serving mesh: the KV page pools (and int8 "
                        "scale pools) shard over the head/group axis "
                        "and every jitted step runs under pjit/GSPMD "
                        "on a (1,1,1,tp) mesh; must divide the "
                        "model's num_query_groups. Greedy token "
                        "streams stay bitwise vs single-chip; 1 = "
                        "single-chip (the default)")
    p.add_argument("--router_replicas", type=int, default=1,
                   help="run N engine replicas behind the prefix-"
                        "affinity router (inference/router.py): each "
                        "replica owns serving_tp devices "
                        "(replica i -> devices [i*tp, (i+1)*tp)), "
                        "shared-prefix traffic routes to the replica "
                        "whose PrefixCache holds the pages, fallback "
                        "least-queue-depth, poisoned replicas leave "
                        "rotation, stop drains the fleet. /metrics "
                        "aggregates; 1 = one engine, no router")
    p.add_argument("--affinity_routing",
                   action=argparse.BooleanOptionalAction, default=True,
                   help="route by the page-aligned prefix -> replica "
                        "index (--no_affinity_routing = pure least-"
                        "queue-depth dispatch, the A/B control arm "
                        "bench extra.serving.scaleout measures "
                        "against)")
    p.add_argument("--prefill_replicas", type=int, default=0,
                   help="disaggregated serving (ISSUE 17): dedicate "
                        "the FIRST N of --router_replicas to chunked "
                        "prefill; long prompts dispatch there, "
                        "finished KV pages ship to the least-"
                        "backlogged decode replica via the jitted "
                        "page export/import pair, short prompts go "
                        "direct. Requires 0 < N < router_replicas; "
                        "0 = symmetric fleet (the default)")
    # ISSUE 19 long-context serving (docs/GUIDE.md "Long-context
    # serving"): RoPE reach knobs + the sliding-window fast path.
    p.add_argument("--rope_theta", type=float, default=None,
                   help="override the rotary base frequency saved in "
                        "the checkpoint (e.g. 1e6 for long-context "
                        "finetunes; default: the checkpoint's value, "
                        "falling back to 10000)")
    p.add_argument("--rope_scaling_factor", type=float, default=None,
                   help="linear RoPE position interpolation: positions "
                        "divide by this factor before the rotation, "
                        "stretching a trained context window by ~the "
                        "factor (pair with a proportionally larger "
                        "--max_context; default: the checkpoint's "
                        "value, falling back to 1.0 = off)")
    p.add_argument("--attention_window_size", type=int, default=None,
                   help="sliding-window attention for serving: each "
                        "token attends only the last W positions, the "
                        "paged kernels skip pages wholly out of window "
                        "(decode KV traffic O(W) not O(context)) and "
                        "the engine reclaims out-of-window pages "
                        "mid-flight (peak pool O(W) per long slot; "
                        "serve_window_reclaimed_pages on /metrics). "
                        "Requires --prefill_chunk_tokens > 0. Only "
                        "sound for models trained/finetuned with a "
                        "matching window; default: full causal "
                        "attention")
    p.add_argument("--ttft_slo_s", type=float, default=None,
                   help="SLO-aware admission: reject (HTTP 503 with "
                        "a modeled-drain-time Retry-After) when every "
                        "candidate replica's modeled backlog exceeds "
                        "this many seconds of device time (needs "
                        "--cost_registry + --chip_spec on the "
                        "engines; without them the gate stays open)")
    # ISSUE 20 self-driving fleet (docs/GUIDE.md "Self-driving fleet
    # operations"): fault injection, sentinel-driven replace cycles,
    # in-flight request recovery, load-adaptive scaling.
    p.add_argument("--chaos", type=str, default=None,
                   help="deterministic fault injection (inference/"
                        "chaos.py grammar), e.g. "
                        "'kill=1@8,probe_drop=0.3,seed=7': kill=RID[@N]"
                        " poisons replica RID after N submits, "
                        "stall=RID:MSxK trips the sentinel, probe_drop"
                        "/probe_latency_ms/submit_latency_ms degrade "
                        "the control plane, corrupt_handoff exercises "
                        "the KV hand-off geometry gate. TEST KNOB — "
                        "never arm in production")
    p.add_argument("--fleet_controller", action="store_true",
                   help="run the FleetController (inference/fleet.py)"
                        ": condemned/poisoned/sentinel-tripped "
                        "replicas are drained, stopped, rebuilt on "
                        "their devices, warmed and rotated back in; "
                        "scale decisions (with --scale_up_backlog_s/"
                        "--scale_down_backlog_s) and replace cycles "
                        "land in the flight record. Needs "
                        "--router_replicas > 1")
    p.add_argument("--recover_requests",
                   action=argparse.BooleanOptionalAction, default=None,
                   help="transparently resubmit queued and not-yet-"
                        "streamed requests of a dead replica to a "
                        "healthy one (greedy retries are bitwise; "
                        "partially-streamed requests fail loudly with "
                        "Retry-After instead). Default: on when "
                        "--fleet_controller is set, off otherwise")
    p.add_argument("--scale_up_backlog_s", type=float, default=None,
                   help="fleet controller scale-up threshold: grow "
                        "the active set when per-replica modeled "
                        "backlog exceeds this many seconds (needs "
                        "--cost_registry + --chip_spec)")
    p.add_argument("--scale_down_backlog_s", type=float, default=None,
                   help="fleet controller scale-down threshold: "
                        "shrink when per-replica modeled backlog "
                        "falls below this (keep a wide dead band "
                        "under --scale_up_backlog_s)")
    p.add_argument("--scale_patience", type=int, default=3,
                   help="consecutive identical scale verdicts before "
                        "the controller acts (flap hysteresis)")
    args = p.parse_args(argv)

    from megatron_llm_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()

    import jax

    from megatron_llm_tpu.inference.server import MegatronServer
    from megatron_llm_tpu.tokenizer import build_tokenizer

    n_rep, tp = max(args.router_replicas, 1), max(args.serving_tp, 1)
    if n_rep * tp > len(jax.devices()):
        raise SystemExit(
            f"--router_replicas {n_rep} x --serving_tp {tp} needs "
            f"{n_rep * tp} devices, have {len(jax.devices())}")
    # serve-time RoPE overrides (ISSUE 19): the rotary tables are
    # computed from the config, not the checkpoint, so retargeting
    # theta / linear interpolation at load time is sound.
    overrides = {k: getattr(args, k) for k in (
        "rope_theta", "rope_scaling_factor", "attention_window_size",
    ) if getattr(args, k) is not None}
    model, params, path = load_served_model(
        args.load, args.model, tp=tp, **overrides)
    tokenizer = build_tokenizer(
        args.tokenizer_type, vocab_file=args.vocab_file,
        merges_file=args.merges_file,
        tokenizer_model=args.tokenizer_model,
        null_vocab_size=args.null_vocab_size,
    )
    engine = None
    if args.serving_slots > 0:
        from megatron_llm_tpu.inference.engine import DecodeEngine

        # --prefix_cache default (None) is AUTO: on whenever chunked
        # admission is on. An explicit --prefix_cache with chunking off
        # reaches the engine ctor's loud incompatibility error.
        prefix_cache = (args.prefix_cache if args.prefix_cache is not None
                        else args.prefill_chunk_tokens > 0)

        def build_engine(replica_id=None, devices=None):
            return DecodeEngine(
                model, params, slots=args.serving_slots,
                page_size=args.page_size, max_context=args.max_context,
                page_budget=args.page_budget, max_queue=args.max_queue,
                step_horizon=args.step_horizon,
                prefill_chunk_tokens=args.prefill_chunk_tokens,
                warmup_compile=args.warmup_compile,
                prefix_cache=prefix_cache,
                spec_decode_k=args.spec_decode_k,
                kv_dtype=args.kv_dtype,
                quantize_weights=args.quantize_weights,
                serving_tp=tp if tp > 1 else 1,
                devices=devices,
                replica_id=replica_id,
                termination_id=tokenizer.eod,
                vocab_size=tokenizer.vocab_size,
                trace_dir=args.trace_dir,
                record_dir=args.record_dir,
                flight_recorder_size=args.flight_recorder_size,
                cost_registry=args.cost_registry,
                chip_spec=args.chip_spec,
                perf_sentinel_ksigma=args.perf_sentinel_ksigma,
                perf_sentinel_window=args.perf_sentinel_window,
                perf_sentinel_patience=args.perf_sentinel_patience,
            )

        chaos = None
        if args.chaos:
            from megatron_llm_tpu.inference.chaos import ChaosPolicy

            if n_rep <= 1:
                raise SystemExit(
                    "--chaos needs --router_replicas > 1 (faults "
                    "target replicas; a one-engine deployment has "
                    "nothing to fail over to)")
            chaos = ChaosPolicy.parse(args.chaos)
        if args.fleet_controller and n_rep <= 1:
            raise SystemExit(
                "--fleet_controller needs --router_replicas > 1")
        recover = (args.recover_requests
                   if args.recover_requests is not None
                   else args.fleet_controller)
        if n_rep > 1:
            # N replicas behind the prefix-affinity router: replica i
            # owns the device block [i*tp, (i+1)*tp)
            from megatron_llm_tpu.inference.router import (
                EngineReplica,
                ReplicaRouter,
            )

            replicas = [
                EngineReplica(build_engine(
                    replica_id=i,
                    devices=jax.devices()[i * tp:(i + 1) * tp]),
                    chaos=chaos)
                for i in range(n_rep)
            ]
            n_pre = args.prefill_replicas
            if n_pre:
                if not 0 < n_pre < n_rep:
                    raise SystemExit(
                        f"--prefill_replicas {n_pre} must leave at "
                        f"least one decode replica out of "
                        f"--router_replicas {n_rep}")
                engine = ReplicaRouter(
                    prefill_replicas=replicas[:n_pre],
                    decode_replicas=replicas[n_pre:],
                    affinity=args.affinity_routing,
                    ttft_slo_s=args.ttft_slo_s)
            else:
                engine = ReplicaRouter(replicas,
                                       affinity=args.affinity_routing,
                                       ttft_slo_s=args.ttft_slo_s,
                                       recover_requests=recover)
            if args.fleet_controller:
                from megatron_llm_tpu.inference.fleet import (
                    FleetController,
                )

                # replacements rebuild on the dead replica's device
                # block, WITHOUT the chaos policy: an injected kill
                # must not re-fire on the replacement forever
                def spawn_replica(old, _tp=tp):
                    rid = old.replica_id
                    return EngineReplica(build_engine(
                        replica_id=rid,
                        devices=jax.devices()[rid * _tp:
                                              (rid + 1) * _tp]))

                FleetController(
                    engine, spawn_replica=spawn_replica,
                    scale_up_backlog_s=args.scale_up_backlog_s,
                    scale_down_backlog_s=args.scale_down_backlog_s,
                    scale_patience=args.scale_patience).start()
        else:
            if args.prefill_replicas:
                raise SystemExit(
                    "--prefill_replicas needs --router_replicas > 1 "
                    "(a disaggregated fleet has at least one prefill "
                    "and one decode replica)")
            engine = build_engine(
                devices=jax.devices()[:tp] if tp > 1 else None)
    serve_target = engine  # what MegatronServer gets (router or engine)
    fleet = ""
    if engine is not None and hasattr(engine, "replicas"):
        # router: per-engine facts from replica 0 (homogeneous fleet)
        engine = engine.replicas[0].engine
        split = (f"{args.prefill_replicas} prefill + "
                 f"{len(serve_target.replicas) - args.prefill_replicas}"
                 f" decode" if args.prefill_replicas
                 else f"{len(serve_target.replicas)} replicas")
        fleet = (f"{split} x tp{tp} "
                 f"(prefix-affinity routing "
                 f"{'ON' if args.affinity_routing else 'OFF'}"
                 + (f", ttft_slo {args.ttft_slo_s}s"
                    if args.ttft_slo_s is not None else "")
                 + (", fleet controller" if args.fleet_controller
                    else "")
                 + (f", CHAOS[{args.chaos}]" if args.chaos else "")
                 + "), ")
    elif engine is not None and engine.serving_tp > 1:
        fleet = f"tp{engine.serving_tp} mesh, "
    print(f"serving {args.model} from {path} on "
          f"http://{args.host}:{args.port}/api"
          + (f" ({fleet}continuous batching: {args.serving_slots} slots, "
             f"{engine.num_pages - 1} pages x {args.page_size}, "
             f"kv_dtype={engine.kv_pool_dtype()} "
             f"({engine.kv_pool_bytes() / 2**20:.0f} MiB/chip pool, "
             f"{engine.kv_bytes_per_token()} B/token/chip), "
             + ("int8 decode weights, " if engine.quantize_weights
                else "")
             + (f"chunked prefill {engine.prefill_chunk_tokens} tok/round"
                if engine.prefill_chunk_tokens else
                "whole-prompt prefill")
             + (", prefix cache" if engine._prefix is not None else "")
             + (f", spec decode k={engine.spec_decode_k}"
                if engine.spec_decode_k else "")
             + (", SSE streaming" if args.stream else "")
             + (f", span tracing -> {args.trace_dir}"
                if args.trace_dir else "")
             + ((", cost registry"
                 + (f" ({engine.chip.label()})" if engine.chip else ""))
                if engine.costs is not None else "")
             + (f", perf sentinel k={args.perf_sentinel_ksigma}"
                if args.perf_sentinel_ksigma > 0 else "")
             + ", counters at /metrics (JSON + Prometheus), health at "
               "/health, flight record at /flight_record, profiler at "
               "POST /profile)"
             if engine else " (whole-batch, no engine)"), flush=True)
    server = MegatronServer(model, params, tokenizer, engine=serve_target,
                            request_deadline_s=args.request_deadline_s,
                            stream_enabled=args.stream)
    if threading.current_thread() is threading.main_thread():
        signal.signal(signal.SIGTERM, signal.default_int_handler)
    try:
        server.run(args.host, args.port)
    except KeyboardInterrupt:
        pass
    finally:
        server.stop()


if __name__ == "__main__":
    main()
