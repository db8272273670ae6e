"""Benchmark: end-to-end Llama training throughput on one real TPU chip.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", "extra"}.
The headline value is the seq-1024 run; "extra" carries the seq-4096 row,
explicit MFU for both lengths, and the flash-vs-XLA attention speedup so
kernel regressions are visible round-over-round (VERDICT r3 #10).

Round-6 audit keys (VERDICT r5 next-round #5): decode rows run with the
Pallas decode-attention kernel ON and OFF (`decode_tok_s_*` vs
`decode_tok_s_*_xla_attn`), the b=8 decode step is broken down into
attention / GLU-matvec / head / sampling components against the measured
step time, the standalone decode-attention op reports achieved HBM
bandwidth (`decode_attn_gbps_b8`, fraction of the 819 GB/s v5e peak),
and the flash kernel reports fwd/bwd MXU utilization (`flash_fwd_mxu`,
`flash_bwd_mxu`) — so the roofline claims are auditable round-over-round.

Round-7 audit keys: the remat-policy ladder (models/remat.py;
full/offload/selective/save_dots/none) is swept at a shared (seq, mbs)
point — per-policy tok/s, MFU, and compiled peak-HBM
(`memory_analysis()` temp/args bytes) land in `extra.remat_sweep`, with
`remat_selective_vs_full_tok_s` as the headline FLOP-tax audit ratio, and
the headline row states which policy it trained under.

Round-8 audit keys (ISSUE 3): `extra.serving` runs mixed-length
synthetic traffic (short+long prompts x short+long budgets, staggered
arrivals) through the continuous-batching engine
(inference/engine.py, paged KV pool + ragged Pallas decode attention)
AND through the whole-batch path at the same concurrency —
`continuous_vs_static_tok_s` is the headline structural-win ratio, with
p50/p95 per-request latency for both paths, slot occupancy, and the
measurement methodology stated in the row itself.

Round-9 audit keys (ISSUE 4): `extra.serving.interference` measures
long-prompt admission under load — short requests decoding while a
max-length prompt arrives — on a CHUNKED engine (mixed prefill+decode
rounds through the ragged paged prefill kernel,
ops/prefill_attention.py) vs a WHOLE-PROMPT engine: TTFT p50/p95 and
per-round decode-latency p95 for both, `chunked_vs_wholeprompt_ttft`
as the headline ratio, per-round prefill-token maxima as the budget
audit, methodology stated in-row.

Round-11 audit keys (ISSUE 9): `extra.quant` quantizes the serving hot
path — bf16 vs int8-KV (and +weight-only-int8) engines on identical
greedy traffic: decode tok/s ratio (`int8_vs_bf16_decode_tok_s`
headline), KV bytes/token derived from the live pools (the capacity
doubling), a standalone paged-attention GB/s pair at the same traffic,
and max teacher-forced prompt-logprob drift vs bf16 stated in-row; the
decode roofline row now derives cache bytes from the active cache
dtype instead of hard-coding bf16.

Round-14 audit keys (ISSUE 14): `extra.serving.scaleout` scales the
engine OUT — N emulated prefix-cache replicas (each pinned to its own
device) behind the prefix-affinity router (inference/router.py) vs the
same fleet under seeded-random dispatch vs a 1-replica baseline, on
the 80%-shared-system-prompt mix: aggregate tok/s and TTFT p50/p95 per
arm, `router_affinity_vs_random_ttft_p95` and
`aggregate_tok_s_scaling` headlines, fleet prefill-token reduction,
methodology in-row (CPU-harness-tested in tests/test_router.py).

Round-13 audit keys (ISSUE 13): `extra.telemetry` prices the
flight-recorder telemetry — span tracing + histograms + recorder ON vs
OFF on identical serving and training traffic, `telemetry_overhead_pct`
headline on decode tok/s and train step_ms, token streams and losses
asserted BITWISE on==off in-row (methodology in-row; CPU-harness-tested
in tests/test_telemetry.py like extra.overlap).

Round-15 audit keys (ISSUE 15): `extra.goodput` runs a short train +
serve pass with the goodput ledger + compiled-cost registry + perf
sentinel ON vs OFF — `goodput_fraction` and `telemetry_overhead_pct`
headlines, the sum-to-wall partition invariant and bitwise on==off
streams/losses asserted in-row; chip peaks for every MFU/roofline
number in this file now come from telemetry/chipspec.py (detected on
the bench host, stated per row) instead of module constants.

Round-10 audit keys (ISSUE 5): `extra.ckpt` measures the
fault-tolerance claim — train-loop stall per checkpoint under the async
CheckpointManager (device→host copy only) vs the synchronous
save-and-commit wall time, at the bench model size with real fp32
master params + Adam m/v; the row asserts the async checkpoint restores
bitwise and that keep_latest_n retention GC holds, and states its
methodology in-row.

Methodology: the reference's in-repo anchor is the Llama-2-7B fine-tune at
~890 tokens/sec/GPU on A100-80GB (BASELINE.md; docs/guide/getting_started.md
:195-201). A 7B model does not fit on the single 16GB v5e chip available
here, so we train the largest complete Llama-architecture model that does
(~0.74B) and normalise by model FLOPs: achieved model-FLOP/s =
tokens/sec * flops_per_token. vs_baseline is our achieved model-FLOP/s over
the A100 baseline's (890 tok/s * 6 * 7e9).

Config matches how the reference actually trains (BASELINE.md row 1):
flash attention ON (the Pallas kernel, compiled by Mosaic on this chip),
bf16 compute; full remat is memory-forced on this 16GB chip (see inline
note). MFU is reported against the v5e bf16 peak (197 TFLOP/s), counting
6*N_params + causal attention FLOPs per token.

Usage: python bench.py [--seq 1024|4096|0]   (0 = both + kernel ratio)
"""

import argparse
import json
import time

import jax
import jax.numpy as jnp

from megatron_llm_tpu.config import ModelConfig, ParallelConfig, TrainConfig
from megatron_llm_tpu.models import LlamaModel
from megatron_llm_tpu.optimizer import init_optimizer_state
from megatron_llm_tpu.training import make_train_step

# Chip peaks come from the ONE runtime spec table (ISSUE 15 dedupe:
# the old module constants V5E_PEAK_BF16 / V5E_HBM_BYTES_S moved onto
# telemetry/chipspec.py, which the trainer's live MFU gauge and the
# engine's dispatch-overhead gauge read too — bench and runtime can no
# longer disagree about the denominator). The spec is DETECTED from the
# device kind and is None off the TPU: a CPU harness that wants the
# gauges exercised names its spec itself (goodput_stats(chip_spec=...)),
# and every row states its spec source in-row.
from megatron_llm_tpu.telemetry.chipspec import (  # noqa: E402
    detect_chip,
    train_flops_per_token,
)

CHIP = detect_chip()


def make_cfg(seq, remat_policy="full"):
    return ModelConfig(
        num_layers=12,
        hidden_size=2048,
        num_attention_heads=16,
        num_attention_heads_kv=16,
        ffn_hidden_size=5504,
        seq_length=seq,
        max_position_embeddings=seq,
        padded_vocab_size=32000,
        position_embedding_type="rotary",
        glu_activation="swiglu",
        use_rms_norm=True,
        use_bias=False,
        tie_embed_logits=False,
        hidden_dropout=0.0,
        attention_dropout=0.0,
        params_dtype=jnp.float32,  # fp32 master params, bf16 compute
        use_flash_attn=True,
        remat_policy=remat_policy,
    )


def run_train(seq, iters, mbs=None, remat_policy="full", with_memory=False):
    """One-chip train-step throughput at `seq` under `remat_policy`
    (models/remat.py ladder). Returns (tok/s, MFU, n_params[, memdict]):
    `with_memory=True` adds the AOT `compiled.memory_analysis()` per-device
    peak temp / args bytes of the exact step that was timed."""
    # Full remat is memory-forced at 0.74B on the 16GB chip at the PEAK
    # mbs (live activations need 23G at mbs 8 / seq 1024 without it,
    # measured r1); mbs swept on-chip r4: 12 peaks at seq 1024 (8/10/14/
    # 16/24 all lower), 6 peaks at seq 4096 (7/8 lower, 10+ OOMs the
    # compiler), 3 at seq 8192. The remat-policy sweep passes a smaller
    # shared mbs so every rung of the ladder fits.
    mbs = mbs if mbs is not None else {1024: 12, 4096: 6, 8192: 3}[seq]
    cfg = make_cfg(seq, remat_policy=remat_policy)
    model = LlamaModel(cfg)
    params = model.init(jax.random.key(0))
    n_params = sum(p.size for p in jax.tree.leaves(params))

    tcfg = TrainConfig(micro_batch_size=mbs, global_batch_size=mbs, lr=1e-4)
    opt_state = init_optimizer_state(params, tcfg)
    step = jax.jit(make_train_step(model, tcfg, ParallelConfig(num_microbatches=1)),
                   donate_argnums=(0, 1))

    tokens = jax.random.randint(jax.random.key(1), (1, mbs, seq), 0, 32000)
    batch = {"tokens": tokens, "labels": jnp.roll(tokens, -1, axis=-1)}
    lr = jnp.float32(1e-4)
    wd = jnp.float32(0.0)

    mem = None
    if with_memory:
        # AOT peak-HBM audit of the exact step about to be timed; the
        # timed calls below go through the SAME compiled executable.
        step = step.lower(params, opt_state, batch, lr, wd).compile()
        m = step.memory_analysis()
        mem = {
            "temp_bytes": int(m.temp_size_in_bytes),
            "args_bytes": int(m.argument_size_in_bytes),
        }

    # warmup (compile); the host fetch (float()) waits for the device
    for _ in range(3):
        params, opt_state, stats = step(params, opt_state, batch, lr, wd)
    float(stats["loss"])

    # best of two passes: a transient host-load spike (anything else
    # running on the VM) can halve a single measurement
    best_dt = float("inf")
    for _ in range(2):
        t0 = time.perf_counter()
        for _ in range(iters):
            params, opt_state, stats = step(params, opt_state, batch, lr,
                                            wd)
        float(stats["loss"])
        best_dt = min(best_dt, time.perf_counter() - t0)
    dt = best_dt

    tok_per_sec = mbs * seq * iters / dt
    # fwd+bwd model FLOPs per token through the ONE shared definition
    # (telemetry/chipspec.train_flops_per_token: 6N + causal attention)
    flops_per_tok = train_flops_per_token(
        n_params, cfg.num_layers, cfg.hidden_size, seq)
    mfu = tok_per_sec * flops_per_tok / CHIP.peak_flops_for("bf16")
    if with_memory:
        return tok_per_sec, mfu, n_params, mem
    return tok_per_sec, mfu, n_params


# every policy the sweep audits, cheapest-HBM first; see models/remat.py
REMAT_SWEEP_POLICIES = ("full", "offload", "selective", "save_dots", "none")
REMAT_SWEEP_MBS = 2  # shared mbs small enough that even "none" fits 16GB


def remat_policy_sweep(seq=1024, iters=10):
    """tok/s + MFU + compiled peak-HBM per remat policy at a SHARED
    (seq, mbs) point, so the ladder's FLOP/memory trade is auditable
    round-over-round. A policy that fails (OOM, unsupported offload on
    this platform) records its error instead of killing the artifact
    run."""
    rows = []
    for pol in REMAT_SWEEP_POLICIES:
        try:
            tok, mfu, _, mem = run_train(
                seq, iters, mbs=REMAT_SWEEP_MBS, remat_policy=pol,
                with_memory=True,
            )
            rows.append({
                "policy": pol,
                "tok_s": round(tok, 1),
                "mfu": round(mfu, 4),
                "mfu_spec_source": CHIP.label(),
                "temp_gb": round(mem["temp_bytes"] / 2**30, 3),
                "args_gb": round(mem["args_bytes"] / 2**30, 3),
            })
        except Exception as e:  # noqa: BLE001 — audit row, not a gate
            rows.append({"policy": pol, "error": str(e)[:200]})
    return rows


def run_decode(b, gen=512, prompt=64, use_decode_attn=True):
    """KV-cached greedy decode tok/s on the bench model served in bf16
    (the b=1 row is ~74% of the weight-streaming roofline after the
    flat-GLU decode layout; VERDICT r4 #6). `use_decode_attn=False`
    forces the pre-kernel XLA matvec attention — the on/off pair is the
    round-over-round audit row for the decode-attention kernel."""
    from megatron_llm_tpu.inference.generation import generate_tokens

    import dataclasses

    cfg = dataclasses.replace(make_cfg(1024), params_dtype=jnp.bfloat16,
                              use_decode_attn=use_decode_attn)
    model = LlamaModel(cfg)
    params = model.init(jax.random.key(0))
    max_len = prompt + gen
    tokens = jax.random.randint(jax.random.key(1), (b, max_len), 0, 32000)
    lengths = jnp.full((b,), prompt, jnp.int32)

    def once():
        out = generate_tokens(
            model, params, tokens, lengths, prefill_len=prompt,
            termination_id=None, use_eod_for_early_termination=False,
        )
        import numpy as np

        np.asarray(out.tokens)  # host sync

    once()  # compile
    best = float("inf")
    for _ in range(2):
        t0 = time.perf_counter()
        once()
        best = min(best, time.perf_counter() - t0)
    return b * gen / best


def make_serving_workload(n, seed=0):
    """Mixed-length synthetic traffic: short and long prompts crossed
    with short and long generation budgets, staggered arrivals — the
    shape continuous batching exists for (a whole batch runs to its
    SLOWEST row; slot-level admission doesn't)."""
    import numpy as np

    rs = np.random.RandomState(seed)
    prompt_lens = [32, 64, 192, 384]
    gens = [32, 64, 128, 224]
    work = []
    for i in range(n):
        p = prompt_lens[i % len(prompt_lens)]
        g = gens[(i * 7 + 3) % len(gens)]
        work.append((list(rs.randint(2, 32000, p)), g))
    # staggered Poisson-ish arrivals, mean 40 ms apart
    arrivals = np.cumsum(rs.exponential(0.04, n))
    arrivals[0] = 0.0
    return work, [float(a) for a in arrivals]


def serving_stats(model, params, workload, arrivals, *, slots=8,
                  page_size=64, max_context=640, vocab_size=32000):
    """Continuous-batching engine vs the whole-batch path on identical
    traffic. Methodology (stated in the emitted row): both paths serve
    the same greedy requests with the same arrival times and the same
    concurrency cap (`slots`); useful tokens = sum of requested
    generation budgets; tok/s = useful / makespan (first arrival ->
    last completion); per-request latency = completion - arrival. The
    static path batches whatever has arrived (up to `slots` rows,
    padded to a fixed compile shape) and runs `generate_tokens`, which
    cannot stop early per row or admit late arrivals mid-batch — that
    structural waste, not kernel speed, is what the ratio measures.
    Both paths are compile-warmed before timing."""
    import numpy as np

    from megatron_llm_tpu.inference.engine import DecodeEngine
    from megatron_llm_tpu.inference.generation import (
        bucket_prefill_len,
        generate_tokens,
    )

    n = len(workload)
    useful = sum(g for _, g in workload)
    min_prompt = min(len(p) for p, _ in workload)
    prefill = bucket_prefill_len(min_prompt)
    max_len = max(len(p) + g for p, g in workload)
    max_len = -(-max_len // 64) * 64

    # ---- continuous (engine) --------------------------------------------
    eng = DecodeEngine(model, params, slots=slots, page_size=page_size,
                       max_context=max_context, max_queue=n,
                       termination_id=None, vocab_size=vocab_size)
    # warm every prefill bucket AND every step-horizon bucket (the scan
    # is traced per pow2 horizon) off the clock — sequentially, so each
    # drain actually exercises its own horizon length
    for plen in sorted({bucket_prefill_len(len(p)) for p, _ in workload}):
        eng.submit(list(range(2, 2 + plen)), 1)
        eng.drain()
    h = 1
    while h <= eng.step_horizon:
        eng.submit([2, 3, 4], h)
        eng.drain()
        h *= 2

    t0 = time.perf_counter()
    submitted = 0
    reqs = []
    while len(reqs) < n or any(not r.done.is_set() for r in reqs):
        now = time.perf_counter() - t0
        while submitted < n and arrivals[submitted] <= now:
            p, g = workload[submitted]
            reqs.append(eng.submit(p, g))
            submitted += 1
        if not eng.step():
            if submitted < n:
                time.sleep(max(arrivals[submitted] - (
                    time.perf_counter() - t0), 0))
    cont_makespan = max(r.t_done for r in reqs) - t0
    cont_lat = sorted(r.t_done - t0 - arrivals[i]
                      for i, r in enumerate(reqs))
    # decode-slot utilization: useful tokens over slots * steps
    cont_occupancy = useful / max(eng._steps * slots, 1)

    # ---- static (whole-batch generate_tokens) ---------------------------
    def run_batch(batch_idx):
        toks = np.zeros((slots, max_len), np.int32)
        lens = np.full((slots,), max_len, np.int32)
        for row, j in enumerate(batch_idx):
            p, g = workload[j]
            toks[row, :len(p)] = p
            lens[row] = len(p)
        for row in range(len(batch_idx), slots):  # pad rows: repeat row 0
            toks[row] = toks[0]
            lens[row] = lens[0]
        out = generate_tokens(
            model, params, jnp.asarray(toks), jnp.asarray(lens),
            prefill_len=prefill, rng=None, top_k=1, termination_id=None,
            use_eod_for_early_termination=False, vocab_size=vocab_size,
        )
        np.asarray(out.tokens)  # host sync

    run_batch(list(range(min(slots, n))))  # warm the one compile shape

    t0 = time.perf_counter()
    done_at = [0.0] * n
    nxt = 0
    while nxt < n:
        now = time.perf_counter() - t0
        if arrivals[nxt] > now:
            time.sleep(arrivals[nxt] - now)
            continue
        now = time.perf_counter() - t0
        batch = [j for j in range(nxt, n) if arrivals[j] <= now][:slots]
        run_batch(batch)
        t_done = time.perf_counter() - t0
        for j in batch:
            done_at[j] = t_done
        nxt = batch[-1] + 1
    static_makespan = max(done_at)
    static_lat = sorted(done_at[i] - arrivals[i] for i in range(n))

    def pct(xs, p):
        return xs[min(int(p * len(xs)), len(xs) - 1)]

    cont_tok_s = useful / cont_makespan
    static_tok_s = useful / static_makespan
    return {
        "requests": n,
        "useful_tokens": useful,
        "slots": slots,
        "page_size": page_size,
        "serving_tok_s": round(cont_tok_s, 1),
        "static_tok_s": round(static_tok_s, 1),
        "continuous_vs_static_tok_s": round(cont_tok_s / static_tok_s, 2),
        "p50_latency_s": round(pct(cont_lat, 0.50), 3),
        "p95_latency_s": round(pct(cont_lat, 0.95), 3),
        "static_p50_latency_s": round(pct(static_lat, 0.50), 3),
        "static_p95_latency_s": round(pct(static_lat, 0.95), 3),
        "slot_occupancy": round(cont_occupancy, 3),
        "methodology": (
            "same greedy requests, same staggered arrivals, same "
            "concurrency cap both paths; useful tokens = sum of "
            "requested gen budgets; tok/s = useful/makespan; latency = "
            "completion - arrival; static path batches arrived requests "
            "(padded to one fixed compile shape) and runs to the "
            "slowest row; both paths compile-warmed before timing"
        ),
    }


def serving_interference_stats(model, params, *, slots=4, page_size=64,
                               max_context=768, chunk=128,
                               vocab_size=32000, n_short=8,
                               short_prompt=32, short_gen=64,
                               long_gen=16):
    """TTFT + decode-latency interference during LONG-prompt admission,
    chunked vs whole-prompt prefill on identical traffic. Methodology
    (stated in the emitted row): `slots` short greedy requests are
    decoding when a max-length prompt (max_context - long_gen tokens)
    arrives, followed by a second wave of short requests; TTFT = submit
    -> first GENERATED token; decode p95 = p95 wall ms per decode-token
    advance per scheduler round (whole-prompt admission runs the full
    prefill inside a round, so its stall lands in this gauge; chunked
    rounds are budget-bounded by construction). Both engines are
    compile-warmed off the clock; `chunked_vs_wholeprompt_ttft` > 1
    means chunked admission cut p95 TTFT."""
    import numpy as np

    from megatron_llm_tpu.inference.engine import DecodeEngine

    long_prompt_len = max_context - long_gen
    rs = np.random.RandomState(0)
    short_prompts = [list(rs.randint(2, vocab_size, short_prompt))
                     for _ in range(n_short)]
    long_prompt = list(rs.randint(2, vocab_size, long_prompt_len))
    pct = DecodeEngine._pct  # the ONE percentile definition the gauges use

    out = {}
    for mode, chunk_toks in (("chunked", chunk), ("wholeprompt", 0)):
        eng = DecodeEngine(
            model, params, slots=slots, page_size=page_size,
            max_context=max_context, max_queue=n_short + 1,
            termination_id=None, vocab_size=vocab_size,
            prefill_chunk_tokens=chunk_toks)
        # compile-warm every executable this traffic reaches: both
        # prompt shapes once through the engine, plus the scan/mixed
        # bucket sweep
        for p in (short_prompts[0], long_prompt):
            eng.submit(p, 2, top_k=1)
            eng.drain()
        eng.warmup()
        eng._ttft_ms.clear()
        eng._decode_ms.clear()
        eng._round_log.clear()

        half = n_short // 2
        first = [eng.submit(p, short_gen, top_k=1)
                 for p in short_prompts[:half]]
        while not all(r.t_first for r in first):  # get them decoding
            eng.step()
        long_req = eng.submit(long_prompt, long_gen, top_k=1)
        rest = [eng.submit(p, short_gen, top_k=1)
                for p in short_prompts[half:]]
        eng.drain()
        reqs = first + [long_req] + rest
        ttfts = [(r.t_first - r.t_submit) * 1e3 for r in reqs]
        out[mode] = {
            "ttft_p50_ms": round(pct(ttfts, 0.50), 2),
            "ttft_p95_ms": round(pct(ttfts, 0.95), 2),
            "decode_p95_ms": round(pct(eng._decode_ms, 0.95), 2),
            "max_round_prefill_tokens": max(
                (r["prefill_tokens"] for r in eng._round_log),
                default=0),
        }
    ratio = out["wholeprompt"]["ttft_p95_ms"] / max(
        out["chunked"]["ttft_p95_ms"], 1e-9)
    return {
        "slots": slots,
        "chunk_tokens": chunk,
        "long_prompt_len": long_prompt_len,
        "n_requests": n_short + 1,
        "chunked": out["chunked"],
        "wholeprompt": out["wholeprompt"],
        "chunked_vs_wholeprompt_ttft": round(ratio, 2),
        "methodology": (
            "identical greedy traffic both engines: slots short "
            "requests decoding when one max-length prompt arrives, then "
            "a second short wave; TTFT = submit -> first generated "
            "token; decode p95 = wall ms per decode-token advance per "
            "scheduler round (whole-prompt admission prefills inside a "
            "round, so its stall lands here; chunked rounds are "
            "budget-bounded); both engines compile-warmed off the "
            "clock; ratio = wholeprompt/chunked p95 TTFT"
        ),
    }


def serving_prefix_stats(model, params, *, slots=4, page_size=64,
                         max_context=768, chunk=128, vocab_size=32000,
                         n_requests=10, shared_frac=0.8,
                         sys_prompt=384, uniq_suffix=32, gen=48):
    """Prefix-sharing benefit at a realistic shared-system-prompt mix
    (ISSUE 6). Methodology (stated in the emitted row): `shared_frac`
    of the requests open with the SAME system prompt plus a short
    unique suffix — the production multi-tenant pattern — and the rest
    are fully unique at the same total length; the identical greedy
    burst runs through a prefix-cache engine and an unshared engine
    (both chunked, both compile-warmed off the clock, cache cold at
    t0 — the first `slots`-wide admission wave looks up before any
    page registers, so those shared requests pay their full prefill
    honestly inside the run; later shared admissions hit). Headlines:
    `shared_vs_unshared_ttft_p95` (> 1 means sharing cut p95 TTFT),
    `shared_vs_unshared_tok_s`, the per-request prefill-token
    reduction (cache-hit tokens never run a forward), and the PEAK
    pages-in-use delta (shared prefix pages are stored once)."""
    import numpy as np

    from megatron_llm_tpu.inference.engine import DecodeEngine

    rs = np.random.RandomState(0)
    sysp = list(rs.randint(2, vocab_size, sys_prompt))
    uniq_every = max(int(round(1.0 / max(1.0 - shared_frac, 1e-9))), 1)
    work = []
    n_shared = 0
    for i in range(n_requests):
        if (i % uniq_every) != uniq_every - 1:
            work.append(sysp + list(rs.randint(2, vocab_size,
                                               uniq_suffix)))
            n_shared += 1
        else:
            work.append(list(rs.randint(2, vocab_size,
                                        sys_prompt + uniq_suffix)))
    pct = DecodeEngine._pct

    out = {}
    for mode, share in (("shared", True), ("unshared", False)):
        eng = DecodeEngine(
            model, params, slots=slots, page_size=page_size,
            max_context=max_context, max_queue=n_requests,
            termination_id=None, vocab_size=vocab_size,
            prefill_chunk_tokens=chunk, prefix_cache=share)
        # compile-warm off the clock (both prompt shapes + the
        # scan/mixed buckets); the prefix CACHE stays cold — clear it
        # so the measured run's first shared request pays the one miss
        eng.submit(work[0][:sys_prompt // 2], 2, top_k=1)
        eng.drain()
        eng.warmup()
        eng.reset_prefix_cache()
        eng._ttft_ms.clear()
        eng._decode_ms.clear()
        pf0 = eng._prefill_tokens
        t0 = time.perf_counter()
        reqs = [eng.submit(p, gen, top_k=1) for p in work]
        peak_pages = 0
        while eng.step():
            c = eng.counters()
            peak_pages = max(peak_pages, c["serve_pages_in_use"])
        makespan = max(r.t_done for r in reqs) - t0
        ttfts = [(r.t_first - r.t_submit) * 1e3 for r in reqs]
        row = {
            "ttft_p50_ms": round(pct(ttfts, 0.50), 2),
            "ttft_p95_ms": round(pct(ttfts, 0.95), 2),
            "tok_s": round(n_requests * gen / makespan, 1),
            "prefill_tokens_per_request": round(
                (eng._prefill_tokens - pf0) / n_requests, 1),
            "peak_pages_in_use": peak_pages,
        }
        if share:
            row.update({k: v for k, v in eng.counters().items()
                        if "prefix" in k})
        out[mode] = row
    return {
        "slots": slots,
        "n_requests": n_requests,
        "shared_requests": n_shared,
        "sys_prompt_tokens": sys_prompt,
        "uniq_suffix_tokens": uniq_suffix,
        "shared": out["shared"],
        "unshared": out["unshared"],
        "shared_vs_unshared_ttft_p95": round(
            out["unshared"]["ttft_p95_ms"]
            / max(out["shared"]["ttft_p95_ms"], 1e-9), 2),
        "shared_vs_unshared_tok_s": round(
            out["shared"]["tok_s"]
            / max(out["unshared"]["tok_s"], 1e-9), 2),
        "prefill_token_reduction": round(
            1.0 - out["shared"]["prefill_tokens_per_request"]
            / max(out["unshared"]["prefill_tokens_per_request"], 1e-9),
            3),
        "peak_pages_in_use_delta": (
            out["unshared"]["peak_pages_in_use"]
            - out["shared"]["peak_pages_in_use"]),
        "methodology": (
            f"identical greedy burst both engines: {n_shared}/"
            f"{n_requests} requests = {sys_prompt}-token shared system "
            f"prompt + {uniq_suffix} unique tokens, the rest fully "
            f"unique at the same length; both engines chunked "
            f"({chunk} tok/round) and compile-warmed off the clock, "
            f"prefix cache cold at t0 (the first {slots}-wide "
            "admission wave looks up before any page registers and "
            "pays full prefill in-run; later shared admissions hit); "
            "TTFT = submit -> first generated "
            "token; tok/s = requested gen tokens / makespan; prefill "
            "tokens/request counts forward-pass prompt tokens "
            "(cache hits skip theirs); peak pages sampled per round"
        ),
    }


def serving_scaleout_stats(model, params, *, replicas=2, slots=2,
                           page_size=64, max_context=768, chunk=128,
                           vocab_size=32000, n_requests=24,
                           shared_frac=0.8, sys_prompt=384,
                           uniq_suffix=32, gen=32, step_horizon=8,
                           devices=None):
    """The `extra.serving.scaleout` harness (ISSUE 14): N emulated
    engine replicas behind the prefix-affinity router
    (inference/router.py) vs the SAME fleet under seeded-random
    dispatch, plus a 1-replica baseline, all on the
    80%-shared-system-prompt mix. Methodology (stated in the emitted
    row): each replica is an independent prefix-cache DecodeEngine
    pinned to its own device (true compute parallelism where the host
    has >= N devices; the row records the device list honestly), each
    fleet is compile-warmed off the clock with a COLD prefix cache and
    cold router index at t0, and the identical greedy burst submits
    through the router. Headlines:
    `router_affinity_vs_random_ttft_p95` (> 1 means affinity routing
    beat random dispatch on p95 TTFT — affinity lands every shared
    prefix on the replica already holding its pages, random scatters
    it and each replica re-prefills) and `aggregate_tok_s_scaling`
    (fleet tok/s over the 1-replica baseline — near N on
    N-device hosts, where replica compute genuinely overlaps)."""
    import numpy as np

    from megatron_llm_tpu.inference.engine import DecodeEngine
    from megatron_llm_tpu.inference.router import (
        EngineReplica,
        ReplicaRouter,
    )

    rs = np.random.RandomState(0)
    sysp = list(rs.randint(2, vocab_size, sys_prompt))
    uniq_every = max(int(round(1.0 / max(1.0 - shared_frac, 1e-9))), 1)
    work = []
    n_shared = 0
    for i in range(n_requests):
        if (i % uniq_every) != uniq_every - 1:
            work.append(sysp + list(rs.randint(2, vocab_size,
                                               uniq_suffix)))
            n_shared += 1
        else:
            work.append(list(rs.randint(2, vocab_size,
                                        sys_prompt + uniq_suffix)))
    devs = list(devices) if devices is not None else list(jax.devices())
    pct = DecodeEngine._pct

    def run_fleet(n, affinity, fallback):
        engines = []
        for i in range(n):
            eng = DecodeEngine(
                model, params, slots=slots, page_size=page_size,
                max_context=max_context, max_queue=n_requests,
                termination_id=None, vocab_size=vocab_size,
                prefill_chunk_tokens=chunk, prefix_cache=True,
                step_horizon=step_horizon, replica_id=i,
                devices=[devs[i % len(devs)]])
            # compile-warm off the clock; measured run starts with a
            # cold prefix cache (the first shared admission per
            # replica pays its full prefill honestly in-run)
            eng.warmup()
            eng.reset_prefix_cache()
            engines.append(eng)
        router = ReplicaRouter(
            [EngineReplica(e) for e in engines], affinity=affinity,
            fallback=fallback, rng_seed=1)
        router.start()
        t0 = time.perf_counter()
        reqs = [router.submit(p, gen, top_k=1) for p in work]
        for r in reqs:
            r.result(timeout=600.0)
        makespan = max(r.t_done for r in reqs) - t0
        ttfts = sorted((r.t_first - r.t_submit) * 1e3 for r in reqs)
        stats = router.router_stats()
        prefix_hits = sum(e.counters().get("serve_prefix_hits", 0)
                          for e in engines)
        prefill_tokens = sum(e.counters()["serve_prefill_tokens"]
                             for e in engines)
        router.stop(drain=True)
        return {
            "replicas": n,
            "affinity": affinity,
            "fallback": fallback,
            "aggregate_tok_s": round(n_requests * gen / makespan, 1),
            "ttft_p50_ms": round(pct(ttfts, 0.50), 2),
            "ttft_p95_ms": round(pct(ttfts, 0.95), 2),
            "affinity_hit_rate": stats["router_affinity_hit_rate"],
            "failovers": stats["router_failovers"],
            "per_replica_dispatches": stats[
                "router_per_replica_dispatches"],
            "prefix_hits": int(prefix_hits),
            "prefill_tokens": int(prefill_tokens),
        }

    aff = run_fleet(replicas, True, "least_loaded")
    rnd = run_fleet(replicas, False, "random")
    base = run_fleet(1, True, "least_loaded")
    return {
        "replicas": replicas,
        "n_requests": n_requests,
        "shared_requests": n_shared,
        "devices": [str(d) for d in devs[:replicas]],
        "affinity": aff,
        "random": rnd,
        "single_replica": base,
        "router_affinity_vs_random_ttft_p95": round(
            rnd["ttft_p95_ms"] / max(aff["ttft_p95_ms"], 1e-9), 2),
        "affinity_vs_random_prefill_tokens": round(
            rnd["prefill_tokens"] / max(aff["prefill_tokens"], 1), 2),
        "aggregate_tok_s_scaling": round(
            aff["aggregate_tok_s"]
            / max(base["aggregate_tok_s"], 1e-9), 2),
        "methodology": (
            f"identical greedy burst through the router 3 ways: "
            f"{replicas}-replica affinity (least-loaded fallback), "
            f"{replicas}-replica seeded-random dispatch (the control "
            f"arm), 1-replica baseline; {n_shared}/{n_requests} "
            f"requests = {sys_prompt}-token shared system prompt + "
            f"{uniq_suffix} unique tokens, the rest fully unique at "
            f"the same length; every replica an independent "
            f"prefix-cache engine pinned to its own device (devices "
            f"listed in-row — scaling is only meaningful where "
            f"replicas own distinct chips), compile-warmed off the "
            f"clock, prefix cache + router index cold at t0; TTFT = "
            f"submit -> first generated token via the replica serve "
            f"loops; aggregate tok/s = requested gen tokens / fleet "
            f"makespan; scaling = fleet tok/s over the 1-replica "
            f"baseline on the same workload"
        ),
    }


def serving_disagg_stats(model, params, *, slots=12, page_size=64,
                         max_context=896, chunk=128, vocab_size=32000,
                         n_long=4, n_short=8, long_prompt=640,
                         short_prompt=32, long_gen=4, short_gen=192,
                         step_horizon=8, devices=None):
    """The `extra.serving.disagg` harness (ISSUE 17): a disaggregated
    fleet (1 chunked-prefill replica handing finished KV pages to 1
    decode replica through the router's two-stage dispatch) vs a
    symmetric fleet of the SAME total replica count, on mixed traffic —
    short prompts with long generations (the decode-heavy class the
    interference hurts) interleaved with long prompts with short
    generations (the prefill-heavy class). Methodology (stated in
    the emitted row): every replica is an independent cost-registry
    prefix-cache engine pinned to its own device, compile-warmed off
    the clock with cold caches at t0; both fleets serve the identical
    greedy burst. Headlines: `disagg_vs_symmetric_ttft_p95` (> 1 means
    splitting the roles beat the symmetric fleet on the INTERACTIVE
    class's p95 TTFT — short prompts stop queueing behind batch
    prefills' remaining chunks, and TTFT for a handed-off request is
    prefill-stage completion since the donor's greedy token IS the
    first token), `disagg_vs_symmetric_tok_s` (aggregate tok/s at
    equal replica count — the decode replica runs fuller, cheaper
    decode batches), `batch_ttft_p95_ratio` (the prefill-heavy class's
    own TTFT ratio, honest about the cost: every batch prefill
    serializes through the single prefill replica), and
    `decode_interference_ratio` (symmetric decode-round p95 over the
    disagg decode replica's — the per-round interference the hand-off
    removes). The disagg run's routing decisions ride
    in-row (`router_decisions`): each records the modeled-FLOPs
    backlog snapshot it was made from, so placement is reproducible
    from the recorded cost model."""
    import numpy as np

    from megatron_llm_tpu.inference.engine import DecodeEngine
    from megatron_llm_tpu.inference.router import (
        EngineReplica,
        ReplicaRouter,
    )

    rs = np.random.RandomState(0)
    longs = [list(rs.randint(2, vocab_size, long_prompt))
             for _ in range(n_long)]
    shorts = [list(rs.randint(2, vocab_size, short_prompt))
              for _ in range(n_short)]
    # interleaved arrival order — the steady-state picture, not a cold
    # fleet: interactive (decode-heavy) requests keep landing between
    # batch (prefill-heavy) arrivals, so on a symmetric fleet a short
    # prompt can queue behind a long prefill's remaining chunks
    # (head-of-line blocking) and decode scans break on prefill
    # rounds — the two interference channels disaggregation removes
    work = []
    is_short = []
    si = li = 0
    while si < n_short or li < n_long:
        for _ in range(2):
            if si < n_short:
                work.append((shorts[si], short_gen))
                is_short.append(True)
                si += 1
        if li < n_long:
            work.append((longs[li], long_gen))
            is_short.append(False)
            li += 1
    gen_total = sum(g for _, g in work)
    devs = list(devices) if devices is not None else list(jax.devices())
    pct = DecodeEngine._pct

    def mk_engine(i):
        eng = DecodeEngine(
            model, params, slots=slots, page_size=page_size,
            max_context=max_context, max_queue=n_long + n_short,
            termination_id=None, vocab_size=vocab_size,
            prefill_chunk_tokens=chunk, prefix_cache=True,
            step_horizon=step_horizon, replica_id=i,
            devices=[devs[i % len(devs)]],
            cost_registry=True, chip_spec="v5e")
        # compile-warm off the clock; cold prefix cache at t0
        eng.warmup()
        eng.reset_prefix_cache()
        return eng

    def run(router, engines, decode_engines):
        router.start()
        t0 = time.perf_counter()
        reqs = [router.submit(p, g, top_k=1) for p, g in work]
        for r in reqs:
            r.result(timeout=600.0)
        makespan = max(r.t_done for r in reqs) - t0
        ttfts = sorted((r.t_first - r.t_submit) * 1e3 for r in reqs)
        short_ttfts = sorted((r.t_first - r.t_submit) * 1e3
                             for r, s in zip(reqs, is_short) if s)
        long_ttfts = sorted((r.t_first - r.t_submit) * 1e3
                            for r, s in zip(reqs, is_short) if not s)
        # decode interference: worst per-round decode p95 across the
        # replicas that serve the decode-heavy class
        decode_p95 = max(
            e.counters().get("serve_decode_p95_ms", 0.0)
            for e in decode_engines)
        stats = router.router_stats()
        decisions = router.decision_log()
        router.stop(drain=True)
        return {
            "replicas": len(engines),
            "aggregate_tok_s": round(gen_total / makespan, 1),
            "ttft_p50_ms": round(pct(ttfts, 0.50), 2),
            "ttft_p95_ms": round(pct(ttfts, 0.95), 2),
            "short_req_ttft_p95_ms": round(pct(short_ttfts, 0.95), 2),
            "long_req_ttft_p95_ms": round(pct(long_ttfts, 0.95), 2),
            "decode_p95_ms": round(decode_p95, 2),
            "transfer_pages": stats.get("serve_transfer_pages", 0),
            "transfer_ms": stats.get("serve_transfer_ms", 0.0),
            "prefill_replica_dispatches": stats.get(
                "serve_prefill_replica", 0),
            "per_replica_dispatches": stats[
                "router_per_replica_dispatches"],
        }, decisions

    # disaggregated: 1 prefill + 1 decode replica, two-stage dispatch
    d_engines = [mk_engine(0), mk_engine(1)]
    d_router = ReplicaRouter(
        prefill_replicas=[EngineReplica(d_engines[0])],
        decode_replicas=[EngineReplica(d_engines[1])],
        disagg_min_prompt_pages=max(2, (short_prompt // page_size) + 1),
        rng_seed=1)
    disagg, decisions = run(d_router, d_engines, d_engines[1:])

    # symmetric control arm: same replica count, every replica does both
    s_engines = [mk_engine(0), mk_engine(1)]
    s_router = ReplicaRouter(
        [EngineReplica(e) for e in s_engines], rng_seed=1)
    sym, _ = run(s_router, s_engines, s_engines)

    return {
        "n_long": n_long, "n_short": n_short,
        "long_prompt": long_prompt, "short_prompt": short_prompt,
        "long_gen": long_gen, "short_gen": short_gen,
        "devices": [str(d) for d in devs[:2]],
        "disagg": disagg,
        "symmetric": sym,
        # headline TTFT is the INTERACTIVE class's p95 — the class the
        # TTFT SLO applies to, and the one symmetric fleets hurt via
        # head-of-line blocking behind batch prefills. The batch
        # class's own TTFT ratio rides alongside (typically < 1: all
        # batch prefills serialize on the single prefill replica —
        # the GUIDE's "when the symmetric fleet wins" trade)
        "disagg_vs_symmetric_ttft_p95": round(
            sym["short_req_ttft_p95_ms"]
            / max(disagg["short_req_ttft_p95_ms"], 1e-9), 2),
        "batch_ttft_p95_ratio": round(
            sym["long_req_ttft_p95_ms"]
            / max(disagg["long_req_ttft_p95_ms"], 1e-9), 2),
        "disagg_vs_symmetric_tok_s": round(
            disagg["aggregate_tok_s"]
            / max(sym["aggregate_tok_s"], 1e-9), 2),
        "decode_interference_ratio": round(
            sym["decode_p95_ms"] / max(disagg["decode_p95_ms"], 1e-9),
            2),
        "router_decisions": decisions,
        "methodology": (
            f"identical greedy burst through two fleets at equal "
            f"replica count: disaggregated (1 chunked-prefill replica "
            f"-> jitted page export/import hand-off -> 1 decode "
            f"replica, two-stage router dispatch, placement by "
            f"modeled-FLOPs backlog from the cost registry) vs "
            f"symmetric (2 replicas, affinity router); traffic = "
            f"{n_short} x {short_prompt}-token prompts generating "
            f"{short_gen} (decode-heavy interactive) interleaved 2:1 "
            f"with {n_long} x {long_prompt}-token prompts generating "
            f"{long_gen} (prefill-heavy batch), modeling steady-state "
            f"mixed arrivals; every replica an independent "
            f"cost-registry prefix-cache engine pinned to its own "
            f"device (listed in-row), compile-warmed off the clock, "
            f"caches cold at t0; TTFT = submit -> first generated "
            f"token (for a handed-off greedy request that is "
            f"prefill-stage completion: the donor's 1-token run "
            f"produces the continuation's first token and the decode "
            f"replica regenerates it bitwise-identically); headline "
            f"TTFT ratio is the interactive class's p95 (the class "
            f"with a TTFT SLO), batch_ttft_p95_ratio reports the "
            f"batch class's own (serialized through the single "
            f"prefill replica, typically < 1); aggregate tok/s = "
            f"requested gen tokens / fleet makespan; decode p95 = "
            f"worst per-round decode-advance p95 over the "
            f"decode-serving replicas (the interference gauge); "
            f"router_decisions records each placement with the "
            f"modeled backlog snapshot it was derived from"
        ),
    }


def quant_paged_op_stats(slots=8, T=512, page_size=64):
    """Decode-row traffic (width-1 chunks at the slot tail) through THE
    ragged paged attention entry point, bf16 vs int8 pools at the SAME
    traffic (same slots, same per-slot lengths, same page tables):
    per-call time, decode-HBM bytes/token per dtype (derived from the
    ACTUAL pool dtypes, never hard-coded), and achieved GB/s for both —
    the kernel-level half of the `extra.quant` row. On TPU the int8 row
    should show ~the same wall time at ~half the bytes (the kernel is
    bandwidth-bound), i.e. honest GB/s near parity and bytes/token
    halved."""
    from megatron_llm_tpu.ops.prefill_attention import (
        ragged_paged_attention,
    )
    from megatron_llm_tpu.ops.quantization import quantize_rows

    import numpy as np

    cfg = make_cfg(1024)
    g, qpk, d = cfg.num_query_groups, cfg.q_per_kv, cfg.head_dim
    mp = T // page_size
    num_pages = 1 + slots * mp
    ks = jax.random.split(jax.random.key(0), 5)
    q = jax.random.normal(ks[0], (slots, 1, g, qpk, d), jnp.bfloat16)
    kn = jax.random.normal(ks[3], (slots, 1, g, d), jnp.bfloat16)
    vn = jax.random.normal(ks[4], (slots, 1, g, d), jnp.bfloat16)
    kpf = jax.random.normal(ks[1], (num_pages, page_size, g, d),
                            jnp.bfloat16)
    vpf = jax.random.normal(ks[2], (num_pages, page_size, g, d),
                            jnp.bfloat16)
    rs = np.random.RandomState(0)
    pt = jnp.asarray((rs.permutation(num_pages - 1) + 1)
                     .reshape(slots, mp), jnp.int32)
    # decode rows at the slot tail: start = T - 1, width 1 (the engine's
    # decode-scan shape since the kernel unification)
    starts = jnp.full((slots,), T - 1, jnp.int32)
    ones = jnp.ones((slots,), jnp.int32)

    t_bf16 = _timed_scan(
        lambda q, kp, vp: ragged_paged_attention(
            q, kn, vn, kp, vp, pt, starts, ones)[0],
        (q, kpf, vpf))
    kq, ksc = quantize_rows(kpf)
    vq, vsc = quantize_rows(vpf)
    t_int8 = _timed_scan(
        lambda q, kp, vp, ksx, vsx: ragged_paged_attention(
            q, kn, vn, kp, vp, pt, starts, ones,
            k_scales=ksx, v_scales=vsx)[0],
        (q, kq, vq, ksc, vsc))
    # cache bytes one call actually streams, from the pool dtypes
    bpt_bf16 = 2 * g * d * kpf.dtype.itemsize
    bpt_int8 = 2 * g * (d * kq.dtype.itemsize + ksc.dtype.itemsize)
    return {
        "slots": slots, "tokens_per_slot": T,
        "paged_attn_us_bf16": round(t_bf16 * 1e6, 2),
        "paged_attn_us_int8": round(t_int8 * 1e6, 2),
        "cache_bytes_per_token_bf16": bpt_bf16,
        "cache_bytes_per_token_int8": bpt_int8,
        "cache_bytes_per_token_reduction": round(
            1.0 - bpt_int8 / bpt_bf16, 4),
        "paged_attn_gbps_bf16": round(
            slots * T * bpt_bf16 / t_bf16 / 1e9, 1),
        "paged_attn_gbps_int8": round(
            slots * T * bpt_int8 / t_int8 / 1e9, 1),
    }


def quant_serving_stats(model, params, *, slots=4, page_size=64,
                        max_context=640, vocab_size=32000, n_requests=8,
                        prompt_len=192, gen=64, chunk=128):
    """The engine half of `extra.quant` (ISSUE 9): bf16 vs int8-KV vs
    int8-KV + weight-only-int8 engines on IDENTICAL greedy traffic.
    Methodology (stated in the emitted row): same prompts, same budget,
    all engines chunked and compile-warmed off the clock; decode tok/s
    comes from the engine's own round log restricted to pure decode
    rounds (prefill rounds excluded, so the ratio isolates the
    bandwidth win); accuracy is max |Δ logprob| against the bf16 run
    over the TEACHER-FORCED prompt positions of the fixed prompt set —
    generated positions diverge with the stream, prompt positions score
    the same context — plus the fraction of requests whose greedy
    token streams match bitwise."""
    import numpy as np

    from megatron_llm_tpu.inference.engine import DecodeEngine

    rs = np.random.RandomState(0)
    prompts = [list(rs.randint(2, vocab_size, prompt_len))
               for _ in range(n_requests)]
    modes = (("bf16", "bf16", False), ("int8", "int8", False),
             ("int8_w", "int8", True))
    rows, lps, toks = {}, {}, {}
    for mode, kv, qw in modes:
        eng = DecodeEngine(
            model, params, slots=slots, page_size=page_size,
            max_context=max_context, max_queue=n_requests,
            termination_id=None, vocab_size=vocab_size,
            prefill_chunk_tokens=chunk, kv_dtype=kv,
            quantize_weights=qw)
        eng.submit(prompts[0], 2, top_k=1)
        eng.drain()
        eng.warmup()
        with eng._lock:
            eng._round_log.clear()
        t0 = time.perf_counter()
        reqs = [eng.submit(p, gen, top_k=1, return_log_probs=True)
                for p in prompts]
        eng.drain()
        makespan = max(r.t_done for r in reqs) - t0
        with eng._lock:
            log = list(eng._round_log)
        dec_tok = sum(r["decode_slots"] * r["decode_steps"]
                      for r in log if not r["prefill_tokens"])
        dec_ms = sum(r["ms"] for r in log if not r["prefill_tokens"])
        outs = [r.result() for r in reqs]
        lps[mode] = [lp[:prompt_len - 1] for _, lp in outs]
        toks[mode] = [t for t, _ in outs]
        rows[mode] = {
            "tok_s": round(n_requests * gen / makespan, 1),
            "decode_tok_s": round(dec_tok / max(dec_ms / 1e3, 1e-9), 1),
            "kv_bytes_per_token": eng.kv_bytes_per_token(),
            "kv_pool_bytes": eng.kv_pool_bytes(),
        }
    for mode in ("int8", "int8_w"):
        rows[mode]["max_prompt_logprob_drift_vs_bf16"] = round(max(
            abs(a - b)
            for ref, got in zip(lps["bf16"], lps[mode])
            for a, b in zip(ref, got)), 5)
        rows[mode]["greedy_token_match_frac"] = round(sum(
            t1 == t2 for t1, t2 in zip(toks["bf16"], toks[mode])
        ) / n_requests, 3)
    bpt_bf16 = rows["bf16"]["kv_bytes_per_token"]
    bpt_int8 = rows["int8"]["kv_bytes_per_token"]
    capacity = bpt_bf16 / bpt_int8
    return {
        "requests": n_requests, "prompt_len": prompt_len, "gen": gen,
        "slots": slots,
        "bf16": rows["bf16"], "int8": rows["int8"],
        "int8_w": rows["int8_w"],
        "int8_vs_bf16_decode_tok_s": round(
            rows["int8"]["decode_tok_s"]
            / max(rows["bf16"]["decode_tok_s"], 1e-9), 2),
        "int8_w_vs_bf16_decode_tok_s": round(
            rows["int8_w"]["decode_tok_s"]
            / max(rows["bf16"]["decode_tok_s"], 1e-9), 2),
        # pages-per-HBM-byte multiple AND its slot-count reading: the
        # SAME pool bytes hold capacity x the max_context slots
        "kv_capacity_ratio": round(capacity, 2),
        "tokens_per_gib_bf16": int(2**30 // bpt_bf16),
        "tokens_per_gib_int8": int(2**30 // bpt_int8),
        "max_context_slots_per_bf16_pool": slots,
        "max_context_slots_per_bf16_pool_at_int8": int(
            rows["bf16"]["kv_pool_bytes"]
            // (bpt_int8 * max_context)),
        "methodology": (
            "identical greedy traffic all three engines (same prompts/"
            "budgets, chunked, compile-warmed off the clock); decode "
            "tok/s = decode-round tokens / decode-round wall from the "
            "engine round log (prefill rounds excluded); drift = max "
            "|Δ logprob| vs the bf16 run over teacher-forced PROMPT "
            "positions of the fixed prompt set (generated positions "
            "follow their own stream); token match = fraction of "
            "requests with bitwise-equal greedy streams; bytes/token "
            "derived from the live pool arrays (data + scales)"
        ),
    }


def run_quant(slots=8):
    """bench-model `extra.quant` row (ISSUE 9): the int8-KV capacity
    and bandwidth claims measured, with the accuracy drift bound stated
    in the same row."""
    import dataclasses

    cfg = dataclasses.replace(make_cfg(1024), params_dtype=jnp.bfloat16)
    model = LlamaModel(cfg)
    params = model.init(jax.random.key(0))
    out = quant_serving_stats(model, params, slots=slots)
    out["paged_attn_op"] = quant_paged_op_stats(slots=slots)
    return out


def kernel_unify_stats(model, params, *, slots=4, page_size=16,
                       max_context=96, vocab_size=256, n_requests=4,
                       prompt_len=24, gen=8, chunk=8, op_T=256,
                       op_page_size=16):
    """The `extra.kernel_unify` row (ISSUE 18): THE ragged paged
    attention kernel vs the pre-unification two-executable shape at
    IDENTICAL traffic.

    Op level: the pre-unification decode round launched TWO executables
    — a standalone KV scatter, then an attend-only paged kernel reading
    the pools the scatter just wrote. The unified entry fuses both into
    one launch. The split shape is EMULATED here (the old kernels are
    deleted) as jit(scatter) + jit(unified op on the pre-written pools):
    the second launch's re-scatter writes the same rows to the same
    [page, offset] — bitwise idempotent — so the in-row assert that
    split == fused (output AND pools, exact) holds by construction and
    the split timing is a floor on the two-launch cost. GB/s is reported
    for BOTH phases through the one kernel — decode rows (width-1
    chunks) and ragged prefill chunks — at the same pool, because "one
    kernel serves both" is the claim.

    Engine level: decode tok/s from the round log of an engine on the
    unified path, compile-warmed by a priming pass of the identical
    traffic (prefill rounds excluded from the timed log). There is no
    pre-unification engine to race — bitwise stream parity old vs new
    was pinned by the parity suites before the fork was deleted.

    Executable inventory: public paged entry points counted by the same
    AST walk as the tier-1 guard (tests/test_static_analysis.py); the
    pre-unification count (2 builders — paged decode + ragged prefill —
    each forking per kv dtype at trace time) is a historical constant.
    """
    import ast
    import os

    import numpy as np

    from megatron_llm_tpu import ops as ops_pkg
    from megatron_llm_tpu.inference.engine import DecodeEngine
    from megatron_llm_tpu.ops.prefill_attention import (
        ragged_paged_attention,
        scatter_chunk_kv,
    )

    cfg = model.cfg
    g, qpk, d = cfg.num_query_groups, cfg.q_per_kv, cfg.head_dim
    mp = op_T // op_page_size
    num_pages = 1 + slots * mp
    ks = jax.random.split(jax.random.key(0), 5)
    kpf = jax.random.normal(ks[1], (num_pages, op_page_size, g, d),
                            jnp.bfloat16)
    vpf = jax.random.normal(ks[2], (num_pages, op_page_size, g, d),
                            jnp.bfloat16)
    rs = np.random.RandomState(0)
    pt = jnp.asarray((rs.permutation(num_pages - 1) + 1)
                     .reshape(slots, mp), jnp.int32)
    bpt = 2 * g * d * kpf.dtype.itemsize  # K + V bytes per kv token

    # --- decode-row traffic: fused vs emulated split, bitwise ---
    q1 = jax.random.normal(ks[0], (slots, 1, g, qpk, d), jnp.bfloat16)
    kn1 = jax.random.normal(ks[3], (slots, 1, g, d), jnp.bfloat16)
    vn1 = jax.random.normal(ks[4], (slots, 1, g, d), jnp.bfloat16)
    starts1 = jnp.full((slots,), op_T - 1, jnp.int32)
    ones = jnp.ones((slots,), jnp.int32)

    fused = jax.jit(lambda q, kn, vn, kp, vp: ragged_paged_attention(
        q, kn, vn, kp, vp, pt, starts1, ones))
    split_scatter = jax.jit(lambda kn, vn, kp, vp: scatter_chunk_kv(
        kn, vn, kp, vp, pt, starts1, ones))
    out_f, kp_f, vp_f = fused(q1, kn1, vn1, kpf, vpf)
    kp_s, vp_s = split_scatter(kn1, vn1, kpf, vpf)
    out_s, kp_s, vp_s = fused(q1, kn1, vn1, kp_s, vp_s)
    assert (np.asarray(out_f) == np.asarray(out_s)).all()
    assert (np.asarray(kp_f) == np.asarray(kp_s)).all()
    assert (np.asarray(vp_f) == np.asarray(vp_s)).all()

    t_fused = _timed_scan(
        lambda q, kp, vp: fused(q, kn1, vn1, kp, vp)[0], (q1, kpf, vpf))
    t_split = _timed_scan(
        lambda q, kp, vp: fused(
            q, kn1, vn1,
            *split_scatter(kn1, vn1, kp, vp))[0], (q1, kpf, vpf))

    # --- ragged-chunk traffic through the SAME entry, same pool ---
    C = 8
    qc = jax.random.normal(ks[0], (slots, C, g, qpk, d), jnp.bfloat16)
    knc = jax.random.normal(ks[3], (slots, C, g, d), jnp.bfloat16)
    vnc = jax.random.normal(ks[4], (slots, C, g, d), jnp.bfloat16)
    startsc = jnp.asarray(
        rs.randint(0, op_T - C, slots).astype(np.int32))
    lensc = jnp.full((slots,), C, jnp.int32)
    t_chunk = _timed_scan(
        lambda q, kp, vp: ragged_paged_attention(
            q, knc, vnc, kp, vp, pt, startsc, lensc)[0], (qc, kpf, vpf))
    kv_read_decode = slots * op_T  # each decode row streams its history
    kv_read_chunk = int(np.asarray(startsc + lensc).sum())

    # --- engine decode tok/s on the unified path ---
    eng = DecodeEngine(
        model, params, slots=slots, page_size=page_size,
        max_context=max_context, max_queue=n_requests,
        termination_id=None, vocab_size=vocab_size,
        prefill_chunk_tokens=chunk)
    prompts = [list(rs.randint(2, vocab_size, prompt_len))
               for _ in range(n_requests)]
    # Prime with IDENTICAL traffic instead of a full warmup(): the timed
    # pass reuses exactly these prefill-chunk/decode buckets, so every
    # executable it runs is already minted (warmup would also compile
    # buckets this harness never times).
    prime = [eng.submit(p, gen, top_k=1) for p in prompts]
    eng.drain()
    _ = [r.result() for r in prime]
    with eng._lock:
        eng._round_log.clear()
    reqs = [eng.submit(p, gen, top_k=1) for p in prompts]
    eng.drain()
    with eng._lock:
        log = list(eng._round_log)
    dec_tok = sum(r["decode_slots"] * r["decode_steps"]
                  for r in log if not r["prefill_tokens"])
    dec_ms = sum(r["ms"] for r in log if not r["prefill_tokens"])
    _ = [r.result() for r in reqs]

    # --- executable inventory: the guard's AST walk, run live ---
    ops_dir = os.path.dirname(ops_pkg.__file__)
    entries = []
    for fname in sorted(os.listdir(ops_dir)):
        if not fname.endswith(".py"):
            continue
        with open(os.path.join(ops_dir, fname), encoding="utf-8") as fh:
            tree = ast.parse(fh.read(), filename=fname)
        entries += [
            n.name for n in tree.body
            if isinstance(n, ast.FunctionDef)
            and not n.name.startswith("_") and "paged" in n.name
            and ("attention" in n.name or "prefill" in n.name
                 or "decode" in n.name)]
    assert entries == ["ragged_paged_attention"], entries

    return {
        "slots": slots, "tokens_per_slot": op_T,
        "unified_decode_us": round(t_fused * 1e6, 2),
        "split_scatter_plus_attend_us": round(t_split * 1e6, 2),
        "fused_vs_split_time_ratio": round(t_fused / t_split, 3),
        "unified_decode_gbps": round(
            kv_read_decode * bpt / t_fused / 1e9, 1),
        "unified_chunk_gbps": round(
            kv_read_chunk * bpt / t_chunk / 1e9, 1),
        "split_equals_fused_bitwise": True,  # asserted above
        "engine_decode_tok_s": round(dec_tok / max(dec_ms / 1e3, 1e-9),
                                     1),
        "paged_entry_points": len(entries),
        "paged_entry_points_pre_unification": 2,
        "methodology": (
            "split shape emulated as jit(scatter) + jit(unified op on "
            "the pre-written pools) — the second launch's re-scatter is "
            "bitwise idempotent, so split == fused is asserted exactly "
            "(output and pools) and the split time is a floor on the "
            "historical two-launch cost; GB/s = KV tokens streamed x "
            "(K+V bytes/token from the live pool dtype) / wall, decode "
            "traffic streams each slot's full history, chunk traffic "
            "streams start+len per slot; engine decode tok/s = "
            "decode-round tokens / decode-round wall from the round "
            "log (compile-warmed by a priming pass of the identical "
            "traffic; prefill rounds excluded); on a CPU harness the op "
            "dispatches to the XLA twin, so timings are path-level, "
            "not kernel-level — kernel numbers are the TPU artifact "
            "run's; entry-point count from a live AST walk of ops/ "
            "(the tier-1 guard's definition), pre-unification count = "
            "the 2 deleted builders"
        ),
    }


def run_kernel_unify(slots=8):
    """bench-model `extra.kernel_unify` row (ISSUE 18)."""
    import dataclasses

    cfg = dataclasses.replace(make_cfg(1024), params_dtype=jnp.bfloat16)
    model = LlamaModel(cfg)
    params = model.init(jax.random.key(0))
    return kernel_unify_stats(
        model, params, slots=slots, page_size=64, max_context=640,
        vocab_size=32000, n_requests=slots, prompt_len=192, gen=64,
        chunk=128, op_T=512, op_page_size=64)


def longcontext_stats(model, params, *, window, slots=2, page_size=16,
                      max_context=192, page_budget=None,
                      dense_page_budget=None, vocab_size=256,
                      long_prompt=24, long_gen=72, short_prompt=8,
                      short_gen=8, n_short=3, chunk=None):
    """The `extra.serving.longcontext` row (ISSUE 19): sliding-window
    serving vs dense on mixed long + short traffic.

    Three engines off ONE param init: DENSE (no window, full page
    reservation — the pre-window cost model), WINDOWED with
    out-of-window page reclamation ON (the fast path: admission prices
    O(window) pages, the frontier tops up lazily, pages wholly behind
    every live window recycle mid-flight), and the same windowed engine
    with reclamation OFF (mask-only) as the in-row control — greedy
    token streams AND logprobs are asserted BITWISE on == off, because
    the clamped kernel never reads a reclaimed page by construction.
    The windowed engine runs inside `page_budget` (a pool the dense
    engine's reservation could NOT serve the same mix through); the
    dense engine gets the full reservation so the comparison is
    fast-path-in-a-small-pool vs old-path-in-a-big-pool.

    Capacity columns are LIVE: peak pages per slot sampled from the
    slot frontiers (mapped - reclaimed) while the traffic drains, the
    reclaim counter from the engine, and the admission bound from
    `_window_slot_pages`. Decode KV read bytes/token is MODELED from
    the kernel's double-ended page clamp (pages touched at length L =
    L//ps - max(0, L - W + 1)//ps + 1; dense reads every page) times
    the live pool's bytes/token — the DMA grid skips out-of-window
    pages wholly, so the model IS the kernel's read set; wall-clock
    kernel numbers are the TPU artifact run's, this harness also runs
    on the CPU XLA twin in tier-1 (tests/test_window_serving.py).
    """
    import dataclasses
    import threading

    import numpy as np

    from megatron_llm_tpu.inference.engine import DecodeEngine

    chunk = chunk or page_size
    # one long-context-capable config family, one init: params are
    # window- and length-independent (rotary tables come from the
    # config at call time), so every engine below shares `params` and
    # stream diffs isolate the window machinery alone.
    pos = max(model.cfg.max_position_embeddings, max_context)
    base_cfg = dataclasses.replace(
        model.cfg, max_position_embeddings=pos,
        seq_length=max(model.cfg.seq_length, max_context))
    dense_model = type(model)(base_cfg)
    win_model = type(model)(dataclasses.replace(
        base_cfg, attention_window_size=window))

    rs = np.random.RandomState(0)
    long_spec = (list(rs.randint(2, vocab_size, long_prompt)), long_gen)
    specs = [long_spec] + [
        (list(rs.randint(2, vocab_size, short_prompt)), short_gen)
        for _ in range(n_short)]

    def run(eng):
        """Drain the mix; return (streams, peak live pages per slot)."""
        reqs = [eng.submit(list(p), g, top_k=1, return_log_probs=True)
                for p, g in specs]
        peak = 0
        done = threading.Event()

        def sample():
            nonlocal peak
            while not done.is_set():
                live = max((s.mapped - s.reclaimed)
                           for s in eng._slots)
                peak = max(peak, live)
                done.wait(0.001)

        th = threading.Thread(target=sample, daemon=True)
        th.start()
        try:
            eng.drain()
        finally:
            done.set()
            th.join()
        return [r.result(300) for r in reqs], peak

    def build(mdl, **over):
        kw = dict(slots=slots, page_size=page_size,
                  max_context=max_context, prefill_chunk_tokens=chunk,
                  vocab_size=vocab_size, termination_id=None)
        kw.update(over)
        return DecodeEngine(mdl, params, **kw)

    # engines run SEQUENTIALLY and release their pools before the next
    # one allocates — at bench scale two full-reservation pools do not
    # coexist in HBM.
    dense = build(dense_model, page_budget=dense_page_budget)
    _, dense_peak = run(dense)
    dense_pool = dense.num_pages - 1
    dense.stop()
    del dense

    win = build(win_model, page_budget=page_budget)
    win_streams, win_peak = run(win)
    bpt = win.kv_bytes_per_token()
    win_pool = win.num_pages - 1
    win_bound = win._window_slot_pages()
    win_reclaimed = win._window_reclaimed
    c = win.counters()
    win.stop()
    del win

    # mask-only control: same window math, no reclamation — it prices
    # the FULL reach at admission, so it runs in the dense engine's
    # reservation (that is the point: without reclamation the small
    # pool is not serviceable).
    mask_only = build(win_model, window_reclaim=False,
                      page_budget=dense_page_budget)
    off_streams, _ = run(mask_only)
    mask_only.stop()
    del mask_only
    assert win_streams == off_streams  # tokens AND float-exact logprobs

    def read_bytes_per_token(w):
        tot = 0
        for L in range(long_prompt, long_prompt + long_gen):
            last = L // page_size
            first = max(0, L - w + 1) // page_size if w else 0
            tot += (last - first + 1) * page_size * bpt
        return tot / long_gen

    return {
        "window_tokens": window,
        "long_context_tokens": long_prompt + long_gen,
        "short_requests": n_short,
        "window_pool_pages": win_pool,
        "dense_pool_pages": dense_pool,
        "window_page_bound_per_slot": win_bound,
        "window_peak_pages_per_long_slot": win_peak,
        "dense_peak_pages_per_long_slot": dense_peak,
        "window_reclaimed_pages": win_reclaimed,
        "streams_bitwise_vs_mask_only": True,  # asserted above
        "window_decode_read_bytes_per_token": round(
            read_bytes_per_token(window), 1),
        "dense_decode_read_bytes_per_token": round(
            read_bytes_per_token(None), 1),
        "decode_read_reduction": round(
            read_bytes_per_token(None) / read_bytes_per_token(window),
            2),
        "window_ttft_p95_ms": c["serve_ttft_p95_ms"],
        "kv_bytes_per_token": bpt,
        "methodology": (
            "three engines, one init: dense (full page reservation), "
            "windowed + reclamation in a page_budget pool the dense "
            "reservation could not serve, and windowed mask-only "
            "(reclamation off) as the control — greedy streams and "
            "logprobs asserted bitwise reclaim-on == mask-only in-row; "
            "peak pages/slot sampled live from the slot frontiers "
            "(mapped - reclaimed) while the mix drains; decode KV read "
            "bytes/token modeled from the kernel's double-ended page "
            "clamp (the DMA grid's exact read set) x live-pool "
            "bytes/token, averaged over the long stream's decode "
            "positions; on a CPU harness the engines run the XLA twin, "
            "so byte and page columns are exact and wall-clock kernel "
            "numbers are the TPU artifact run's"
        ),
    }


def run_longcontext(model, params):
    """bench-model `extra.serving.longcontext` row (ISSUE 19): a 12k-
    token stream decoding through a 2k window in a pool sized well
    under its full reach, plus short interactive traffic."""
    return longcontext_stats(
        model, params, window=2048, slots=4, page_size=64,
        max_context=16384, page_budget=4 * 4096,
        dense_page_budget=16384, vocab_size=32000,
        long_prompt=12288, long_gen=256, short_prompt=128,
        short_gen=64, chunk=512)


def serving_autonomy_stats(model, params, *, replicas=2, slots=2,
                           page_size=64, max_context=512, chunk=128,
                           vocab_size=32000, n_requests=16,
                           prompt_len=64, gen=32, kill_after=2,
                           step_horizon=8, devices=None):
    """The `extra.serving.autonomy` harness (ISSUE 20): the ROADMAP
    acceptance headline for the self-driving fleet. The SAME greedy
    burst runs twice through an N-replica recover_requests router
    under a FleetController: once clean (the oracle: per-request token
    streams + fleet tok/s), once with a seeded ChaosPolicy killing
    replica 0 mid-traffic through the engine's real poison path. The
    controller condemns, drains, rebuilds a warmed replacement on the
    dead replica's device and rotates it back in; the router's
    recovery proxies transparently resubmit the dead replica's queued
    and un-streamed requests. Headlines: `failed_requests` (the zero-
    failed-request bar — every request of the chaos run must return),
    `bitwise_resubmits_match` (every chaos-run token stream equals the
    no-chaos oracle's: greedy determinism makes the retry bitwise),
    `recovery_s` (condemn -> replacement back in rotation, from the
    controller's replace event), and `convergence_tok_s_ratio` (chaos-
    run fleet tok/s over the clean run's — the fleet converging back
    to baseline throughput)."""
    import numpy as np

    from megatron_llm_tpu.inference.chaos import ChaosPolicy
    from megatron_llm_tpu.inference.engine import DecodeEngine
    from megatron_llm_tpu.inference.fleet import FleetController
    from megatron_llm_tpu.inference.router import (
        EngineReplica,
        ReplicaRouter,
    )

    rs = np.random.RandomState(0)
    work = [list(rs.randint(2, vocab_size, prompt_len))
            for _ in range(n_requests)]
    devs = list(devices) if devices is not None else list(jax.devices())

    def build_engine(i):
        return DecodeEngine(
            model, params, slots=slots, page_size=page_size,
            max_context=max_context, max_queue=n_requests,
            termination_id=None, vocab_size=vocab_size,
            prefill_chunk_tokens=chunk, prefix_cache=True,
            step_horizon=step_horizon, replica_id=i,
            devices=[devs[i % len(devs)]])

    def run_burst(chaos):
        engines = [build_engine(i) for i in range(replicas)]
        for e in engines:
            e.warmup()
            e.reset_prefix_cache()
        router = ReplicaRouter(
            [EngineReplica(e, chaos=chaos) for e in engines],
            recover_requests=True, unhealthy_cooldown_s=60.0)
        ctl = FleetController(
            router, check_interval_s=0.05, drain_timeout_s=5.0,
            spawn_replica=lambda old: EngineReplica(
                build_engine(old.replica_id)))
        router.start()
        ctl.start()
        t0 = time.perf_counter()
        reqs = [router.submit(p, gen, top_k=1) for p in work]
        streams, failures = [], []
        for i, r in enumerate(reqs):
            try:
                toks, _ = r.result(timeout=600.0)
                streams.append(list(toks))
            except Exception as e:  # noqa: BLE001 — the headline counts
                streams.append(None)
                failures.append(f"request {i}: {e!r}")
        makespan = time.perf_counter() - t0
        if chaos is not None:
            # the burst usually outruns the replace cycle (building +
            # warming the replacement engine takes seconds): wait,
            # bounded, for the replacement to rotate back in so the
            # recovery_s / fleet_replaced headlines reflect the full
            # condemn -> back-in-rotation cycle
            deadline = time.perf_counter() + 120.0
            while (router.router_stats().get(
                    "serve_fleet_replaced", 0) < 1
                   and time.perf_counter() < deadline):
                time.sleep(0.1)
        stats = router.router_stats()
        events = ctl.flight_events()
        ctl.stop()
        router.stop(drain=True)
        return {
            "streams": streams, "failures": failures,
            "tok_s": round(n_requests * gen / makespan, 1),
            "resubmitted": stats.get("serve_resubmitted", 0),
            "replaced": stats.get("serve_fleet_replaced", 0),
            "evictions": router.evictions(),
            "events": events,
        }

    clean = run_burst(None)
    chaos = run_burst(ChaosPolicy(seed=0, kill_replica=0,
                                  kill_after_submits=kill_after))
    replace_evs = [e for e in chaos["events"] if e["kind"] == "replace"]
    recovery_s = max((e.get("recovery_s", 0.0) for e in replace_evs),
                     default=None)
    bitwise = (None not in chaos["streams"]
               and chaos["streams"] == clean["streams"])
    return {
        "replicas": replicas,
        "n_requests": n_requests,
        "devices": [str(d) for d in devs[:replicas]],
        "failed_requests": len(chaos["failures"]),
        "failures": chaos["failures"][:4],
        "resubmitted": int(chaos["resubmitted"]),
        "fleet_replaced": int(chaos["replaced"]),
        "recovery_s": recovery_s,
        "bitwise_resubmits_match": bool(bitwise),
        "tok_s_clean": clean["tok_s"],
        "tok_s_chaos": chaos["tok_s"],
        "convergence_tok_s_ratio": round(
            chaos["tok_s"] / max(clean["tok_s"], 1e-9), 3),
        "eviction_flight_dumps": [
            e.get("flight_dump") for e in chaos["evictions"]][:4],
        "methodology": (
            f"identical greedy burst ({n_requests} x {prompt_len}-token "
            f"prompts, {gen} generated) through a {replicas}-replica "
            f"recover_requests router under a FleetController, twice: "
            f"clean (the oracle) and with a seeded ChaosPolicy killing "
            f"replica 0 after {kill_after} accepted submits via the "
            f"engine's real serve-loop poison path; the controller "
            f"condemns, drains, rebuilds + warms a replacement on the "
            f"freed device and rotates it back in while the router's "
            f"recovery proxies resubmit the dead replica's queued/"
            f"un-streamed requests; failed_requests counts chaos-run "
            f"requests that raised, bitwise_resubmits_match compares "
            f"every chaos-run token stream to the oracle's, recovery_s "
            f"is condemn -> back-in-rotation from the controller's "
            f"replace event, convergence = chaos-run fleet tok/s over "
            f"clean"
        ),
    }


def run_serving(n_requests=16, slots=8):
    """bench-model serving row (bf16 decode weights, decode kernel on):
    the ISSUE-3 continuous-vs-static comparison, the ISSUE-4
    long-prompt-admission interference audit, and the ISSUE-6
    shared-system-prompt prefix-sharing comparison."""
    import dataclasses

    cfg = dataclasses.replace(make_cfg(1024), params_dtype=jnp.bfloat16)
    model = LlamaModel(cfg)
    params = model.init(jax.random.key(0))
    work, arrivals = make_serving_workload(n_requests)
    stats = serving_stats(model, params, work, arrivals, slots=slots)
    stats["interference"] = serving_interference_stats(model, params)
    stats["prefix"] = serving_prefix_stats(model, params)
    stats["scaleout"] = serving_scaleout_stats(model, params)
    stats["disagg"] = serving_disagg_stats(model, params)
    stats["longcontext"] = run_longcontext(model, params)
    stats["autonomy"] = serving_autonomy_stats(model, params)
    return stats


def ckpt_stall_stats(model_cfg, params, opt_state, base_dir, n_saves=3):
    """Sync-vs-async checkpoint stall (ISSUE 5): how long the train loop
    is BLOCKED per checkpoint with the synchronous path (full
    write-and-commit wall time) vs the CheckpointManager async path
    (device→host copy only; commits land on a background thread between
    save intervals — each measured save first waits out the previous
    commit OFF the clock, exactly like a real save_interval's worth of
    compute would). Also exercises keep_latest_n GC and certifies the
    async checkpoint restores byte-identically. CPU-testable harness:
    bench calls it with the bench model, tests with a tiny one
    (tests/test_fault_tolerance.py)."""
    import os
    import shutil

    import numpy as np

    from megatron_llm_tpu.training.checkpointing import (
        CheckpointManager,
        is_checkpoint_complete,
        load_checkpoint,
        save_checkpoint,
    )

    ckpt_bytes = sum(
        int(np.prod(l.shape)) * l.dtype.itemsize
        for tree in (params, opt_state.m, opt_state.v)
        if tree is not None
        for l in jax.tree.leaves(tree)
    )
    sync_dir = os.path.join(base_dir, "sync")
    async_dir = os.path.join(base_dir, "async")
    try:
        t0 = time.perf_counter()
        save_checkpoint(sync_dir, 1, params, opt_state, model_cfg)
        sync_ms = (time.perf_counter() - t0) * 1e3

        mgr = CheckpointManager(async_dir, keep_latest_n=1)
        blocked = []
        for i in range(1, n_saves + 1):
            mgr.save(i, params, opt_state, model_cfg)
            blocked.append(mgr.last_blocked_ms)
            # the commit finishes during the next save_interval's
            # compute in a real run: wait it out off the clock
            mgr.wait_until_finished()
        async_blocked_ms = sorted(blocked)[len(blocked) // 2]
        last = os.path.join(async_dir, f"iter_{n_saves:07d}")
        assert is_checkpoint_complete(last), last
        restored = load_checkpoint(async_dir, params, opt_state, model_cfg)
        assert restored is not None and restored[3] == n_saves
        for a, b in zip(jax.tree.leaves(params),
                        jax.tree.leaves(restored[0])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        # keep_latest_n=1 GC: only the newest iter dir survives
        survivors = [d for d in os.listdir(async_dir)
                     if d.startswith("iter_")]
        assert survivors == [f"iter_{n_saves:07d}"], survivors
    finally:
        shutil.rmtree(base_dir, ignore_errors=True)
    return {
        "ckpt_bytes": ckpt_bytes,
        "sync_save_ms": round(sync_ms, 1),
        "async_blocked_ms": round(async_blocked_ms, 1),
        "async_vs_sync_stall": round(async_blocked_ms / sync_ms, 4),
        "sync_save_mb_s": round(ckpt_bytes / 1e6 / (sync_ms / 1e3), 1),
        "async_restore_bitwise": True,
        "methodology": (
            "one full params+optimizer checkpoint of the bench model; "
            "sync = save_checkpoint wall (write+commit+sentinel); async "
            "= CheckpointManager.save blocked ms (median of "
            f"{n_saves}, device→host copy only; each save's commit "
            "waited out off the clock, as a save_interval of compute "
            "would); restore asserted bitwise; keep_latest_n=1 GC "
            "asserted"
        ),
    }


def run_ckpt_bench():
    """bench-model fault-tolerance row: the ckpt_blocked_ms claim
    (async save stall < 25% of sync save wall, ISSUE 5 acceptance)
    measured at the bench model size with real fp32 master params +
    Adam m/v."""
    import tempfile

    cfg = make_cfg(1024)
    model = LlamaModel(cfg)
    params = model.init(jax.random.key(0))
    opt_state = init_optimizer_state(params, TrainConfig())
    base = tempfile.mkdtemp(prefix="bench_ckpt_")
    return ckpt_stall_stats(cfg, params, opt_state, base, n_saves=3)


def zero1_stats(dp=2, steps=50, seq=64, hidden=128, layers=4):
    """The `extra.zero1` harness (ISSUE 10): replicated adam vs the
    explicit ZeRO-1 decomposition vs its int8-quantized gradient
    reduction, on a dp-way virtual CPU mesh, same model/data/seeds.

    Reported per variant: median step ms + tok/s, per-device
    optimizer-state bytes (from the LIVE opt-state shardings), and the
    train step's AOT collective counts. Cross-variant: the fp zero1
    path's per-step losses are asserted BITWISE equal to replicated
    (the tests pin params/moments too); the quantized path's
    loss-trajectory drift over >= `steps` steps is MEASURED, never
    assumed. CPU-testable harness: bench's artifact run calls it in a
    virtual-device subprocess, tests call it directly
    (tests/test_zero1.py)."""
    import re

    import numpy as np

    from megatron_llm_tpu.config import tiny_config
    from megatron_llm_tpu.parallel.mesh import (
        destroy_parallel,
        initialize_parallel,
    )
    from megatron_llm_tpu.training.trainer import Trainer, get_batch

    assert len(jax.devices()) >= dp, (len(jax.devices()), dp)
    cfg = tiny_config(
        num_layers=layers, hidden_size=hidden, num_attention_heads=8,
        num_attention_heads_kv=4, ffn_hidden_size=2 * hidden,
        seq_length=seq, max_position_embeddings=seq,
        padded_vocab_size=512, compute_dtype=jnp.float32,
        params_dtype=jnp.float32)
    num_micro, mbs = 2, 2
    rows = mbs * dp

    def run(zero1, quant, n_steps):
        ctx = initialize_parallel(dp=dp, pp=1, tp=1)
        try:
            tcfg = TrainConfig(
                micro_batch_size=mbs, global_batch_size=num_micro * rows,
                lr=1e-3, train_iters=n_steps)
            pcfg = ParallelConfig(
                data_parallel_size=dp, num_microbatches=num_micro,
                use_distributed_optimizer=zero1,
                quantized_grad_reduce=quant)
            trainer = Trainer(LlamaModel(cfg), tcfg, pcfg)
            state = trainer.setup()
            rs = np.random.RandomState(0)
            losses, times = [], []
            for _ in range(n_steps):
                text = rs.randint(
                    0, 512, (num_micro, rows, seq + 1)).astype(np.int32)
                t0 = time.perf_counter()
                losses.append(float(trainer.train_step(state, text)["loss"]))
                times.append((time.perf_counter() - t0) * 1e3)
            per_dev = sum(
                int(np.prod(l.sharding.shard_shape(l.shape)))
                * l.dtype.itemsize
                for l in jax.tree.leaves(
                    (state.opt_state.m, state.opt_state.v)))
            # AOT collective counts of the exact step (cache hit)
            text = rs.randint(0, 512,
                              (num_micro, rows, seq + 1)).astype(np.int32)
            batch = get_batch(text, None)
            txt = trainer._get_step_fn(num_micro).lower(
                state.params, state.opt_state, batch,
                jnp.float32(1e-3), jnp.float32(0.01), None,
                jnp.float32(float("inf"))).compile().as_text()
            coll = {
                k: len(re.findall(rf"\b{k}(?:-start)?\(", txt))
                for k in ("all-reduce", "all-gather", "reduce-scatter",
                          "all-to-all")
            }
            # steady-state median: drop the first (compile) step
            med = sorted(times[1:])[len(times[1:]) // 2] if len(times) > 1 \
                else times[0]
            return {
                "losses": losses,
                "step_ms_median": round(med, 2),
                "tok_s": round(num_micro * rows * seq / (med / 1e3), 1),
                "opt_state_bytes_per_device": per_dev,
                "collectives": {k: v for k, v in coll.items() if v},
            }
        finally:
            destroy_parallel()

    rep = run(False, False, steps)
    z1 = run(True, False, steps)
    zq = run(True, True, steps)

    fp_bitwise = rep["losses"] == z1["losses"][:len(rep["losses"])]
    drift = [
        abs(a - b) / max(abs(a), 1e-9)
        for a, b in zip(rep["losses"], zq["losses"])
    ]
    out = {
        "dp": dp,
        "steps": steps,
        "zero1_vs_replicated_tok_s": round(z1["tok_s"] / rep["tok_s"], 3),
        "opt_state_bytes_per_device_replicated":
            rep["opt_state_bytes_per_device"],
        "opt_state_bytes_per_device_zero1":
            z1["opt_state_bytes_per_device"],
        "opt_state_sharding_ratio": round(
            rep["opt_state_bytes_per_device"]
            / max(z1["opt_state_bytes_per_device"], 1), 2),
        "zero1_fp_losses_bitwise_vs_replicated": fp_bitwise,
        "quantized_drift_steps": len(drift),
        "quantized_max_rel_loss_drift": round(max(drift), 6),
        "quantized_final_loss_pair": [rep["losses"][-1],
                                      zq["losses"][-1]],
        "replicated": {k: v for k, v in rep.items() if k != "losses"},
        "zero1": {k: v for k, v in z1.items() if k != "losses"},
        "zero1_quant": {k: v for k, v in zq.items() if k != "losses"},
        "methodology": (
            f"dp{dp} virtual CPU mesh, {layers}L/h{hidden}/seq{seq} "
            f"fp32 Llama-arch, identical data stream and seeds; three "
            f"trainers: replicated adam, zero1 explicit "
            f"reduce-scatter/all-gather (optimizer/zero1.py), zero1 + "
            f"int8 quantized reduction; step_ms is the median over "
            f"{steps - 1} post-compile steps (CPU — layout-relative "
            f"only, not TPU time); opt-state bytes read from the live "
            f"m/v shardings; collectives counted in the optimized "
            f"per-device HLO; quantized drift = max |loss_q - "
            f"loss_fp|/|loss_fp| over {len(drift)} steps of compounding "
            f"divergence, fp zero1 losses asserted bitwise vs "
            f"replicated in-row")
    }
    assert fp_bitwise, (
        "zero1 fp losses diverged from replicated adam — the bitwise "
        "contract (tests/test_zero1.py) is broken")
    return out


def overlap_stats(dp=2, steps=6, seq=64, hidden=128, layers=4,
                  bucket_mb=0.05):
    """The `extra.overlap` harness (ISSUE 12): eager ZeRO-1 vs the
    overlap-scheduled trainer (--overlap_grad_reduce +
    --overlap_param_gather) on a dp-way virtual CPU mesh, same
    model/data/seeds. CPU measures STRUCTURE, not speed: the losses
    are asserted bitwise in-row, the per-step async -start/-done pair
    count is measured from the compiled HLO by analysis/overlap.py (an
    honest 0 on CPU — this backend has no async collectives; the same
    field is the real pair count when this row runs on TPU, which is
    where the step_ms delta becomes meaningful), and the sync-schedule
    interleave witness (reduce-scatter gaps carrying the per-group
    backward) proves the issue points survived compilation. step_ms is
    the median of the post-compile steps — on CPU a layout-relative
    number only; the overlap win is an ICI-latency effect the CPU
    timing cannot show, as the methodology states."""
    import numpy as np

    from megatron_llm_tpu.analysis.overlap import (
        collective_overlap_report,
    )
    from megatron_llm_tpu.config import tiny_config
    from megatron_llm_tpu.parallel.mesh import (
        destroy_parallel,
        initialize_parallel,
    )
    from megatron_llm_tpu.training.trainer import Trainer, get_batch

    assert len(jax.devices()) >= dp, (len(jax.devices()), dp)
    cfg = tiny_config(
        num_layers=layers, hidden_size=hidden, num_attention_heads=8,
        num_attention_heads_kv=4, ffn_hidden_size=2 * hidden,
        seq_length=seq, max_position_embeddings=seq,
        padded_vocab_size=512, compute_dtype=jnp.float32,
        params_dtype=jnp.float32)
    num_micro, mbs = 2, 2
    rows = mbs * dp

    def run(overlap, n_steps):
        ctx = initialize_parallel(dp=dp, pp=1, tp=1)
        try:
            tcfg = TrainConfig(
                micro_batch_size=mbs, global_batch_size=num_micro * rows,
                lr=1e-3, train_iters=n_steps)
            pcfg = ParallelConfig(
                data_parallel_size=dp, num_microbatches=num_micro,
                use_distributed_optimizer=True,
                overlap_grad_reduce=overlap,
                overlap_param_gather=overlap,
                grad_rs_bucket_mb=bucket_mb)
            trainer = Trainer(LlamaModel(cfg), tcfg, pcfg)
            state = trainer.setup()
            rs = np.random.RandomState(0)
            losses, times = [], []
            for _ in range(n_steps):
                text = rs.randint(
                    0, 512, (num_micro, rows, seq + 1)).astype(np.int32)
                t0 = time.perf_counter()
                losses.append(
                    float(trainer.train_step(state, text)["loss"]))
                times.append((time.perf_counter() - t0) * 1e3)
            text = rs.randint(0, 512,
                              (num_micro, rows, seq + 1)).astype(np.int32)
            batch = get_batch(text, None)
            txt = trainer._get_step_fn(num_micro).lower(
                state.params, state.opt_state, batch,
                jnp.float32(1e-3), jnp.float32(0.01), None,
                jnp.float32(float("inf"))).compile().as_text()
            rep = collective_overlap_report(txt)
            rs_gaps = rep.compute_between.get("reduce-scatter", [])
            post = times[1:] if len(times) > 1 else times
            med = sorted(post)[len(post) // 2]
            return {
                "losses": losses,
                "step_ms_median": round(med, 2),
                "step_ms_n": len(post),
                "async_collective_pairs": rep.async_pairs,
                "collective_counts": rep.collective_counts,
                "rs_interleaved_gaps":
                    sum(1 for g in rs_gaps if g >= 2),
            }
        finally:
            destroy_parallel()

    eager = run(False, steps)
    over = run(True, steps)
    bitwise = eager["losses"] == over["losses"]
    out = {
        "dp": dp,
        "steps": steps,
        "overlap_vs_eager_step_ms": round(
            over["step_ms_median"] / max(eager["step_ms_median"], 1e-9),
            3),
        "overlap_losses_bitwise_vs_eager": bitwise,
        "eager": {k: v for k, v in eager.items() if k != "losses"},
        "overlap": {k: v for k, v in over.items() if k != "losses"},
        "methodology": (
            f"dp{dp} virtual CPU mesh, {layers}L/h{hidden}/seq{seq} fp32 "
            f"Llama-arch, identical data stream/seeds; eager zero1 vs "
            f"overlap_grad_reduce+overlap_param_gather at "
            f"grad_rs_bucket_mb={bucket_mb}; step_ms median of "
            f"{steps - 1} post-compile steps — CPU layout-relative only "
            f"(sync collectives; the overlap win is ICI latency hiding, "
            f"measurable only on TPU where async_collective_pairs "
            f"counts real -start/-done pairs — 0 here is a MEASURED "
            f"property of this backend, analysis/overlap.py); "
            f"rs_interleaved_gaps = reduce-scatter gaps carrying >= 2 "
            f"heavy ops (the per-group backward loops), the CPU-visible "
            f"witness of the backward-interleaved schedule; losses "
            f"asserted bitwise eager==overlap in-row")
    }
    assert bitwise, (
        "overlap-scheduled losses diverged from eager zero1 — the "
        "bitwise contract (tests/test_overlap.py) is broken")
    assert over["rs_interleaved_gaps"] >= 1, over
    return out


def run_overlap_bench():
    """bench artifact wrapper for extra.overlap — virtual-CPU
    subprocess, like run_zero1_bench."""
    import json
    import os
    import subprocess
    import sys

    from megatron_llm_tpu.utils.virtual_mesh import (
        force_virtual_cpu_devices,
    )

    repo = os.path.dirname(os.path.abspath(__file__))
    env = force_virtual_cpu_devices(8, dict(os.environ))
    code = (
        "import jax\n"
        "jax.config.update('jax_platforms', 'cpu')\n"
        f"import sys; sys.path.insert(0, {repo!r})\n"
        "import json\n"
        "from bench import overlap_stats\n"
        "print('OVERLAP: ' + json.dumps(overlap_stats()))\n"
    )
    proc = subprocess.run([sys.executable, "-c", code], env=env, cwd=repo,
                          capture_output=True, text=True, timeout=1800)
    for line in proc.stdout.splitlines():
        if line.startswith("OVERLAP: "):
            return json.loads(line[len("OVERLAP: "):])
    return {"error": (proc.stderr or proc.stdout)[-300:]}


def telemetry_stats(slots=4, n_reqs=12, gen=24, prompt_len=20,
                    train_steps=8, seq=32):
    """The `extra.telemetry` harness (ISSUE 13): flight-recorder
    telemetry ON vs OFF on identical traffic, both hot paths. ON = the
    opt-in span tracer (trace_dir) live while serving/training; the
    flight recorder and latency histograms are unconditionally on in
    BOTH runs — they are the production default, so the measured delta
    is exactly what an operator pays for turning tracing on. The
    bitwise contract is asserted IN-ROW: telemetry-on greedy token
    streams and train losses equal telemetry-off to the bit, or the
    row refuses to report an overhead number for a subsystem that
    changed the math. CPU-harness-tested (tests/test_telemetry.py)
    like extra.overlap; wall-clock overheads are layout-relative on
    CPU and real on TPU, as the methodology states."""
    import tempfile

    import numpy as np

    from megatron_llm_tpu.config import tiny_config
    from megatron_llm_tpu.inference.engine import DecodeEngine

    cfg = tiny_config(compute_dtype=jnp.float32, use_decode_attn=False,
                      seq_length=seq, max_position_embeddings=seq)
    model = LlamaModel(cfg)
    params = model.init(jax.random.key(0))
    rs = np.random.RandomState(7)
    prompts = [[int(x) for x in rs.randint(1, 200, size=prompt_len)]
               for _ in range(n_reqs)]

    def serve(telemetry):
        eng = DecodeEngine(
            model, params, slots=slots, page_size=16, max_context=64,
            prefill_chunk_tokens=16, vocab_size=256,
            trace_dir=tempfile.mkdtemp(prefix="bench_telemetry_")
            if telemetry else None)
        eng.warmup()  # compile outside the measured window
        t0 = time.perf_counter()
        reqs = [eng.submit(p, gen, top_k=1) for p in prompts]
        eng.drain()
        wall = time.perf_counter() - t0
        streams = [r.result(5)[0] for r in reqs]
        out = {
            "decode_tok_s": round(eng._tokens_out / max(wall, 1e-9), 1),
            "rounds": eng._rounds,
            "span_events": len(eng.tracer.events()),
            "recorder_events": len(eng.recorder.snapshot(
                reason="bench")["events"]),
            "ttft_hist_count": eng._hists["serve_ttft_ms"].count,
        }
        return streams, out

    streams_off, srv_off = serve(False)
    streams_on, srv_on = serve(True)
    streams_bitwise = streams_on == streams_off

    def train(telemetry):
        from megatron_llm_tpu.training.trainer import Trainer

        tcfg = TrainConfig(
            micro_batch_size=2, global_batch_size=2, lr=1e-3,
            train_iters=train_steps, log_interval=10**9,
            eval_interval=0,
            trace_dir=tempfile.mkdtemp(prefix="bench_telemetry_")
            if telemetry else None)
        trainer = Trainer(LlamaModel(cfg), tcfg,
                          ParallelConfig(num_microbatches=1))
        state = trainer.setup()
        rs2 = np.random.RandomState(3)
        losses, times = [], []
        for _ in range(train_steps):
            text = rs2.randint(
                0, cfg.padded_vocab_size, (1, 2, seq + 1)).astype(np.int32)
            trainer.tracer.set_context(step=state.iteration + 1)
            t0 = time.perf_counter()
            stats = trainer.train_step(state, text)
            loss = float(stats["loss"])  # the loop's own host sync
            times.append((time.perf_counter() - t0) * 1e3)
            losses.append(loss)
            trainer._step_ms_hist.observe(times[-1])
            trainer.recorder.record("step", step=state.iteration,
                                    loss=loss, ms=round(times[-1], 3))
        post = times[1:] if len(times) > 1 else times
        return losses, {
            "step_ms_median": round(sorted(post)[len(post) // 2], 3),
            "span_events": len(trainer.tracer.events()),
            "recorder_events": len(trainer.recorder.snapshot(
                reason="bench")["events"]),
        }

    losses_off, tr_off = train(False)
    losses_on, tr_on = train(True)
    losses_bitwise = losses_on == losses_off

    decode_overhead = (srv_off["decode_tok_s"]
                       / max(srv_on["decode_tok_s"], 1e-9) - 1.0)
    train_overhead = (tr_on["step_ms_median"]
                      / max(tr_off["step_ms_median"], 1e-9) - 1.0)
    out = {
        "telemetry_overhead_pct": round(
            max(decode_overhead, train_overhead) * 100, 2),
        "decode_overhead_pct": round(decode_overhead * 100, 2),
        "train_step_overhead_pct": round(train_overhead * 100, 2),
        "streams_bitwise_on_vs_off": streams_bitwise,
        "train_losses_bitwise_on_vs_off": losses_bitwise,
        "serve_off": srv_off,
        "serve_on": srv_on,
        "train_off": tr_off,
        "train_on": tr_on,
        "methodology": (
            f"identical traffic both runs: {n_reqs} greedy requests "
            f"(prompt {prompt_len}, gen {gen}) through {slots}-slot "
            f"chunked-prefill engines, and {train_steps} train steps "
            f"(median of post-compile step ms) on a tiny fp32 "
            f"Llama-arch; ON = opt-in span tracer live (trace_dir), "
            f"flight recorder + histograms unconditionally on in BOTH "
            f"(the production default) so the delta prices tracing "
            f"alone; token streams and per-step losses asserted "
            f"BITWISE on==off in-row (telemetry never touches jitted "
            f"code — the graft-check audit pins the same claim on the "
            f"compiled artifacts); wall-clock numbers are "
            f"layout-relative on a CPU harness, real on TPU"),
    }
    assert streams_bitwise, (
        "telemetry-on greedy streams diverged from telemetry-off — "
        "the bitwise contract (tests/test_telemetry.py) is broken")
    assert losses_bitwise, (
        "telemetry-on train losses diverged from telemetry-off — "
        "the bitwise contract (tests/test_telemetry.py) is broken")
    assert srv_on["span_events"] > 0 and tr_on["span_events"] > 0, (
        "the telemetry-on run recorded no spans — the overhead "
        "number would be measuring a disabled tracer")
    return out


def run_telemetry():
    """bench artifact wrapper for extra.telemetry — inline (no mesh
    needed), like run_serving."""
    try:
        return telemetry_stats()
    except Exception as e:  # noqa: BLE001 — a broken row must not
        # take the whole artifact down
        return {"error": repr(e)[-300:]}


def goodput_stats(slots=4, n_reqs=10, gen=20, prompt_len=16,
                  train_steps=8, seq=32, chip_spec=None):
    """The `extra.goodput` harness (ISSUE 15): the goodput ledger +
    compiled-cost registry + perf sentinel ON vs OFF on identical
    traffic, both hot paths. Headlines: `goodput_fraction` (the train
    run's productive/wall partition — the ledger's sum-to-wall
    invariant asserted in-row) and `telemetry_overhead_pct` (what the
    cost/ledger/sentinel stack costs on decode tok/s and train
    step_ms). The bitwise contract is asserted IN-ROW exactly like
    extra.telemetry: ledger/registry/sentinel-on greedy token streams
    and train losses equal off to the bit, or the row refuses to
    report. CPU-harness-tested (tests/test_goodput.py); the chip spec
    is the DETECTED one on TPU, the caller's `chip_spec` override on the CPU
    harness — stated in-row."""
    import tempfile

    import numpy as np

    from megatron_llm_tpu.config import tiny_config
    from megatron_llm_tpu.inference.engine import DecodeEngine

    chip = detect_chip(override=chip_spec)
    if chip is None:
        raise RuntimeError(
            "goodput_stats needs a chip spec: run on a TPU or pass "
            "chip_spec=")

    cfg = tiny_config(compute_dtype=jnp.float32, use_decode_attn=False,
                      seq_length=seq, max_position_embeddings=seq)
    model = LlamaModel(cfg)
    params = model.init(jax.random.key(0))
    rs = np.random.RandomState(11)
    prompts = [[int(x) for x in rs.randint(1, 200, size=prompt_len)]
               for _ in range(n_reqs)]

    def serve(cost_on):
        kw = {}
        if cost_on:
            kw = dict(cost_registry=True, chip_spec=chip.name,
                      perf_sentinel_ksigma=6.0,
                      perf_sentinel_window=16,
                      perf_sentinel_patience=8,
                      record_dir=tempfile.mkdtemp(prefix="bench_goodput_"))
        eng = DecodeEngine(
            model, params, slots=slots, page_size=16, max_context=64,
            prefill_chunk_tokens=16, vocab_size=256, **kw)
        eng.warmup()  # compile (and capture) outside the measured window
        t0 = time.perf_counter()
        reqs = [eng.submit(p, gen, top_k=1) for p in prompts]
        eng.drain()
        wall = time.perf_counter() - t0
        streams = [r.result(5)[0] for r in reqs]
        c = eng.counters()
        out = {"decode_tok_s": round(eng._tokens_out / max(wall, 1e-9), 1)}
        if cost_on:
            out.update({
                "modeled_gflops": c["serve_modeled_gflops"],
                "page_rounds": c["serve_page_rounds"],
                "cost_records": c["serve_cost_records"],
                "dispatch_overhead_pct":
                    c.get("serve_dispatch_overhead_pct"),
                "perf_regressions": c["serve_perf_regressions"],
            })
        return streams, out

    streams_off, srv_off = serve(False)
    streams_on, srv_on = serve(True)
    streams_bitwise = streams_on == streams_off

    def train(cost_on):
        from megatron_llm_tpu.training.trainer import Trainer

        kw = {}
        if cost_on:
            kw = dict(device_cost_registry=True, chip_spec=chip.name,
                      perf_sentinel_ksigma=6.0, perf_sentinel_window=16,
                      perf_sentinel_patience=8)
        tcfg = TrainConfig(
            micro_batch_size=2, global_batch_size=2, lr=1e-3,
            train_iters=train_steps, log_interval=10**9,
            eval_interval=0, **kw)
        trainer = Trainer(LlamaModel(cfg), tcfg,
                          ParallelConfig(num_microbatches=1))

        class _It:
            def __iter__(self):
                rs2 = np.random.RandomState(3)
                while True:
                    yield rs2.randint(
                        0, cfg.padded_vocab_size,
                        (1, 2, seq + 1)).astype(np.int32)

        trainer.train_data_iterator = _It()
        state = trainer.setup()
        state = trainer.train(state)
        losses = [e["loss"] for e in trainer.recorder.snapshot(
            reason="bench")["events"] if e["kind"] == "step"]
        snap = trainer.ledger.snapshot()
        post = [e["ms"] for e in trainer.recorder.snapshot(
            reason="bench")["events"]
            if e["kind"] == "step" and e["bucket"] == "productive"]
        out = {
            "step_ms_median": round(sorted(post)[len(post) // 2], 3)
            if post else None,
            "goodput": snap,
        }
        return losses, out

    losses_off, tr_off = train(False)
    losses_on, tr_on = train(True)
    losses_bitwise = losses_on == losses_off
    snap = tr_on["goodput"]
    bucket_sum = sum(snap["buckets"].values())

    decode_overhead = (srv_off["decode_tok_s"]
                       / max(srv_on["decode_tok_s"], 1e-9) - 1.0)
    train_overhead = (tr_on["step_ms_median"]
                      / max(tr_off["step_ms_median"], 1e-9) - 1.0)
    out = {
        "goodput_fraction": snap["goodput_fraction"],
        "goodput_buckets_s": snap["buckets"],
        "goodput_wall_s": snap["wall_s"],
        # tolerance: the snapshot rounds each bucket to 6 decimals, so
        # the rounded sum may differ from the rounded wall by up to
        # 0.5us x bucket count — 1e-5 s states exactly that
        "goodput_sum_to_wall_ok":
            abs(bucket_sum - snap["wall_s"]) < 1e-5
            and snap["overcount_s"] == 0,
        "telemetry_overhead_pct": round(
            max(decode_overhead, train_overhead) * 100, 2),
        "decode_overhead_pct": round(decode_overhead * 100, 2),
        "train_step_overhead_pct": round(train_overhead * 100, 2),
        "streams_bitwise_on_vs_off": streams_bitwise,
        "train_losses_bitwise_on_vs_off": losses_bitwise,
        "chip_spec": chip.label(),
        "serve_off": srv_off,
        "serve_on": srv_on,
        "train_off": tr_off,
        "train_on": tr_on,
        "methodology": (
            f"identical traffic both runs: {n_reqs} greedy requests "
            f"(prompt {prompt_len}, gen {gen}) through {slots}-slot "
            f"chunked-prefill engines and {train_steps} train steps on "
            f"a tiny fp32 Llama-arch; ON = cost registry (mint-time "
            f"capture) + goodput ledger gauges + perf sentinel armed "
            f"at a non-tripping ksigma, OFF = production defaults "
            f"(ledger alone is always on — it is pure host float "
            f"adds); token streams and per-step losses asserted "
            f"BITWISE on==off in-row; the goodput partition's "
            f"sum-to-wall invariant asserted in-row; chip spec "
            f"{chip.label()} — compile dominates wall at this toy "
            f"scale, so goodput_fraction here demonstrates the "
            f"ACCOUNTING, the TPU artifact run carries the "
            f"representative number"),
    }
    assert streams_bitwise, (
        "cost/ledger/sentinel-on greedy streams diverged from off — "
        "the bitwise contract (tests/test_goodput.py) is broken")
    assert losses_bitwise, (
        "cost/ledger/sentinel-on train losses diverged from off — "
        "the bitwise contract (tests/test_goodput.py) is broken")
    assert out["goodput_sum_to_wall_ok"], (
        "goodput buckets do not partition wall time", snap)
    assert srv_on["cost_records"] > 0, (
        "the cost-on serve run captured no compiled-cost records")
    return out


def run_goodput():
    """bench artifact wrapper for extra.goodput — inline, like
    run_telemetry."""
    try:
        return goodput_stats()
    except Exception as e:  # noqa: BLE001 — a broken row must not
        # take the whole artifact down
        return {"error": repr(e)[-300:]}


def run_zero1_bench():
    """bench artifact wrapper: the TPU bench machine has ONE chip, so
    the dp-mesh harness runs in a subprocess on virtual CPU devices —
    the row
    measures the decomposition's structure (collectives, state bytes,
    drift), not TPU step time, and says so in its methodology."""
    import json
    import os
    import subprocess
    import sys

    from megatron_llm_tpu.utils.virtual_mesh import (
        force_virtual_cpu_devices,
    )

    repo = os.path.dirname(os.path.abspath(__file__))
    env = force_virtual_cpu_devices(8, dict(os.environ))
    code = (
        "import jax\n"
        "jax.config.update('jax_platforms', 'cpu')\n"
        f"import sys; sys.path.insert(0, {repo!r})\n"
        "import json\n"
        "from bench import zero1_stats\n"
        "print('ZERO1: ' + json.dumps(zero1_stats()))\n"
    )
    proc = subprocess.run([sys.executable, "-c", code], env=env, cwd=repo,
                          capture_output=True, text=True, timeout=1800)
    for line in proc.stdout.splitlines():
        if line.startswith("ZERO1: "):
            return json.loads(line[len("ZERO1: "):])
    return {"error": (proc.stderr or proc.stdout)[-300:]}


def _timed_scan(f, operands, n=20):
    """Median-free best-of-2 of an n-deep jitted scan over `f`; returns
    seconds per call. The carry threads a zero-scaled output back into
    the first operand so XLA cannot hoist or DCE the op."""

    @jax.jit
    def loop(*ops):
        def body(c, _):
            out = f(*c)
            out = jax.tree.leaves(out)[0]
            first = c[0] + (out * 0).astype(c[0].dtype).reshape(c[0].shape) \
                if out.size == c[0].size else \
                c[0] + jnp.sum(out.astype(jnp.float32)).astype(c[0].dtype) * 0
            return (first,) + c[1:], ()
        c, _ = jax.lax.scan(body, ops, None, length=n)
        return c[0]

    r = loop(*operands)
    float(jnp.sum(r.astype(jnp.float32)))  # compile + sync
    best = float("inf")
    for _ in range(2):
        t0 = time.perf_counter()
        r = loop(*operands)
        float(jnp.sum(r.astype(jnp.float32)))
        best = min(best, time.perf_counter() - t0)
    return best / n


def decode_attn_op_stats(b=8, T=576):
    """Standalone decode-attention op at the bench decode shape, kernel
    vs XLA, full cache (steady-state worst case). Returns per-call times,
    achieved HBM bandwidth, and the fraction of the v5e peak — the
    line-rate claim, measured directly. Head geometry derives from
    make_cfg so the row keeps describing the served model if the bench
    config moves."""
    from megatron_llm_tpu.ops.decode_attention import decode_attention

    cfg = make_cfg(1024)
    g, qpk, d = cfg.num_query_groups, cfg.q_per_kv, cfg.head_dim
    ks = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(ks[0], (b, 1, g, qpk, d), jnp.bfloat16)
    k = jax.random.normal(ks[1], (b, g, T, d), jnp.bfloat16)
    v = jax.random.normal(ks[2], (b, g, T, d), jnp.bfloat16)
    length = jnp.int32(T)

    t_kernel = _timed_scan(
        lambda q, k, v: decode_attention(q, k, v, length, layout="gtd",
                                         use_pallas=True), (q, k, v))
    t_xla = _timed_scan(
        lambda q, k, v: decode_attention(q, k, v, length, layout="gtd",
                                         use_pallas=False), (q, k, v))
    # K + V bytes DERIVED from the cache array's actual dtype — a
    # hard-coded bf16 itemsize here would overstate achieved GB/s the
    # moment a quantized cache rides this row (ISSUE 9 small fix)
    cache_bytes = 2 * b * g * T * d * k.dtype.itemsize
    return {
        "decode_attn_us_b8": round(t_kernel * 1e6, 2),
        "decode_attn_us_b8_xla": round(t_xla * 1e6, 2),
        "decode_attn_vs_xla_speedup": round(t_xla / t_kernel, 2),
        "decode_attn_gbps_b8": round(cache_bytes / t_kernel / 1e9, 1),
        "decode_attn_hbm_frac_b8": round(
            cache_bytes / t_kernel / CHIP.hbm_bytes_s, 3),
        "decode_attn_spec_source": CHIP.label(),
    }


def decode_step_breakdown(b=8, gen=512, prompt=64, step_ms=None):
    """Per-step decode time budget at the bench serving shape: attention
    (decode kernel x L), GLU matvec (flat decode layout x L), qkv/wo
    matvecs x L, head matvec + greedy sampling — against the measured
    end-to-end step time (`other_ms` is the remainder: norms, embeds,
    loop bookkeeping). All components run at the T = prompt + gen cache
    shape, i.e. the end-of-generation worst case."""
    from megatron_llm_tpu.ops.decode_attention import decode_attention
    from megatron_llm_tpu.inference.generation import select_next_token

    cfg = make_cfg(1024)
    L, h, f = cfg.num_layers, cfg.hidden_size, cfg.ffn_hidden_size
    g, qpk, d = cfg.num_query_groups, cfg.q_per_kv, cfg.head_dim
    V = cfg.padded_vocab_size
    T = prompt + gen
    ks = jax.random.split(jax.random.key(0), 8)
    dt = jnp.bfloat16
    q = jax.random.normal(ks[0], (b, 1, g, qpk, d), dt)
    kc = jax.random.normal(ks[1], (b, g, T, d), dt)
    vc = jax.random.normal(ks[2], (b, g, T, d), dt)
    hid = jax.random.normal(ks[3], (b, 1, h), dt)
    w1 = jax.random.normal(ks[4], (h, 2 * f), dt)
    w2 = jax.random.normal(ks[5], (f, h), dt)
    wqkv = jax.random.normal(ks[6], (h, cfg.qkv_projection_size), dt)
    wo = jax.random.normal(ks[7], (g * qpk * d, h), dt)
    whead = jax.random.normal(ks[4], (h, V), dt)
    logits = jax.random.normal(ks[5], (b, V), jnp.float32)
    prev = jnp.zeros((b,), jnp.int32)

    t_attn = L * _timed_scan(
        lambda q, kc, vc: decode_attention(q, kc, vc, jnp.int32(T),
                                           layout="gtd"), (q, kc, vc))
    t_glu = L * _timed_scan(
        lambda hid, w1, w2: ((hid @ w1).reshape(b, 1, 2, f)[..., 0, :]
                             @ w2), (hid, w1, w2))
    t_proj = L * _timed_scan(
        lambda hid, wqkv, wo: (hid @ wqkv)[..., : g * qpk * d] @ wo,
        (hid, wqkv, wo))
    t_head = _timed_scan(lambda hid, whead: hid @ whead, (hid, whead))
    t_sample = _timed_scan(
        lambda logits, prev: select_next_token(
            logits, prev, None, jnp.float32(0.0), greedy=True, top_k=1,
            top_p=0.0, temperature=1.0, vocab_size=32000,
        ).astype(jnp.float32).reshape(b, 1),
        (logits, prev))
    out = {
        "attn_ms": round(t_attn * 1e3, 3),
        "glu_matvec_ms": round(t_glu * 1e3, 3),
        "qkv_wo_matvec_ms": round(t_proj * 1e3, 3),
        "head_matvec_ms": round(t_head * 1e3, 3),
        "sampling_ms": round(t_sample * 1e3, 3),
    }
    if step_ms is not None:
        known = sum(out.values())
        out["step_ms"] = round(step_ms, 3)
        out["other_ms"] = round(step_ms - known, 3)
    return out


def flash_mxu_stats():
    """fwd and bwd MXU utilization of the flash kernel at the bench
    attention shape (VERDICT r5 next-round #5): causal attention FLOPs
    over measured kernel time, against the v5e bf16 peak."""
    from megatron_llm_tpu.ops.flash_attention import flash_attention

    cfg = make_cfg(4096)
    b, s = 2, 4096  # same point flash_vs_xla_ratio measures
    g, qpk, d = cfg.num_query_groups, cfg.q_per_kv, cfg.head_dim
    q = jax.random.normal(jax.random.key(0), (b, s, g, qpk, d), jnp.bfloat16)
    k = jax.random.normal(jax.random.key(1), (b, s, g, d), jnp.bfloat16)
    v = jax.random.normal(jax.random.key(2), (b, s, g, d), jnp.bfloat16)

    t_fwd = _timed_scan(
        lambda q, k, v: flash_attention(q, k, v, causal=True), (q, k, v))

    def fwd_bwd(q, k, v):
        o, vjp = jax.vjp(
            lambda q, k, v: flash_attention(q, k, v, causal=True), q, k, v)
        dq, dk, dv = vjp(o)
        return dq
    t_fwd_bwd = _timed_scan(fwd_bwd, (q, k, v))

    # causal: half the s x s score cells; fwd = QK^T + PV = 4*b*H*s^2*d
    # MACs-as-2FLOPs halved; bwd recomputes scores and runs dq/dk/dv/dv-p
    # = 5 score-shaped matmuls vs the forward's 2
    heads = g * qpk
    fwd_flops = 0.5 * 4 * b * heads * s * s * d
    bwd_flops = 2.5 * fwd_flops
    t_bwd = max(t_fwd_bwd - t_fwd, 1e-9)
    peak = CHIP.peak_flops_for("bf16")
    return {
        "flash_fwd_mxu": round(fwd_flops / t_fwd / peak, 4),
        "flash_bwd_mxu": round(bwd_flops / t_bwd / peak, 4),
        "flash_mxu_spec_source": CHIP.label(),
    }


def flash_vs_xla_ratio():
    """fwd+bwd time ratio XLA-attention / Pallas-flash at the bench seq
    length (b2 keeps the XLA path's fp32 score tensor under HBM; measured
    r4 on v5e: 2.56x here, 2.96x at s8192, ~1x at s<=2048 where attention
    is too small to matter)."""
    from megatron_llm_tpu.ops.flash_attention import (
        _xla_reference,
        flash_attention,
    )

    b, s, g, qpk, d = 2, 4096, 16, 1, 128
    q = jax.random.normal(jax.random.key(0), (b, s, g, qpk, d), jnp.bfloat16)
    k = jax.random.normal(jax.random.key(1), (b, s, g, d), jnp.bfloat16)
    v = jax.random.normal(jax.random.key(2), (b, s, g, d), jnp.bfloat16)

    def timed(f):
        n = 20

        @jax.jit
        def loop(q, k, v):
            def body(c, _):
                o, vjp = jax.vjp(lambda q, k, v: f(q, k, v), *c)
                dq, dk, dv = vjp(o)
                return (c[0] + dq * 0, c[1] + dk * 0, c[2] + dv * 0), ()
            c, _ = jax.lax.scan(body, (q, k, v), None, length=n)
            return c[0]
        r = loop(q, k, v)
        float(jnp.sum(r[0, 0].astype(jnp.float32)))
        t0 = time.perf_counter()
        r = loop(q, k, v)
        float(jnp.sum(r[0, 0].astype(jnp.float32)))
        return (time.perf_counter() - t0) / n

    t_flash = timed(lambda q, k, v: flash_attention(q, k, v, causal=True))
    t_xla = timed(lambda q, k, v: _xla_reference(q, k, v, True))
    return t_xla / t_flash


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--seq", type=int, default=0, choices=[0, 1024, 4096, 8192],
                   help="0 = all three lengths + kernel ratio (the "
                        "artifact run)")
    p.add_argument("--iters", type=int, default=20)
    args = p.parse_args()
    from megatron_llm_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    assert jax.default_backend() == "tpu", jax.default_backend()

    if args.seq:
        tok, mfu, n_params = run_train(args.seq, args.iters)
        print(json.dumps({
            "metric": (f"tokens/sec/chip, Llama-arch 0.74B pretrain, "
                       f"seq {args.seq}, bf16, flash-attn(Pallas) ON, "
                       f"remat_policy=full (memory-forced at peak mbs), "
                       f"v5e, MFU {mfu:.1%}"),
            "value": round(tok, 1),
            "unit": "tokens/sec/chip",
            "vs_baseline": round(tok * 6 * n_params / (890.0 * 6 * 7.0e9), 3),
        }))
        return

    tok1, mfu1, n_params = run_train(1024, args.iters)
    tok4, mfu4, _ = run_train(4096, args.iters)
    tok8, mfu8, _ = run_train(8192, max(args.iters // 2, 5))
    # remat-policy ladder audit (models/remat.py) at a shared sweep shape
    remat_rows = remat_policy_sweep(seq=1024, iters=max(args.iters // 2, 5))
    by_pol = {r["policy"]: r for r in remat_rows}
    sel, ful = by_pol.get("selective", {}), by_pol.get("full", {})
    sel_vs_full = (round(sel["tok_s"] / ful["tok_s"], 3)
                   if sel.get("tok_s") and ful.get("tok_s") else None)
    ratio = flash_vs_xla_ratio()
    gen = 512
    dec1 = run_decode(1, gen=gen)
    dec8 = run_decode(8, gen=gen)
    dec1_xla = run_decode(1, gen=gen, use_decode_attn=False)
    dec8_xla = run_decode(8, gen=gen, use_decode_attn=False)
    step_ms = 8.0 / dec8 * 1e3  # b=8 per-step wall time (8 tok per step)
    breakdown = decode_step_breakdown(b=8, gen=gen, step_ms=step_ms)
    attn_stats = decode_attn_op_stats(b=8, T=64 + gen)
    mxu = flash_mxu_stats()
    serving = run_serving()
    quant = run_quant()
    kunify = run_kernel_unify()
    ckpt = run_ckpt_bench()
    zero1 = run_zero1_bench()
    overlap = run_overlap_bench()
    telemetry = run_telemetry()
    goodput = run_goodput()
    achieved = tok1 * 6 * n_params
    baseline = 890.0 * 6 * 7.0e9  # A100 anchor, BASELINE.md
    print(json.dumps({
        "metric": (
            f"tokens/sec/chip, Llama-arch 0.74B pretrain, seq 1024, bf16, "
            f"flash-attn(Pallas) ON, remat_policy=full (memory-forced at "
            f"peak mbs), v5e, MFU {mfu1:.1%} "
            f"(FLOP-normalized vs A100 7B anchor); "
            f"seq 4096: {tok4:.0f} tok/s, MFU {mfu4:.1%}; "
            f"seq 8192: {tok8:.0f} tok/s, MFU {mfu8:.1%}; "
            + (f"remat sweep @mbs{REMAT_SWEEP_MBS}: selective/full tok/s "
               f"{sel_vs_full}x; " if sel_vs_full else "")
            + f"flash-vs-XLA fwd+bwd speedup {ratio:.2f}x, "
            f"fwd MXU {mxu['flash_fwd_mxu']:.1%}; "
            f"greedy decode {dec1:.0f} tok/s @b1, {dec8:.0f} @b8 "
            f"(decode-attn kernel ON; XLA-attn: {dec1_xla:.0f} @b1, "
            f"{dec8_xla:.0f} @b8; kernel "
            f"{attn_stats['decode_attn_gbps_b8']:.0f} GB/s = "
            f"{attn_stats['decode_attn_hbm_frac_b8']:.0%} of HBM peak); "
            f"continuous-batching serving "
            f"{serving['serving_tok_s']:.0f} tok/s = "
            f"{serving['continuous_vs_static_tok_s']}x whole-batch on "
            f"mixed-length traffic (p50/p95 "
            f"{serving['p50_latency_s']}/{serving['p95_latency_s']}s); "
            f"chunked prefill cuts long-prompt-admission p95 TTFT "
            f"{serving['interference']['chunked_vs_wholeprompt_ttft']}x "
            f"vs whole-prompt (decode p95 "
            f"{serving['interference']['chunked']['decode_p95_ms']} vs "
            f"{serving['interference']['wholeprompt']['decode_p95_ms']}"
            f" ms); prefix sharing at the 80%-shared-system-prompt mix: "
            f"p95 TTFT "
            f"{serving['prefix']['shared_vs_unshared_ttft_p95']}x, "
            f"tok/s {serving['prefix']['shared_vs_unshared_tok_s']}x, "
            f"prefill tokens/request "
            f"-{serving['prefix']['prefill_token_reduction']:.0%}, "
            f"peak pages -{serving['prefix']['peak_pages_in_use_delta']}"
            f"; replica router at "
            f"{serving['scaleout']['replicas']} emulated replicas "
            f"(80%-shared mix): affinity vs random dispatch p95 TTFT "
            f"{serving['scaleout']['router_affinity_vs_random_ttft_p95']}"
            f"x, fleet prefill tokens /"
            f"{serving['scaleout']['affinity_vs_random_prefill_tokens']}"
            f", aggregate tok/s "
            f"{serving['scaleout']['aggregate_tok_s_scaling']}x the "
            f"1-replica baseline"
            f"; disaggregated prefill/decode at equal replica count "
            f"(interactive decodes interleaved with batch prefills): "
            f"interactive p95 TTFT "
            f"{serving['disagg']['disagg_vs_symmetric_ttft_p95']}x, "
            f"aggregate tok/s "
            f"{serving['disagg']['disagg_vs_symmetric_tok_s']}x, "
            f"decode-round interference "
            f"{serving['disagg']['decode_interference_ratio']}x vs "
            f"symmetric ({serving['disagg']['disagg']['transfer_pages']}"
            f" KV pages handed off)"
            f"; sliding-window long-context serving (window "
            f"{serving['longcontext']['window_tokens']} tok over a "
            f"{serving['longcontext']['long_context_tokens']}-tok "
            f"stream): decode KV reads "
            f"/{serving['longcontext']['decode_read_reduction']}x, peak "
            f"pages/long-slot "
            f"{serving['longcontext']['dense_peak_pages_per_long_slot']}"
            f" -> "
            f"{serving['longcontext']['window_peak_pages_per_long_slot']}"
            f", {serving['longcontext']['window_reclaimed_pages']} pages"
            f" reclaimed mid-flight, streams bitwise vs mask-only"
            f"; int8 KV pages: "
            f"{quant['int8_vs_bf16_decode_tok_s']}x decode tok/s, "
            f"{quant['kv_capacity_ratio']}x tokens/HBM-byte "
            f"({quant['bf16']['kv_bytes_per_token']} -> "
            f"{quant['int8']['kv_bytes_per_token']} B/token), max prompt "
            f"logprob drift "
            f"{quant['int8']['max_prompt_logprob_drift_vs_bf16']} "
            f"(+int8 weights: "
            f"{quant['int8_w_vs_bf16_decode_tok_s']}x, drift "
            f"{quant['int8_w']['max_prompt_logprob_drift_vs_bf16']})"
            f"; ONE ragged paged attention kernel "
            f"({kunify['paged_entry_points_pre_unification']} paged "
            f"builders -> {kunify['paged_entry_points']}): fused "
            f"scatter+attend {kunify['fused_vs_split_time_ratio']}x the "
            f"split two-launch time, split == fused bitwise in-row, "
            f"decode {kunify['unified_decode_gbps']} / chunk "
            f"{kunify['unified_chunk_gbps']} GB/s through the one "
            f"entry, engine decode {kunify['engine_decode_tok_s']:.0f} "
            f"tok/s"
            f"; async ckpt blocks the loop "
            f"{ckpt['async_blocked_ms']:.0f}ms = "
            f"{ckpt['async_vs_sync_stall']:.0%} of the "
            f"{ckpt['sync_save_ms']:.0f}ms sync save "
            f"({ckpt['ckpt_bytes'] / 1e9:.1f}GB, restore bitwise)"
            + (f"; ZeRO-1 dp{zero1['dp']} (CPU harness): opt-state "
               f"bytes/device /{zero1['opt_state_sharding_ratio']}, fp "
               f"losses bitwise vs replicated adam, int8 grad-reduce "
               f"drift {zero1['quantized_max_rel_loss_drift']:.1e} over "
               f"{zero1['quantized_drift_steps']} steps"
               if "error" not in zero1 else "")
            + (f"; overlap-scheduled zero1 (CPU harness): losses "
               f"bitwise vs eager, "
               f"{overlap['overlap']['rs_interleaved_gaps']} "
               f"backward-interleaved reduce-scatter gaps, step_ms "
               f"ratio {overlap['overlap_vs_eager_step_ms']}x "
               f"(CPU-relative; async pairs measured "
               f"{overlap['overlap']['async_collective_pairs']} on this "
               f"backend, real on TPU)"
               if "error" not in overlap else "")
            + (f"; flight-recorder telemetry: "
               f"{telemetry['telemetry_overhead_pct']}% overhead with "
               f"tracing on (decode "
               f"{telemetry['decode_overhead_pct']}%, train step "
               f"{telemetry['train_step_overhead_pct']}%), token "
               f"streams + losses bitwise on==off"
               if "error" not in telemetry else "")
            + (f"; goodput ledger (CPU harness): goodput_fraction "
               f"{goodput['goodput_fraction']}, buckets sum to wall, "
               f"cost-registry+sentinel overhead "
               f"{goodput['telemetry_overhead_pct']}%, streams + "
               f"losses bitwise on==off, spec {goodput['chip_spec']}"
               if "error" not in goodput else "")
        ),
        "value": round(tok1, 1),
        "unit": "tokens/sec/chip",
        "vs_baseline": round(achieved / baseline, 3),
        "extra": {
            "remat_policy": "full",
            "remat_sweep_mbs": REMAT_SWEEP_MBS,
            "remat_sweep": remat_rows,
            "remat_selective_vs_full_tok_s": sel_vs_full,
            "mfu_seq1024": round(mfu1, 4),
            "tok_s_seq4096": round(tok4, 1),
            "mfu_seq4096": round(mfu4, 4),
            "tok_s_seq8192": round(tok8, 1),
            "mfu_seq8192": round(mfu8, 4),
            "flash_vs_xla_fwd_bwd_speedup": round(ratio, 2),
            **mxu,
            "decode_tok_s_b1": round(dec1, 1),
            "decode_tok_s_b8": round(dec8, 1),
            "decode_tok_s_b1_xla_attn": round(dec1_xla, 1),
            "decode_tok_s_b8_xla_attn": round(dec8_xla, 1),
            "decode_attn_kernel": True,
            **attn_stats,
            "decode_step_breakdown_b8": breakdown,
            "chip_spec": CHIP.label(),
            "serving": serving,
            "quant": quant,
            "kernel_unify": kunify,
            "ckpt": ckpt,
            "zero1": zero1,
            "overlap": overlap,
            "telemetry": telemetry,
            "goodput": goodput,
        },
    }))


if __name__ == "__main__":
    main()
