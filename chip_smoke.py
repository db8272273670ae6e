#!/usr/bin/env python
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the program's main path once through the entry points a user calls,
at the full widths of Llama-2-7B (hidden 4096, 32 heads x 128, 32 KV groups,
ffn 11008, vocab 32000, seq 4096; depth cut to what one 16 GB chip holds),
with random weights made from a seed and a seeded synthetic corpus:

  train   finetune.main: a few optimizer steps (bf16, flash kernel, full
          remat), finite losses, a committed checkpoint
  serve   tools/run_text_generation_server.py main() on that checkpoint: the
          paged Pallas kernel behind chunked prefill + prefix cache; the
          parent is the HTTP client (mixed prompt lengths, one streamed)
  check   every Pallas entry point compiled at the smoke shapes against its
          XLA twin, and the served tokens/logprobs against generate_tokens

and, when the machine has four chips, tp4+sp training with the depth raised,
the server at --serving_tp 4 and at --router_replicas 4.

One process per chip: this parent imports neither jax nor the package; each
leg is a child (`--leg NAME`) that owns the chips for its lifetime and has
exited before the next starts. A failing leg fails the run; nothing is
caught and turned into a row. The last line of stdout is the result:

  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

`--leg train --layout dp2tp2zero1|pp2tp2|cp2tp2|pp2cp2` (four chips, by
hand) runs two training steps of one more mesh layout.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import re
import shutil
import signal
import subprocess
import sys
import threading
import time
from http.client import HTTPConnection

HERE = os.path.dirname(os.path.abspath(__file__))
WORK = os.path.join(HERE, ".chip_smoke")  # corpus + checkpoints, git-ignored
OUT = os.path.join(HERE, "chiprun_out")  # what comes back from a chip run
REPORT = os.path.join(OUT, "chip_smoke.json")
RESULT_TAG = "CHIP_SMOKE_LEG "
NULL_VOCAB = 31999  # NullTokenizer ids 0..31998 + eod 31999 -> vocab 32000
PORT = 5917

# the one model: Llama-2-7B widths come from the preset; only depth is cut
SEQ, GROUPS, HEAD_DIM = 4096, 32, 128
MODEL_FLAGS = ["--model_name", "llama2", "--model_size", "7",
               "--seq_length", str(SEQ),
               "--max_position_embeddings", str(SEQ)]
# how it is served (the kernel check runs at these shapes too); the page
# is the tool's default
CTX, SLOTS, PAGE = 2048, 8, 64
# training layouts: finetune.py flags and the depth each one holds. The
# first two are chip_smoke's own legs (they save what the servers load);
# the rest are two-step runs for four chips, by hand
LAYOUTS = {
    # 7.45 GB args + 5.08 GB temp of 16 GB (compile-only accounting)
    "one-chip": ([], 2),
    # 7.52 + 4.33 GB per chip (compile-only accounting)
    "tp4sp": (["--tensor_model_parallel_size", "4", "--sequence_parallel"],
              12),
    "dp2tp2zero1": (["--data_parallel_size", "2",
                     "--tensor_model_parallel_size", "2",
                     "--global_batch_size", "2",
                     "--use_distributed_optimizer"], 4),
    "pp2tp2": (["--pipeline_model_parallel_size", "2",
                "--tensor_model_parallel_size", "2",
                "--global_batch_size", "2"], 4),
    "cp2tp2": (["--context_parallel_size", "2",
                "--tensor_model_parallel_size", "2"], 4),
    # no tp to halve the weights: 11.3 GB per chip (compile-only)
    "pp2cp2": (["--pipeline_model_parallel_size", "2",
                "--context_parallel_size", "2",
                "--global_batch_size", "2"], 2),
}
TRAIN_ITERS = 4  # step 1 compiles; >= 3 post-compile steps

# HTTP traffic: mixed prompt lengths (one spans several prefill chunks),
# the first prompt asked twice (determinism + a prefix-cache hit)
PROMPT_LENS = (5, 37, 300, 5)
GEN_TOKENS = 12
# served vs generate_tokens logprobs at the same inputs differ by bf16
# rounding between two attention paths: measured 4.3e-2 on the v5e (PR 21,
# CHANGES.md) — about one bf16 ulp of a logit in [4, 8). The band leaves
# ~2x; a token flip is accepted only inside it
LOGPROB_TOL = 0.1
KERNEL_TOL = 2e-2  # bf16 kernel vs XLA twin, max-abs on O(1) inputs


# ---------------------------------------------------------------------------
# Parent side: no jax, no package
# ---------------------------------------------------------------------------


def run_leg(name, leg, extra=(), timeout=900):
    """Run one leg as a child that owns the chips; returns its result."""
    cmd = [sys.executable, os.path.abspath(__file__), "--leg", leg, *extra]
    print(f"=== leg {name}: {' '.join(cmd[2:])}", flush=True)
    t0 = time.time()
    proc = subprocess.Popen(cmd, cwd=HERE, stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT, text=True)
    try:
        with open(os.path.join(OUT, f"chip_smoke_{name}.log"), "w") as log:
            result = _pump(proc, timeout, log)
    finally:
        _stop(proc)
    if proc.returncode != 0 or result is None:
        raise SystemExit(f"leg {name} failed (exit {proc.returncode})")
    result["wall_s"] = round(time.time() - t0, 1)
    return result


def _pump(proc, timeout, log):
    """Echo the child's output (and keep it in `log`); pick out its
    result line. A child that outlives `timeout` is killed (its pipe
    then closes)."""
    watchdog = threading.Timer(timeout, proc.kill)
    watchdog.start()
    result = None
    try:
        for line in proc.stdout:
            if line.startswith(RESULT_TAG):
                result = json.loads(line[len(RESULT_TAG):])
            log.write(line)
            sys.stdout.write(line)
            sys.stdout.flush()
        proc.wait()
    finally:
        watchdog.cancel()
    return result


def _stop(proc):
    if proc.poll() is None:
        proc.terminate()
        try:
            proc.wait(timeout=30)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait()


def prompts_for(seed):
    import random

    rs = random.Random(seed)
    first = [rs.randrange(1, NULL_VOCAB) for _ in range(PROMPT_LENS[0])]
    out = [first]
    for n in PROMPT_LENS[1:-1]:
        out.append([rs.randrange(1, NULL_VOCAB) for _ in range(n)])
    out.append(list(first))
    return out


def _put(conn, body):
    conn.request("PUT", "/api", json.dumps(body),
                 {"Content-Type": "application/json"})
    return conn.getresponse()


def serve_leg(name, ckpt, extra_flags=(), timeout=900):
    """Start the server child, be its HTTP client, stop it. Returns the
    child's result plus the served answers."""
    cmd = [sys.executable, os.path.abspath(__file__), "--leg", "serve",
           "--ckpt", ckpt, "--", *extra_flags]
    print(f"=== leg {name}: serve {' '.join(extra_flags)}", flush=True)
    t0 = time.time()
    log_path = os.path.join(OUT, f"chip_smoke_{name}.log")
    with open(log_path, "w") as log:
        proc = subprocess.Popen(cmd, cwd=HERE, stdout=log,
                                stderr=subprocess.STDOUT, text=True)
        try:
            answers, metrics, ready_s = _drive_server(proc, t0, timeout)
            proc.send_signal(signal.SIGTERM)  # graceful: drain, exit 0
            proc.wait(timeout=120)
        finally:
            _stop(proc)
    result = None
    with open(log_path) as log:
        for line in log:
            if line.startswith(RESULT_TAG):
                result = json.loads(line[len(RESULT_TAG):])
            sys.stdout.write(line)
    if proc.returncode != 0 or result is None:
        raise SystemExit(f"leg {name} failed (exit {proc.returncode})")
    result.update(ready_s=ready_s, wall_s=round(time.time() - t0, 1),
                  # each first-of-its-shape request pays a compile
                  request_wall_s=[a["wall_s"] for a in answers],
                  serve_admitted=metrics["serve_admitted"],
                  serve_retired=metrics["serve_retired"])
    return result, answers


def _drive_server(proc, t0, timeout):
    while True:  # wait for /health
        if proc.poll() is not None:
            raise SystemExit(f"server exited early ({proc.returncode})")
        if time.time() - t0 > timeout:
            raise SystemExit("server did not come up")
        try:
            conn = HTTPConnection("127.0.0.1", PORT, timeout=5)
            conn.request("GET", "/health")
            if conn.getresponse().status == 200:
                break
        except OSError:
            time.sleep(1.0)
    ready_s = round(time.time() - t0, 1)
    conn = HTTPConnection("127.0.0.1", PORT, timeout=timeout)
    answers = []
    for p in prompts_for(0):
        t = time.time()
        resp = _put(conn, {"prompts": [" ".join(map(str, p))],
                           "tokens_to_generate": GEN_TOKENS, "top_k": 1,
                           "logprobs": True})
        body = json.loads(resp.read())
        if resp.status != 200:
            raise SystemExit(f"PUT /api -> {resp.status}: {body}")
        toks = [int(t) for t in body["text"][0].split()]
        if toks[:len(p)] != p or len(toks) <= len(p):
            raise SystemExit(f"answer does not extend its prompt: {toks}")
        answers.append({"prompt": p, "tokens": toks,
                        "logprobs": body["logprobs"][0],
                        "wall_s": round(time.time() - t, 2)})
    if answers[0]["tokens"] != answers[-1]["tokens"]:
        raise SystemExit("the same greedy prompt gave different tokens")
    # one streamed request: SSE events, one per token, then done
    resp = _put(conn, {"prompts": [" ".join(map(str, prompts_for(0)[1]))],
                       "tokens_to_generate": GEN_TOKENS, "top_k": 1,
                       "stream": True})
    if resp.headers["Content-Type"] != "text/event-stream":
        raise SystemExit("streamed PUT did not answer text/event-stream")
    events = [json.loads(line.decode()[6:]) for line in resp.fp
              if line.decode().startswith("data: ")]
    if not events or not events[-1].get("done"):
        raise SystemExit(f"stream did not finish: {events[-1:]}")
    conn = HTTPConnection("127.0.0.1", PORT, timeout=60)
    conn.request("GET", "/metrics")
    metrics = json.loads(conn.getresponse().read())
    if metrics["serve_admitted"] != metrics["serve_retired"] \
            or metrics["serve_admitted"] < len(answers) + 1:
        raise SystemExit(f"admitted != retired: {metrics}")
    if "serve_kernel_fallbacks" in metrics:
        raise SystemExit("a requested kernel gave way: "
                         + metrics["serve_kernel_fallbacks"])
    return answers, metrics, ready_s


def parent():
    shutil.rmtree(WORK, ignore_errors=True)
    os.makedirs(WORK)
    os.makedirs(OUT, exist_ok=True)
    legs = {}
    try:
        ckpt1 = os.path.join(WORK, "ckpt_1chip")
        legs["train"] = run_leg("train", "train", ["--ckpt", ckpt1])
        device = legs["train"]["device"]
        serve_flags = ["--serving_slots", str(SLOTS),
                       "--max_context", str(CTX)]
        legs["serve"], answers = serve_leg("serve", ckpt1, serve_flags)
        with open(os.path.join(WORK, "answers.json"), "w") as f:
            json.dump(answers, f)
        legs["check"] = run_leg("check", "check", ["--ckpt", ckpt1])
        if device["count"] >= 4:
            ckpt4 = os.path.join(WORK, "ckpt_tp4")
            legs["train_tp4"] = run_leg(
                "train_tp4", "train", ["--ckpt", ckpt4, "--layout", "tp4sp"],
                timeout=1500)
            legs["serve_tp4"], _ = serve_leg(
                "serve_tp4", ckpt4, serve_flags + ["--serving_tp", "4"])
            legs["serve_router4"], _ = serve_leg(
                "serve_router4", ckpt1,
                serve_flags + ["--router_replicas", "4"])
    finally:
        with open(REPORT, "w") as f:
            json.dump(legs, f, indent=1)
        shutil.rmtree(WORK, ignore_errors=True)
    for name, leg in legs.items():
        print(f"leg {name}: " + json.dumps(
            {k: v for k, v in leg.items() if k != "device"}), flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)


# ---------------------------------------------------------------------------
# Child side: each leg owns the chips for its lifetime
# ---------------------------------------------------------------------------


def claim_chips():
    """First JAX touch of a leg: refuse anything but a TPU."""
    sys.path.insert(0, HERE)
    from megatron_llm_tpu.utils.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    import jax

    dev = jax.devices()
    info = {"platform": dev[0].platform, "kind": dev[0].device_kind,
            "count": len(dev)}
    print(f"platform={info['platform']} device_kind={info['kind']} "
          f"count={info['count']} jax={jax.__version__} "
          f"compile_cache={cache_dir}", flush=True)
    if info["platform"] != "tpu":
        print("chip_smoke needs a TPU; refusing to run on "
              f"{info['platform']}", file=sys.stderr, flush=True)
        sys.exit(3)
    return info


def finish(info, used=1, **facts):
    """Common tail of a leg: nothing fell back, and the bytes are spread
    over the `used` devices the leg ran on."""
    import jax

    from megatron_llm_tpu.ops import dispatch

    if dispatch.fallbacks():
        raise SystemExit(f"requested kernels gave way: "
                         f"{dispatch.fallbacks()}")
    peak = [int(d.memory_stats()["peak_bytes_in_use"])
            for d in jax.devices()[:used]]
    mean = sum(peak) / len(peak)
    if max(peak) > 1.5 * mean:
        raise SystemExit(f"device bytes out of balance: {peak}")
    facts.update(device=info, kernels=dispatch.kernels(),
                 peak_bytes_per_device=peak)
    print(RESULT_TAG + json.dumps(facts), flush=True)


def write_corpus(prefix, seed=0, docs=48):
    """Seeded .bin/.idx corpus: enough tokens for the few steps taken."""
    import numpy as np

    from megatron_llm_tpu.data.indexed_dataset import (
        MMapIndexedDatasetBuilder,
    )

    rs = np.random.RandomState(seed)
    builder = MMapIndexedDatasetBuilder(prefix + ".bin", dtype=np.int32)
    for _ in range(docs):
        n = int(rs.randint(200, 2000))
        builder.add_item(rs.randint(1, NULL_VOCAB, size=n).astype(np.int32))
        builder.end_document()
    builder.finalize(prefix + ".idx")


class Tee:
    def __init__(self, stream):
        self.stream, self.text = stream, []

    def write(self, s):
        self.text.append(s)
        return self.stream.write(s)

    def flush(self):
        self.stream.flush()


def leg_train(args):
    info = claim_chips()
    import contextlib

    import finetune
    from megatron_llm_tpu.training.checkpointing import (
        checkpoint_dir,
        is_checkpoint_complete,
        read_tracker,
    )

    layout, depth = LAYOUTS[args.layout]
    iters = TRAIN_ITERS if args.ckpt else 2
    prefix = os.path.join(WORK, "corpus_text_document")
    if not os.path.exists(prefix + ".idx"):
        os.makedirs(WORK, exist_ok=True)
        write_corpus(prefix)
    argv = MODEL_FLAGS + [
        "--num_layers", str(depth),
        "--micro_batch_size", "1", "--data_parallel_size", "1",
        "--train_iters", str(iters),
        "--lr", "1e-4", "--lr_decay_style", "constant", "--bf16",
        "--use_flash_attn", "--recompute_granularity", "full",
        "--tokenizer_type", "NullTokenizer",
        "--null_vocab_size", str(NULL_VOCAB),
        "--data_path", prefix, "--split", "100,0,0",
        "--log_interval", "1", "--eval_interval", "1000",
        "--eval_iters", "0", *layout]
    if args.ckpt:
        # weights only: what serving loads (the optimizer state of the
        # 12-layer model would be another 20 GB of disk for nothing)
        argv += ["--save", args.ckpt, "--no_save_optim"]
    tee = Tee(sys.stdout)
    with contextlib.redirect_stdout(tee):
        finetune.main(argv)
    log = "".join(tee.text)
    losses = [float(x) for x in re.findall(r"lm loss: (\S+)", log)]
    step_ms = [float(x) for x in re.findall(
        r"elapsed time per iteration \(ms\): (\S+)", log)]
    if len(losses) != iters or not all(math.isfinite(x) for x in losses):
        raise SystemExit(f"expected {iters} finite losses, got {losses}")
    if args.ckpt:
        it, _ = read_tracker(args.ckpt)
        if it != iters or not is_checkpoint_complete(
                checkpoint_dir(args.ckpt, it)):
            raise SystemExit(f"no committed checkpoint at iteration {iters}")
    finish(info, used=4 if layout else 1, losses=losses,
           compile_step_s=round(step_ms[0] / 1e3, 1),
           step_ms=step_ms[1:], depth=depth, layout=args.layout)


def server_tool():
    """tools/run_text_generation_server.py as a module (tools/ is not a
    package)."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "run_text_generation_server",
        os.path.join(HERE, "tools", "run_text_generation_server.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    return tool


def leg_serve(args, tool_flags):
    info = claim_chips()
    tool = server_tool()
    t0 = time.time()
    # returns after SIGTERM from the parent has drained the engine
    tool.main(["--load", args.ckpt, "--tokenizer_type", "NullTokenizer",
               "--null_vocab_size", str(NULL_VOCAB), "--host", "127.0.0.1",
               "--port", str(PORT), *tool_flags])
    flag = dict(zip(tool_flags, tool_flags[1:]))
    finish(info, used=int(flag.get("--serving_tp", 1))
           * int(flag.get("--router_replicas", 1)),
           served_s=round(time.time() - t0, 1), flags=" ".join(tool_flags))


def leg_check(args):
    info = claim_chips()
    kernel_err = check_kernels()
    served = check_against_generate_tokens(args.ckpt)
    finish(info, kernel_max_abs_err=kernel_err, **served)


def check_kernels():
    """Every Pallas entry point, compiled at the smoke model's shapes,
    against its XLA twin: bf16, O(1) inputs, max-abs error (`err`)."""
    import jax
    import jax.numpy as jnp

    from megatron_llm_tpu.models.norms import rms_norm
    from megatron_llm_tpu.ops.decode_attention import decode_attention
    from megatron_llm_tpu.ops.flash_attention import (
        _xla_reference,
        flash_attention,
    )
    from megatron_llm_tpu.ops.prefill_attention import (
        ragged_paged_attention,
    )
    from megatron_llm_tpu.ops.rmsnorm import fused_rms_norm

    seq, ctx, g, d, bf16 = SEQ, CTX, GROUPS, HEAD_DIM, jnp.bfloat16
    keys = iter(jax.random.split(jax.random.key(0), 64))

    def rnd(*shape, dtype=bf16):
        return jax.random.normal(next(keys), shape, jnp.float32).astype(dtype)

    def err(a, b):
        # max-abs error in units of the twin's scale where that exceeds
        # 1 (a gradient of size 8 carries a bf16 ulp of 0.03 by itself)
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        return float(jnp.max(jnp.abs(a - b))
                     / jnp.maximum(1.0, jnp.max(jnp.abs(b))))

    out = {}
    # flash forward + backward at the training shape (batch 1, MHA)
    q, k, v, w = rnd(1, seq, g, 1, d), rnd(1, seq, g, d), \
        rnd(1, seq, g, d), rnd(1, seq, g, 1, d)

    def flash(use, q, k, v, w):
        def weighted(q, k, v):
            o = flash_attention(q, k, v, causal=True, use_pallas=True) \
                if use else _xla_reference(q, k, v, True)
            return jnp.sum(o.astype(jnp.float32) * w.astype(jnp.float32)), o

        (_, o), grads = jax.value_and_grad(
            weighted, argnums=(0, 1, 2), has_aux=True)(q, k, v)
        return o, grads

    flash = jax.jit(flash, static_argnums=0)
    (ok, gk), (ot, gt) = flash(True, q, k, v, w), flash(False, q, k, v, w)
    out["flash_fwd"] = err(ok, ot)
    out["flash_bwd"] = max(err(a, b) for a, b in zip(gk, gt))

    # the paged kernel at the serving shapes: decode row and a 256-wide
    # chunk; bf16 and int8 pools; full causal and a window
    slots, page = SLOTS, PAGE
    n_pages = slots * ctx // page + 1
    table = (1 + jnp.arange(slots * (ctx // page), dtype=jnp.int32)
             ).reshape(slots, ctx // page)
    # lane-packed pools: a token's g heads side by side
    kp, vp = rnd(n_pages, page, g * d), rnd(n_pages, page, g * d)
    k8 = jax.random.randint(next(keys), kp.shape, -127, 128, jnp.int8)
    v8 = jax.random.randint(next(keys), kp.shape, -127, 128, jnp.int8)
    ks = jax.random.uniform(next(keys), (n_pages, page, g), jnp.float32,
                            0.005, 0.02)

    def paged(use, window, qc, kn, vn, kpool, vpool, starts, lens, *scales):
        kw = dict(k_scales=scales[0], v_scales=scales[1]) if scales else {}
        return ragged_paged_attention(
            qc, kn, vn, kpool, vpool, table, starts, lens, use_pallas=use,
            window_size=window, **kw)[0]

    paged = jax.jit(paged, static_argnums=(0, 1))
    for C in (1, 256):
        # ragged spans: empty cache, mid-page, page-aligned, deep, idle
        # (0 tokens), half-filled chunk, and flush against the context end
        starts = jnp.asarray(
            [0, 5, 64, ctx // 7, ctx // 2, ctx // 2 + 77, ctx // 2 + 11,
             ctx - C], jnp.int32)
        lens = jnp.asarray([C, C, max(C // 2, 1), C, C, 0, C, C], jnp.int32)
        chunk = (rnd(slots, C, g, 1, d), rnd(slots, C, g, d),
                 rnd(slots, C, g, d))
        for tag, pools in (("bf16", (kp, vp)), ("int8", (k8, v8, ks, ks))):
            for window in (None, 300):
                ops = (*chunk, *pools[:2], starts, lens, *pools[2:])
                out[f"paged_C{C}_{tag}_w{window}"] = err(
                    paged(True, window, *ops), paged(False, window, *ops))

    # the dense decode kernel, both cache layouts (batch 4)
    def decode(use, layout, q, k, v):
        return decode_attention(q, k, v, jnp.int32(ctx * 3 // 5),
                                layout=layout, use_pallas=use)

    decode = jax.jit(decode, static_argnums=(0, 1))
    qd = rnd(4, 1, g, 1, d)
    for layout, shape in (("gtd", (4, g, ctx, d)), ("tgd", (4, ctx, g, d))):
        kc, vc = rnd(*shape), rnd(*shape)
        out[f"decode_{layout}"] = err(decode(True, layout, qd, kc, vc),
                                      decode(False, layout, qd, kc, vc))

    # fused RMSNorm forward + backward at (seq, hidden 4096)
    def norm(use, x, scale, gy):
        y, vjp = jax.vjp(
            lambda x, s: fused_rms_norm(x, s, 1e-5, use_pallas=True)
            if use else rms_norm(x, s, 1e-5), x, scale)
        return (y, *vjp(gy))

    norm = jax.jit(norm, static_argnums=0)
    ops = (rnd(seq, 4096), 1 + 0.1 * rnd(4096, dtype=jnp.float32),
           rnd(seq, 4096))
    (yk, dxk, dsk), (yt, dxt, dst) = norm(True, *ops), norm(False, *ops)
    out["rmsnorm_fwd"], out["rmsnorm_bwd_dx"] = err(yk, yt), err(dxk, dxt)
    out["rmsnorm_bwd_dscale"] = err(dsk, dst)

    for name, e in out.items():
        print(f"kernel {name}: max-abs err {e:.3e}", flush=True)
    bad = {n: e for n, e in out.items() if not e <= KERNEL_TOL}
    if bad:
        raise SystemExit(f"kernel vs XLA twin beyond {KERNEL_TOL}: {bad}")
    return {k: round(v, 5) for k, v in out.items()}


def check_against_generate_tokens(ckpt):
    """What the server answered over HTTP vs generate_tokens on the same
    checkpoint: token agreement, and logprob agreement wherever both
    paths saw the same inputs."""
    import jax.numpy as jnp
    import numpy as np

    from megatron_llm_tpu.inference.generation import (
        bucket_prefill_len,
        generate_tokens,
    )

    model, params, _ = server_tool().load_served_model(ckpt)
    with open(os.path.join(WORK, "answers.json")) as f:
        answers = json.load(f)
    worst, matched, total = 0.0, 0, 0
    for ans in answers[:-1]:  # the last repeats the first
        p, served = ans["prompt"], ans["tokens"]
        # generate_tokens allocates its cache at the buffer's length.
        # Rounded up to a multiple of decode_attn_min_cache (128) so the
        # reference's decode steps run the dense Pallas kernel in the
        # model: a shorter cache the config routes to XLA, and a length
        # with no power-of-two divisor >= 16 (the 312 of the long prompt)
        # the gate refuses — reported, and this leg would fail on it. The
        # reference then generates past the served answer and only the
        # common part is compared
        buf = np.zeros((1, -(-len(served) // 128) * 128), np.int32)
        buf[0, :len(p)] = p
        ref = generate_tokens(
            model, params, jnp.asarray(buf),
            jnp.asarray([len(p)], np.int32),
            prefill_len=bucket_prefill_len(len(p)), rng=None, top_k=1,
            termination_id=NULL_VOCAB, return_log_probs=True,
            vocab_size=NULL_VOCAB + 1)
        ref_toks = [int(t) for t in np.asarray(ref.tokens)[0]]
        ref_lp = np.asarray(ref.log_probs)[0]
        same = len(p)
        while same < len(served) and served[same] == ref_toks[same]:
            same += 1
        matched += same - len(p)
        total += len(served) - len(p)
        # logprob i scores token i+1; inputs agree through token same-1,
        # so entries < same are comparable, and entry same-1 at a flip
        # compares the two paths' winners: a near-tie, or a real defect
        upto = min(same, len(served) - 1)
        diff = float(np.max(np.abs(
            np.asarray(ans["logprobs"][:upto]) - ref_lp[:upto])))
        worst = max(worst, diff)
        print(f"prompt len {len(p)}: {same - len(p)}/{len(served) - len(p)}"
              f" generated tokens equal generate_tokens; max |dlogprob| "
              f"{diff:.3e}", flush=True)
    if worst > LOGPROB_TOL:
        raise SystemExit(f"served logprobs differ from generate_tokens by "
                         f"{worst} > {LOGPROB_TOL}")
    return {"generated_tokens_equal": f"{matched}/{total}",
            "max_logprob_diff": round(worst, 5)}


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--leg", choices=["train", "serve", "check"])
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--layout", choices=sorted(LAYOUTS), default="one-chip")
    args, rest = ap.parse_known_args()
    if args.leg is None:
        parent()
    elif args.leg == "train":
        leg_train(args)
    elif args.leg == "serve":
        leg_serve(args, [a for a in rest if a != "--"])
    else:
        leg_check(args)


if __name__ == "__main__":
    main()
