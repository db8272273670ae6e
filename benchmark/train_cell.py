"""A training cell's run: build the trainer once, follow its first steps
for the check, warm up, measure a window of whole steps, then compare
with the reference.

The window opens at the fence (loss fetch) of a warm-up step once two
consecutive step times agree, and closes at the fence of the last step
that ends inside `--seconds`. The rate divides by the time between those
two fences. Nothing of the harness runs in the window but the step loop:
batches are made before it, nothing is written, the garbage collector is
frozen, and with `--trace 0` there is no profiler.
"""

from __future__ import annotations

import gc
import math
import sys
import time

from . import check, families, harness, program, traffic

WARM_MIN, WARM_MAX, WARM_AGREE = 3, 12, 0.02


def follow(prog, texts) -> dict:
    """Drive `prog` through its first len(texts) steps, by the window's
    own call, and read what the reference is compared with: each step's
    loss, the first gradient as the optimizer got it (Adam's first moment
    after one step, over 1 - beta1), the parameters' change at the end."""
    readings = {"loss": []}
    for s, text in enumerate(texts):
        readings["loss"].append(prog.step(text))
        if s == 0:
            m = prog.first_moment_norms()
            readings["grad_norms"] = {
                k: v / (1.0 - prog.use["adam_beta1"]) for k, v in m.items()}
    readings["change_norms"] = prog.change_norms()
    return readings


def run(cell: dict, seed: int, seconds: float, trace_on: bool, t_start: float,
        device: dict, stages=None, fault: str | None = None) -> dict:
    cfg, mix = cell["cfg"], cell["mix"]
    use = cfg["train"]
    fam = families.find(cfg)
    chips = cell["entry"]["chips"]
    layers, seq = use["num_hidden_layers"], mix["seq_length"]
    tokens_per_step = mix["rows_per_step"] * seq
    compiles = harness.CompileCounter()
    stages = stages or harness.Stages(t_start)

    prog = program.TrainProgram(cfg, seed, chips, stages.mark)
    prog.fault = fault
    ref_steps = mix["reference_steps"]
    n_batches = ref_steps + WARM_MAX + max(64, int(seconds / 0.02))
    texts = traffic.train_batches(mix, seed, n_batches, cfg["vocab_size"])

    readings = follow(prog, texts[:ref_steps])
    stages.mark("followed_steps")

    # warm up: whole steps until two consecutive step times agree
    nxt = ref_steps
    prev = None
    for w in range(WARM_MAX):
        t0 = time.perf_counter()
        prog.step(texts[nxt])
        nxt += 1
        dt = time.perf_counter() - t0
        if w + 1 >= WARM_MIN and prev and abs(dt - prev) <= WARM_AGREE * prev:
            break
        prev = dt

    stages.mark("warm_up")
    pool = len(texts) - nxt  # batches of the window, gone round if short
    ends = [0.0] * (int(seconds / 0.001) + 2)
    losses = [0.0] * len(ends)
    tracer = harness.TraceWindow(trace_on, seconds)
    gc.collect()
    gc.freeze()
    tracer.start()
    n = 0
    step = prog.step
    clock = time.perf_counter
    t_open = clock()
    deadline = t_open + seconds
    while True:
        losses[n] = step(texts[nxt + n % pool])
        now = clock()
        ends[n] = now
        n += 1
        if now >= deadline or n >= len(ends):
            break
    gc.unfreeze()
    # the last step counts only if it ended inside the window
    done = n if ends[n - 1] <= deadline else n - 1
    t_close = ends[done - 1] if done else t_open
    window_s = t_close - t_open
    setup_s = t_open - t_start
    stages.mark("window")
    trace = tracer.finish()
    stages.mark("trace_read")

    step_ms = [(e - s) * 1e3 for s, e in
               zip([t_open] + ends[:done - 1], ends[:done])]
    tok_s_chip = done * tokens_per_step / window_s / chips
    fpt = fam.train_flops_per_token(cfg, layers, seq)
    values = {
        "train_tok_s_chip": tok_s_chip,
        "setup_s": setup_s,
        "train_step_ms_p50": harness.median(step_ms),
        "train_step_ms_max": max(step_ms),
        "compiles_in_window": compiles.between(t_open, t_close),
        "kernel_fallbacks": prog.kernel_fallbacks(),
        "window_flops": fpt * done * tokens_per_step,
        "window_s": window_s,
    }
    # what the last step's stats carry beside the loss: read once, here,
    # after the window has closed
    for name, value in prog.stats().items():
        values.setdefault(name, value)
    traced = {}
    if trace_on:
        # the traced part's own steps: fence to fence inside the trace
        inside = [e for e in ends[:done] if tracer.t0 <= e <= tracer.t1]
        if len(inside) >= 2:
            k = len(inside) - 1
            traced = {"steps": k, "tokens": k * tokens_per_step,
                      "seq_length": seq, "seconds": inside[-1] - inside[0]}
            values["traced_flops"] = fpt * traced["tokens"]
            values["traced_s"] = traced["seconds"]
    peak = harness.memory_peak_bytes(chips)
    values["peak_hbm_gb"] = peak / 1e9 if peak else None
    device = dict(device, memory_peak_bytes=peak)
    bad = sum(1 for x in losses[:done] if not math.isfinite(x))
    print(f"steps {done} window_s {window_s:.4f} step_ms "
          f"p50 {values['train_step_ms_p50']:.3f} max "
          f"{values['train_step_ms_max']:.3f} setup_s {setup_s:.2f}",
          file=sys.stderr)
    harness.write_record(
        f"steps_{cell['name']}_{seed}_{int(trace_on)}.json",
        {"step_ms": step_ms, "loss_first": readings["loss"],
         "window_s": window_s, "setup_s": setup_s})

    # the check: after the window, after the peak is read, state freed
    import jax

    devices = jax.devices()[:chips]
    prog.free()
    gc.collect()
    t_ref = time.perf_counter()
    reference = check.train_reference(
        cfg, seed, texts[:ref_steps], devices=devices)
    numbers = check.train_numbers(readings, reference)
    print(f"reference took {time.perf_counter() - t_ref:.1f} s",
          file=sys.stderr)
    ok, compared = check.verdict(numbers, cell["limits"])
    ok = ok and bad == 0 and done > 0

    ctx = harness.reader_context(cell, use, values, traced, tracer, trace,
                                 device)
    return harness.result_line(
        cell, trace_on, correct=ok, attempted=done, failed=bad,
        values=values, device=device, ctx=ctx, compared=compared)

