#!/usr/bin/env python3
"""Find the serving shape and the knee, once, on the chip. Not part of a
benchmark run: its findings are written as numbers into the configuration
and traffic files.

  python3 benchmark/sweep.py shapes "8x512,16x128,32x64" [seconds] [config]
      saturated tokens/s of the offline-batch mix for each slots x chunk
  python3 benchmark/sweep.py rates "0.4,0.6,0.8,1.0" [seconds] [config]
      chat-steady at each fixed rate, with the configuration file's shape
One process, one set of weights; each shape builds its own engine.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import harness, program, serve_cell, traffic  # noqa: E402


DEFAULT_CONFIG = "falcon-7b"  # a by-hand tool's default, no more


def drive(prog, mix, seed, seconds, slots, vocab):
    arr = mix["arrivals"]
    open_loop = arr["process"] == "poisson"
    if open_loop:
        reqs = traffic.serve_requests(mix, seed, seconds, vocab)
        target = 0
    else:
        target = slots * (1 + arr["waiting_per_slot"])
        reqs = traffic.serve_requests(
            mix, seed, seconds, vocab,
            n=target + int((seconds + mix["ramp_s"])
                         * arr["max_rate_per_s"]))
    load = serve_cell.Load(prog, reqs, open_loop, target)
    load.t_open = time.perf_counter()
    load.thread.start()
    time.sleep(0 if open_loop else mix["ramp_s"])
    c_open = prog.counters()
    t_open = load.t_open if open_loop else time.perf_counter()
    time.sleep(t_open + seconds - time.perf_counter())
    t_close = time.perf_counter()
    c_close = prog.counters()
    done = sum(1 for r in load.records if r["tap"].closed
               and t_open <= r["tap"].closed <= t_close)
    backlog = sum(1 for r in load.records if r["req"] is not None
                  and not r["req"].done.is_set())
    if open_loop:
        load.thread.join(timeout=5)
        for rec in load.records:
            if rec["req"] is not None:
                rec["req"].done.wait(max(t_close + 60 - time.perf_counter(),
                                         0))
    load.stop = True
    toks = sum(1 for r in load.records for t in r["tap"].stamps
               if t_open <= t <= t_close)
    ttft = [(r["tap"].stamps[0] - r["due"]) * 1e3 for r in load.records
            if r["tap"].stamps]
    tpot = [(r["tap"].stamps[-1] - r["tap"].stamps[0])
            / (len(r["tap"].stamps) - 1) * 1e3 for r in load.records
            if len(r["tap"].stamps) > 1 and r["req"].done.is_set()]
    return {"tok_s": toks / (t_close - t_open), "requests": len(load.records),
            "finished_per_s": done / (t_close - t_open),
            "rounds": c_close["steps"] - c_open["steps"],
            "prefill_tokens": c_close["prefill_tokens"]
            - c_open["prefill_tokens"],
            "in_flight_at_close": backlog,
            "ttft_p50_ms": harness.percentile(ttft, 0.5),
            "ttft_p90_ms": harness.percentile(ttft, 0.9),
            "tpot_p50_ms": harness.percentile(tpot, 0.5),
            "tpot_p90_ms": harness.percentile(tpot, 0.9),
            "drain_s": time.perf_counter() - t_close}


def main():
    what, arg = sys.argv[1], sys.argv[2]
    seconds = float(sys.argv[3]) if len(sys.argv) > 3 else 12.0
    program.enable_compile_cache()
    harness.require_chips(1)
    config = sys.argv[4] if len(sys.argv) > 4 else DEFAULT_CONFIG
    cfg = harness.load_json(harness.HERE, "configs", config + ".json")
    vocab, seed = cfg["vocab_size"], 4242
    made = program.ServeProgram.make_weights(cfg, seed, cfg["serve"])
    if what == "shapes":
        mix = harness.load_json(harness.HERE, "traffic", "offline-batch.json")
        for shape in arg.split(","):
            slots, chunk = (int(x) for x in shape.split("x"))
            use = dict(cfg["serve"], slots=slots, prefill_chunk_tokens=chunk)
            t0 = time.perf_counter()
            try:
                prog = program.ServeProgram(cfg, seed, use=use, made=made)
            except Exception as e:  # noqa: BLE001 a shape that does not fit
                print(json.dumps({"shape": shape, "refused": repr(e)[:300]}),
                      flush=True)
                continue
            row = drive(prog, mix, seed, seconds, slots, vocab)
            row.update(shape=shape, build_s=time.perf_counter() - t0,
                       peak_gb=harness.memory_peak_bytes(1) / 1e9)
            print(json.dumps(row), flush=True)
            prog.free(keep_weights=True)
    else:
        mix = harness.load_json(harness.HERE, "traffic", "chat-steady.json")
        prog = program.ServeProgram(cfg, seed, made=made)
        for rate in arg.split(","):
            m = dict(mix, arrivals={"process": "poisson",
                                    "rate_per_s": float(rate)})
            row = drive(prog, m, seed, seconds, cfg["serve"]["slots"], vocab)
            row["rate_per_s"] = float(rate)
            print(json.dumps(row), flush=True)
    sys.stdout.flush()
    os._exit(0)


if __name__ == "__main__":
    main()
