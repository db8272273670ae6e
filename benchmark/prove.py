#!/usr/bin/env python3
"""The readings the limits in `limits/<cell>.json` are set from, taken on
the chip at the cell's own size, many seeds in one process. Not part of a
benchmark run.

  python3 benchmark/prove.py <workload> <first seed> <seeds> [controls] [seconds]

For each seed: the program's own numbers against the float32 reference
(the lower readings). For the first `controls` seeds also the upper
readings: the int8 reference put in the program's place, and for a
training cell each fault it can have, planted in the reference put in the
program's place (half of the batch left out; on several chips the
exchange left out; a state left unchanged reads 1 by the measure and
needs no run). One JSON line per reading, on standard output and in
`chiprun_out/prove_<workload>.jsonl`.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import check, harness, program, traffic  # noqa: E402


def emit(out, limits=None, **row):
    if limits is not None:
        # under the limits file as it stands: the program's runs have to
        # read true, a control's and a fault's false
        row["correct"], _ = check.verdict(row["numbers"], limits)
    line = json.dumps(row)
    print(line, flush=True)
    out.write(line + "\n")
    out.flush()


def prove_train(cell, seeds, controls, out):
    import jax

    from benchmark import train_cell

    cfg, mix = cell["cfg"], cell["mix"]
    chips = cell["entry"]["chips"]
    devices = jax.devices()[:chips]
    faults = ["half_batch"] + (["no_exchange"] if chips > 1 else [])
    limits = cell["limits"]
    for k, seed in enumerate(seeds):
        t0 = time.perf_counter()
        texts = traffic.train_batches(mix, seed, mix["reference_steps"],
                                      cfg["vocab_size"])
        prog = program.TrainProgram(cfg, seed, chips)
        got = train_cell.follow(prog, texts)
        prog.free()
        want = check.train_reference(cfg, seed, texts, devices=devices)
        emit(out, limits, what="program", seed=seed, loss=got["loss"],
             numbers=check.train_numbers(got, want),
             seconds=time.perf_counter() - t0)
        if k >= controls:
            continue
        ctl = check.train_reference(cfg, seed, texts, precision="int8",
                                    devices=devices)
        emit(out, limits, what="control_int8", seed=seed,
             numbers=check.train_numbers(ctl, want))
        for fault in faults:
            bad = check.train_reference(cfg, seed, texts, fault=fault,
                                        devices=devices)
            emit(out, limits, what="fault_" + fault, seed=seed,
                 numbers=check.train_numbers(bad, want))


def prove_serve(cell, seeds, controls, seconds, out, device):
    from benchmark import serve_cell

    for k, seed in enumerate(seeds):
        t0 = time.perf_counter()
        line = serve_cell.run(cell, seed, seconds, False, t0, device,
                              control="int8" if k < controls else None)
        emit(out, what="program", seed=seed, numbers=line["compared"],
             attempted=line["attempted"], failed=line["failed"],
             metrics=line["metrics"], seconds=time.perf_counter() - t0)
        if "control" in line:
            emit(out, what="control_int8", seed=seed,
                 numbers=line["control"])


def main():
    workload, first, n = sys.argv[1], int(sys.argv[2]), int(sys.argv[3])
    controls = int(sys.argv[4]) if len(sys.argv) > 4 else 3
    seconds = float(sys.argv[5]) if len(sys.argv) > 5 else 25.0
    cell = harness.load_cell(workload)
    program.enable_compile_cache()
    device = harness.require_chips(cell["entry"]["chips"])
    seeds = [first + 7919 * i for i in range(n)]
    os.makedirs(harness.OUT_DIR, exist_ok=True)
    with open(os.path.join(harness.OUT_DIR, f"prove_{workload}.jsonl"),
              "a") as out:
        if cell["mix"]["kind"] == "train":
            prove_train(cell, seeds, controls, out)
        else:
            prove_serve(cell, seeds, controls, seconds, out, device)
    sys.stdout.flush()
    os._exit(0)  # the engines' daemon threads must not hold the exit


if __name__ == "__main__":
    main()
