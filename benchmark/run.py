#!/usr/bin/env python3
"""One run of one cell:

  python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A new process each time: it finds the cell's configuration, traffic mix
and per-layer metric files by the names in BENCHMARK.json and the
architecture's family by the configuration's `model_type`, builds the
model on the device from the seed, warms up, measures for --seconds, and
prints one JSON object as the last line of standard output. Without a
TPU (or with fewer chips than the cell asks for) it exits non-zero and
prints no result.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import faulthandler  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    # a run that outlasts its allowance is lost anyway: say where it hung
    faulthandler.dump_traceback_later(1150, exit=True)

    from benchmark import harness

    stages = harness.Stages(T_START)
    cell = harness.load_cell(args.workload)
    from benchmark import program  # needs the program under test

    program.enable_compile_cache()
    stages.mark("imports")
    device = harness.require_chips(cell["entry"]["chips"])
    stages.mark("chip_reached")
    kind = cell["mix"]["kind"]
    if kind == "train":
        from benchmark import train_cell as runner
    elif kind == "serve":
        from benchmark import serve_cell as runner
    else:
        raise SystemExit(f"unknown traffic kind {kind!r}")
    result = runner.run(cell, args.seed, args.seconds, bool(args.trace),
                        T_START, device, stages)
    from benchmark import check

    check.print_compared(result["compared"])
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)  # daemon threads of the engine must not hold the exit
