"""The on-chip benchmark's one entry point.

    python3 benchmark/run.py --workload <config>.<traffic> --seed N \
        --seconds S --trace 0|1

Everything a cell needs is found by name: the cell in BENCHMARK.json, its
configuration in configs/<config>.json, its traffic in
traffic/<traffic>.json, the runner of the traffic's `kind` in
harness/runners/, the model family's loader in families/, and (traced run)
each per-layer metric's reader through layer_metrics/<metric>.json. Adding
a cell, a configuration, a mix or a metric adds files and entries; nothing
here is edited.

The last line of standard output is the result object the driver reads;
the line before it ("info") holds what that object may not: sample sizes,
the schedule's digest, compile seconds per program, cache hits and misses.
No chip, or fewer chips than the cell asks for, is exit code 3 and no
result: a CPU number is never printed under a device metric's name.
"""

from __future__ import annotations

import time

T_PROCESS_START = time.perf_counter()   # setup_s counts from here

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
for p in (ROOT, BENCH_DIR):
    if p not in sys.path:
        sys.path.insert(0, p)


def parse_args(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    # for the benchmark's own tests; the driver never passes them
    ap.add_argument("--benchmark-json", default=None,
                    help="another BENCHMARK.json (tests add a dummy cell)")
    ap.add_argument("--allow-cpu-rehearsal", action="store_true",
                    help="run without a chip; device metrics are withheld "
                         "and the result is marked as a rehearsal")
    return ap.parse_args(argv)


def main(argv=None):
    args = parse_args(sys.argv[1:] if argv is None else argv)
    from harness import cells, device, result

    cell = cells.load_cell(args.workload, args.benchmark_json)
    dev = device.require_chips(cell.chips, rehearsal=args.allow_cpu_rehearsal)
    if dev is None:
        return 3
    device.setup_compile_cache()

    runner = importlib.import_module(f"harness.runners.{cell.traffic['kind']}")
    run = runner.run(cell, seed=args.seed, seconds=args.seconds,
                     trace=bool(args.trace), t_start=T_PROCESS_START,
                     device_info=dev)
    info, line = result.assemble(cell, run, dev, trace=bool(args.trace))
    print("info " + json.dumps(info, sort_keys=True), flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
