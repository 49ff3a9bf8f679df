"""Read a training cell's comparison (its family's `reference_loss`: every
sub-block, gradient and first step against the reference, with the limits'
verdicts) on several seeds and planted faults in ONE process, so that the
compiles are paid once:

    python3 benchmark/tools/block_readings.py \
        --workload phi-4-mini-flash-reasoning-d10.pretrain-32k \
        --seeds 3000000029 3800000071 --plants "" bf16 \
        --out chiprun_out/readings.jsonl

For each seed the cell's trainer is built as the benchmark builds it and
the comparison made on the benchmark's first batch; for each plant the
family's variable (`<FAMILY>_PLANT`) is set first ("" for none). The line
the family prints on standard error ("<family> blocks {...}") goes to
--out as one JSON object with its seed and plant. Nothing is timed. A
tool, not part of the yardstick; families whose comparison takes a planted
fault from such a variable can use it.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
for p in (ROOT, BENCH_DIR):
    if p not in sys.path:
        sys.path.insert(0, p)


class _Lines:
    """Standard error, with the lines that start with `tag` kept."""

    def __init__(self, stream, tag):
        self.stream, self.tag, self.kept = stream, tag, []

    def write(self, text):
        for line in text.splitlines():
            if line.startswith(self.tag):
                self.kept.append(json.loads(line[len(self.tag):]))
        return self.stream.write(text)

    def flush(self):
        self.stream.flush()


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--plants", nargs="+", default=[""])
    ap.add_argument("--out", required=True)
    ap.add_argument("--benchmark-json", default=None)
    args = ap.parse_args(argv)
    from harness import cells, device

    cell = cells.load_cell(args.workload, args.benchmark_json)
    name = cell.config["family"]
    family = importlib.import_module(f"families.{name}")
    device.setup_compile_cache()
    lines = _Lines(sys.stderr, f"{name} blocks ")
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    sys.stderr = lines
    try:
        _read(args, cell, family, name, lines)
    finally:
        sys.stderr = lines.stream
    return 0


def _read(args, cell, family, name, lines):
    import gc
    from harness.runners.train import _batches
    with open(args.out, "a") as out:
        for seed in args.seeds:
            trainer, cfg, _ = family.build_trainer(cell.config, cell.traffic,
                                                   seed)
            ids = _batches(cell.traffic, cfg.vocab_size, seed)[0]
            for plant in args.plants:
                os.environ[f"{name.upper()}_PLANT"] = plant
                loss = family.reference_loss(trainer, cfg, ids)
                row = dict(lines.kept.pop(), seed=seed, plant=plant,
                           returned=loss)
                out.write(json.dumps(row) + "\n")
                out.flush()
            del trainer
            gc.collect()


if __name__ == "__main__":
    sys.exit(main())
