"""From a runner's raw material to the two printed lines."""

from __future__ import annotations

import importlib
import math

from harness import device, trace as tr


class Context:
    """What a per-layer reader may read: the runner's samples, the loaded
    trace (None when there is none), the cell, the device's peaks."""

    def __init__(self, cell, run, dev):
        self.cell = cell
        self.samples = run["samples"]
        self.trace = run["trace"]
        self.e2e = run["e2e"]
        self.device = dev
        self._peaks = None

    @property
    def peaks(self):
        if self._peaks is None:
            if self.device.get("rehearsal"):
                # no chip, no peak: shares of a peak come out as NaN and
                # are left out
                nan = float("nan")
                self._peaks = {"bf16_flops": nan, "hbm_bytes_per_s": nan}
            else:
                self._peaks = device.peaks(self.device["kind"])
        return self._peaks


def read_layer_metrics(cell, run, dev):
    """({metric: value}, {metric: error}) from each metric's own reader. A
    reader that finds nothing to read returns None and the metric is left
    out; one that raises is left out too and named on the info line, so
    that a per-layer reader (which has no bound) cannot cost a cell its
    run."""
    ctx = Context(cell, run, dev)
    out, errors = {}, {}
    for m in cell.per_layer:
        spec = cell.layer_files[m["name"]]
        try:
            reader = importlib.import_module(f"readers.{spec['reader']}")
            value = reader.read(ctx, spec.get("params", {}))
        except Exception as e:  # noqa: BLE001 — reported, never hidden
            errors[m["name"]] = f"{type(e).__name__}: {e}"
            continue
        if value is not None and math.isfinite(value):
            out[m["name"]] = float(value)
    return out, errors


def assemble(cell, run, dev, trace):
    """(info line's object, result line's object)."""
    rehearsal = dev.get("rehearsal", False)
    units = {m["name"]: m["unit"] for m in cell.end_to_end + cell.per_layer}
    device_obj = {"platform": dev["platform"], "kind": dev["kind"],
                  "count": dev["count"],
                  "memory_peak_bytes": run["memory_peak_bytes"]}
    info = dict(run["info"], workload=cell.name, traced=trace)
    line = {"correct": bool(run["correct"]), "attempted": run["attempted"],
            "failed": run["failed"]}
    if trace:
        values, info["reader_errors"] = read_layer_metrics(cell, run, dev)
        if run["trace"] is not None:
            busy, window = tr.busy_and_window(run["trace"])
            if not busy and not rehearsal:
                raise SystemExit("the trace holds no device operation: "
                                 "no result")
            device_obj["busy_s"], device_obj["window_s"] = busy, window
            line["breakdown"] = {
                "device_ops": [[k, v] for k, v in tr.top_ops(run["trace"])],
                "idle_gaps": [[k, v] for k, v
                              in tr.idle_gaps_by_span(run["trace"])]}
    else:
        values = dict(run["e2e"], setup_s=run["setup_s"])
        missing = [m["name"] for m in cell.end_to_end
                   if m["name"] not in values]
        if missing:
            raise SystemExit(f"the run measured no {missing}: no result")
        values = {m["name"]: values[m["name"]] for m in cell.end_to_end}
    if rehearsal:
        # a CPU rehearsal proves control flow and counts; its timings are
        # not the device's and are printed under no metric's name
        info["cpu_rehearsal_values"] = values
        values = {}
        line["rehearsal"] = True
    line["metrics"] = {k: {"value": v, "unit": units[k]}
                       for k, v in values.items()}
    line["device"] = device_obj
    return info, line
