"""Median duration (ms) of the program's host spans whose name matches, in
the traced window (harness/program_spans.py). None where the program has
no such span. params: {"regex"}"""

from harness import program_spans, stats


def read(ctx, params):
    if ctx.trace is None:
        return None
    events = program_spans.host_events(
        program_spans.trace_dir(ctx.cell.name), params["regex"])
    if not events:
        return None
    return 1e3 * stats.percentile([d for _, _, d, _ in events], 50)
