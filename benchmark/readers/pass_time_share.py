"""Time of the device operations of one pass of a train step (forward, the
program's recomputation, XLA's own rematerialisation, backward, update),
as a share (%) of the device's busy time in the traced window.

The pass is the PROGRAM's to say: `paddle_tpu.observability.catalog
.trace_pass(op_name, instruction_name)` reads it off the names a compiled
step carries (TRACE_PASSES; held by tests/test_trace_names.py against every
model's compiled step). A program that has no such rule (an older commit)
reads nothing here. XLA gives a fusion the metadata of one of its members:
a recomputed elementwise chain fused into a backward matmul counts as
backward.

Every instant of the busy time goes to the INNERMOST operation running then
(`innermost_seconds`: a `while` less its body, in whole nanoseconds), so the
five passes partition the busy time exactly. `op_names.self_seconds`, which
the scope readers use, compares float ends: where one operation ends on the
nanosecond the next starts, it takes the first for still open and leaves
the second out of the enclosing loop's subtraction, and its own times sum
to 101.4-103.3% of busy in the three cells whose steps have loops (PR 36;
ROADMAP B5). `op_names.named_ops` does not return an operation's
instruction name either, which the rule needs beside the op_name (XLA's
rematerialisation shows in the instruction, `fusion.12.remat`), so
`passed_ops` builds its list here from the same HloProto. None where the
trace holds no HLO metadata or nothing matches. params: {"pass", "regex"
(optional, on the op_name as scope_time_share's)}"""

import bisect
import heapq
import re

from harness import op_names, program_spans, trace as tr


def _rule():
    try:
        from paddle_tpu.observability.catalog import trace_pass
    except ImportError:
        return None
    return trace_pass


def innermost_seconds(ops):
    """Each operation's seconds as the innermost one running, in the order
    of `ops`: at every instant the time goes to the running operation that
    started last (of two that start together, the shorter). Whatever the
    nesting, the values sum to the union of the operations' intervals.
    Times are taken back to the whole nanoseconds the trace holds."""
    spans = [(round(s * 1e9), round((s + d) * 1e9)) for _, s, d, _ in ops]
    order = sorted(range(len(ops)),
                   key=lambda i: (spans[i][0], -spans[i][1]))
    own = [0] * len(ops)
    running = []                    # heap of (-rank, end, index)
    cursor = 0

    def advance(to):
        nonlocal cursor
        while running and cursor < to:
            _, end, i = running[0]
            if end <= cursor:
                heapq.heappop(running)
                continue
            upto = min(end, to)
            own[i] += upto - cursor
            cursor = upto
        cursor = max(cursor, to)

    for rank, i in enumerate(order):
        advance(spans[i][0])
        heapq.heappush(running, (-rank, spans[i][1], i))
    advance(max((e for _, e in spans), default=0))
    return [n * 1e-9 for n in own]


def passed_ops(dev, module_names, rule):
    """[(op_name, instruction name, pass, own seconds)] of one device
    plane's operations; the module of an operation and its instruction as
    `op_names.named_ops` finds them."""
    mods = sorted((s, s + d, name) for name, s, d, _ in dev["modules"])
    starts = [m[0] for m in mods]
    out = []
    for (name, s, _, _), own in zip(dev["ops"],
                                    innermost_seconds(dev["ops"])):
        k = bisect.bisect_right(starts, s) - 1
        module = mods[k][2] if k >= 0 and s < mods[k][1] else ""
        m = op_names._INSTRUCTION.match(name)
        inst = m.group(1) if m else ""
        op_name = module_names.get(module, {}).get(inst, "")
        out.append((op_name, inst, rule(op_name, inst), own))
    return out


def pass_seconds(trace, module_names, rule, which, pattern=None):
    """Own seconds of the operations of pass `which` (whose op_name matches
    `pattern`, where one is given), averaged over the device planes."""
    rx = re.compile(pattern) if pattern else None
    ndev = max(1, len(trace["devices"]))
    return sum(own for dev in trace["devices"].values()
               for op_name, _, p, own in passed_ops(dev, module_names, rule)
               if p == which and (rx is None or rx.search(op_name))) / ndev


def read(ctx, params):
    rule = _rule()
    if ctx.trace is None or rule is None:
        return None
    names = op_names.modules(program_spans.trace_dir(ctx.cell.name))
    if not names:
        return None
    secs = pass_seconds(ctx.trace, names, rule, params["pass"],
                        params.get("regex"))
    busy, _ = tr.busy_and_window(ctx.trace)
    if not secs or not busy:
        return None
    return 100.0 * secs / busy
