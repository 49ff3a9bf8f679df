"""The program's name for each device operation, from the traced run's file.

On this TPU a device event's name is its HLO line without metadata
("%fusion.770 = (f32[4,2048]{...}) fusion(...)") and it has no stat that
carries the name stack, so the component scopes the program enters
(jax.named_scope "pt.mlp", "pt.loss", ...; paddle_tpu/observability/
catalog.py TRACE_SCOPES) are not on the events themselves. The profiler
stores every executed module's HloProto in the trace's "/host:metadata"
plane, and there each instruction has metadata.op_name: its name stack,
e.g. "jit(train_step)/jit(main)/transpose(jvp(pt.mlp))/dot_general".
jax.profiler.ProfileData does not expose that plane's event metadata, so
this module reads the protobuf wire format directly (no dependency): only
the fields named below.

XLA gives a fusion the metadata of one of its members, so a share by scope
is exact for kernels and approximate at fusion boundaries.
"""

from __future__ import annotations

import bisect
import functools
import re

from harness import program_spans

METADATA_PLANE = "/host:metadata"
HLO_PROTO_STAT = "Hlo Proto"
_INSTRUCTION = re.compile(r"^%?([\w.\-]+)")


def _varint(buf, i):
    val = shift = 0
    while True:
        b = buf[i]
        i += 1
        val |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return val, i


def _fields(buf):
    """(field number, wire type, value) of one protobuf message: varints as
    int, length-delimited fields as memoryview, fixed ones skipped over."""
    buf = memoryview(buf)
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wt = key >> 3, key & 7
        if wt == 0:
            val, i = _varint(buf, i)
        elif wt == 2:
            size, i = _varint(buf, i)
            val, i = buf[i:i + size], i + size
        elif wt == 1:
            val, i = None, i + 8
        elif wt == 5:
            val, i = None, i + 4
        else:
            raise ValueError(f"wire type {wt} at byte {i}")
        yield num, wt, val


def _sub(buf, num):
    """The length-delimited values of field `num` of one message."""
    return [v for n, w, v in _fields(buf) if n == num and w == 2]


def _text(view):
    return bytes(view).decode("utf-8", "replace")


def _instruction_names(hlo_proto):
    """{instruction name: metadata.op_name} of one xla.HloProto:
    hlo_module = 1; HloModuleProto.computations = 3;
    HloComputationProto.instructions = 2; HloInstructionProto.name = 1,
    .metadata = 7; OpMetadata.op_name = 2."""
    out = {}
    for module in _sub(hlo_proto, 1):
        for comp in _sub(module, 3):
            for inst in _sub(comp, 2):
                name = op_name = ""
                for num, wt, val in _fields(inst):
                    if num == 1 and wt == 2:
                        name = _text(val)
                    elif num == 7 and wt == 2:
                        op_name = "".join(map(_text, _sub(val, 2)))
                out[name] = op_name
    return out


def modules(directory):
    """{module name as on the "XLA Modules" line, e.g. "jit_train_step(115)":
    {instruction name: op_name}} from the newest trace under `directory`;
    {} where there is no trace or no metadata plane."""
    path = program_spans.newest_xplane(directory)
    return _modules_of(path) if path else {}


@functools.lru_cache(maxsize=2)
def _modules_of(path):
    """XSpace.planes = 1; XPlane.name = 2, .event_metadata = 4 and
    .stat_metadata = 5 (maps: value = 2); XEventMetadata.name = 2,
    .stats = 5; XStat.metadata_id = 1, .bytes_value = 6;
    XStatMetadata.id = 1, .name = 2."""
    with open(path, "rb") as f:
        space = f.read()
    out = {}
    for plane in _sub(space, 1):
        if [_text(v) for v in _sub(plane, 2)] != [METADATA_PLANE]:
            continue
        hlo_stat_ids = set()
        for entry in _sub(plane, 5):
            for meta in _sub(entry, 2):
                stat = {n: v for n, _, v in _fields(meta)}
                if _text(stat.get(2, b"")) == HLO_PROTO_STAT:
                    hlo_stat_ids.add(stat.get(1))
        for entry in _sub(plane, 4):
            for meta in _sub(entry, 2):
                for xstat in _sub(meta, 5):
                    stat = {n: v for n, _, v in _fields(xstat)}
                    if stat.get(1) in hlo_stat_ids and 6 in stat:
                        name = "".join(map(_text, _sub(meta, 2)))
                        out[name] = _instruction_names(stat[6])
    return out


def self_seconds(ops):
    """Each operation's own seconds, in the order of `ops`: its duration
    less that of the operations nested directly inside it. On the "XLA
    Ops" line a `while` (a scan) or a call holds the operations of its
    body, so summed durations count that time once per level; own times
    sum to the line's busy time."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][1], -ops[i][2]))
    own = [op[2] for op in ops]
    open_ = []                      # (end, index) of the enclosing events
    for i in order:
        _, s, d, _ = ops[i]
        while open_ and open_[-1][0] <= s:
            open_.pop()
        if open_ and s + d <= open_[-1][0]:     # inside, not just overlapping
            own[open_[-1][1]] -= d
        open_.append((s + d, i))
    return [max(0.0, x) for x in own]


def named_ops(dev, module_names):
    """[(op_name, own seconds, module)] of one device plane's operations.
    An operation belongs to the module whose "XLA Modules" event contains
    its start; its instruction is the leading "%name" of the event's name;
    one the HLO gives no op_name has the empty one."""
    mods = sorted((s, s + d, name) for name, s, d, _ in dev["modules"])
    starts = [m[0] for m in mods]
    out = []
    for (name, s, _, _), own in zip(dev["ops"], self_seconds(dev["ops"])):
        k = bisect.bisect_right(starts, s) - 1
        module = mods[k][2] if k >= 0 and s < mods[k][1] else ""
        m = _INSTRUCTION.match(name)
        op_name = module_names.get(module, {}).get(m.group(1), "") if m \
            else ""
        out.append((op_name, own, module))
    return out


def op_name_seconds(trace, module_names, pattern):
    """Own seconds of the device operations whose op_name matches `pattern`
    (re.search), averaged over the device planes."""
    rx = re.compile(pattern)
    ndev = max(1, len(trace["devices"]))
    return sum(own for dev in trace["devices"].values()
               for op_name, own, _ in named_ops(dev, module_names)
               if rx.search(op_name)) / ndev
