"""The readers of the program's own names on a hand-built trace file:
host spans (harness/program_spans.py, readers/program_span_ms.py), the
op_name of device operations (harness/op_names.py,
readers/scope_time_share.py) and the backward kernels' roofline share."""

import os
import types

import pytest

from harness import flops, op_names, program_spans
from readers import attn_bwd_roofline, program_span_ms, scope_time_share


# -- a protobuf encoder of a dozen lines: ints are varints, the rest bytes ----

def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _f(num, val):
    if isinstance(val, int):
        return _varint(num << 3) + _varint(val)
    val = val.encode() if isinstance(val, str) else val
    return _varint(num << 3 | 2) + _varint(len(val)) + val


def _map_entry(key, message):
    return _f(1, key) + _f(2, message)


def _instruction(name, op_name):
    return _f(1, name) + _f(2, "fusion") + (
        _f(7, _f(1, "op") + _f(2, op_name)) if op_name else b"")


def _hlo_proto(instructions):
    comp = _f(1, "main") + b"".join(_f(2, _instruction(n, o))
                                    for n, o in instructions)
    return _f(1, _f(1, "jit_train_step") + _f(3, comp))


STEP = [("fusion.1", "jit(train_step)/jvp(pt.mlp)/dot_general"),
        ("fusion.2", "jit(train_step)/transpose(jvp(pt.mlp))/dot_general"),
        ("fusion.3", "jit(train_step)/jvp(pt.loss)/jit(log_softmax)/sub"),
        ("fa_bwd_dq.4", "jit(train_step)/transpose(jvp(pt.attn))/fa_bwd_dq/"
                        "pallas_call"),
        ("copy.5", ""),
        ("fusion.6", "jit(train_step)/params['gpt.h.0.fc1.weight']")]
OTHER = [("fusion.1", "jit(convert_element_type)/convert_element_type")]


def _xspace():
    metadata = (
        _f(2, op_names.METADATA_PLANE)
        + _f(5, _map_entry(3, _f(1, 3) + _f(2, "Hlo Proto")))
        + _f(4, _map_entry(7, _f(1, 7) + _f(2, "jit_train_step(7)")
                           + _f(5, _f(1, 3) + _f(6, _hlo_proto(STEP)))))
        + _f(4, _map_entry(8, _f(1, 8) + _f(2, "jit_convert(8)")
                           + _f(5, _f(1, 3) + _f(6, _hlo_proto(OTHER))))))

    def event(meta_id, offset_us, dur_us, stats=b""):
        return _f(4, _f(1, meta_id) + _f(2, offset_us * 10 ** 6)
                  + _f(3, dur_us * 10 ** 6) + stats)

    host = (
        _f(2, "/host:CPU")
        + _f(4, _map_entry(1, _f(1, 1) + _f(2, "trainer.step")))
        + _f(4, _map_entry(2, _f(1, 2) + _f(2, "trainer.stage")))
        + _f(4, _map_entry(3, _f(1, 3) + _f(2, "serving.phase")))
        + _f(4, _map_entry(4, _f(1, 4) + _f(2, "bench.train_step")))
        + _f(5, _map_entry(1, _f(1, 1) + _f(2, "step_num")))
        + _f(5, _map_entry(2, _f(1, 2) + _f(2, "phase")))
        + _f(3, _f(1, 1) + _f(2, "python") + _f(3, 1000)
             + event(4, 0, 7000)
             + event(1, 100, 6000, _f(4, _f(1, 1) + _f(4, 5)))
             + event(2, 200, 3000)
             + event(1, 8000, 4000, _f(4, _f(1, 1) + _f(4, 6)))
             + event(1, 14000, 9000, _f(4, _f(1, 1) + _f(4, 7)))
             + event(3, 30000, 2000, _f(4, _f(1, 2) + _f(5, "hostsync")))
             + event(3, 32000, 500, _f(4, _f(1, 2) + _f(5, "commit")))
             + event(3, 33000, 1000, _f(4, _f(1, 2) + _f(5, "hostsync")))))
    return _f(1, metadata) + _f(1, host)


@pytest.fixture()
def traced_cell(tmp_path, monkeypatch):
    monkeypatch.setenv("TMPDIR", str(tmp_path))
    op_names._modules_of.cache_clear()
    d = os.path.join(program_spans.trace_dir("c.t"), "plugins", "profile",
                     "2026")
    os.makedirs(d)
    with open(os.path.join(d, "host.xplane.pb"), "wb") as f:
        f.write(_xspace())
    return "c.t"


def _device_trace():
    # one step program 0-10 s and a small one 10-11 s on one device
    ops = [("%fusion.1 = bf16[8,8]{1,0} fusion(...)", 0.0, 4.0, {}),
           ("%fusion.2 = bf16[8,8]{1,0} fusion(...)", 4.0, 2.0, {}),
           ("%fusion.3 = f32[8]{0} fusion(...)", 6.0, 1.0, {}),
           ("%fa_bwd_dq.4 = bf16[8,8]{1,0} custom-call(...), "
            "custom_call_target=\"tpu_custom_call\"", 7.0, 2.0, {}),
           ("%copy.5 = bf16[8]{0} copy(...)", 9.0, 0.5, {}),
           ("%fusion.6 = bf16[8]{0} fusion(...)", 9.5, 0.5, {}),
           ("%fusion.1 = f32[] fusion(...)", 10.0, 1.0, {}),
           ("%fusion.9 = f32[] fusion(...)", 12.0, 1.0, {})]   # no module
    mods = [("jit_train_step(7)", 0.0, 10.0, {}),
            ("jit_convert(8)", 10.0, 1.0, {})]
    return {"devices": {0: {"ops": ops, "modules": mods}}, "host": []}


def _ctx(cell_name, trace=None, **samples):
    cell = types.SimpleNamespace(name=cell_name, traffic={"trace_steps": 3})
    return types.SimpleNamespace(
        cell=cell, trace=trace, samples=samples,
        peaks={"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11})


def test_host_spans_by_name_with_their_stats(traced_cell):
    d = program_spans.trace_dir(traced_cell)
    steps = program_spans.host_events(d, r"^trainer\.step$")
    assert [round(e[2], 6) for e in steps] == [0.006, 0.004, 0.009]
    assert [e[3]["step_num"] for e in steps] == [5, 6, 7]
    # not only bench.*: every host span of the program is within reach
    names = {e[0] for e in program_spans.host_events(d, r".")}
    assert names == {"trainer.step", "trainer.stage", "serving.phase",
                     "bench.train_step"}
    phases = program_spans.seconds_by_stat(
        program_spans.host_events(d, r"^serving\.phase$"), "phase")
    assert phases == {"hostsync": pytest.approx(0.003),
                      "commit": pytest.approx(0.0005)}


def test_program_span_ms_is_the_median(traced_cell):
    ctx = _ctx(traced_cell, trace=_device_trace())
    assert program_span_ms.read(ctx, {"regex": r"^trainer\.step$"}) == \
        pytest.approx(6.0)
    assert program_span_ms.read(ctx, {"regex": r"^no\.such$"}) is None
    assert program_span_ms.read(_ctx(traced_cell), {"regex": "."}) is None


def test_op_names_come_from_the_hlo_protos(traced_cell):
    mods = op_names.modules(program_spans.trace_dir(traced_cell))
    assert set(mods) == {"jit_train_step(7)", "jit_convert(8)"}
    assert mods["jit_train_step(7)"] == dict(STEP)
    assert mods["jit_convert(8)"] == dict(OTHER)


def test_seconds_by_op_name_respect_the_module_an_op_ran_in(traced_cell):
    mods = op_names.modules(program_spans.trace_dir(traced_cell))
    t = _device_trace()
    assert op_names.op_name_seconds(t, mods, r"\bpt\.mlp\b") == 6.0
    assert op_names.op_name_seconds(t, mods, r"\bpt\.(head|loss)\b") == 1.0
    assert op_names.op_name_seconds(t, mods, r"\bfa_bwd_dq\b") == 2.0
    # unnamed: the copy, the parameter fusion ("gpt." is no scope), the
    # small program's fusion.1 (same instruction name, another module) and
    # the operation no module covers
    assert op_names.op_name_seconds(
        t, mods, r"^(?!.*\b(pt\.|fa_))") == 0.5 + 0.5 + 1.0 + 1.0


def test_scope_time_share_partitions_the_busy_time(traced_cell):
    ctx = _ctx(traced_cell, trace=_device_trace())
    busy = 12.0
    share = lambda rx: scope_time_share.read(ctx, {"regex": rx})  # noqa: E731
    parts = [share(r"\bpt\.mlp\b"), share(r"\bpt\.(head|loss)\b"),
             share(r"\bpt\.attn\b"), share(r"^(?!.*\b(pt\.|fa_))")]
    assert parts == [pytest.approx(100 * x / busy)
                     for x in (6.0, 1.0, 2.0, 3.0)]
    assert sum(parts) == pytest.approx(100.0)
    assert share(r"\bpt\.opt\b") is None          # nothing to read


def test_a_program_without_names_reads_nothing_and_does_not_raise(
        tmp_path, monkeypatch):
    monkeypatch.setenv("TMPDIR", str(tmp_path))       # no trace file at all
    ctx = _ctx("c.t", trace=_device_trace())
    assert scope_time_share.read(ctx, {"regex": r"\bpt\.mlp\b"}) is None
    assert program_span_ms.read(ctx, {"regex": r"^trainer\.step$"}) is None
    assert program_spans.host_events(program_spans.trace_dir("c.t"), ".") \
        == []


def test_attn_bwd_roofline_credits_twice_the_forward():
    shapes = {"layers": 2, "heads": 4, "head_dim": 8}
    ctx = _ctx("c.t", trace=_device_trace(), shapes=shapes, batch=3, seq=16,
               chips=1)
    ops = 2.0 * 3 * 2 * 3 * flops.attention_fwd_flops(shapes, 16)
    got = attn_bwd_roofline.read(
        ctx, {"regex": "fa_bwd_(dq|dkv)", "field": "name"})
    assert got == pytest.approx(100.0 * (ops / 1e12) / 2.0)
    assert attn_bwd_roofline.read(ctx, {"regex": "fa_fwd"}) is None


def test_a_while_counts_its_own_time_not_its_body():
    # a scan: while 0-10 holding an inner while 1-9 that holds two leaves;
    # an operation that only overlaps the while's end is no part of it
    ops = [("%while.1 = s32[] while(...)", 0.0, 10.0, {}),
           ("%while.2 = s32[] while(...)", 1.0, 8.0, {}),
           ("%fusion.3 = bf16[8]{0} fusion(...)", 2.0, 3.0, {}),
           ("%fusion.4 = bf16[8]{0} fusion(...)", 5.0, 3.5, {}),
           ("%copy.5 = bf16[8]{0} copy(...)", 9.5, 1.0, {})]
    own = op_names.self_seconds(ops)
    assert own == pytest.approx([2.0, 1.5, 3.0, 3.5, 1.0])
    names = {"jit_decode(1)": {"while.1": "jit(decode)/while",
                               "while.2": "jit(decode)/while/body/while",
                               "fusion.3": "pt.attn/pt.serve.gather/gather",
                               "fusion.4": "pt.mlp/dot_general"}}
    t = {"devices": {0: {"ops": ops,
                         "modules": [("jit_decode(1)", 0.0, 10.5, {})]}}}
    assert op_names.op_name_seconds(
        t, names, r"\bpt\.serve\.gather\b") == pytest.approx(3.0)
    assert op_names.op_name_seconds(
        t, names, r"^(?!.*\b(pt\.|fa_))") == pytest.approx(2.0 + 1.5 + 1.0)
