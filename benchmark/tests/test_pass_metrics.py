"""The pass of an operation and the parts of a mixer, read off a hand-built
trace: readers/pass_time_share.py, the eleven layer_metrics files of PR 36
and tools/pass_report.py. The rule itself is the program's
(paddle_tpu.observability.catalog.trace_pass; tests/test_trace_names.py
holds it against compiled steps)."""

import importlib.util
import json
import os
import types

import pytest

from harness import cells, op_names, program_spans
from readers import pass_time_share, scope_time_share

from conftest import BENCH_DIR
from test_program_names import _f, _hlo_proto, _map_entry

# by path: the checkout has a tools/ of its own beside benchmark/tools/
_spec = importlib.util.spec_from_file_location(
    "pass_report", os.path.join(BENCH_DIR, "tools", "pass_report.py"))
pass_report = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(pass_report)

GPT_CELL = "gpt3-xl-d12.pretrain-2k"
LISTED = ("backward_time_share", "mixer_in_time_share",
          "mixer_out_time_share")
UNLISTED = ("recompute_time_share", "xla_remat_time_share",
            "mixer_pos_time_share", "ssm_conv_time_share",
            "ssm_gate_time_share", "ssm_recompute_time_share",
            "retention_scan_recompute_time_share",
            "attn_window_recompute_time_share")

_BWD = "jit(train_step)/transpose(jvp(jvp()))/checkpoint/"
# (instruction, op_name, start, seconds): a backward loop holding a
# recomputed and a backward product, an instruction XLA rematerialised, a
# forward product, an operation without a name, the update, and one
# operation directly under the mixer's scope
STEP = [
    ("while.1", _BWD + "pt.ssm/pt.ssm.scan/while", 0.0, 10.0),
    ("fusion.2", _BWD + "pt.ssm/pt.ssm.scan/while/body/closed_call/"
     "checkpoint/rematted_computation/pt.ssm.scan/dot_general", 1.0, 4.0),
    ("fusion.3", _BWD + "pt.ssm/pt.ssm.scan/while/body/"
     "transpose(jvp(pt.ssm.scan))/mul", 5.0, 4.0),
    ("fusion.4.remat", "jit(train_step)/jvp(pt.ssm)/pt.ssm.in/dot_general",
     10.0, 1.0),
    ("fusion.5", "jit(train_step)/jvp(pt.ssm)/pt.ssm.in/dot_general",
     11.0, 2.0),
    ("copy.6", "", 13.0, 1.0),
    ("fusion.7", "jit(train_step)/pt.opt/mul", 14.0, 1.0),
    ("fusion.8", "jit(train_step)/jvp(pt.ssm)/reshape", 15.0, 1.0),
]
BUSY = 16.0
SECONDS = {"forward": 4.0, "recompute": 4.0, "xla_remat": 1.0,
           "backward": 6.0, "update": 1.0}


def _xspace():
    proto = _hlo_proto([(n, o) for n, o, _, _ in STEP])
    return _f(1, (
        _f(2, op_names.METADATA_PLANE)
        + _f(5, _map_entry(3, _f(1, 3) + _f(2, "Hlo Proto")))
        + _f(4, _map_entry(7, _f(1, 7) + _f(2, "jit_train_step(7)")
                           + _f(5, _f(1, 3) + _f(6, proto))))))


def _device_trace():
    ops = [(f"%{n} = bf16[8,8]{{1,0}} fusion(...)", s, d, {})
           for n, _, s, d in STEP]
    # two executed steps, so that a step is half the window
    mods = [("jit_train_step(7)", 0.0, 8.0, {}),
            ("jit_train_step(7)", 8.0, 8.0, {})]
    return {"devices": {0: {"ops": ops, "modules": mods}}, "host": []}


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


def _ctx(cell_name, trace):
    return types.SimpleNamespace(
        cell=types.SimpleNamespace(name=cell_name), trace=trace)


@pytest.mark.parametrize("which", sorted(SECONDS))
def test_a_pass_s_share_counts_own_time_once(traced_cell, which):
    """The loop's own 2 s are the backward's, its body's 4 + 4 s the
    recomputation's and the backward's; the rematerialised instruction is
    XLA's whatever its op_name; the operation without a name is forward."""
    ctx = _ctx(traced_cell, _device_trace())
    assert pass_time_share.read(ctx, {"pass": which}) == pytest.approx(
        100.0 * SECONDS[which] / BUSY)


def test_back_to_back_operations_in_a_loop_are_counted_once():
    """A loop 100-900 ms holding two operations of which the first ends on
    the nanosecond the second starts: as floats the first's end lies one
    ulp past the second's start (819492001 ns + 277 ns), which must not
    leave the second in the loop's own time too. Every instant goes to the
    innermost operation, so the values sum to the union."""
    ns = 1e-9
    ops = [("%while.1 = s32[] while(...)", 0.1, 0.8, {}),
           ("%fusion.2 = bf16[8]{0} fusion(...)", 819492001 * ns, 277 * ns,
            {}),
           ("%fusion.3 = bf16[8]{0} fusion(...)", 819492278 * ns, 1000 * ns,
            {}),
           ("%reshape.4 = bf16[8]{0} reshape(...)", 819492278 * ns, 0.0, {}),
           ("%copy.5 = bf16[8]{0} copy(...)", 0.95, 0.01, {})]
    assert 819492001 * ns + 277 * ns > 819492278 * ns     # the float trap
    own = pass_time_share.innermost_seconds(ops)
    assert own == pytest.approx([0.8 - 1277 * ns, 277 * ns, 1000 * ns, 0.0,
                                 0.01], abs=1e-12)
    assert sum(own) == pytest.approx(0.81, abs=1e-12)


def test_the_passes_partition_the_busy_time(traced_cell):
    ctx = _ctx(traced_cell, _device_trace())
    assert sum(pass_time_share.read(ctx, {"pass": p})
               for p in SECONDS) == pytest.approx(100.0)


def test_a_pass_inside_a_scope(traced_cell):
    ctx = _ctx(traced_cell, _device_trace())
    share = lambda p, rx: pass_time_share.read(  # noqa: E731
        ctx, {"pass": p, "regex": rx})
    assert share("recompute", r"\bpt\.ssm\b") == pytest.approx(25.0)
    assert share("backward", r"\bpt\.ssm\.scan\b") == pytest.approx(37.5)
    assert share("forward", r"\bpt\.ssm\.in\b") == pytest.approx(12.5)
    assert share("recompute", r"\bpt\.retn\.scan\b") is None
    assert share("recompute", r"\bfaw_fwd\b") is None


def test_a_program_without_the_rule_reads_nothing_and_does_not_raise(
        traced_cell, tmp_path, monkeypatch):
    ctx = _ctx(traced_cell, _device_trace())
    monkeypatch.setattr(pass_time_share, "_rule", lambda: None)
    assert pass_time_share.read(ctx, {"pass": "backward"}) is None
    monkeypatch.undo()
    monkeypatch.setenv("TMPDIR", str(tmp_path / "empty"))   # no trace file
    assert pass_time_share.read(ctx, {"pass": "backward"}) is None
    assert pass_time_share.read(_ctx(traced_cell, None),
                                {"pass": "backward"}) is None


def test_the_rule_is_the_program_s():
    from paddle_tpu.observability.catalog import TRACE_PASSES, trace_pass
    assert pass_time_share._rule() is trace_pass
    assert set(SECONDS) == set(TRACE_PASSES)


@pytest.mark.parametrize("name", LISTED + UNLISTED)
def test_a_new_metric_file_resolves_and_reads_the_synthetic_trace(
        traced_cell, name):
    with open(os.path.join(BENCH_DIR, "layer_metrics",
                           name + ".json")) as f:
        spec = json.load(f)
    assert os.path.exists(os.path.join(BENCH_DIR, "readers",
                                       spec["reader"] + ".py"))
    reader = {"pass_time_share": pass_time_share,
              "scope_time_share": scope_time_share}[spec["reader"]]
    got = reader.read(_ctx(traced_cell, _device_trace()), spec["params"])
    expected = {"backward_time_share": 37.5, "recompute_time_share": 25.0,
                "xla_remat_time_share": 6.25, "mixer_in_time_share": 18.75,
                "ssm_recompute_time_share": 25.0}.get(name)
    assert got == (pytest.approx(expected) if expected else None)
    assert ("status" in spec) and ("UNLISTED" in spec["status"]) == (
        name in UNLISTED)


def test_the_three_listed_metrics_list_the_gpt_cell_and_nothing_else(
        benchmark_json):
    by_name = {m["name"]: m for m in benchmark_json["per_layer"]}
    for name in LISTED:
        m = by_name[name]
        assert m["workloads"] == [GPT_CELL]
        assert (m["moves"], m["source"], m["unit"], m["better"]) == (
            "train_tokens_per_s", "device_trace", "%", "lower")
    assert by_name["backward_time_share"]["layer"] == \
        by_name["train_mfu"]["layer"]
    assert by_name["mixer_in_time_share"]["layer"] == \
        by_name["mixer_out_time_share"]["layer"] == \
        by_name["mlp_time_share"]["layer"]
    reported = {m["name"] for m in cells.load_cell(GPT_CELL).per_layer}
    assert set(LISTED) <= reported


def test_pass_report_splits_by_scope_and_pass_and_checks_itself(traced_cell):
    from paddle_tpu.observability.catalog import trace_pass
    names = op_names.modules(program_spans.trace_dir(traced_cell))
    rep = pass_report.report(_device_trace(), names, trace_pass)
    assert rep["steps"] == 2 and rep["busy_s"] == pytest.approx(BUSY)
    for p, s in SECONDS.items():
        assert rep["passes"][p]["seconds_a_step"] == pytest.approx(s / 2)
    scan = rep["scopes"]["pt.ssm/pt.ssm.scan"]
    assert scan["by_pass"] == {"backward": pytest.approx(3.0),
                               "recompute": pytest.approx(2.0)}
    assert rep["scopes"]["(unnamed)"]["by_pass"] == {
        "forward": pytest.approx(0.5)}
    ssm = rep["mixers"]["pt.ssm"]
    assert ssm["share_of_busy_pct"] == pytest.approx(100 * 14 / BUSY)
    assert {k: v["share_of_busy_pct"] for k, v in ssm["parts"].items()} == {
        "pt.ssm.scan": pytest.approx(62.5), "pt.ssm.in": pytest.approx(18.75)}
    assert ssm["remainder"]["share_of_busy_pct"] == pytest.approx(6.25)
    checks = rep["checks"]
    assert checks["passes_partition_the_busy_time"]
    assert not checks["every_mixer_remainder_under_2pct"]
    assert checks["mfu_scale"] == pytest.approx(1 / (1 - 0.3125))
    assert "| `pt.ssm/pt.ssm.scan` |" in pass_report.markdown(rep)
    # a tree without the rule: one pass, the scopes as before
    bare = pass_report.report(_device_trace(), names, None)
    assert set(bare["passes"]) == {"(no rule)"}
    assert bare["mixers"]["pt.ssm"]["share_of_busy_pct"] == \
        ssm["share_of_busy_pct"]


@pytest.mark.parametrize("op_name, mixer, part", [
    ("jit(s)/jvp(pt.attn)/pt.attn.sliding/pt.attn.pos/mul",
     "pt.attn/pt.attn.sliding", "pt.attn.pos"),
    ("jit(s)/transpose(jvp(jvp()))/checkpoint/pt.attn/pt.attn.full/fa_bwd_dq"
     "/pallas_call", "pt.attn/pt.attn.full", "fa_bwd_dq"),
    ("jit(s)/jvp(pt.attn)/fa_fwd/pallas_call", "pt.attn", "fa_fwd"),
    ("jit(s)/checkpoint/pt.retn/pt.retn.scan/while/body/"
     "transpose(jvp(pt.retn.scan))/retn_back/pallas_call", "pt.retn",
     "pt.retn.scan"),
    ("jit(s)/jvp(pt.ssm)/reshape", "pt.ssm", pass_report.REMAINDER),
    ("jit(s)/jvp(pt.mlp)/dot_general", None, None),
])
def test_a_mixer_s_part_is_the_next_name_down_the_path(op_name, mixer, part):
    assert pass_report.mixer_and_part(
        pass_report.scope_path(op_name)) == (mixer, part)
