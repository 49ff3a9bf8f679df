"""The program names its own work on the device trace (ISSUE 26).

(a) host spans: under a jax profiler session every tracer span, every
    RecordEvent, the trainer's step spans and the engine's phase segments
    land once on the host plane of the profiler's trace, children inside
    parents, and the phase seconds read from the trace agree with
    PhaseAccountant.report(); with the tracer off and no session, span()
    is the shared no-op.
(b) device names: every component scope of the closed set
    (observability/catalog.py TRACE_SCOPES) is in the lowered text of the
    tiny GPT train step, forward and backward, and of the engine's
    pir_jit decode and prefill programs AFTER PIR replay; the flash
    kernels carry their names.
(c) parts and passes (ISSUE 36): every part of a mixer (pt.attn.in, ...)
    is under its mixer in the COMPILED step of each model with a training
    cell, forward and backward; catalog.py trace_pass, the program's rule
    for the pass of an operation, finds forward, recompute and backward
    under every rematerialised sub-block and gives every instruction one
    pass. These tests are the contract with jax's name stack: an upgrade
    that renames `rematted_computation` or `transpose(` fails here.
"""

import glob
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import optimizer, profiler
from paddle_tpu.inference import ContinuousBatchingEngine
from paddle_tpu.models.gpt import gpt_tiny
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.observability import tracing
from paddle_tpu.observability.catalog import (KERNEL_NAMES, TRACE_PASSES,
                                              TRACE_SCOPES, trace_pass)
from paddle_tpu.parallel import GPT_SHARDING_RULES, SpmdTrainer, create_mesh
from paddle_tpu.profiler.phases import PHASES, get_phase_accountant

TRAIN_SCOPES = ("pt.embed", "pt.attn", "pt.mlp", "pt.head", "pt.loss",
                "pt.opt")
SERVE_SCOPES = ("pt.embed", "pt.attn", "pt.mlp", "pt.head",
                "pt.serve.gather", "pt.serve.attend", "pt.serve.sample")
# what models/granite_moe_hybrid.py adds to a train step's names
HYBRID_SCOPES = ("pt.ssm", "pt.ssm.scan", "pt.moe", "pt.moe.route")
# what models/brumby.py adds
RETENTION_SCOPES = ("pt.retn", "pt.retn.scan")
# what models/mellum.py adds: the attention kind, inside pt.attn
KIND_SCOPES = ("pt.attn.sliding", "pt.attn.full")
# what models/phi4flash.py adds: the cross-decoder's attention kind and
# the gated memory unit
SAMBAY_SCOPES = ("pt.attn.cross", "pt.gmu")
# the parts of every mixer, by the model with a training cell that enters
# them: {model: {mixer scope: its parts}} (ISSUE 36)
PART_SCOPES = {
    "gpt": {"pt.attn": ("pt.attn.in", "pt.attn.out")},
    "granite": {"pt.ssm": ("pt.ssm.in", "pt.ssm.conv", "pt.ssm.gate",
                           "pt.ssm.out"),
                "pt.attn": ("pt.attn.in", "pt.attn.out")},      # NoPE
    "brumby": {"pt.retn": ("pt.retn.in", "pt.retn.pos", "pt.retn.out")},
    "mellum": {"pt.attn.sliding": ("pt.attn.in", "pt.attn.pos",
                                   "pt.attn.out"),
               "pt.attn.full": ("pt.attn.in", "pt.attn.pos",
                                "pt.attn.out")},
    "phi4flash": {"pt.ssm": ("pt.ssm.in", "pt.ssm.conv", "pt.ssm.sel",
                             "pt.ssm.out"),
                  "pt.attn.sliding": ("pt.attn.in", "pt.attn.diff",
                                      "pt.attn.out"),
                  "pt.attn.full": ("pt.attn.in", "pt.attn.diff",
                                   "pt.attn.out"),
                  "pt.attn.cross": ("pt.attn.in", "pt.attn.diff",
                                    "pt.attn.out")},
}
# the scopes whose sub-block is rematerialised (models/sub_block.py), and
# with it runs forward, recomputed and backward
REMAT_SCOPES = {
    "granite": ("pt.ssm", "pt.ssm.scan", "pt.attn", "pt.mlp", "pt.moe"),
    "brumby": ("pt.retn", "pt.retn.scan", "pt.mlp", "pt.head", "pt.loss"),
    "mellum": ("pt.attn.sliding", "pt.attn.full", "pt.moe", "pt.head",
               "pt.loss"),
    "phi4flash": ("pt.ssm", "pt.ssm.sel", "pt.attn.sliding", "pt.attn.full",
                  "pt.attn.cross", "pt.gmu", "pt.mlp", "pt.head", "pt.loss"),
}
# the scope of the scan whose chunks a loop recomputes, by model
LOOP_SCANS = {"granite": "pt.ssm.scan", "brumby": "pt.retn.scan",
              "phi4flash": "pt.ssm.sel"}
# entered by a custom_vjp backward rule, not by a model (ops/mamba2.py)
RULE_SCOPES = ("pt.recompute",)


def _trainer(model="gpt"):
    """(SpmdTrainer over the model's tiny configuration on one device, the
    (batch, seq) its step is lowered at)."""
    from paddle_tpu.parallel import DP_ONLY_RULES
    paddle.seed(0)
    if model == "gpt":
        net, rules, shape = gpt_tiny(), GPT_SHARDING_RULES, (2, 64)
    elif model == "granite":
        from paddle_tpu.models.granite_moe_hybrid import granite_hybrid_tiny
        net, rules, shape = granite_hybrid_tiny(), DP_ONLY_RULES, (1, 16)
    elif model == "brumby":
        from paddle_tpu.models.brumby import brumby_tiny
        net, rules, shape = brumby_tiny(), DP_ONLY_RULES, (1, 16)
    elif model == "mellum":
        from paddle_tpu.models.mellum import mellum_tiny
        net, rules, shape = mellum_tiny(), DP_ONLY_RULES, (1, 16)
    else:
        from paddle_tpu.models.phi4flash import phi4flash_tiny
        net, rules, shape = phi4flash_tiny(), DP_ONLY_RULES, (1, 16)
    opt = optimizer.AdamW(1e-3, parameters=net.parameters())
    return SpmdTrainer(net, opt, create_mesh(devices=jax.devices()[:1]),
                       rules), shape


def _lowered_step(trainer, shape):
    """The trainer's jitted step lowered at a zero batch of `shape`."""
    from paddle_tpu.framework.random import get_rng_state
    ids = np.zeros(shape, np.int32)
    batch = trainer._batch_arrays((ids, ids))
    with jax.set_mesh(trainer.mesh):
        return trainer._compiled.lower(
            trainer.params, trainer.opt_state, batch, get_rng_state()[0],
            jnp.asarray(1, jnp.int32), jnp.asarray(1e-3, jnp.float32))


def _engine(**kw):
    cfg = LlamaConfig(vocab_size=128, hidden_size=64, intermediate_size=128,
                      num_hidden_layers=2, num_attention_heads=4,
                      num_key_value_heads=2, max_position_embeddings=256)
    paddle.seed(0)
    return ContinuousBatchingEngine(
        LlamaForCausalLM(cfg), num_blocks=64, block_size=8, max_batch=4,
        prefill_buckets=(16,), decode_steps=4, **kw)


def _serve(eng, n=3):
    rs = np.random.RandomState(1)
    for _ in range(n):
        eng.add_request(rs.randint(0, 128, (11,)), max_new_tokens=9)
    eng.run()


def _host_events(trace_dir):
    """[(name, start_ns, end_ns, stats)] of every host-plane event."""
    path = glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb")[0]
    out = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    out.append((e.name, e.start_ns,
                                e.start_ns + e.duration_ns, dict(e.stats)))
    return out


# -- (a) host spans on the trace's clock -------------------------------------

@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One profiler session over a tracer span, a RecordEvent, two trainer
    steps and a served batch; the accountant counts the same period."""
    trace_dir = str(tmp_path_factory.mktemp("trace"))
    trainer, eng = _trainer()[0], _engine()
    ids = np.random.RandomState(0).randint(0, 512, (2, 64)).astype(np.int32)
    trainer.step((ids, ids)).block_until_ready()        # compile outside
    _serve(eng)
    acct = get_phase_accountant()
    was = acct.enabled
    acct.enable()
    acct.reset()
    tracer_was_on = tracing.enabled()     # another test's leftover
    tracing.disable()
    assert tracing.span("off") is tracing._NOOP        # tracer off, no session
    marker = tracing.get_tracer().marker()
    jax.profiler.start_trace(trace_dir)
    try:
        with tracing.span("x", rid=7, trace_id="t-1", lane=2):
            with profiler.RecordEvent("rec"):
                time.sleep(0.001)
        for _ in range(2):
            loss = trainer.step((ids, ids))
        loss.block_until_ready()
        _serve(eng)
    finally:
        jax.profiler.stop_trace()
    report = acct.report()
    acct.reset()
    if not was:
        acct.disable()
    assert tracing.span("off") is tracing._NOOP        # the session is over
    if tracer_was_on:
        tracing.enable()
    return {"events": _host_events(trace_dir), "report": report,
            "step_count": trainer.step_count,
            "ring": [sp.name for sp in
                     tracing.get_tracer().spans_since(marker)]}


def _named(traced, name):
    return [e for e in traced["events"] if e[0] == name]


def _inside(child, parent):
    return parent[1] <= child[1] and child[2] <= parent[2]


def test_span_and_record_event_land_once_with_metadata(traced):
    (x,), (rec,) = _named(traced, "x"), _named(traced, "rec")
    assert x[3] == {"rid": 7, "trace_id": "t-1", "lane": 2}
    assert _inside(rec, x)          # RecordEvent: one path, no event twice
    assert rec[2] - rec[1] >= 1_000_000       # the 1 ms sleep, in ns


def test_session_only_span_stays_out_of_the_ring(traced):
    # the tracer was off: the annotation alone; RecordEvent is ungated
    assert traced["ring"] == ["rec"]


def test_trainer_step_spans_nest_and_carry_the_step_number(traced):
    steps = _named(traced, "trainer.step")
    stages = _named(traced, "trainer.stage")
    dispatches = _named(traced, "trainer.dispatch")
    assert len(steps) == len(stages) == len(dispatches) == 2
    assert [s[3]["step_num"] for s in steps] == [
        traced["step_count"] - 1, traced["step_count"]]
    for step, stage, disp in zip(steps, stages, dispatches):
        assert _inside(stage, step) and _inside(disp, step)
        assert stage[2] <= disp[1]


def test_engine_spans_nest(traced):
    steps = _named(traced, "serving.step")
    assert steps
    for name in ("serving.prefill", "serving.decode_step"):
        kids = _named(traced, name)
        assert kids, name
        assert all(any(_inside(k, s) for s in steps) for k in kids)
    assert all("rid" in k[3] for k in _named(traced, "serving.prefill"))


def test_phase_segments_agree_with_the_accountant(traced):
    segs = _named(traced, "serving.phase")
    steps = _named(traced, "serving.step")
    report = traced["report"]
    from_trace, marks = {}, {}
    for _, t0, t1, stats in segs:
        p = stats["phase"]
        from_trace[p] = from_trace.get(p, 0.0) + (t1 - t0) * 1e-9
        marks[p] = marks.get(p, 0) + 1
    attributed = {p: v for p, v in from_trace.items() if p != "unattributed"}
    assert set(attributed) == set(report["phases"]) <= set(PHASES)
    for p, row in report["phases"].items():
        assert marks[p] == row["marks"]                 # each segment once
        # two clocks read a few microseconds apart at every boundary
        assert attributed[p] == pytest.approx(
            row["seconds"], rel=0.02, abs=20e-6 * row["marks"]), p
    assert sum(attributed.values()) == pytest.approx(
        report["attributed_s"], rel=0.02)
    # a segment lies in the engine step it was marked in
    assert all(any(s[1] - 50_000 <= g[1] and g[2] <= s[2] + 50_000
                   for s in steps) for g in segs)


def test_standalone_tracing_never_imports_jax():
    """tracing.py is stdlib-only and loadable outside the package: without
    jax in the process there is no session and span() is the no-op."""
    import os
    import subprocess
    import sys
    path = os.path.join(os.path.dirname(tracing.__file__), "tracing.py")
    code = (
        "import importlib.util, sys\n"
        f"spec = importlib.util.spec_from_file_location('t', {path!r})\n"
        "t = importlib.util.module_from_spec(spec)\n"
        "spec.loader.exec_module(t)\n"
        "assert t.span('x') is t._NOOP and t.session_annotation() is None\n"
        "tr = t.Tracer(enabled=True)\n"
        "with tr.span('y', rid=1):\n"
        "    pass\n"
        "assert [s.name for s in tr.spans_since()] == ['y']\n"
        "assert 'jax' not in sys.modules\n")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True)
    assert r.returncode == 0, r.stderr


# -- (b) names on device operations ------------------------------------------

def _scope_hits(text, scope):
    """Locations of the lowered text under `scope` as a whole component."""
    return re.findall(r'loc\("(?:[^"]*[/(])?' + re.escape(scope)
                      + r'(?:[/)][^"]*)?"', text)


def test_train_step_lowers_with_every_scope_forward_and_backward():
    text = _lowered_step(*_trainer()).as_text(debug_info=True)
    for scope in TRAIN_SCOPES:
        hits = _scope_hits(text, scope)
        assert hits, scope
        if scope != "pt.opt":       # the update has no backward
            assert any("transpose(jvp(" in h for h in hits), scope


def test_hybrid_train_step_lowers_with_the_mixer_and_expert_scopes():
    """State-space and routed-expert work carries its scope through the
    per-sub-block rematerialisation, forward and backward, nested as the
    readers' patterns expect (pt.ssm.scan inside pt.ssm, pt.moe.route
    inside pt.moe); attention and the shared expert keep pt.attn, pt.mlp."""
    text = _lowered_step(*_trainer("granite")).as_text(debug_info=True)
    for scope in HYBRID_SCOPES + TRAIN_SCOPES:
        hits = _scope_hits(text, scope)
        assert hits, scope
        if scope != "pt.opt":
            assert any("transpose(jvp(" in h for h in hits), scope
    assert any("pt.ssm/pt.ssm.scan" in h
               for h in _scope_hits(text, "pt.ssm.scan"))
    assert any("pt.moe/pt.moe.route" in h
               for h in _scope_hits(text, "pt.moe.route"))


def test_retention_train_step_lowers_with_the_mixer_scopes():
    """The power-retention mixer carries its scope through the sub-block's
    rematerialisation, the map over state heads and the rematerialised
    scan over chunks, forward and backward, nested as the readers'
    patterns expect (pt.retn.scan inside pt.retn); the FFN keeps pt.mlp,
    and the head and the loss, taken a block of tokens at a time, pt.head
    and pt.loss."""
    text = _lowered_step(*_trainer("brumby")).as_text(debug_info=True)
    for scope in RETENTION_SCOPES + TRAIN_SCOPES:
        if scope == "pt.attn":
            assert not _scope_hits(text, scope)     # no attention here
            continue
        hits = _scope_hits(text, scope)
        assert hits, scope
        if scope != "pt.opt":
            assert any("transpose(jvp(" in h for h in hits), scope
    scan = _scope_hits(text, "pt.retn.scan")
    assert any("pt.retn/pt.retn.scan" in h for h in scan)
    # the two readers' patterns: the mixer's takes the scan's operations
    # too, the scan's takes nothing of the mixer outside it
    mixer = re.compile(r"\bpt\.retn\b")
    inner = re.compile(r"\bpt\.retn\.scan\b")
    assert all(mixer.search(h) for h in scan)
    assert any(not inner.search(h) for h in _scope_hits(text, "pt.retn"))


def test_mellum_train_step_lowers_with_both_attention_kinds_scopes():
    """A model that mixes window and full layers names each kind's whole
    mixer inside pt.attn, forward and backward through the sub-block's
    rematerialisation; its experts keep pt.moe and pt.moe.route (no
    pt.mlp: there is no shared expert), the blocked head and loss pt.head
    and pt.loss. The cell's two mixer metrics read these patterns."""
    text = _lowered_step(*_trainer("mellum")).as_text(debug_info=True)
    for scope in KIND_SCOPES + ("pt.moe", "pt.moe.route") + TRAIN_SCOPES:
        if scope == "pt.mlp":
            assert not _scope_hits(text, scope)     # no dense FFN here
            continue
        hits = _scope_hits(text, scope)
        assert hits, scope
        if scope != "pt.opt":
            assert any("transpose(jvp(" in h for h in hits), scope
    sliding = re.compile(r"\bpt\.attn\.sliding\b")
    full = re.compile(r"\bpt\.attn\.full\b")
    for kind, rx, other in (("sliding", sliding, full),
                            ("full", full, sliding)):
        hits = _scope_hits(text, f"pt.attn.{kind}")
        # nested: "pt.attn/pt.attn.<kind>", or with the transformation
        # that wrapped the outer scope, "jvp(pt.attn)/pt.attn.<kind>"
        nested = re.compile(r"pt\.attn\)*/pt\.attn\." + kind)
        assert all(nested.search(h) for h in hits)
        assert all(rx.search(h) and not other.search(h) for h in hits)
    # the whole mixer is inside its kind's scope: nothing of pt.attn is
    # outside both
    assert all(sliding.search(h) or full.search(h)
               for h in _scope_hits(text, "pt.attn"))
    assert any("pt.moe/pt.moe.route" in h
               for h in _scope_hits(text, "pt.moe.route"))


def test_phi4flash_train_step_lowers_with_its_mixers_scopes():
    """The hybrid of Mamba-1, differential attention of three kinds and
    gated memory units names each mixer, forward and backward through the
    sub-block's rematerialisation: the cross-decoder's attention under
    pt.attn/pt.attn.cross (what `attn_cross_mixer_time_share` reads), the
    GMU under pt.gmu (`gmu_time_share`), the recurrence under
    pt.ssm/pt.ssm.sel (`sel_scan_time_share`); the Mamba-1 mixer has no
    gate part (its gate is the scan's), and no mixer enters pt.attn.pos
    (no position encoding)."""
    text = _lowered_step(*_trainer("phi4flash")).as_text(debug_info=True)
    for scope in (KIND_SCOPES + SAMBAY_SCOPES + ("pt.ssm", "pt.ssm.sel",
                                                 "pt.attn.diff")
                  + TRAIN_SCOPES):
        hits = _scope_hits(text, scope)
        assert hits, scope
        if scope != "pt.opt":
            assert any("transpose(jvp(" in h for h in hits), scope
    for absent in ("pt.attn.pos", "pt.ssm.gate", "pt.ssm.scan", "pt.moe"):
        assert not _scope_hits(text, absent), absent
    nested = re.compile(r"pt\.attn\)*/pt\.attn\.cross")
    assert all(nested.search(h) for h in _scope_hits(text, "pt.attn.cross"))
    assert all(re.search(r"pt\.ssm\)*/pt\.ssm\.sel", h)
               for h in _scope_hits(text, "pt.ssm.sel"))


def test_every_declared_scope_is_held_by_a_test_and_none_else():
    """catalog.py TRACE_SCOPES against the scopes these tests look for in
    lowered programs, both directions."""
    parts = {p for mixers in PART_SCOPES.values()
             for ps in mixers.values() for p in ps}
    assert set(TRAIN_SCOPES) | set(SERVE_SCOPES) | set(HYBRID_SCOPES) \
        | set(RETENTION_SCOPES) | set(KIND_SCOPES) | set(SAMBAY_SCOPES) \
        | parts | set(RULE_SCOPES) == set(TRACE_SCOPES)


# -- (c) parts of a mixer and the pass of an operation ----------------------

_NAME = re.compile(r"(?<![\w.])pt\.[a-z_.]+")
_COMPILED = {}


def _path(op_name):
    out = []
    for name in _NAME.findall(op_name):
        if name not in out:
            out.append(name)
    return out


def _compiled_ops(model):
    """[(instruction name, op_name, scope path)] of every instruction of
    the model's tiny train step AS COMPILED for the CPU (fusions' members
    included): an optimised instruction's op_name is the whole path,
    through loop bodies too, as on a device trace's HloProto. One compile a
    model."""
    if model not in _COMPILED:
        text = _lowered_step(*_trainer(model)).compile().as_text()
        ops = []
        for line in text.splitlines():
            m = re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = ", line)
            if m:
                named = re.search(r'op_name="([^"]*)"', line)
                op = named.group(1) if named else ""
                ops.append((m.group(1), op, _path(op)))
        _COMPILED[model] = ops
    return _COMPILED[model]


@pytest.mark.parametrize("model, mixer, part", [
    (model, mixer, part) for model, mixers in PART_SCOPES.items()
    for mixer, parts in mixers.items() for part in parts])
def test_every_part_is_under_its_mixer_forward_and_backward(model, mixer,
                                                            part):
    """In the compiled step the part's operations are there in the forward
    and in the backward, and every one of them has the mixer's scope
    outside the part's: what `mixer_*_time_share` and the mixer's own
    metric both rest on."""
    ops = _compiled_ops(model)
    hits = [(inst, op, path) for inst, op, path in ops
            if part in path and mixer in path]
    assert hits, (model, part)
    for _, op, path in hits:
        assert path.index(mixer) < path.index(part), op
    passes = {trace_pass(op, inst) for inst, op, _ in hits}
    assert {"forward", "backward"} <= passes, (model, part, passes)
    # a part is entered nowhere but inside a mixer
    assert all(set(path) & set(PART_SCOPES[model])
               for _, _, path in ops if part in path)


@pytest.mark.parametrize("model", sorted(REMAT_SCOPES))
def test_every_rematerialised_sub_block_runs_in_three_passes(model):
    """models/sub_block.py rematerialises every sub-block: under each of
    their scopes the compiled step has operations of the forward, of the
    program's recomputation and of the backward, by the program's rule;
    the update is there; and the rule gives every instruction of the step
    exactly one of TRACE_PASSES."""
    ops = _compiled_ops(model)
    by_pass = {}
    for inst, op, path in ops:
        which = trace_pass(op, inst)
        assert which in TRACE_PASSES, (inst, op)
        by_pass.setdefault(which, []).append((op, path))
    assert sum(map(len, by_pass.values())) == len(ops)
    for scope in REMAT_SCOPES[model]:
        for which in ("forward", "recompute", "backward"):
            assert any(scope in path for _, path in by_pass[which]), \
                (model, scope, which)
    assert by_pass["update"]
    assert all("pt.opt" in path for _, path in by_pass["update"])
    # the recomputation inside the backward's loop over chunks keeps its
    # pass through the loop's body
    if model in LOOP_SCANS:
        assert any("while/body" in op and LOOP_SCANS[model] in path
                   for op, path in by_pass["recompute"])


def test_a_step_that_rematerialises_nothing_has_no_recomputation():
    """The GPT step keeps its activations: forward, backward and update,
    and nothing the rule would call recomputation."""
    passes = {trace_pass(op, inst) for inst, op, _ in _compiled_ops("gpt")}
    assert passes == {"forward", "backward", "update"}


@pytest.mark.parametrize("op_name, instruction, expected", [
    ("jit(loss)/jvp(pt.ssm)/pt.ssm.in/dot_general", "fusion.1", "forward"),
    ("jit(loss)/transpose(jvp(jvp()))/checkpoint/rematted_computation/"
     "pt.ssm/pt.ssm.in/dot_general", "fusion.2", "recompute"),
    ("jit(loss)/transpose(jvp(jvp()))/checkpoint/pt.ssm/while/body/mul",
     "fusion.3", "backward"),
    ("jit(loss)/transpose(jvp(jvp()))/checkpoint/pt.ssm/while/body/"
     "closed_call/checkpoint/rematted_computation/pt.ssm.scan/dot_general",
     "fusion.4", "recompute"),
    ("jit(train_step)/pt.opt/mul", "fusion.5", "update"),
    ("jit(train_step)/pt.opt/mul", "fusion.5.remat", "update"),
    ("jit(loss)/jvp(pt.ssm)/pt.ssm.in/dot_general", "fusion.6.remat",
     "xla_remat"),
    ("jit(loss)/transpose(jvp(jvp()))/checkpoint/rematted_computation/"
     "pt.mlp/dot_general", "fusion.7.remat2", "xla_remat"),
    ("jit(loss)/transpose(jvp(pt.ssm))/pt.ssm.conv/pt.recompute/mul",
     "fusion.8", "recompute"),
    ("jit(loss)/transpose(jvp(pt.attn))/fa_bwd_dq/pallas_call",
     "fa_bwd_dq.9", "backward"),
    ("jit(loss)/jvp(pt.optics)/mul", "fusion.10", "forward"),
    ("", "copy.11", "forward"),
    ("jit(train_step)/params['gpt.wte.weight']", "", "forward"),
])
def test_trace_pass_table(op_name, instruction, expected):
    assert trace_pass(op_name, instruction) == expected
    assert expected in TRACE_PASSES


def test_every_pass_has_its_row_in_the_observability_doc():
    import os
    doc = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "OBSERVABILITY.md")
    with open(doc) as f:
        rows = set(re.findall(r"^\| `pass/([a-z_]+)` \|", f.read(), re.M))
    assert rows == set(TRACE_PASSES)


def test_a_backward_rule_s_own_forward_reads_as_recomputation():
    """ops/mamba2.py _conv_bwd makes the conv's taps again by hand, inside
    scope pt.recompute: in the lowered backward those operations are under
    the call site's scopes and the rule reads them as recompute, the rest
    of the rule as backward. (In a rematerialised sub-block XLA merges them
    with the checkpoint's own recomputed taps, which read the same.)"""
    from paddle_tpu.ops.mamba2 import causal_conv1d_silu

    def loss(x, w, b):
        with jax.named_scope("pt.ssm"), jax.named_scope("pt.ssm.conv"):
            return causal_conv1d_silu(x, w, b).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        jnp.ones((1, 16, 8)), jnp.ones((4, 8)), jnp.zeros((8,))
    ).compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', text))
    again = {n for n in names if "pt.recompute" in _path(n)}
    assert again and all(trace_pass(n) == "recompute" for n in again)
    assert all(_path(n)[:2] == ["pt.ssm", "pt.ssm.conv"] for n in again)
    rest = {n for n in names if "pt.ssm.conv" in _path(n)} - again
    assert rest and all(trace_pass(n) == "backward" for n in rest)


def test_pir_replay_keeps_a_gpt_block_s_parts():
    """The GPT model runs through nn.Linear / execute: its mixer's parts
    reach a replayed program as the component scopes do."""
    from paddle_tpu.pir import pir_jit
    paddle.seed(0)
    model = gpt_tiny()
    block = model.gpt.h[0]
    g = pir_jit(lambda x: block(paddle.Tensor(x))._data, name="gpt_block")
    g(jnp.ones((2, 16, model.config.hidden_size), jnp.float32))
    assert g.report.fallback is None
    text = _replayed_text(g)
    for part in PART_SCOPES["gpt"]["pt.attn"]:
        hits = _scope_hits(text, part)
        assert hits and all("pt.attn/" + part in h for h in hits), part


@pytest.fixture(scope="module")
def served_engine():
    eng = _engine()
    _serve(eng, n=2)
    return eng


def _replayed_text(pir_fn):
    """Lowered text of a pir_jit program as the pipeline compiles it: the
    post-pass Program replayed eqn by eqn (pir/ir.py Operation.evaluate)."""
    prog = pir_fn.report.program
    avals = [jax.ShapeDtypeStruct(v.shape, v.dtype) for v in prog.inputs]
    return jax.jit(prog.bind).lower(*avals).as_text(debug_info=True)


@pytest.mark.parametrize("which", ["decode", "prefill"])
def test_pir_replay_keeps_the_scopes(served_engine, which):
    table = (served_engine._decode_jit if which == "decode"
             else served_engine._prefill_jit)
    assert table
    for fn in table.values():
        assert fn.report.fallback is None
        text = _replayed_text(fn)
        for scope in SERVE_SCOPES:
            if which == "prefill" and scope == "pt.serve.sample":
                continue            # prefill samples on the host
            assert _scope_hits(text, scope), (which, scope)
        assert _scope_hits(text, "kv.write")        # effect scope, as before


def test_scope_is_metadata_not_identity():
    """A scope changes no canonical text or hash, so no compile-cache key
    and no golden moves because of a name."""
    from paddle_tpu.pir import capture

    def body(x, w):
        return jnp.tanh(x @ w) * 2.0

    def scoped(x, w):
        with jax.named_scope("pt.mlp"):
            return body(x, w)

    x, w = jnp.ones((4, 8)), jnp.ones((8, 8))
    a, b = capture(body, x, w)[0], capture(scoped, x, w)[0]
    assert a.canonical_text() == b.canonical_text()
    assert a.canonical_hash() == b.canonical_hash()
    assert all(op.scope is None for op in a.ops)
    assert all(str(op.scope) == "pt.mlp" for op in b.ops)


def test_fused_region_keeps_its_members_scope_inside_its_own():
    from paddle_tpu.pir import pir_jit

    def f(x, w):
        with jax.named_scope("pt.mlp"):
            return jnp.tanh((x @ w) * 2.0 + 1.0)

    x, w = jnp.ones((4, 16)), jnp.ones((16, 16))
    g = pir_jit(f, name="scoped")
    g(x, w)
    assert g.report.fusion_groups
    text = _replayed_text(g)
    assert re.search(r'pir\.fuse\.scoped\.g\d+/pt\.mlp/', text)


def test_flash_kernels_carry_their_names():
    from paddle_tpu.ops.pallas.flash_attention import flash_attention_bshd
    q = jnp.ones((1, 256, 2, 128), jnp.bfloat16)

    def loss(q, k, v):
        return flash_attention_bshd(q, k, v, causal=True).astype(
            jnp.float32).sum()

    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, q, q)
    names = []

    def walk(jp):
        for eqn in jp.eqns:
            if eqn.primitive.name == "pallas_call":
                names.append(eqn.params["name"])
            for v in eqn.params.values():
                for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                    sub = getattr(sub, "jaxpr", sub)
                    if hasattr(sub, "eqns"):
                        walk(sub)

    walk(jaxpr.jaxpr)
    assert sorted(set(names)) == sorted(
        n for n in KERNEL_NAMES if n.startswith("fa_"))


def test_retention_kernels_sit_under_the_scan_s_scope_both_ways():
    """At head_dim 128 the retention's products with an expansion are the
    `retn_*` kernels (ops/pallas/power_retention.py). In the lowered train
    step every one of their operations, the forward's, the recomputation's
    and the backward's (whose calls are traced when the scan is
    transposed, outside the forward's scope, and enter it themselves),
    is under pt.retn/pt.retn.scan, so the kernels' time on a trace stays
    in `retention_scan_time_share` and out of `unnamed_op_time_share`."""
    from paddle_tpu.models.brumby import brumby_tiny
    from paddle_tpu.parallel import DP_ONLY_RULES
    paddle.seed(0)
    model = brumby_tiny(num_hidden_layers=1, num_attention_heads=2,
                        num_key_value_heads=1, head_dim=128)
    opt = optimizer.AdamW(1e-3, parameters=model.parameters())
    trainer = SpmdTrainer(model, opt, create_mesh(devices=jax.devices()[:1]),
                          DP_ONLY_RULES)
    # compiled, not lowered: a lowered operation inside the scan's body
    # carries the body's own name stack, an optimised one's op_name the
    # whole path, as a device trace's HloProto does
    text = _lowered_step(trainer, (1, 16)).compile().as_text()
    op_names = set(re.findall(r'op_name="([^"]*)"', text))
    retention = sorted(n for n in KERNEL_NAMES if n.startswith("retn_"))
    assert retention == ["retn_back", "retn_read", "retn_write"]
    mixer = re.compile(r"\bpt\.retn\b")
    scan = re.compile(r"\bpt\.retn\.scan\b")
    for name in retention:
        hits = [n for n in op_names if f"/{name}/" in n]
        assert hits, name
        for h in hits:
            assert scan.search(h) and mixer.search(
                h[:scan.search(h).start()]), h
        # retn_back runs in the backward alone; the other two in both
        assert any("transpose(jvp(" in h for h in hits), name
        if name != "retn_back":
            assert any("transpose(jvp(" not in h for h in hits), name


@pytest.mark.parametrize("model, scopes", [
    ("mellum", ("pt.attn.sliding/pt.attn.pos", "pt.attn.full/pt.attn.pos")),
    ("brumby", ("pt.retn/pt.retn.pos",))])
def test_position_kernels_keep_the_name_stack_of_their_part(monkeypatch,
                                                            model, scopes):
    """At head_dim 128 (and, here, a rule told that the backend is a TPU)
    models/rope.py norm_rope is the `normrope_*` kernels
    (ops/pallas/rope_norm.py). In the compiled train step every operation
    of theirs is under the mixer's position part, for every kind of layer:
    `normrope_fwd` in the forward and in the sub-block's recomputation,
    `normrope_bwd` in the backward alone (catalog.py trace_pass), so the
    kernels' time stays in `mixer_pos_time_share` and in the mixer's own
    share, and a `pass_report.py` table shows each once a pass."""
    from paddle_tpu.models import rope
    from paddle_tpu.parallel import DP_ONLY_RULES
    monkeypatch.setattr(rope, "_rotates_whole_lanes", lambda x, w: True)
    paddle.seed(0)
    if model == "mellum":
        from paddle_tpu.models.mellum import mellum_tiny
        net = mellum_tiny(num_hidden_layers=2, num_attention_heads=2,
                          head_dim=128, layer_types=[
                              "sliding_attention", "full_attention"])
    else:
        from paddle_tpu.models.brumby import brumby_tiny
        net = brumby_tiny(num_hidden_layers=1, num_attention_heads=2,
                          num_key_value_heads=1, head_dim=128)
    opt = optimizer.AdamW(1e-3, parameters=net.parameters())
    trainer = SpmdTrainer(net, opt, create_mesh(devices=jax.devices()[:1]),
                          DP_ONLY_RULES)
    text = _lowered_step(trainer, (1, 16)).compile().as_text()
    positions = sorted(n for n in KERNEL_NAMES if n.startswith("normrope_"))
    assert positions == ["normrope_bwd", "normrope_fwd"]
    found = {name: set() for name in positions}
    for instruction, op_name in re.findall(
            r'^\s*(?:ROOT )?%?([\w.\-]+) = .*op_name="([^"]*)"', text,
            re.MULTILINE):
        for name in positions:
            if f"/{name}/" in op_name:
                plain = op_name.replace("jvp(", "").replace(
                    "transpose(", "").replace(")", "")
                assert any(scope in plain for scope in scopes), op_name
                found[name].add((next(s for s in scopes if s in plain),
                                 trace_pass(op_name, instruction)))
    assert found["normrope_fwd"] == {
        (scope, p) for scope in scopes for p in ("forward", "recompute")}
    assert found["normrope_bwd"] == {(scope, "backward") for scope in scopes}


def test_scan_kernels_keep_the_name_stack_of_the_scan(monkeypatch):
    """Where the rule picks them (here steered, the kernels interpreted),
    Mamba-1's recurrence is the `selscan_*` kernels: in the compiled train
    step every operation of theirs is under pt.ssm/pt.ssm.sel, for the
    plain and the memory Mamba alike, `selscan_fwd` in the forward and in
    the sub-block's recomputation, `selscan_bwd` in the backward alone
    (its call enters the scope itself where the step is transposed), so
    that the kernels' time stays in `sel_scan_time_share` and
    `sel_scan_roofline` finds them by name."""
    from paddle_tpu.models.phi4flash import phi4flash_tiny
    from paddle_tpu.ops.pallas import selective_scan as kernels
    from paddle_tpu.parallel import DP_ONLY_RULES
    monkeypatch.setattr(kernels, "supported", lambda u, a: True)
    paddle.seed(0)
    net = phi4flash_tiny(num_hidden_layers=4, layer_indices=[0, 16, 17, 18],
                         layer_types=["mamba", "memory_mamba",
                                      "full_attention", "gmu"])
    opt = optimizer.AdamW(1e-3, parameters=net.parameters())
    trainer = SpmdTrainer(net, opt, create_mesh(devices=jax.devices()[:1]),
                          DP_ONLY_RULES)
    text = _lowered_step(trainer, (1, 16)).compile().as_text()
    names = sorted(n for n in KERNEL_NAMES if n.startswith("selscan_"))
    assert names == ["selscan_bwd", "selscan_fwd"]
    found = {name: set() for name in names}
    for instruction, op_name in re.findall(
            r'^\s*(?:ROOT )?%?([\w.\-]+) = .*op_name="([^"]*)"', text,
            re.MULTILINE):
        for name in names:
            if f"/{name}/" in op_name:
                path = _path(op_name)
                assert path[:2] == ["pt.ssm", "pt.ssm.sel"], op_name
                found[name].add(trace_pass(op_name, instruction))
    assert found["selscan_fwd"] == {"forward", "recompute"}
    assert found["selscan_bwd"] == {"backward"}


def test_scope_names_stay_clear_of_effect_scopes():
    from paddle_tpu.pir.verifier import EFFECT_SCOPES
    for name in list(TRACE_SCOPES) + list(KERNEL_NAMES):
        assert not set(name.split("/")) & set(EFFECT_SCOPES)
