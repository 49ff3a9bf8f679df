#!/usr/bin/env python
"""Repo-contract linter: pins the registries to the code that uses them.

The repo's observability/resilience/flags surfaces are all *closed
registries* (a metric must be in the catalog, a fault site in
FAULT_SITES, ...). Runtime enforcement exists (``catalog.metric``
raises on unknown names) but only fires on the code path that runs;
this tool proves the containments **statically**, over every call
site, by parsing the source with ``ast`` — no jax import, no device,
<1s. STATIC_ANALYSIS.md is the runbook.

Rules (closed registry, like everything else here):

  metrics-in-catalog   metric("name") literals  ⊆ catalog.py CATALOG
  catalog-docs-sync    CATALOG keys            == OBSERVABILITY.md rows
  fault-sites          fault_point("s") ⊆ FAULT_SITES ⊆ chaos_drill
                       SCENARIOS; every site backticked in RESILIENCE.md
  recorder-kinds       record("kind") literals  ⊆ recorder EVENT_KINDS
  profiler-phases      mark("phase") literals in profiler/ + serving.py
                       ⊆ phases.py PHASES == OBSERVABILITY.md phase rows
  trace-scopes         named_scope("pt.*") literals == catalog.py
                       TRACE_SCOPES, pallas_call(name=) literals ==
                       KERNEL_NAMES, both == OBSERVABILITY.md scope/ and
                       kernel/ rows
  scheduler-actions    brownout-level literals (level_index("x")) and
                       priority-class literals (priority= defaults /
                       keywords, .priority comparisons) in the serving +
                       scheduler code ⊆ scheduler.py BROWNOUT_LEVELS /
                       PRIORITY_CLASSES == RESILIENCE.md rows
  flags-registered     os.environ FLAGS_* accesses and flag_value("x")
                       args ⊆ define_flag names (collected repo-wide)
  host-sync            device->host syncs (np.asarray / .item() /
                       jax.device_get / .block_until_ready) in the
                       serving hot path outside the audited allowlist
  pir-passes           pir/passes.py PASSES == FLAGS_pir_passes
                       default == COMPILER.md pass-catalog rows, and
                       the doc-table row ORDER == the flag default's
                       pipeline order
  mesh-wiring          serving-mesh fault_point/check site and record()
                       kind literals ⊆ the closed registries; every
                       registered mesh.* site armed by mesh code AND
                       backticked in RESILIENCE.md, no phantom mesh.*
                       docs — both directions; health verdict literals
                       == health.py VERDICTS == RESILIENCE.md
                       verdict/NAME rows, both directions
  recording-rules      timeseries.py RECORDING_RULES == OBSERVABILITY.md
                       `rule/NAME` rows (both directions); rule-name
                       literals at lookup sites ⊆ the registry; the
                       plane's obs.sample fault seam registered in
                       FAULT_SITES, drilled, documented in
                       RESILIENCE.md, and actually armed by the sampler
  adapter-wiring       serving_adapter_* metric literals (emitted as
                       `_metric`) ⊆ CATALOG with OBSERVABILITY.md rows
                       and all actually emitted; the `adapter` recorder
                       kind registered + emitted + documented; the
                       serve.adapter_load / serve.adapter_gather seams
                       registered, armed, drilled, in RESILIENCE.md

Usage:
  python tools/static_check.py                 # whole repo, all rules
  python tools/static_check.py --rule host-sync
  python tools/static_check.py --paths f.py    # scan these files only
                                               # (registries still come
                                               # from the repo)
  python tools/static_check.py --list-rules
  python tools/static_check.py --json

Exit 0 clean, 1 violations, 2 usage error (unknown rule).
"""

from __future__ import annotations

import argparse
import ast
import json
import os
import re
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# source roots scanned for *call sites* (tests are excluded on purpose:
# they assert that unknown names raise, which would be false positives)
SCAN_ROOTS = ("paddle_tpu", "tools")

# registry source locations (parsed as AST / text, never imported)
CATALOG_PY = "paddle_tpu/observability/catalog.py"
FAULTS_PY = "paddle_tpu/resilience/faults.py"
RECORDER_PY = "paddle_tpu/observability/recorder.py"
FLAGS_PY = "paddle_tpu/framework/flags.py"
PHASES_PY = "paddle_tpu/profiler/phases.py"
SCHEDULER_PY = "paddle_tpu/inference/scheduler.py"
CHAOS_PY = "tools/chaos_drill.py"
HEALTH_PY = "paddle_tpu/inference/mesh/health.py"
PASSES_PY = "paddle_tpu/pir/passes.py"
TIMESERIES_PY = "paddle_tpu/observability/timeseries.py"
OBS_MD = "OBSERVABILITY.md"
RES_MD = "RESILIENCE.md"
COMPILER_MD = "COMPILER.md"

# profiler-phases rule scope: the files whose mark("...") literals must
# resolve against the PHASES registry (`mark` is too generic a name to
# scan repo-wide)
PHASE_MARK_FILES = ("paddle_tpu/profiler/", "paddle_tpu/inference/serving.py")

# scheduler-actions rule scope: the files whose brownout-level /
# priority-class literals must resolve against the scheduler registries
# (`priority` is too generic a keyword to scan repo-wide)
SCHED_ACTION_FILES = ("paddle_tpu/inference/serving.py",
                      "paddle_tpu/inference/scheduler.py")

# mesh-wiring rule scope: the serving-mesh sources whose fault-site and
# event-kind literals are pinned to the closed registries (dir entry —
# matched by containment, like PHASE_MARK_FILES)
MESH_FILES = ("paddle_tpu/inference/mesh/",)

# adapter-wiring rule scope: the multi-adapter (LoRA) sources whose
# metric / event-kind / fault-site literals are pinned to the closed
# registries. adapters.py is the core gate for the reverse checks
# (like router.py for mesh-wiring): a --paths run that doesn't include
# it must not fire "never emitted" violations.
ADAPTER_FILES = ("paddle_tpu/inference/adapters.py",
                 "paddle_tpu/inference/serving.py",
                 "paddle_tpu/inference/scheduler.py",
                 "paddle_tpu/inference/loadgen.py")
ADAPTER_SITES = ("serve.adapter_load", "serve.adapter_gather")

# host-sync rule scope + allowlist: methods audited as intentional
# host syncs (see STATIC_ANALYSIS.md "Host-sync allowlist policy").
# "Cls.*" allowlists every method of the class.
HOST_SYNC_FILES = ("paddle_tpu/inference/serving.py",
                   "paddle_tpu/ops/paged_attention.py")
HOST_SYNC_ALLOW = {
    "paddle_tpu/inference/serving.py": (
        "Request.__init__",            # host-side prompt normalization
        "Request.choose",              # sampling on already-fetched logits
        "ContinuousBatchingEngine._prefill_one_chunk",  # first-token read
        "ContinuousBatchingEngine._drain_one",          # the one readback
        "ContinuousBatchingEngine._upload_lane_state",  # admission repack
        "ContinuousBatchingEngine.export_kv",   # handoff wire serialization
        "ContinuousBatchingEngine.import_kv",   # handoff block install
    ),
    "paddle_tpu/ops/paged_attention.py": (
        "BlockKVCacheManager.*",       # host-side block-table bookkeeping
    ),
}
HOST_SYNC_CALLS = {"asarray", "array", "device_get", "block_until_ready",
                   "item"}


class Violation:
    __slots__ = ("rule", "path", "line", "message")

    def __init__(self, rule, path, line, message):
        self.rule, self.path, self.line, self.message = \
            rule, path, line, message

    def as_dict(self):
        return {"rule": self.rule, "path": self.path, "line": self.line,
                "message": self.message}

    def __str__(self):
        loc = f"{self.path}:{self.line}" if self.line else self.path
        return f"{loc}: [{self.rule}] {self.message}"


# ---------------------------------------------------------------------------
# registry extraction (AST / text; no imports)
# ---------------------------------------------------------------------------

def _parse(relpath):
    path = os.path.join(REPO, relpath)
    with open(path, encoding="utf-8") as f:
        return ast.parse(f.read(), filename=relpath)


def _read(relpath):
    with open(os.path.join(REPO, relpath), encoding="utf-8") as f:
        return f.read()


def _dict_keys(relpath, var):
    """String keys of a module-level ``var = {...}`` dict literal."""
    for node in _parse(relpath).body:
        if isinstance(node, ast.Assign) \
                and any(isinstance(t, ast.Name) and t.id == var
                        for t in node.targets) \
                and isinstance(node.value, ast.Dict):
            return {k.value for k in node.value.keys
                    if isinstance(k, ast.Constant)
                    and isinstance(k.value, str)}
    raise RuntimeError(f"{relpath}: no dict literal named {var!r}")


def _defined_flags():
    """First-arg literals of every define_flag(...) call under
    paddle_tpu/ — the registry is distributed: flags.py holds the core
    set, and kernel modules (ops/pallas/*) register their own on
    import. Collected from a fixed repo walk so --paths can't shrink
    the registry out from under the rule."""
    names = set()
    for dirpath, _, files in os.walk(os.path.join(REPO, "paddle_tpu")):
        for f in files:
            if not f.endswith(".py"):
                continue
            rel = os.path.relpath(os.path.join(dirpath, f), REPO)
            for node in ast.walk(_parse(rel)):
                if isinstance(node, ast.Call) \
                        and _callee(node) == "define_flag" \
                        and node.args \
                        and isinstance(node.args[0], ast.Constant):
                    names.add(node.args[0].value)
    return names


def _pir_flag_default():
    """The pass names in the FLAGS_pir_passes default — the comma list
    in ``define_flag("pir_passes", "<literal>", ...)`` in flags.py.
    Returns the ORDERED list (the default IS the pipeline order)."""
    for node in ast.walk(_parse(FLAGS_PY)):
        if isinstance(node, ast.Call) and _callee(node) == "define_flag" \
                and node.args \
                and isinstance(node.args[0], ast.Constant) \
                and node.args[0].value == "pir_passes" \
                and len(node.args) > 1 \
                and isinstance(node.args[1], ast.Constant) \
                and isinstance(node.args[1].value, str):
            return [n for n in node.args[1].value.split(",") if n]
    raise RuntimeError(
        f"{FLAGS_PY}: no define_flag('pir_passes', <string literal>, ...)")


def _compiler_pass_rows():
    """Backticked first-cell names of the COMPILER.md pass-catalog
    table rows, scoped to the '## Pass catalog' section (the next
    '## ' heading ends it; '### ' sub-headings don't). Returns the
    ORDERED list (the table documents the default pipeline order)."""
    text = _read(COMPILER_MD)
    m = re.search(r"^## Pass catalog$(.*?)(?=^## |\Z)", text,
                  re.M | re.S)
    if not m:
        raise RuntimeError(f"{COMPILER_MD}: no '## Pass catalog' section")
    return re.findall(r"^\| `([a-z_]+)` \|", m.group(1), re.M)


def _callee(call):
    """Trailing name of a call target: f(...) and o.f(...) both -> 'f'."""
    f = call.func
    if isinstance(f, ast.Name):
        return f.id
    if isinstance(f, ast.Attribute):
        return f.attr
    return None


class Context:
    """Parsed registries + the scanned source files (path -> AST)."""

    def __init__(self, paths=None):
        self.catalog = _dict_keys(CATALOG_PY, "CATALOG")
        self.fault_sites = _dict_keys(FAULTS_PY, "FAULT_SITES")
        self.event_kinds = _dict_keys(RECORDER_PY, "EVENT_KINDS")
        self.scenarios = _dict_keys(CHAOS_PY, "SCENARIOS")
        self.phases = _dict_keys(PHASES_PY, "PHASES")
        self.flags = _defined_flags()
        self.obs_rows = set(re.findall(r"^\| `([a-z0-9_]+)` \|",
                                       _read(OBS_MD), re.M))
        self.phase_rows = set(re.findall(r"^\| `phase/([a-z_.]+)` \|",
                                         _read(OBS_MD), re.M))
        self.trace_scopes = _dict_keys(CATALOG_PY, "TRACE_SCOPES")
        self.kernel_names = _dict_keys(CATALOG_PY, "KERNEL_NAMES")
        self.scope_rows = set(re.findall(r"^\| `scope/([a-z_.]+)` \|",
                                         _read(OBS_MD), re.M))
        self.kernel_rows = set(re.findall(r"^\| `kernel/([a-z_.]+)` \|",
                                          _read(OBS_MD), re.M))
        self.res_ticks = set(re.findall(r"`([a-z_]+\.[a-z_]+)`",
                                        _read(RES_MD)))
        self.priority_classes = _dict_keys(SCHEDULER_PY, "PRIORITY_CLASSES")
        self.brownout_levels = _dict_keys(SCHEDULER_PY, "BROWNOUT_LEVELS")
        self.res_brownout_rows = set(re.findall(
            r"^\| `brownout/([a-z_]+)` \|", _read(RES_MD), re.M))
        self.res_priority_rows = set(re.findall(
            r"^\| `priority/([a-z_]+)` \|", _read(RES_MD), re.M))
        self.pir_passes = _dict_keys(PASSES_PY, "PASSES")
        self.pir_flag_default_order = _pir_flag_default()
        self.pir_flag_default = set(self.pir_flag_default_order)
        self.compiler_pass_row_order = _compiler_pass_rows()
        self.compiler_pass_rows = set(self.compiler_pass_row_order)
        self.verdicts = _dict_keys(HEALTH_PY, "VERDICTS")
        self.res_verdict_rows = set(re.findall(
            r"^\| `verdict/([a-z_]+)` \|", _read(RES_MD), re.M))
        self.recording_rules = _dict_keys(TIMESERIES_PY, "RECORDING_RULES")
        self.obs_rule_rows = set(re.findall(r"^\| `rule/([a-z0-9_]+)` \|",
                                            _read(OBS_MD), re.M))
        self.sources = {}
        for rel in (paths if paths is not None else self._default_paths()):
            try:
                self.sources[rel] = _parse(rel) if not os.path.isabs(rel) \
                    else ast.parse(open(rel, encoding="utf-8").read(),
                                   filename=rel)
            except SyntaxError as e:
                raise RuntimeError(f"{rel}: unparseable: {e}") from None

    @staticmethod
    def _default_paths():
        out = []
        for root in SCAN_ROOTS:
            for dirpath, _, files in os.walk(os.path.join(REPO, root)):
                for f in sorted(files):
                    if f.endswith(".py"):
                        out.append(os.path.relpath(
                            os.path.join(dirpath, f), REPO))
        return sorted(out)


# ---------------------------------------------------------------------------
# rules: fn(ctx) -> [Violation]
# ---------------------------------------------------------------------------

def _str_arg_calls(ctx, callee_names):
    """(path, line, literal) for every call f("literal") whose trailing
    callee name is in `callee_names`."""
    for path, tree in ctx.sources.items():
        for node in ast.walk(tree):
            if isinstance(node, ast.Call) \
                    and _callee(node) in callee_names \
                    and node.args \
                    and isinstance(node.args[0], ast.Constant) \
                    and isinstance(node.args[0].value, str):
                yield path, node.lineno, node.args[0].value


def rule_metrics_in_catalog(ctx):
    return [Violation("metrics-in-catalog", p, ln,
                      f"metric({name!r}) is not in {CATALOG_PY} CATALOG")
            for p, ln, name in _str_arg_calls(ctx, {"metric"})
            if name not in ctx.catalog]


def rule_catalog_docs_sync(ctx):
    out = []
    for name in sorted(ctx.catalog - ctx.obs_rows):
        out.append(Violation("catalog-docs-sync", OBS_MD, 0,
                             f"CATALOG metric {name!r} has no "
                             f"`| `{name}` |` row in {OBS_MD}"))
    for name in sorted(ctx.obs_rows - ctx.catalog):
        out.append(Violation("catalog-docs-sync", OBS_MD, 0,
                             f"{OBS_MD} documents {name!r} which is not "
                             f"in {CATALOG_PY} CATALOG"))
    return out


def rule_fault_sites(ctx):
    out = []
    for p, ln, name in _str_arg_calls(ctx, {"fault_point"}):
        if name not in ctx.fault_sites:
            out.append(Violation(
                "fault-sites", p, ln,
                f"fault_point({name!r}) is not in {FAULTS_PY} FAULT_SITES"))
    for name in sorted(ctx.fault_sites - ctx.scenarios):
        out.append(Violation(
            "fault-sites", CHAOS_PY, 0,
            f"FAULT_SITES entry {name!r} has no chaos_drill SCENARIOS "
            "drill (every registered site must be drillable)"))
    for name in sorted(ctx.fault_sites - ctx.res_ticks):
        out.append(Violation(
            "fault-sites", RES_MD, 0,
            f"FAULT_SITES entry {name!r} is never mentioned (backticked) "
            f"in {RES_MD}"))
    return out


def rule_recorder_kinds(ctx):
    return [Violation("recorder-kinds", p, ln,
                      f"record({kind!r}) is not in {RECORDER_PY} "
                      "EVENT_KINDS")
            for p, ln, kind in _str_arg_calls(ctx, {"record"})
            if kind not in ctx.event_kinds]


def rule_profiler_phases(ctx):
    """The per-phase profiler's registry (profiler/phases.py PHASES) is
    closed like the metric catalog: every mark("...") literal in the
    profiler and the serving engine must name a registered phase, and
    every registered phase must have a `| \\`phase/NAME\\` |` row in
    OBSERVABILITY.md — both directions, so the docs can't drift."""
    out = []
    for path, tree in ctx.sources.items():
        norm = path.replace(os.sep, "/")
        # dir entries (trailing /) match by containment so --paths runs
        # on copies still resolve; file entries match by suffix
        if not any((s.endswith("/") and s in norm) or norm.endswith(s)
                   for s in PHASE_MARK_FILES):
            continue
        for node in ast.walk(tree):
            if not (isinstance(node, ast.Call) and _callee(node) == "mark"
                    and node.args):
                continue
            arg = node.args[0]
            # plain literal, or both arms of mark("a" if c else "b")
            lits = [arg] if isinstance(arg, ast.Constant) else \
                ([arg.body, arg.orelse] if isinstance(arg, ast.IfExp)
                 else [])
            for lit in lits:
                if isinstance(lit, ast.Constant) \
                        and isinstance(lit.value, str) \
                        and lit.value not in ctx.phases:
                    out.append(Violation(
                        "profiler-phases", path, node.lineno,
                        f"mark({lit.value!r}) is not in "
                        f"{PHASES_PY} PHASES"))
    for name in sorted(ctx.phases - ctx.phase_rows):
        out.append(Violation(
            "profiler-phases", OBS_MD, 0,
            f"PHASES entry {name!r} has no `| `phase/{name}` |` row in "
            f"{OBS_MD}"))
    for name in sorted(ctx.phase_rows - ctx.phases):
        out.append(Violation(
            "profiler-phases", OBS_MD, 0,
            f"{OBS_MD} documents phase {name!r} which is not in "
            f"{PHASES_PY} PHASES"))
    return out


def _str_literals(node):
    """The string literals an argument can be: a constant, or a conditional
    expression both of whose arms are (``"faw_fwd" if banded else
    "fa_fwd"``); [] for anything else."""
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return [node.value]
    if isinstance(node, ast.IfExp):
        arms = _str_literals(node.body) + _str_literals(node.orelse)
        return arms if len(arms) == 2 else []
    return []


def rule_trace_scopes(ctx):
    """The names the program gives its work on a device trace are closed
    like the metric catalog (observability/catalog.py TRACE_SCOPES and
    KERNEL_NAMES): every ``named_scope("pt. ...")`` literal must be a
    declared component scope, every ``pallas_call(..., name="...")``
    literal a declared kernel name, every declared name must be entered
    somewhere, and each must have a `| \\`scope/NAME\\` |` /
    `| \\`kernel/NAME\\` |` row in OBSERVABILITY.md — both directions.
    A per-layer metric of the benchmark matches on these names; one that
    drifts silently reads nothing."""
    out = []
    used_scopes, used_kernels = set(), set()
    for path, tree in ctx.sources.items():
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            callee = _callee(node)
            if callee == "named_scope" and node.args:
                for name in _str_literals(node.args[0]):
                    if not name.startswith("pt."):
                        continue
                    used_scopes.add(name)
                    if name not in ctx.trace_scopes:
                        out.append(Violation(
                            "trace-scopes", path, node.lineno,
                            f"named_scope({name!r}) is not in {CATALOG_PY} "
                            "TRACE_SCOPES"))
            elif callee == "pallas_call":
                for kw in node.keywords:
                    if kw.arg != "name":
                        continue
                    for name in _str_literals(kw.value):
                        used_kernels.add(name)
                        if name not in ctx.kernel_names:
                            out.append(Violation(
                                "trace-scopes", path, node.lineno,
                                f"pallas_call(name={name!r}) is "
                                f"not in {CATALOG_PY} KERNEL_NAMES"))
    # reverse direction only on a scan that includes the registry's own
    # file (a --paths run on one module must not fire "never entered")
    if any(p.replace(os.sep, "/").endswith(CATALOG_PY)
           for p in ctx.sources):
        for name in sorted(ctx.trace_scopes - used_scopes):
            out.append(Violation(
                "trace-scopes", CATALOG_PY, 0,
                f"TRACE_SCOPES entry {name!r} is entered by no "
                "named_scope() literal"))
        for name in sorted(ctx.kernel_names - used_kernels):
            out.append(Violation(
                "trace-scopes", CATALOG_PY, 0,
                f"KERNEL_NAMES entry {name!r} names no pallas_call()"))
    for reg, rows, kind in ((ctx.trace_scopes, ctx.scope_rows, "scope"),
                            (ctx.kernel_names, ctx.kernel_rows, "kernel")):
        for name in sorted(reg - rows):
            out.append(Violation(
                "trace-scopes", OBS_MD, 0,
                f"{kind} name {name!r} has no `| `{kind}/{name}` |` row "
                f"in {OBS_MD}"))
        for name in sorted(rows - reg):
            out.append(Violation(
                "trace-scopes", OBS_MD, 0,
                f"{OBS_MD} documents {kind}/{name} which is not in "
                f"{CATALOG_PY}"))
    return out


def rule_scheduler_actions(ctx):
    """The SLO scheduler's registries (scheduler.py BROWNOUT_LEVELS /
    PRIORITY_CLASSES) are closed like the metric catalog: every
    brownout-level literal (``level_index("x")``) and priority-class
    literal (a ``priority=`` default or call keyword, or a string
    compared against a ``.priority`` attribute) in the serving +
    scheduler code must name a registered entry, and every entry must
    have a `| \\`brownout/NAME\\` |` / `| \\`priority/NAME\\` |` row in
    RESILIENCE.md's overload runbook — both directions."""
    out = []

    def bad_level(path, line, name):
        out.append(Violation(
            "scheduler-actions", path, line,
            f"level_index({name!r}) is not in {SCHEDULER_PY} "
            "BROWNOUT_LEVELS"))

    def bad_prio(path, line, name, how):
        out.append(Violation(
            "scheduler-actions", path, line,
            f"{how} {name!r} is not in {SCHEDULER_PY} PRIORITY_CLASSES"))

    for path, tree in ctx.sources.items():
        norm = path.replace(os.sep, "/")
        if not any(norm.endswith(s) for s in SCHED_ACTION_FILES):
            continue
        for node in ast.walk(tree):
            if isinstance(node, ast.Call):
                if _callee(node) == "level_index" and node.args \
                        and isinstance(node.args[0], ast.Constant) \
                        and isinstance(node.args[0].value, str) \
                        and node.args[0].value not in ctx.brownout_levels:
                    bad_level(path, node.lineno, node.args[0].value)
                for kw in node.keywords:
                    if kw.arg == "priority" \
                            and isinstance(kw.value, ast.Constant) \
                            and isinstance(kw.value.value, str) \
                            and kw.value.value not in ctx.priority_classes:
                        bad_prio(path, node.lineno, kw.value.value,
                                 "priority= keyword")
            elif isinstance(node, ast.Compare):
                sides = [node.left, *node.comparators]
                if not any(isinstance(s, ast.Attribute)
                           and s.attr == "priority" for s in sides):
                    continue
                for s in sides:
                    if isinstance(s, ast.Constant) \
                            and isinstance(s.value, str) \
                            and s.value not in ctx.priority_classes:
                        bad_prio(path, node.lineno, s.value,
                                 ".priority compared against")
            elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                a = node.args
                pos = a.posonlyargs + a.args
                pairs = list(zip(pos[len(pos) - len(a.defaults):],
                                 a.defaults))
                pairs += [(p, d) for p, d in
                          zip(a.kwonlyargs, a.kw_defaults) if d is not None]
                for param, default in pairs:
                    if param.arg == "priority" \
                            and isinstance(default, ast.Constant) \
                            and isinstance(default.value, str) \
                            and default.value not in ctx.priority_classes:
                        bad_prio(path, node.lineno, default.value,
                                 "priority= default")
    for reg, rows, kind in ((ctx.brownout_levels, ctx.res_brownout_rows,
                             "brownout"),
                            (ctx.priority_classes, ctx.res_priority_rows,
                             "priority")):
        for name in sorted(reg - rows):
            out.append(Violation(
                "scheduler-actions", RES_MD, 0,
                f"{kind} registry entry {name!r} has no "
                f"`| `{kind}/{name}` |` row in {RES_MD}"))
        for name in sorted(rows - reg):
            out.append(Violation(
                "scheduler-actions", RES_MD, 0,
                f"{RES_MD} documents {kind}/{name} which is not in "
                f"{SCHEDULER_PY}"))
    return out


def rule_flags_registered(ctx):
    """Two access shapes must resolve against flags.py:

    * environment reads/writes of a ``FLAGS_*`` literal — via
      ``os.environ.get/.setdefault`` or subscripting — which is how
      standalone-importable modules (metrics, recorder, tracing) see
      flags without importing the framework;
    * ``flag_value("name")`` / ``set_flags({"name": ...})`` calls.

    Flag *help texts* routinely mention reference-paddle ``FLAGS_*``
    names that are deliberately not registered here, so the rule only
    looks at access expressions, never at arbitrary string literals.
    """
    out = []
    for path, tree in ctx.sources.items():
        for node in ast.walk(tree):
            name = None
            if isinstance(node, ast.Call) and _callee(node) in \
                    ("get", "setdefault") and node.args \
                    and isinstance(node.func, ast.Attribute) \
                    and isinstance(node.func.value, ast.Attribute) \
                    and node.func.value.attr == "environ" \
                    and isinstance(node.args[0], ast.Constant) \
                    and isinstance(node.args[0].value, str) \
                    and node.args[0].value.startswith("FLAGS_"):
                name = node.args[0].value
            elif isinstance(node, ast.Subscript) \
                    and isinstance(node.value, ast.Attribute) \
                    and node.value.attr == "environ" \
                    and isinstance(node.slice, ast.Constant) \
                    and isinstance(node.slice.value, str) \
                    and node.slice.value.startswith("FLAGS_"):
                name = node.slice.value
            if name is not None \
                    and name.removeprefix("FLAGS_") not in ctx.flags:
                out.append(Violation(
                    "flags-registered", path, node.lineno,
                    f"environment access to {name!r} but "
                    f"{name.removeprefix('FLAGS_')!r} is not "
                    "define_flag()ed anywhere under paddle_tpu/"))
    for p, ln, name in _str_arg_calls(ctx, {"flag_value"}):
        short = name.removeprefix("FLAGS_")
        if short not in ctx.flags:
            out.append(Violation(
                "flags-registered", p, ln,
                f"flag_value({name!r}) but {short!r} is not "
                "define_flag()ed anywhere under paddle_tpu/"))
    # set_flags({"name": v}) / get_flags(["name"]) dict/list literals
    for path, tree in ctx.sources.items():
        for node in ast.walk(tree):
            if not (isinstance(node, ast.Call)
                    and _callee(node) in ("set_flags", "get_flags")
                    and node.args):
                continue
            arg = node.args[0]
            lits = []
            if isinstance(arg, ast.Dict):
                lits = [k for k in arg.keys if isinstance(k, ast.Constant)]
            elif isinstance(arg, (ast.List, ast.Tuple)):
                lits = [e for e in arg.elts if isinstance(e, ast.Constant)]
            for k in lits:
                if not isinstance(k.value, str):
                    continue
                short = k.value.removeprefix("FLAGS_")
                if short not in ctx.flags:
                    out.append(Violation(
                        "flags-registered", path, node.lineno,
                        f"{_callee(node)}({k.value!r}) but {short!r} is "
                        "not define_flag()ed anywhere under paddle_tpu/"))
    return out


def _qualnames(tree):
    """(node, 'Cls.meth'/'fn') for every function, walked with scope."""
    out = []

    def visit(node, stack):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            out.append((node, ".".join(stack)))
        for ch in ast.iter_child_nodes(node):
            nxt = stack
            if isinstance(ch, (ast.FunctionDef, ast.AsyncFunctionDef,
                               ast.ClassDef)):
                nxt = stack + [ch.name]
            visit(ch, nxt)

    visit(tree, [])
    return out


def _allowed(qual, allow):
    for a in allow:
        if a.endswith(".*"):
            if qual.startswith(a[:-1]) or qual == a[:-2]:
                return True
        elif qual == a:
            return True
    return False


def rule_host_sync(ctx):
    """A device->host sync in the serving hot path stalls the whole
    batch (SERVING.md's single-readback design) — any new one must be
    audited into HOST_SYNC_ALLOW, not merged silently. jnp.asarray is
    host->device (an upload) and is not flagged."""
    out = []
    for path, tree in ctx.sources.items():
        norm = path.replace(os.sep, "/")
        scope = next((f for f in HOST_SYNC_FILES if norm.endswith(f)),
                     None)
        if scope is None:
            continue
        allow = HOST_SYNC_ALLOW.get(scope, ())
        for fn, qual in _qualnames(tree):
            if _allowed(qual, allow):
                continue
            for node in ast.walk(fn):
                if not isinstance(node, ast.Call):
                    continue
                callee = _callee(node)
                if callee not in HOST_SYNC_CALLS:
                    continue
                # np.asarray / np.array are syncs; jnp.* is an upload
                if callee in ("asarray", "array"):
                    f = node.func
                    if not (isinstance(f, ast.Attribute)
                            and isinstance(f.value, ast.Name)
                            and f.value.id == "np"):
                        continue
                out.append(Violation(
                    "host-sync", path, node.lineno,
                    f"device->host sync `{callee}` in {qual} (not in the "
                    "audited allowlist; see STATIC_ANALYSIS.md)"))
    return out


def rule_pir_passes(ctx):
    """The PIR pass registry (pir/passes.py PASSES) is closed like the
    metric catalog, and it has two mirrors that must not drift: the
    FLAGS_pir_passes default (every registered pass ships enabled — a
    pass that shouldn't run by default must be *removed* deliberately,
    in both places) and the COMPILER.md pass-catalog table (every pass
    documented, nothing phantom documented). All pairwise, both
    directions — and ORDER-pinned: the COMPILER.md table rows must list
    the flag default's pipeline order (the table documents the order
    the passes actually run in; a reorder in one place without the
    other is doc rot)."""
    out = []
    pairs = ((ctx.pir_flag_default, FLAGS_PY,
              "the FLAGS_pir_passes default"),
             (ctx.compiler_pass_rows, COMPILER_MD,
              f"the {COMPILER_MD} pass-catalog table"))
    for other, where, desc in pairs:
        for name in sorted(ctx.pir_passes - other):
            out.append(Violation(
                "pir-passes", where, 0,
                f"PASSES entry {name!r} is missing from {desc}"))
        for name in sorted(other - ctx.pir_passes):
            out.append(Violation(
                "pir-passes", where, 0,
                f"{desc} lists {name!r} which is not in "
                f"{PASSES_PY} PASSES"))
    if (not out
            and ctx.compiler_pass_row_order != ctx.pir_flag_default_order):
        out.append(Violation(
            "pir-passes", COMPILER_MD, 0,
            f"pass-catalog row order {ctx.compiler_pass_row_order} does "
            f"not match the FLAGS_pir_passes default order "
            f"{ctx.pir_flag_default_order}"))
    return out


def rule_mesh_wiring(ctx):
    """The serving mesh's failure wiring is pinned both ways: every
    fault site it arms — ``fault_point`` AND the behavioral ``check()``
    (which the fault-sites rule does not scan) — every flight-recorder
    kind it emits, and every metric it counts must name a registered
    entry; every registered ``mesh.*`` site must actually be consulted
    by mesh code and backticked in RESILIENCE.md's mesh runbook; every
    ``mesh_*`` catalog metric and the mesh-owned event kinds (``mesh``,
    ``controller``) must actually be emitted by mesh code; and
    RESILIENCE.md may not document a phantom ``mesh.*`` site.

    The round-21 health verdicts close the same way: every string a
    mesh source assigns to or compares against a ``verdict`` variable
    must be a ``health.VERDICTS`` key, every key must be exercised by
    mesh code, and the registry must mirror RESILIENCE.md's
    ``verdict/NAME`` table rows in both directions."""
    out = []
    used_sites, used_kinds, used_metrics = set(), set(), set()
    used_verdicts = set()
    scanned_mesh_core = False

    def _verdict_literals(node):
        # verdict = "slow" / verdict ==|!= "dead" (either operand order)
        if isinstance(node, ast.Assign):
            if any(isinstance(t, ast.Name) and t.id == "verdict"
                   for t in node.targets) \
                    and isinstance(node.value, ast.Constant) \
                    and isinstance(node.value.value, str):
                yield node.value.value
        elif isinstance(node, ast.Compare):
            operands = [node.left, *node.comparators]
            if any(isinstance(o, ast.Name) and o.id == "verdict"
                   for o in operands):
                for o in operands:
                    if isinstance(o, ast.Constant) \
                            and isinstance(o.value, str):
                        yield o.value

    for path, tree in ctx.sources.items():
        norm = path.replace(os.sep, "/")
        if not any(s in norm for s in MESH_FILES):
            continue
        if norm.endswith("inference/mesh/router.py"):
            scanned_mesh_core = True
        for node in ast.walk(tree):
            for lit in _verdict_literals(node):
                used_verdicts.add(lit)
                if lit not in ctx.verdicts:
                    out.append(Violation(
                        "mesh-wiring", path, node.lineno,
                        f"verdict literal {lit!r} is not in {HEALTH_PY} "
                        "VERDICTS"))
            if not (isinstance(node, ast.Call) and node.args
                    and isinstance(node.args[0], ast.Constant)
                    and isinstance(node.args[0].value, str)):
                continue
            callee = _callee(node)
            lit = node.args[0].value
            if callee in ("fault_point", "check"):
                used_sites.add(lit)
                if lit not in ctx.fault_sites:
                    out.append(Violation(
                        "mesh-wiring", path, node.lineno,
                        f"{callee}({lit!r}) is not in {FAULTS_PY} "
                        "FAULT_SITES"))
            elif callee == "record":
                used_kinds.add(lit)
                if lit not in ctx.event_kinds:
                    out.append(Violation(
                        "mesh-wiring", path, node.lineno,
                        f"record({lit!r}) is not in {RECORDER_PY} "
                        "EVENT_KINDS"))
            elif callee in ("metric", "_metric"):
                # the metrics-in-catalog rule only sees the bare
                # `metric` callee; mesh sources import it as `_metric`
                used_metrics.add(lit)
                if lit not in ctx.catalog:
                    out.append(Violation(
                        "mesh-wiring", path, node.lineno,
                        f"{callee}({lit!r}) is not in {CATALOG_PY} "
                        "CATALOG"))
    mesh_sites = {s for s in ctx.fault_sites if s.startswith("mesh.")}
    if scanned_mesh_core:
        # reverse containment only when the real mesh sources were in
        # the scan set (a --paths run on one file must not fire these)
        for name in sorted(mesh_sites - used_sites):
            out.append(Violation(
                "mesh-wiring", FAULTS_PY, 0,
                f"mesh fault site {name!r} is registered but never "
                "armed (fault_point/check) under "
                "paddle_tpu/inference/mesh/"))
        for kind in ("mesh", "controller"):
            if kind in ctx.event_kinds and kind not in used_kinds:
                out.append(Violation(
                    "mesh-wiring", RECORDER_PY, 0,
                    f"EVENT_KINDS entry {kind!r} is never emitted by "
                    "paddle_tpu/inference/mesh/ code"))
        mesh_metrics = {m for m in ctx.catalog if m.startswith("mesh_")}
        for name in sorted(mesh_metrics - used_metrics):
            out.append(Violation(
                "mesh-wiring", CATALOG_PY, 0,
                f"catalog metric {name!r} is never emitted by "
                "paddle_tpu/inference/mesh/ code"))
        for name in sorted(ctx.verdicts - used_verdicts):
            out.append(Violation(
                "mesh-wiring", HEALTH_PY, 0,
                f"VERDICTS entry {name!r} is never assigned or compared "
                "by paddle_tpu/inference/mesh/ code"))
    for name in sorted(ctx.verdicts - ctx.res_verdict_rows):
        out.append(Violation(
            "mesh-wiring", RES_MD, 0,
            f"VERDICTS entry {name!r} has no `| `verdict/{name}` |` row "
            f"in {RES_MD}"))
    for name in sorted(ctx.res_verdict_rows - ctx.verdicts):
        out.append(Violation(
            "mesh-wiring", RES_MD, 0,
            f"{RES_MD} documents verdict/{name} which is not in "
            f"{HEALTH_PY} VERDICTS"))
    res_mesh = {t for t in ctx.res_ticks if t.startswith("mesh.")}
    for name in sorted(mesh_sites - res_mesh):
        out.append(Violation(
            "mesh-wiring", RES_MD, 0,
            f"mesh fault site {name!r} is not backticked in {RES_MD}"))
    for name in sorted(res_mesh - mesh_sites):
        out.append(Violation(
            "mesh-wiring", RES_MD, 0,
            f"{RES_MD} mentions mesh site {name!r} which is not in "
            f"{FAULTS_PY} FAULT_SITES"))
    return out


def rule_adapter_wiring(ctx):
    """The multi-adapter (LoRA) serving surface is pinned both ways:
    every ``serving_adapter_*`` metric literal the adapter sources emit
    (they import the accessor as ``_metric``, which the
    metrics-in-catalog rule's bare-``metric`` scan does not see) must
    be a catalog entry with an OBSERVABILITY.md row; every
    ``serving_adapter_*`` catalog entry must actually be emitted by
    the adapter sources; the ``adapter`` flight-recorder kind must be
    registered, emitted, and described in OBSERVABILITY.md's flight
    recorder section; and the two admission fault seams
    (``serve.adapter_load`` / ``serve.adapter_gather``) must be
    registered in FAULT_SITES, armed (``fault_point``) by the serving
    engine, drilled by chaos_drill SCENARIOS, and backticked in
    RESILIENCE.md — the typed-reject degrade contract is only real if
    every leg of that chain exists."""
    out = []
    used_metrics, used_kinds, armed_sites = set(), set(), set()
    scanned_core = False
    for path, tree in ctx.sources.items():
        norm = path.replace(os.sep, "/")
        if not any(norm.endswith(s) for s in ADAPTER_FILES):
            continue
        if norm.endswith("inference/adapters.py"):
            scanned_core = True
        for node in ast.walk(tree):
            if not (isinstance(node, ast.Call) and node.args
                    and isinstance(node.args[0], ast.Constant)
                    and isinstance(node.args[0].value, str)):
                continue
            callee = _callee(node)
            lit = node.args[0].value
            if callee in ("metric", "_metric"):
                if lit.startswith("serving_adapter_"):
                    used_metrics.add(lit)
                if lit not in ctx.catalog:
                    out.append(Violation(
                        "adapter-wiring", path, node.lineno,
                        f"{callee}({lit!r}) is not in {CATALOG_PY} "
                        "CATALOG"))
            elif callee == "record" and lit == "adapter":
                used_kinds.add(lit)
            elif callee == "fault_point" and lit in ADAPTER_SITES:
                armed_sites.add(lit)
    adapter_metrics = {m for m in ctx.catalog
                       if m.startswith("serving_adapter_")}
    if not adapter_metrics:
        out.append(Violation(
            "adapter-wiring", CATALOG_PY, 0,
            "no serving_adapter_* metrics in CATALOG (the adapter "
            "store's evidence surface is gone)"))
    for name in sorted(adapter_metrics - ctx.obs_rows):
        out.append(Violation(
            "adapter-wiring", OBS_MD, 0,
            f"catalog metric {name!r} has no `| `{name}` |` row in "
            f"{OBS_MD}"))
    if "adapter" not in ctx.event_kinds:
        out.append(Violation(
            "adapter-wiring", RECORDER_PY, 0,
            "flight-recorder kind 'adapter' is not in EVENT_KINDS"))
    elif not re.search(r"`adapter`\s*\(", _read(OBS_MD)):
        out.append(Violation(
            "adapter-wiring", OBS_MD, 0,
            "flight-recorder kind 'adapter' is not described in "
            f"{OBS_MD}'s flight recorder section"))
    for site in ADAPTER_SITES:
        if site not in ctx.fault_sites:
            out.append(Violation(
                "adapter-wiring", FAULTS_PY, 0,
                f"adapter fault site {site!r} is not registered in "
                f"{FAULTS_PY} FAULT_SITES"))
        if site not in ctx.scenarios:
            out.append(Violation(
                "adapter-wiring", CHAOS_PY, 0,
                f"adapter fault site {site!r} has no chaos_drill "
                "SCENARIOS drill"))
        if site not in ctx.res_ticks:
            out.append(Violation(
                "adapter-wiring", RES_MD, 0,
                f"adapter fault site {site!r} is never mentioned "
                f"(backticked) in {RES_MD}"))
    if scanned_core:
        # reverse containment only when the real adapter sources were
        # in the scan set (a --paths run on one file must not fire)
        for name in sorted(adapter_metrics - used_metrics):
            out.append(Violation(
                "adapter-wiring", CATALOG_PY, 0,
                f"catalog metric {name!r} is never emitted by the "
                "adapter serving sources"))
        if "adapter" in ctx.event_kinds and "adapter" not in used_kinds:
            out.append(Violation(
                "adapter-wiring", RECORDER_PY, 0,
                "EVENT_KINDS entry 'adapter' is never emitted by the "
                "adapter serving sources"))
        for site in ADAPTER_SITES:
            if site in ctx.fault_sites and site not in armed_sites:
                out.append(Violation(
                    "adapter-wiring", FAULTS_PY, 0,
                    f"adapter fault site {site!r} is registered but "
                    "never armed (fault_point) by the serving engine"))
    return out


def rule_recording_rules(ctx):
    """The recording-rule registry (timeseries.py RECORDING_RULES) is
    closed like the metric catalog, with one documentation mirror:
    every rule must have a `| \\`rule/NAME\\` |` row in
    OBSERVABILITY.md's recording-rule table and vice versa. Rule-name
    literals at lookup sites (``rule_latest("x")`` anywhere; the mesh
    router's ``collector.latest("x")``) must name a registered rule.
    And the plane's failure seam is pinned end to end: ``obs.sample``
    must be registered in FAULT_SITES, drilled by chaos_drill
    SCENARIOS, backticked in RESILIENCE.md, and actually armed
    (``fault_point``) by the sampler source."""
    out = []
    for name in sorted(ctx.recording_rules - ctx.obs_rule_rows):
        out.append(Violation(
            "recording-rules", OBS_MD, 0,
            f"RECORDING_RULES entry {name!r} has no `| `rule/{name}` |` "
            f"row in {OBS_MD}"))
    for name in sorted(ctx.obs_rule_rows - ctx.recording_rules):
        out.append(Violation(
            "recording-rules", OBS_MD, 0,
            f"{OBS_MD} documents rule/{name} which is not in "
            f"{TIMESERIES_PY} RECORDING_RULES"))
    for p, ln, name in _str_arg_calls(ctx, {"rule_latest"}):
        if name not in ctx.recording_rules:
            out.append(Violation(
                "recording-rules", p, ln,
                f"rule_latest({name!r}) is not in {TIMESERIES_PY} "
                "RECORDING_RULES"))
    scanned_sampler = False
    armed = False
    for path, tree in ctx.sources.items():
        norm = path.replace(os.sep, "/")
        if norm.endswith(TIMESERIES_PY):
            scanned_sampler = True
            armed = any(
                isinstance(node, ast.Call)
                and _callee(node) == "fault_point" and node.args
                and isinstance(node.args[0], ast.Constant)
                and node.args[0].value == "obs.sample"
                for node in ast.walk(tree))
        elif norm.endswith("inference/mesh/router.py"):
            # MeshCollector.latest() takes rule names (the sampler's
            # own .latest() takes raw metric names, so only the
            # router's call sites are in scope)
            for node in ast.walk(tree):
                if isinstance(node, ast.Call) \
                        and _callee(node) == "latest" and node.args \
                        and isinstance(node.args[0], ast.Constant) \
                        and isinstance(node.args[0].value, str) \
                        and node.args[0].value not in ctx.recording_rules:
                    out.append(Violation(
                        "recording-rules", path, node.lineno,
                        f"collector.latest({node.args[0].value!r}) is "
                        f"not in {TIMESERIES_PY} RECORDING_RULES"))
    if "obs.sample" not in ctx.fault_sites:
        out.append(Violation(
            "recording-rules", FAULTS_PY, 0,
            "the observability plane's fault seam 'obs.sample' is not "
            f"registered in {FAULTS_PY} FAULT_SITES"))
    if "obs.sample" not in ctx.scenarios:
        out.append(Violation(
            "recording-rules", CHAOS_PY, 0,
            "'obs.sample' has no chaos_drill SCENARIOS drill (the "
            "plane-off degradation must be drillable)"))
    if "obs.sample" not in ctx.res_ticks:
        out.append(Violation(
            "recording-rules", RES_MD, 0,
            f"'obs.sample' is never mentioned (backticked) in {RES_MD}"))
    if scanned_sampler and not armed:
        # gated on the real sampler source being in the scan set (a
        # --paths run on another file must not fire this)
        out.append(Violation(
            "recording-rules", TIMESERIES_PY, 0,
            "'obs.sample' is registered but never armed (fault_point) "
            f"in {TIMESERIES_PY}"))
    return out


RULES = {
    "metrics-in-catalog": (rule_metrics_in_catalog,
                           "metric() literals are catalog entries"),
    "catalog-docs-sync": (rule_catalog_docs_sync,
                          "CATALOG == OBSERVABILITY.md rows, both ways"),
    "fault-sites": (rule_fault_sites,
                    "fault_point ⊆ FAULT_SITES ⊆ chaos drills ⊆ docs"),
    "recorder-kinds": (rule_recorder_kinds,
                       "record() kinds are EVENT_KINDS entries"),
    "profiler-phases": (rule_profiler_phases,
                        "mark() literals ⊆ profiler PHASES == "
                        "OBSERVABILITY.md phase rows"),
    "trace-scopes": (rule_trace_scopes,
                     "named_scope(pt.*) / pallas_call(name=) literals == "
                     "TRACE_SCOPES / KERNEL_NAMES == OBSERVABILITY.md rows"),
    "scheduler-actions": (rule_scheduler_actions,
                          "brownout/priority literals ⊆ scheduler "
                          "registries == RESILIENCE.md rows"),
    "flags-registered": (rule_flags_registered,
                         "FLAGS_* env accesses and flag_value args are "
                         "define_flag()ed"),
    "host-sync": (rule_host_sync,
                  "no unaudited device->host syncs in the serving path"),
    "pir-passes": (rule_pir_passes,
                   "pir PASSES == FLAGS_pir_passes default == "
                   "COMPILER.md pass-catalog rows"),
    "mesh-wiring": (rule_mesh_wiring,
                    "mesh site/kind literals ⊆ registries; mesh.* "
                    "sites armed + in RESILIENCE.md, both ways"),
    "recording-rules": (rule_recording_rules,
                        "RECORDING_RULES == OBSERVABILITY.md rule/ rows; "
                        "obs.sample registered, drilled, documented, "
                        "armed"),
    "adapter-wiring": (rule_adapter_wiring,
                       "serving_adapter_* metrics emitted + cataloged + "
                       "documented; adapter sites armed, drilled, in "
                       "RESILIENCE.md"),
}


def run(rules=None, paths=None):
    ctx = Context(paths=paths)
    out = []
    for name in (rules or sorted(RULES)):
        fn, _ = RULES[name]
        out.extend(fn(ctx))
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="repo-contract linter (see STATIC_ANALYSIS.md)")
    ap.add_argument("--rule", action="append",
                    help="run only this rule (repeatable)")
    ap.add_argument("--paths", nargs="+",
                    help="scan these source files instead of the repo "
                         "roots (registries still come from the repo)")
    ap.add_argument("--list-rules", action="store_true")
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args(argv)

    if args.list_rules:
        for name in sorted(RULES):
            print(f"{name:20s} {RULES[name][1]}")
        return 0
    for r in args.rule or ():
        if r not in RULES:
            print(f"unknown rule {r!r}; --list-rules shows the registry",
                  file=sys.stderr)
            return 2

    violations = run(rules=args.rule, paths=args.paths)
    if args.json:
        print(json.dumps([v.as_dict() for v in violations], indent=2))
    else:
        for v in violations:
            print(v)
    if violations:
        ran = ", ".join(args.rule) if args.rule else "all rules"
        print(f"static_check: {len(violations)} violation(s) ({ran})",
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
