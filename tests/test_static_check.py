"""tools/static_check.py — the repo-contract linter IS a tier-1 gate:
the repo must lint clean, and an injected violation must be caught.
Runs the tool as a subprocess (it is pure stdlib — no jax — so each
run is fast) exactly the way CI invokes it."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOOL = os.path.join(REPO, "tools", "static_check.py")


def _run(*argv):
    return subprocess.run(
        [sys.executable, TOOL, *argv], cwd=REPO,
        capture_output=True, text=True, timeout=120)


def test_repo_is_clean():
    r = _run()
    assert r.returncode == 0, \
        f"repo-contract violations:\n{r.stdout}{r.stderr}"


def test_list_rules_names_the_closed_registry():
    r = _run("--list-rules")
    assert r.returncode == 0
    for rule in ("metrics-in-catalog", "catalog-docs-sync", "fault-sites",
                 "recorder-kinds", "flags-registered", "host-sync",
                 "profiler-phases", "scheduler-actions", "pir-passes",
                 "mesh-wiring", "recording-rules", "adapter-wiring",
                 "trace-scopes"):
        assert rule in r.stdout


def test_unknown_rule_is_a_usage_error():
    r = _run("--rule", "no-such-rule")
    assert r.returncode == 2
    assert "unknown rule" in r.stderr


@pytest.mark.parametrize("source,rule", [
    ('from paddle_tpu.observability.catalog import metric\n'
     'metric("nonexistent_metric_xyz").inc()\n', "metrics-in-catalog"),
    ('from paddle_tpu.resilience.faults import fault_point\n'
     'fault_point("no.such_site")\n', "fault-sites"),
    ('rec.record("not_a_kind", x=1)\n', "recorder-kinds"),
    ('import os\n'
     'os.environ.get("FLAGS_totally_unregistered")\n', "flags-registered"),
    ('import jax\n'
     'with jax.named_scope("pt.not_a_scope"):\n    pass\n', "trace-scopes"),
    ('from jax.experimental import pallas as pl\n'
     'pl.pallas_call(k, out_shape=s, name="unnamed_kernel")(x)\n',
     "trace-scopes"),
    # a name chosen between two literals: both arms are held
    ('from jax.experimental import pallas as pl\n'
     'pl.pallas_call(k, out_shape=s,\n'
     '               name="faw_fwd" if banded else "fa_forward")(x)\n',
     "trace-scopes"),
    ('import jax\n'
     'with jax.named_scope("pt.attn.full" if full else "pt.attn.other"):\n'
     '    pass\n', "trace-scopes"),
])
def test_injected_violation_fails(tmp_path, source, rule):
    bad = tmp_path / "bad_module.py"
    bad.write_text(source)
    r = _run("--paths", str(bad), "--json")
    assert r.returncode == 1, f"violation not caught:\n{r.stdout}"
    found = json.loads(r.stdout)
    assert any(v["rule"] == rule for v in found), found


def test_scheduler_actions_rule_catches_unregistered_literals(tmp_path):
    # a file masquerading as the scheduler with literals outside the
    # closed PRIORITY_CLASSES / BROWNOUT_LEVELS registries
    bad = tmp_path / "paddle_tpu" / "inference"
    bad.mkdir(parents=True)
    f = bad / "scheduler.py"
    f.write_text("_IDX = level_index('panic')\n"
                 "def admit(req, priority='vip'):\n"
                 "    if req.priority == 'urgent':\n"
                 "        return submit(req, priority='turbo')\n")
    r = _run("--paths", str(f), "--json")
    assert r.returncode == 1
    found = [v for v in json.loads(r.stdout)
             if v["rule"] == "scheduler-actions"]
    msgs = " | ".join(v["message"] for v in found)
    for lit in ("panic", "vip", "urgent", "turbo"):
        assert f"'{lit}'" in msgs, (lit, found)


def test_pir_passes_rule_catches_drift():
    # the rule compares repo registries (not scanned --paths sources),
    # so drift is injected by calling it on a stub context in-process
    import importlib.util
    from types import SimpleNamespace
    spec = importlib.util.spec_from_file_location("_sc", TOOL)
    sc = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(sc)

    order = ["fold", "fuse", "dce"]
    aligned = set(order)

    def ctx(passes=aligned, flag=order, rows=order):
        return SimpleNamespace(
            pir_passes=passes, pir_flag_default=set(flag),
            pir_flag_default_order=list(flag),
            compiler_pass_rows=set(rows),
            compiler_pass_row_order=list(rows))

    assert sc.rule_pir_passes(ctx()) == []
    drifted = sc.rule_pir_passes(ctx(
        passes=aligned | {"undocumented"},
        flag=order + ["unregistered"],
        rows=["fold"]))
    msgs = " | ".join(v.message for v in drifted)
    # registry entry missing from both mirrors, phantom flag name,
    # registry entries missing from the doc table: all directions fire
    assert "'undocumented'" in msgs and "'unregistered'" in msgs \
        and "'dce'" in msgs and "'fuse'" in msgs, msgs
    # same SETS, doc rows reordered vs the flag default: the order pin
    # fires (the pass-catalog table documents the real pipeline order)
    reordered = sc.rule_pir_passes(ctx(rows=["fuse", "fold", "dce"]))
    assert len(reordered) == 1 and "order" in reordered[0].message, \
        reordered


def test_recording_rules_rule_catches_drift():
    # the rule compares repo registries (not scanned --paths sources),
    # so drift is injected by calling it on a stub context in-process
    import importlib.util
    from types import SimpleNamespace
    spec = importlib.util.spec_from_file_location("_sc2", TOOL)
    sc = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(sc)

    rules = {"goodput_rate", "shed_fraction"}
    seam = {"obs.sample"}
    aligned = SimpleNamespace(
        recording_rules=set(rules), obs_rule_rows=set(rules),
        fault_sites=set(seam), scenarios=set(seam), res_ticks=set(seam),
        sources={})
    assert sc.rule_recording_rules(aligned) == []
    drifted = sc.rule_recording_rules(SimpleNamespace(
        recording_rules=rules | {"undocumented_rule"},
        obs_rule_rows=rules | {"phantom_rule"},
        fault_sites=set(), scenarios=set(), res_ticks=set(),
        sources={}))
    msgs = " | ".join(v.message for v in drifted)
    # registry->docs, docs->registry, and all three obs.sample
    # containments (registered, drilled, documented) fire
    assert "'undocumented_rule'" in msgs and "phantom_rule" in msgs, msgs
    assert "FAULT_SITES" in msgs and "SCENARIOS drill" in msgs \
        and "RESILIENCE.md" in msgs, msgs


def test_mesh_wiring_rule_catches_unregistered_literals(tmp_path):
    # a file masquerading as mesh code: a check() on a fault site
    # outside FAULT_SITES and a record() kind outside EVENT_KINDS.
    # (Not named router.py, so the reverse-containment checks — which
    # need the real router in the scan set — stay dormant.)
    bad = tmp_path / "paddle_tpu" / "inference" / "mesh"
    bad.mkdir(parents=True)
    f = bad / "bad_worker.py"
    f.write_text("def pump(inj, rec):\n"
                 "    inj.check('mesh.bogus_site')\n"
                 "    rec.record('bogus_mesh_kind', x=1)\n")
    r = _run("--paths", str(f), "--json")
    assert r.returncode == 1, f"violation not caught:\n{r.stdout}"
    found = [v for v in json.loads(r.stdout) if v["rule"] == "mesh-wiring"]
    msgs = " | ".join(v["message"] for v in found)
    assert "mesh.bogus_site" in msgs and "bogus_mesh_kind" in msgs, found


def test_adapter_wiring_rule_catches_uncataloged_metric(tmp_path):
    # a file masquerading as the adapter store emitting a metric
    # outside the catalog through the aliased `_metric` accessor the
    # generic metrics-in-catalog rule cannot see. (Not the real
    # adapters.py in the scan set, so the reverse-containment checks
    # stay dormant.)
    bad = tmp_path / "paddle_tpu" / "inference"
    bad.mkdir(parents=True)
    f = bad / "serving.py"
    f.write_text("def retire(rid):\n"
                 "    _metric('serving_adapter_bogus_total').inc()\n")
    r = _run("--paths", str(f), "--json")
    assert r.returncode == 1, f"violation not caught:\n{r.stdout}"
    found = [v for v in json.loads(r.stdout)
             if v["rule"] == "adapter-wiring"]
    msgs = " | ".join(v["message"] for v in found)
    assert "serving_adapter_bogus_total" in msgs, found


def test_adapter_wiring_rule_catches_unarmed_site(tmp_path):
    # the real adapters.py in the scan set arms the reverse checks; a
    # stand-in serving.py with no fault_point must trip "registered
    # but never armed" for both adapter seams (and "never emitted" for
    # the serving-side metrics the stand-in dropped)
    real = os.path.join(REPO, "paddle_tpu", "inference", "adapters.py")
    bad = tmp_path / "paddle_tpu" / "inference"
    bad.mkdir(parents=True)
    f = bad / "serving.py"
    f.write_text("def admit(req):\n"
                 "    return req\n")
    r = _run("--paths", real, str(f), "--json")
    assert r.returncode == 1, f"violation not caught:\n{r.stdout}"
    found = [v for v in json.loads(r.stdout)
             if v["rule"] == "adapter-wiring"]
    msgs = " | ".join(v["message"] for v in found)
    assert "serve.adapter_load" in msgs \
        and "serve.adapter_gather" in msgs \
        and "never armed" in msgs, found


def test_host_sync_rule_catches_new_sync(tmp_path):
    # a file masquerading as serving.py with an unallowlisted sync
    bad = tmp_path / "paddle_tpu" / "inference"
    bad.mkdir(parents=True)
    f = bad / "serving.py"
    f.write_text("import numpy as np\n"
                 "def _hot_loop(x):\n"
                 "    return np.asarray(x)\n")
    r = _run("--paths", str(f), "--json")
    assert r.returncode == 1
    found = json.loads(r.stdout)
    assert any(v["rule"] == "host-sync" and "_hot_loop" in v["message"]
               for v in found), found


def test_trace_scopes_rule_passes_declared_names_and_other_prefixes(tmp_path):
    # declared scopes and kernel names pass; scopes outside the pt.
    # namespace (kv.write, pir.fuse.*) are not this rule's business; a
    # one-file scan must not fire the "never entered" direction
    ok = tmp_path / "ok_module.py"
    ok.write_text(
        'import jax\n'
        'from jax.experimental import pallas as pl\n'
        'with jax.named_scope("pt.mlp"), jax.named_scope("kv.write"):\n'
        '    pl.pallas_call(k, out_shape=s, name="fa_fwd")(x)\n'
        'with jax.named_scope("pt.attn.sliding" if w else "pt.attn.full"):\n'
        '    pl.pallas_call(k, out_shape=s,\n'
        '                   name="faw_fwd" if w else "fa_fwd")(x)\n')
    r = _run("--rule", "trace-scopes", "--paths", str(ok), "--json")
    assert r.returncode == 0, r.stdout
