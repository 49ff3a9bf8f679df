"""The Phi-4-mini-flash-reasoning cell, by files and entries alone: its own
scratch tree (a tiny configuration of the cell's ten layers, its traffic
and a BENCHMARK json of one cell, under tests/tiny_phi4flash/, with every
per-layer metric file as committed) passed to run.py by --benchmark-json;
the real cell's files resolve; the real configuration file keeps every
published number; the operation and byte counts equal hand counts and the
two new readers read a recorded trace as the hand count does; each
planted fault comes out over its limits by the blocks it touches."""

import json
import os
import shutil
import subprocess
import sys
from types import SimpleNamespace

import pytest

from harness import cells, diff_attn_flops, flops, selective_scan_bytes, \
    window_flops

from conftest import BENCH_DIR, ROOT

TINY = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                    "tiny_phi4flash")
CELL = "phi-4-mini-flash-reasoning-d10.pretrain-32k"
TINY_CELL = "tiny-phi4flash.tiny-train-32k"
NO_LIST = {"train_mfu", "train_step_hbm_gib", "compiles_in_window"}
NEW_METRICS = {"sel_scan_time_share", "sel_scan_roofline", "gmu_time_share",
               "attn_cross_mixer_time_share", "attn_diff_fwd_roofline",
               "attn_diff_bwd_roofline"}
KINDS = ("mamba", "memory_mamba", "sliding_attention", "full_attention",
         "cross_attention", "gmu")
# readings reported and not judged: no control reads at least 3 x over the
# program's on them (PERF.md section 4)
UNJUDGED = ("mlp", "dD.selscan_backward", "grad_lambda", "update_lambda")
FIRST_STEP = ("first_step.grad", "first_step.update")


@pytest.fixture()
def phi4flash_tree(tmp_path):
    base = tmp_path / "bench"
    shutil.copytree(os.path.join(TINY, "configs"), base / "configs")
    shutil.copytree(os.path.join(TINY, "traffic"), base / "traffic")
    shutil.copytree(os.path.join(BENCH_DIR, "layer_metrics"),
                    base / "layer_metrics")
    path = tmp_path / "BENCHMARK.json"
    shutil.copy(os.path.join(TINY, "BENCHMARK.tiny-phi4flash.json"), path)
    return str(path)


def test_the_cell_resolves_with_its_metrics(phi4flash_tree, benchmark_json):
    for name, path in ((TINY_CELL, phi4flash_tree), (CELL, None)):
        cell = cells.load_cell(name, path)
        assert cell.config["family"] == "phi4flash"
        assert cell.traffic["kind"] == "train" and cell.chips == 1
        reported = {m["name"] for m in cell.per_layer}
        # at least these: a later benchmark change may add shared ones
        assert NEW_METRICS | NO_LIST <= reported
        for m in cell.per_layer:
            reader = cell.layer_files[m["name"]]["reader"]
            assert os.path.exists(os.path.join(BENCH_DIR, "readers",
                                               reader + ".py"))
    by_name = {m["name"]: m for m in benchmark_json["per_layer"]}
    for name in NEW_METRICS:            # listed for the cell, at least
        assert CELL in by_name[name]["workloads"]
        assert by_name[name]["moves"] == "train_tokens_per_s"
    assert by_name["sel_scan_roofline"]["layer"] == \
        by_name["sel_scan_time_share"]["layer"] == \
        "state-space scan ops/selective_scan.py"
    assert by_name["attn_diff_fwd_roofline"]["layer"] == \
        by_name["attn_fwd_roofline"]["layer"]
    # the patterns: both flash kinds a differential layer calls, the scan
    # kernels, and the scopes whole
    import re
    files = cells.load_cell(CELL).layer_files
    fwd = files["attn_diff_fwd_roofline"]["params"]["regex"]
    bwd = files["attn_diff_bwd_roofline"]["params"]["regex"]
    for prefix in ("fa", "faw"):
        assert re.search(fwd, f"%{prefix}_fwd.3")
        assert not re.search(fwd, f"%{prefix}_bwd_dq.3")
        for kernel in ("dq", "dkv"):
            assert re.search(bwd, f"%{prefix}_bwd_{kernel}.3")
    scan = files["sel_scan_roofline"]["params"]["regex"]
    assert re.search(scan, "%selscan_fwd.1") and re.search(
        scan, "%selscan_bwd.2")
    share = files["sel_scan_time_share"]["params"]["regex"]
    assert re.search(share, "jit(loss)/pt.ssm/pt.ssm.sel/selscan_fwd")
    assert not re.search(share, "jit(loss)/pt.ssm/pt.ssm.scan/dot")
    real = cells.load_cell(CELL)
    assert (real.traffic["batch"], real.traffic["seq"]) == (1, 32768)
    assert real.traffic["learning_rate"] == 1e-4
    assert real.config["reduced"] == ["num_hidden_layers", "vocab_rows"]
    limits = real.traffic["block_tolerance"]
    assert set(limits) == set(KINDS) | set(UNJUDGED) | {
        "selscan_backward", "grad", "update"}
    assert all(limits[k] is None for k in UNJUDGED)
    assert all(limits[k] > 0 for k in limits if k not in UNJUDGED)


def test_published_widths_are_unchanged_in_the_configuration_file():
    """Every number of the catalog's config for Phi-4-mini-flash-reasoning
    but the depth; the vocabulary's slice under its own key; the ten
    layers the published ones at their indices."""
    from paddle_tpu.models.phi4flash import published_layer_types
    published = {
        "embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560,
        "intermediate_size": 10240, "layer_norm_eps": 1e-05,
        "max_position_embeddings": 262144, "mb_per_layer": 2,
        "model_type": "phi4flash", "num_attention_heads": 40,
        "num_key_value_heads": 20, "resid_pdrop": 0, "sliding_window": 512,
        "tie_word_embeddings": True, "mlp_bias": False,
        "lm_head_bias": False, "vocab_size": 200064}
    config = cells.load_cell(CELL).config
    for key, value in published.items():
        assert config[key] == value, key
    assert config["num_hidden_layers"] == 10
    assert config["published"]["num_hidden_layers"] == 32
    kinds = published_layer_types(32, config["mb_per_layer"])
    assert config["layer_indices"] == [0, 1, 2, 3, 16, 17, 18, 19, 20, 21]
    assert config["layer_types"] == [kinds[i]
                                     for i in config["layer_indices"]]
    assert config["vocab_rows"] * 8 == config["vocab_size"]
    assert config["deployment"]["vocab_parallel"] == 8
    for key in ("mamba sizes", "differential attention",
                "gated memory unit", "no position encoding",
                "initial weights", "dtype and optimizer", "learning_rate"):
        assert key in config["assumed"], key
    assert set(config["cut"]) == set(config["reduced"])
    assert "1,111,912,320" in config["memory_arithmetic"]


def test_operation_and_byte_counts_are_the_hand_counts():
    from families import phi4flash
    real = cells.load_cell(CELL)
    cfg = phi4flash.model_config(real.config)
    cfg.counted_seq = real.traffic["seq"]
    shapes = phi4flash.shapes(cfg)
    mamba = 2560 * 10240 + 5120 * 192 + 160 * 5120 + 5120 * 2560
    attention = 2560 * 5120 + 2560 * 2560                 # qkv, o
    cross, gmu, mlp = 2 * 2560 * 2560, 2 * 2560 * 5120, 3 * 2560 * 10240
    assert (mamba, attention, mlp) == (41_123_840, 19_660_800, 78_643_200)
    total = 3 * mamba + 3 * attention + 2 * cross + 2 * gmu + 10 * mlp
    assert shapes["matmul_params_per_layer"] * 10 == pytest.approx(total)
    assert shapes["head_params"] == 25008 * 2560          # the tied head
    # the pairs the masks leave: three causal layers, two of a 512-key band
    seq = 32768
    triangle, band = seq * (seq + 1) // 2, 512 * 513 // 2 + (seq - 512) * 512
    assert window_flops.band_pairs(seq, 512) == band
    assert shapes["diff_windows"] == [512, 512, None, None, None]
    fwd = 2 * (40 * 64 + 40 * 128) * (3 * triangle + 2 * band)
    assert diff_attn_flops.diff_fwd_flops(shapes, seq) == fwd
    assert diff_attn_flops.diff_bwd_flops(shapes, seq) == 2 * fwd
    # harness/flops.py's attention term is the same work
    got = flops.train_flops_per_token(shapes, seq)
    assert got == pytest.approx(6 * (total + 25008 * 2560) + 3 * fwd / seq)
    # the scan's bytes: bf16 (seq, 5120) arrays, float32 the rest
    big = seq * 5120 * 2
    small = 4 * (5120 * 16 + 2 * seq * 16 + 2 * 5120)
    states = 4 * (seq // 128) * 16 * 5120
    assert shapes["sel_layers"] == 3
    assert selective_scan_bytes.forward_bytes(shapes, seq) == \
        4 * big + small + states
    assert selective_scan_bytes.train_bytes(shapes, seq) == \
        11 * big + 3 * small + 2 * states


def _ctx(shapes, ops, seq=32768):
    """A reader's context over a recorded trace of one device: ops
    [(name, seconds)], three traced steps of one sequence."""
    trace = {"devices": {"/device:TPU:0": {
        "ops": [(name, 0.0, secs, {}) for name, secs in ops]}}}
    return SimpleNamespace(
        trace=trace, cell=SimpleNamespace(traffic={"trace_steps": 3}),
        samples={"shapes": shapes, "batch": 1, "seq": seq, "chips": 1},
        peaks={"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9})


def test_the_new_readers_read_a_recorded_trace_as_the_hand_count():
    from families import phi4flash
    from readers import attn_diff_roofline, sel_scan_roofline
    cfg = phi4flash.model_config(cells.load_cell(CELL).config)
    cfg.counted_seq = 32768
    shapes = phi4flash.shapes(cfg)
    files = cells.load_cell(CELL).layer_files
    ops = [("fa_fwd.1", 0.2), ("faw_fwd.2", 0.05), ("fa_bwd_dq.3", 0.3),
           ("faw_bwd_dkv.4", 0.1), ("selscan_fwd.5", 0.06),
           ("selscan_bwd.6", 0.09), ("fusion.7", 1.0)]
    fwd = attn_diff_roofline.read(
        _ctx(shapes, ops), files["attn_diff_fwd_roofline"]["params"])
    want = 100 * 3 * diff_attn_flops.diff_fwd_flops(shapes, 32768) \
        / 197e12 / 0.25
    assert fwd == pytest.approx(want)
    bwd = attn_diff_roofline.read(
        _ctx(shapes, ops), files["attn_diff_bwd_roofline"]["params"])
    assert bwd == pytest.approx(2 * want * 0.25 / 0.4)
    scan = sel_scan_roofline.read(
        _ctx(shapes, ops), files["sel_scan_roofline"]["params"])
    assert scan == pytest.approx(
        100 * 3 * 3 * selective_scan_bytes.train_bytes(shapes, 32768)
        / 819e9 / 0.15)
    # a program without the kernels, or a cell without the shapes: nothing
    none = [("fusion.1", 1.0)]
    assert attn_diff_roofline.read(
        _ctx(shapes, none), files["attn_diff_fwd_roofline"]["params"]) \
        is None
    assert sel_scan_roofline.read(
        _ctx({"layers": 1}, ops), files["sel_scan_roofline"]["params"]) \
        is None


def test_cpu_rehearsal_runs_the_cells_control_flow(phi4flash_tree):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH_DIR, "run.py"), "--workload",
         TINY_CELL, "--seed", "2147483659", "--seconds", "2", "--trace",
         "1", "--benchmark-json", phi4flash_tree, "--allow-cpu-rehearsal"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    lines = p.stdout.strip().splitlines()
    line, info = json.loads(lines[-1]), json.loads(lines[-2][5:])
    tag = "phi4flash blocks "
    blocks = json.loads([x for x in p.stderr.splitlines()
                         if x.startswith(tag)][-1][len(tag):])
    assert line["correct"] is True and info["problems"] == []
    assert line["rehearsal"] is True and line["metrics"] == {}
    assert info["steps"] > 0 and info["compiles_in_window"] == 0
    assert info["loss_diff"] < 0.001            # float32 both sides
    assert info["reader_errors"] == {}
    # every sub-block, the scan's eight cotangents and the first step held
    # to the reference, float32 both sides: summation order alone
    kinds = ["mamba", "sliding_attention"] * 2 + [
        "memory_mamba", "full_attention"] + ["gmu", "cross_attention"] * 2
    want = {f"{i}.{k}" for i, k in enumerate(kinds)} | {
        f"{i}.mlp" for i in range(10)} | {
        f"{g}.selscan_backward" for g in ("du", "ddelta", "dA", "dB", "dC",
                                          "dD", "dz", "ddelta_bias")}
    step = {f"first_step.{r}" for r in ("grad", "update", "grad_lambda",
                                        "update_lambda")}
    assert set(blocks["errors"]) == want | step
    assert max(blocks["errors"][k] for k in want) < 1e-5
    # the update: p - lr u rounded in float32 on each side (lr 0.001)
    assert max(blocks["errors"][k] for k in step) < 1e-3
    assert blocks["over"] == {}
    assert set(blocks["first_step_worst"]) == step


@pytest.fixture(scope="module")
def tiny_trainer():
    from families import phi4flash
    with open(os.path.join(TINY, "configs", "tiny-phi4flash.json")) as f:
        config = json.load(f)
    with open(os.path.join(TINY, "traffic", "tiny-train-32k.json")) as f:
        traffic = json.load(f)
    import numpy as np
    trainer, cfg, _ = phi4flash.build_trainer(config, traffic, 2 ** 31 + 11)
    ids = np.random.RandomState(1).randint(0, cfg.vocab_size, (1, 40))
    ids = ids.astype(np.int32)
    weights = {k: np.array(a) for k, a in trainer.params.items()}
    first = float(trainer.step((ids, ids)))     # the untouched step's loss
    _put_back(trainer, weights)
    return trainer, cfg, ids, weights, first


def _put_back(trainer, weights):
    import jax
    from families import phi4flash
    trainer.params = jax.device_put(weights)
    phi4flash.restore_moments(trainer)


@pytest.mark.parametrize("plant, touched", [
    # dD = sum over t of dy u reads no state: the dropped state leaves it
    ("state", tuple(f"{g}.selscan_backward" for g in (
        "du", "ddelta", "dA", "dB", "dC", "dz", "ddelta_bias"))),
    ("memory", ("gmu",)),
    ("kv", ("cross_attention",)),
    ("lambda", ("sliding_attention", "full_attention", "cross_attention")),
    ("window", ("sliding_attention",)), ("bf16", None), ("frozen", ())])
def test_a_planted_fault_is_over_its_limits_by_the_blocks_it_touches(
        tiny_trainer, monkeypatch, capsys, plant, touched):
    """PHI4FLASH_PLANT gives the unchanged reference faulty inputs (the
    state dropped every quarter of the sequence; the GMUs fed zeros; the
    cross layers their own keys and values; lam = 0; no band; everything
    in bf16), or reads the program's first step as if it had changed
    nothing (`frozen`): every reading the fault touches passes its limit
    and no other does, and the loader returns NaN, which the runner's
    comparison cannot pass. Every fault in the reference moves its
    gradients, so the first step is over too. At this size the state's
    share of a Mamba block's output is under the tiny limit, so `state`
    shows in the scan's gradients alone (all but dD, which reads no
    state); at the cell's widths the state is 8% of a Mamba layer's
    output (PERF.md). The trainer is left as it was found: the next step's
    loss is the untouched model's."""
    import math
    from families import phi4flash
    trainer, cfg, ids, weights, first = tiny_trainer
    monkeypatch.setenv("PHI4FLASH_PLANT", plant)
    assert math.isnan(phi4flash.reference_loss(trainer, cfg, ids))
    tag = "phi4flash blocks "
    blocks = json.loads([x for x in capsys.readouterr().err.splitlines()
                         if x.startswith(tag)][-1][len(tag):])
    over = sorted(blocks["over"])
    limits = blocks["block_tolerance"]
    judged = sorted(k for k in blocks["errors"]
                    if phi4flash.limit_of(limits, k) is not None)
    if touched is None:
        assert over == judged
    else:
        assert over == sorted(k for k in judged
                              if k in touched + FIRST_STEP
                              or k.split(".", 1)[1] in touched)
    assert trainer.step_count == 0
    assert float(trainer.step((ids, ids))) == first
    _put_back(trainer, weights)


def test_an_unknown_plant_is_refused():
    from families import phi4flash
    with pytest.raises(SystemExit, match="state, memory, kv, lambda"):
        phi4flash._planted({}, "nonsense", 40)
