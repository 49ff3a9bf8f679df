"""The Brumby-14B-Base cell, by files and entries alone: its own scratch
tree (a tiny Brumby configuration, its traffic and a BENCHMARK json of one
cell, under tests/tiny_brumby/, with every per-layer metric file as
committed) passed to run.py by --benchmark-json; the real cell's files
resolve; the real configuration file keeps every published number; the
operation counts equal hand counts; the loader's gate biases give the
horizons it says."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from harness import cells, flops, retention_flops

from conftest import BENCH_DIR, ROOT

TINY = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                    "tiny_brumby")
CELL = "brumby-14b-base-d4.pretrain-16k"
TINY_CELL = "tiny-brumby.tiny-train-16k"
ACCEPTED_HERE_TOO = {
    "loss_head_time_share", "mlp_time_share", "optimizer_time_share",
    "unnamed_op_time_share", "trainer_host_ms_per_step"}
NO_LIST = {"train_mfu", "train_step_hbm_gib", "compiles_in_window"}
NEW_METRICS = {"retention_time_share", "retention_scan_time_share",
               "retention_scan_roofline", "retention_mean_horizon_tokens"}
ATTN_METRICS = {"attn_kernel_time_share", "attn_fwd_roofline",
                "attn_bwd_roofline", "attn_bwd_dq_time_share",
                "attn_bwd_dkv_time_share"}


@pytest.fixture()
def brumby_tree(tmp_path):
    base = tmp_path / "bench"
    shutil.copytree(os.path.join(TINY, "configs"), base / "configs")
    shutil.copytree(os.path.join(TINY, "traffic"), base / "traffic")
    shutil.copytree(os.path.join(BENCH_DIR, "layer_metrics"),
                    base / "layer_metrics")
    path = tmp_path / "BENCHMARK.json"
    shutil.copy(os.path.join(TINY, "BENCHMARK.tiny-brumby.json"), path)
    return str(path)


def test_the_cell_resolves_with_its_metrics(brumby_tree, benchmark_json):
    for name, path in ((TINY_CELL, brumby_tree), (CELL, None)):
        cell = cells.load_cell(name, path)
        assert cell.config["family"] == "brumby"
        assert cell.traffic["kind"] == "train" and cell.chips == 1
        reported = {m["name"] for m in cell.per_layer}
        assert reported == NEW_METRICS | NO_LIST | ACCEPTED_HERE_TOO
        for m in cell.per_layer:
            reader = cell.layer_files[m["name"]]["reader"]
            assert os.path.exists(os.path.join(BENCH_DIR, "readers",
                                               reader + ".py"))
    by_name = {m["name"]: m for m in benchmark_json["per_layer"]}
    for name in NEW_METRICS:            # the new cell's alone
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["moves"] == "train_tokens_per_s"
    assert by_name["retention_scan_roofline"]["layer"] == \
        by_name["retention_scan_time_share"]["layer"] == \
        "retention ops/power_retention.py"
    # no flash kernel runs here: the five attention metrics keep to the two
    # cells that have one
    for name in ATTN_METRICS:
        assert by_name[name]["workloads"] == [
            "gpt3-xl-d12.pretrain-2k", "granite-4.0-h-small-d10.pretrain-8k"]
    real = cells.load_cell(CELL)
    assert (real.traffic["batch"], real.traffic["seq"]) == (1, 16384)
    assert real.config["reduced"] == ["num_hidden_layers", "vocab_rows"]
    assert set(real.traffic["block_tolerance"]) == {"retention", "mlp"}


def test_published_widths_are_unchanged_in_the_configuration_file():
    """Every number of the catalog's config for Brumby-14B-Base but the
    depth; the vocabulary's slice under its own key."""
    published = {
        "attention_bias": False, "head_dim": 128, "hidden_act": "silu",
        "hidden_size": 5120, "intermediate_size": 17408,
        "max_position_embeddings": 32768, "max_window_layers": 40,
        "model_type": "brumby", "num_attention_heads": 40,
        "num_key_value_heads": 8, "rms_norm_eps": 1e-06,
        "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
        "tie_word_embeddings": False, "use_sliding_window": False,
        "vocab_size": 151936}
    config = cells.load_cell(CELL).config
    for key, value in published.items():
        assert config[key] == value, key
    assert config["num_hidden_layers"] == 4         # the guide's floor
    assert config["published"]["num_hidden_layers"] == 40
    assert config["vocab_rows"] * 8 == config["vocab_size"]
    assert config["deployment"]["chips"] == 1
    for key in ("degree", "gate", "scale and eps", "q/k norm and RoPE",
                "gate-bias draw", "learning_rate"):
        assert key in config["assumed"], key
    assert set(config["cut"]) == set(config["reduced"])
    assert "memory_arithmetic" in config


def test_operation_counts_of_the_real_configuration_are_the_hand_counts():
    from families import brumby
    cfg = brumby.model_config(cells.load_cell(CELL).config)
    shapes = brumby.shapes(cfg)
    per_layer = (2 * 5120 * 5120 + 2 * 5120 * 1024 + 5120 * 8   # q, o; k, v; g
                 + 3 * 5120 * 17408)                             # SwiGLU
    assert per_layer == 330_342_400
    assert shapes["matmul_params_per_layer"] == per_layer
    assert shapes["head_params"] == 18992 * 5120 == 97_239_040
    assert (shapes["layers"], shapes["heads"], shapes["kv_heads"]) == (4, 0, 0)
    # no softmax-attention term: 6 per matmul parameter and nothing else
    want = 6 * (4 * per_layer + 18992 * 5120)
    assert want == 8_511_651_840
    assert flops.train_flops_per_token(shapes, 16384) == want
    # the retention's own work, left out of the above: the smaller form
    assert (shapes["retention_heads"], shapes["retention_state_heads"],
            shapes["head_dim"]) == (40, 8, 128)
    assert shapes["retention_features"] == 65 * 128     # the program's layout
    assert retention_flops.minimal_features(128) == 8256  # what is counted
    quadratic = 4 * 40 * 128 * (16384 * 16385 // 2)
    recurrent = 16384 * (8 + 40) * 2 * 8256 * 128
    assert quadratic == 2_748_946_841_600 and recurrent == 1_662_152_343_552
    assert retention_flops.quadratic_fwd_flops(shapes, 16384) == quadratic
    assert retention_flops.recurrent_fwd_flops(shapes, 16384) == recurrent
    assert retention_flops.retention_fwd_flops(shapes, 16384) == recurrent
    assert retention_flops.retention_train_flops(shapes, 16384) == \
        3 * recurrent
    # a short sequence is cheaper in the quadratic form: 4 x 40 x 128 x
    # 512 x 513 / 2 against 512 x 48 x 2 x 8256 x 128
    assert retention_flops.retention_fwd_flops(shapes, 512) == \
        4 * 40 * 128 * 512 * 513 // 2 == 2_689_597_440


def test_the_redrawn_gate_biases_give_horizons_inside_their_range():
    import jax
    import numpy as np
    from families import brumby
    b = np.asarray(brumby.gate_bias(jax.random.key(7), (4096,)), np.float64)
    horizon = 1.0 / (1.0 - 1.0 / (1.0 + np.exp(-b)))
    low, high = brumby.HORIZON
    assert (low, high) == (64.0, 8192.0)
    # float32's logit near 9 is good to 1e-6: 0.1% of a horizon
    assert horizon.min() >= low * 0.999 and horizon.max() <= high * 1.001
    # log-uniform: the median is the geometric mean, 724 tokens
    assert 600 < np.median(horizon) < 870
    # and one key a layer: two layers do not share their draw
    other = np.asarray(brumby.gate_bias(jax.random.key(8), (4096,)))
    assert np.abs(other - b).max() > 1.0


def _rehearse(brumby_tree, trace, **env):
    """(result line, info line, the family's line of sub-block errors)"""
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env)
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH_DIR, "run.py"), "--workload",
         TINY_CELL, "--seed", "2147483659", "--seconds", "2", "--trace",
         trace, "--benchmark-json", brumby_tree, "--allow-cpu-rehearsal"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    lines = p.stdout.strip().splitlines()
    tag = "brumby blocks "
    blocks = [l for l in p.stderr.splitlines() if l.startswith(tag)]
    return (json.loads(lines[-1]), json.loads(lines[-2][5:]),
            json.loads(blocks[-1][len(tag):]))


def test_cpu_rehearsal_runs_the_cells_control_flow(brumby_tree):
    line, info, blocks = _rehearse(brumby_tree, "1")
    assert line["correct"] is True and info["problems"] == []
    assert line["rehearsal"] is True and line["metrics"] == {}
    assert info["steps"] > 0 and info["compiles_in_window"] == 0
    assert info["loss_diff"] < 0.001            # float32 both sides
    assert info["reader_errors"] == {}
    values = info["cpu_rehearsal_values"]
    assert values["compiles_in_window"] == 0
    # a count, not a time: the gauge the loader set from the model's gate.
    # Biases for 64-8192 tokens, moved by W_g x (std 0.02 x sqrt(64)): the
    # mean over 4 state heads lies well inside 20-20,000
    assert 20.0 < values["retention_mean_horizon_tokens"] < 20000.0
    assert len(blocks["horizons"]) == 4         # 2 layers x 2 state heads
    # every sub-block of the program was held to the reference's, float32
    # both sides: summation order alone (1e-5 is ~100 roundings)
    assert sorted(blocks["errors"]) == ["0.mlp", "0.retention", "1.mlp",
                                        "1.retention"]
    assert max(blocks["errors"].values()) < 1e-5 and blocks["over"] == {}


@pytest.mark.parametrize("plant", ["state", "gate", "bf16"])
def test_a_planted_fault_comes_out_not_correct(brumby_tree, plant):
    """BRUMBY_PLANT gives the unchanged reference faulty inputs (the sum
    cut off one chunk behind the query, what a dropped carried state
    computes; every gate 1; everything in bf16): the retention blocks pass
    their limit, the loader returns NaN for the reference's loss and the
    runner's comparison says `correct` false. The FFN blocks stay inside
    theirs unless everything is bf16."""
    line, info, blocks = _rehearse(brumby_tree, "0", BRUMBY_PLANT=plant)
    assert line["correct"] is False
    over = sorted(blocks["over"])
    assert [k for k in over if "retention" in k] == ["0.retention",
                                                     "1.retention"]
    if plant != "bf16":
        assert over == ["0.retention", "1.retention"]
    assert len(info["problems"]) == 1 and "nan" in info["problems"][0]
