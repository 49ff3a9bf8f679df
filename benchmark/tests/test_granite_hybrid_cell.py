"""The granite-4.0-h-small cell, by files and entries alone: its own
scratch tree (a tiny Granite configuration, its traffic and a BENCHMARK
json of one cell, under tests/tiny_granite/, with every per-layer metric
file as committed) passed to run.py by --benchmark-json; the real cell's
files resolve; and the operation count of the real configuration file
equals a hand count."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from harness import cells, flops

from conftest import BENCH_DIR, ROOT

TINY = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                    "tiny_granite")
CELL = "granite-4.0-h-small-d10.pretrain-8k"
TINY_CELL = "tiny-granite.tiny-train-8k"
ACCEPTED_HERE_TOO = {
    "attn_fwd_roofline", "attn_bwd_roofline", "attn_bwd_dq_time_share",
    "attn_bwd_dkv_time_share", "loss_head_time_share", "mlp_time_share",
    "optimizer_time_share", "unnamed_op_time_share",
    "trainer_host_ms_per_step"}
NEW_METRICS = {"ssm_time_share", "ssm_scan_time_share", "moe_time_share",
               "moe_route_time_share", "moe_load_max_over_mean",
               "moe_held_assignment_share", "moe_grouped_matmul_roofline"}


@pytest.fixture()
def granite_tree(tmp_path):
    base = tmp_path / "bench"
    shutil.copytree(os.path.join(TINY, "configs"), base / "configs")
    shutil.copytree(os.path.join(TINY, "traffic"), base / "traffic")
    shutil.copytree(os.path.join(BENCH_DIR, "layer_metrics"),
                    base / "layer_metrics")
    path = tmp_path / "BENCHMARK.json"
    shutil.copy(os.path.join(TINY, "BENCHMARK.tiny-granite.json"), path)
    return str(path)


def test_the_cell_resolves_with_its_metrics(granite_tree, benchmark_json):
    for name, path in ((TINY_CELL, granite_tree), (CELL, None)):
        cell = cells.load_cell(name, path)
        assert cell.config["family"] == "granite_hybrid"
        assert cell.traffic["kind"] == "train" and cell.chips == 1
        reported = {m["name"] for m in cell.per_layer}
        # its own seven, the four the accepted benchmark gives no list and,
        # in the real cell, the nine accepted ones whose layers it runs too
        # (the cell appended to their lists)
        assert reported == NEW_METRICS | {
            "train_mfu", "train_step_hbm_gib", "attn_kernel_time_share",
            "compiles_in_window"} | (ACCEPTED_HERE_TOO if path is None
                                     else set())
        for m in cell.per_layer:
            reader = cell.layer_files[m["name"]]["reader"]
            assert os.path.exists(os.path.join(BENCH_DIR, "readers",
                                               reader + ".py"))
    # the new metrics are the new cell's alone
    for m in benchmark_json["per_layer"]:
        if m["name"] in NEW_METRICS:
            assert m["workloads"] == [CELL]
            assert m["moves"] == "train_tokens_per_s"
    real = cells.load_cell(CELL)
    assert (real.traffic["batch"], real.traffic["seq"]) == (1, 8192)
    assert real.config["reduced"] == ["num_hidden_layers",
                                      "num_local_experts", "vocab_rows"]


def test_published_widths_are_unchanged_in_the_configuration_file():
    """Every number of the catalog's config for granite-4.0-h-small, but
    the two counts that are reduced."""
    published = {
        "attention_multiplier": 0.0078125, "embedding_multiplier": 12,
        "hidden_size": 4096, "intermediate_size": 768, "logits_scaling": 16,
        "mamba_chunk_size": 256, "mamba_d_conv": 4, "mamba_d_head": 64,
        "mamba_d_state": 128, "mamba_expand": 2, "mamba_n_groups": 1,
        "mamba_n_heads": 128, "max_position_embeddings": 131072,
        "num_attention_heads": 32, "num_experts_per_tok": 10,
        "num_key_value_heads": 8, "residual_multiplier": 0.22,
        "rms_norm_eps": 1e-05, "rope_theta": 10000,
        "shared_intermediate_size": 1536, "vocab_size": 100352}
    config = cells.load_cell(CELL).config
    for key, value in published.items():
        assert config[key] == value, key
    assert config["num_hidden_layers"] == 10
    assert config["num_local_experts"] == 9 == config["experts_held"][1]
    assert config["router_outputs"] == 72
    assert config["vocab_rows"] * 8 == config["vocab_size"]
    kinds = config["layer_types"]
    assert len(kinds) == 40 and kinds[5::10] == ["attention"] * 4
    assert kinds.count("mamba") == 36
    assert config["published"]["num_local_experts"] == 72


def test_operation_count_of_the_real_configuration_is_the_hand_count():
    from families import granite_hybrid
    cfg = granite_hybrid.model_config(cells.load_cell(CELL).config)
    shapes = granite_hybrid.shapes(cfg)
    mamba = 4096 * (8192 + 8448 + 128) + 8192 * 4096        # in, out
    attn = 2 * 4096 * 4096 + 2 * 4096 * 1024                # q, o; k, v
    per_layer = (0.9 * mamba + 0.1 * attn                   # period's mean
                 + 3 * 4096 * 1536                          # shared expert
                 + 4096 * 72                                # router, whole
                 + 10 * 9 / 72 * 3 * 4096 * 768)            # 1.25 experts
    assert per_layer == pytest.approx(127_172_608, rel=1e-12)
    assert shapes["layers"] == 10 and shapes["head_dim"] == 128
    assert shapes["heads"] == pytest.approx(3.2)            # 32 x 1/10
    assert shapes["matmul_params_per_layer"] == pytest.approx(per_layer,
                                                              rel=1e-12)
    assert shapes["head_params"] == 12544 * 4096
    want = (6 * (10 * per_layer + 12544 * 4096)
            + 3 * 10 * 4 * 3.2 * 128 * (8192 * 8193 / 2) / 8192)
    assert want == pytest.approx(8_139_988_992, rel=1e-9)
    assert flops.train_flops_per_token(shapes, 8192) == pytest.approx(
        want, rel=1e-12)


def test_grouped_matmul_counts_are_the_hand_counts():
    """harness/moe_flops.py for one layer of the real configuration under
    even routing (10,240 rows = 8192 tokens x 10 choices x 9 / 72)."""
    from families import granite_hybrid
    from harness import moe_flops
    cfg = granite_hybrid.model_config(cells.load_cell(CELL).config)
    shapes = granite_hybrid.shapes(cfg)
    assert (shapes["expert_ffn"], shapes["experts_held"],
            shapes["top_k"]) == (768, 9, 10)
    rows = 8192 * 10 * 9 / 72
    # gate-and-up 4096 x 1536, down 768 x 4096; forward + two backward
    assert moe_flops.grouped_matmul_train_flops(shapes, rows) == \
        3 * rows * (2 * 4096 * 1536 + 2 * 768 * 4096) == 579_820_584_960
    # bf16: 9 experts' 9.44M weights three times, each row's x, y (4096),
    # gate-and-up (1536) and activation (768) three times
    assert moe_flops.grouped_matmul_train_bytes(shapes, rows) == \
        3 * 2 * (9 * 3 * 4096 * 768 + rows * (2 * 4096 + 1536 + 768)) \
        == 1_154_482_176


def _rehearse(granite_tree, trace, **env):
    """(result line, info line, the family's line of sub-block errors)"""
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env)
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH_DIR, "run.py"), "--workload",
         TINY_CELL, "--seed", "2147483659", "--seconds", "2", "--trace",
         trace, "--benchmark-json", granite_tree, "--allow-cpu-rehearsal"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    lines = p.stdout.strip().splitlines()
    tag = "granite_hybrid blocks "
    blocks = [l for l in p.stderr.splitlines() if l.startswith(tag)]
    return (json.loads(lines[-1]), json.loads(lines[-2][5:]),
            json.loads(blocks[-1][len(tag):]))


def test_cpu_rehearsal_runs_the_cells_control_flow(granite_tree):
    line, info, blocks = _rehearse(granite_tree, "1")
    assert line["correct"] is True and info["problems"] == []
    assert line["rehearsal"] is True and line["metrics"] == {}
    assert info["steps"] > 0 and info["compiles_in_window"] == 0
    assert info["loss_diff"] < 0.001            # float32 both sides
    assert info["reader_errors"] == {}
    values = info["cpu_rehearsal_values"]
    # counts, not times: the gauge the loader set from the routing function
    assert values["moe_load_max_over_mean"] >= 1.0
    assert values["compiles_in_window"] == 0

    # every sub-block of the program was held to the reference's, float32
    # both sides: summation order alone (1e-5 is ~100 roundings)
    assert sorted(blocks["errors"]) == [
        "0.block_sparse_moe", "0.mamba", "1.block_sparse_moe", "1.self_attn",
        "2.block_sparse_moe", "2.mamba"]
    assert max(blocks["errors"].values()) < 1e-5 and blocks["over"] == {}
    # 4 of 8 experts held: half the assignments under even routing, in %
    assert 30.0 < values["moe_held_assignment_share"] < 70.0


@pytest.mark.parametrize("plant, blocks_over", [
    ("no_routed", ["0.block_sparse_moe", "1.block_sparse_moe",
                   "2.block_sparse_moe"]),
    ("residual", ["0.block_sparse_moe", "0.mamba", "1.block_sparse_moe",
                  "1.self_attn", "2.block_sparse_moe", "2.mamba"])])
def test_a_planted_fault_comes_out_not_correct(granite_tree, plant,
                                               blocks_over):
    """GRANITE_PLANT gives the unchanged reference faulty inputs: the
    sub-blocks it touches pass their limit, the loader returns NaN for the
    reference's loss and the runner's comparison says `correct` false."""
    line, info, blocks = _rehearse(granite_tree, "0", GRANITE_PLANT=plant)
    assert line["correct"] is False
    assert sorted(blocks["over"]) == blocks_over
    assert len(info["problems"]) == 1 and "nan" in info["problems"][0]
