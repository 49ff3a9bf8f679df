"""The Mellum2-12B-A2.5B cell, by files and entries alone: its own scratch
tree (a tiny Mellum configuration, its traffic and a BENCHMARK json of one
cell, under tests/tiny_mellum/, with every per-layer metric file as
committed) passed to run.py by --benchmark-json; the real cell's files
resolve; the real configuration file keeps every published number; the
operation counts equal hand counts; each planted fault comes out not
correct by the blocks it touches."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from harness import cells, flops, moe_flops, window_flops

from conftest import BENCH_DIR, ROOT

TINY = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                    "tiny_mellum")
CELL = "mellum2-12b-a2.5b-d8.pretrain-2x16k"
TINY_CELL = "tiny-mellum.tiny-train-2x16k"
JOINED = {"loss_head_time_share", "optimizer_time_share",
          "unnamed_op_time_share", "trainer_host_ms_per_step"}
NO_LIST = {"train_mfu", "train_step_hbm_gib", "compiles_in_window"}
NEW_METRICS = {"attn_window_time_share", "attn_window_fwd_roofline",
               "attn_window_bwd_roofline", "attn_full_time_share",
               "attn_sliding_mixer_time_share", "attn_full_mixer_time_share",
               "attn_window_visited_pair_share"}
# their counts assume one kind of layer: the cell stays off their lists
ONE_KIND = {"attn_kernel_time_share", "attn_fwd_roofline",
            "attn_bwd_roofline", "attn_bwd_dq_time_share",
            "attn_bwd_dkv_time_share", "mlp_time_share"}


@pytest.fixture()
def mellum_tree(tmp_path):
    base = tmp_path / "bench"
    shutil.copytree(os.path.join(TINY, "configs"), base / "configs")
    shutil.copytree(os.path.join(TINY, "traffic"), base / "traffic")
    shutil.copytree(os.path.join(BENCH_DIR, "layer_metrics"),
                    base / "layer_metrics")
    path = tmp_path / "BENCHMARK.json"
    shutil.copy(os.path.join(TINY, "BENCHMARK.tiny-mellum.json"), path)
    return str(path)


def test_the_cell_resolves_with_its_metrics(mellum_tree, benchmark_json):
    for name, path in ((TINY_CELL, mellum_tree), (CELL, None)):
        cell = cells.load_cell(name, path)
        assert cell.config["family"] == "mellum"
        assert cell.traffic["kind"] == "train" and cell.chips == 1
        reported = {m["name"] for m in cell.per_layer}
        assert reported == NEW_METRICS | NO_LIST | JOINED       # pinned
        for m in cell.per_layer:
            reader = cell.layer_files[m["name"]]["reader"]
            assert os.path.exists(os.path.join(BENCH_DIR, "readers",
                                               reader + ".py"))
    by_name = {m["name"]: m for m in benchmark_json["per_layer"]}
    for name in NEW_METRICS:            # the new cell's alone
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["moves"] == "train_tokens_per_s"
    for name in JOINED:                 # appended, nothing else changed
        assert by_name[name]["workloads"][-1] == CELL
    for name in ONE_KIND:
        assert CELL not in by_name[name]["workloads"]
    # the kernels' patterns tell the two kinds of layer apart
    import re
    window = cell.layer_files["attn_window_time_share"]["params"]["regex"]
    full = cell.layer_files["attn_full_time_share"]["params"]["regex"]
    accepted = cells.load_cell("gpt3-xl-d12.pretrain-2k").layer_files
    for kernel in ("fwd", "bwd_dq", "bwd_dkv"):
        assert re.search(window, f"%faw_{kernel}.3 = custom-call")
        assert not re.search(window, f"%fa_{kernel}.3 = custom-call")
        assert re.search(full, f"%fa_{kernel}.3 = custom-call")
        assert not re.search(full, f"%faw_{kernel}.3 = custom-call")
    for name in ("attn_fwd_roofline", "attn_bwd_roofline"):
        rx = accepted[name]["params"]["regex"]
        assert not any(re.search(rx, f"%faw_{k}.1") for k in (
            "fwd", "bwd_dq", "bwd_dkv"))
    real = cells.load_cell(CELL)
    assert (real.traffic["batch"], real.traffic["seq"]) == (2, 16384)
    assert real.config["reduced"] == ["num_hidden_layers", "num_experts",
                                      "vocab_rows"]
    assert set(real.traffic["block_tolerance"]) == {
        "sliding_attention", "full_attention", "sparse_moe",
        "window_backward"}
    # the cell as ISSUE 34 set it
    assert real.traffic["learning_rate"] == 1e-4


def test_published_widths_are_unchanged_in_the_configuration_file():
    """Every number of the catalog's config for Mellum2-12B-A2.5B but the
    two counts that are reduced; the vocabulary's slice under its own
    key."""
    published = {
        "attention_bias": False, "head_dim": 128, "hidden_act": "silu",
        "hidden_size": 2304, "intermediate_size": 7168,
        "max_position_embeddings": 131072, "max_window_layers": 0,
        "model_type": "mellum", "moe_intermediate_size": 896,
        "norm_topk_prob": True, "num_attention_heads": 32,
        "num_experts_per_tok": 8, "num_key_value_heads": 4,
        "rms_norm_eps": 1e-06, "sliding_window": 1024,
        "tie_word_embeddings": False, "vocab_size": 98304,
        "use_sliding_window": True,
        "rope_parameters": {
            "full_attention": {
                "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
                "original_max_position_embeddings": 8192, "beta_fast": 32,
                "beta_slow": 1, "attention_factor": 1.2772588722239782},
            "sliding_attention": {"rope_type": "default",
                                  "rope_theta": 500000}}}
    config = cells.load_cell(CELL).config
    for key, value in published.items():
        assert config[key] == value, key
    period = ["sliding_attention"] * 3 + ["full_attention"]
    assert config["layer_types"] == period * 7
    assert config["mlp_layer_types"] == ["sparse"] * 28
    assert config["num_hidden_layers"] == 8         # two published periods
    assert config["num_experts"] == 16 == config["experts_held"][1]
    assert config["router_outputs"] == 64
    # a share's gates have the held experts' terms of their gradient alone
    assert config["differentiate_routing"] is False
    assert "NOT DIFFERENTIATED" in config["cut"]["num_experts"]
    assert config["vocab_rows"] * 4 == config["vocab_size"]
    assert config["published"]["num_experts"] == 64
    assert config["published"]["num_hidden_layers"] == 28
    assert config["deployment"]["expert_parallel"] == 4
    assert "4-chip" in config["deployment"]["stands_for"]
    for key in ("q/k norm", "no MTP head", "no auxiliary loss",
                "learning_rate", "initial weights", "dtype and optimizer"):
        assert key in config["assumed"], key
    assert set(config["cut"]) == set(config["reduced"])
    assert "1,077,059,840" in config["memory_arithmetic"]


def test_the_loader_redraws_two_kinds_from_the_seed(mellum_tree):
    """families/mellum.py _redraw: the embedding's rows N(0, 1), the
    attention output projections N(0, 0.02 / sqrt(2 x layers)), every
    other matrix weights.install's N(0, 0.02); the seed decides them."""
    import numpy as np
    from families import mellum
    cell = cells.load_cell(TINY_CELL, mellum_tree)

    def weights(seed):
        trainer, cfg, _ = mellum.build_trainer(cell.config, cell.traffic,
                                               seed)
        assert cfg.num_hidden_layers == 4 and not cfg.differentiate_routing
        return {k: np.asarray(v, np.float32)
                for k, v in trainer.params.items()}

    w, again, other = weights(2 ** 31 + 7), weights(2 ** 31 + 7), weights(5)
    std = {k: float(v.std()) for k, v in w.items() if v.ndim >= 2}
    for name, s in std.items():
        want = (1.0 if name == "model.embed_tokens.weight" else
                0.02 / 8 ** 0.5 if name.endswith("o_proj.weight") else 0.02)
        assert abs(s - want) < 0.1 * want, (name, s)
    assert sum(k.endswith("o_proj.weight") for k in std) == 4
    for name in ("model.embed_tokens.weight",
                 "model.layers.2.self_attn.o_proj.weight"):
        assert (w[name] == again[name]).all()
        assert (w[name] != other[name]).any()


def test_operation_counts_of_the_real_configuration_are_the_hand_counts():
    from families import mellum
    real = cells.load_cell(CELL)
    cfg = mellum.model_config(real.config)
    cfg.counted_seq = real.traffic["seq"]
    shapes = mellum.shapes(cfg)
    attn = 2 * 2304 * 4096 + 2 * 2304 * 512                 # q, o; k, v
    assert attn == 21_233_664
    expert = 3 * 2304 * 896
    assert expert == 6_193_152
    # a token's expected held experts: 8 x 16 / 64 = 2
    per_layer = attn + 2304 * 64 + 2 * expert
    assert per_layer == 33_767_424
    assert shapes["matmul_params_per_layer"] == per_layer
    assert shapes["head_params"] == 24576 * 2304 == 56_623_104
    assert 6 * (8 * per_layer + 24576 * 2304) == 1_960_574_976
    # the pairs the masks leave: a full layer's triangle, a window layer's
    # band of 1,024 keys
    triangle, band = 16384 * 16385 // 2, 1024 * 1025 // 2 + 15360 * 1024
    assert (triangle, band) == (134_225_920, 16_253_440)
    assert window_flops.band_pairs(16384) == triangle
    assert window_flops.band_pairs(16384, 1024) == band
    assert window_flops.band_pairs(512, 1024) == 512 * 513 // 2
    assert window_flops.band_pairs(16384, 1) == 16384
    attention = 3 * 4 * 32 * 128 * (2 * triangle + 6 * band) / 16384
    assert attention == 3 * (2 * triangle + 6 * band) == 1_097_917_440
    got = flops.train_flops_per_token(shapes, 16384)
    assert abs(got - (1_960_574_976 + attention)) < 1e-3 * got / 1e6
    # the windowed kernels' own count, and the backward's twice that
    assert (shapes["window_layers"], shapes["window"],
            shapes["window_heads"]) == (6, 1024, 32)
    assert window_flops.window_fwd_flops(32, 128, 16384, 1024) == \
        4 * 32 * 128 * band == 266_296_360_960
    assert window_flops.window_bwd_flops(32, 128, 16384, 1024) == \
        2 * 266_296_360_960
    # the grouped matmuls read the same shapes as granite's
    assert moe_flops.grouped_matmul_train_flops(shapes, 1) == \
        3 * 2 * 2304 * 3 * 896


def _dense_grads(q, k, v, do, window):
    """jax.grad through every score of every head at once, float64-free
    and blockless: what `attention_grads` must equal at a size where
    nothing needs splitting."""
    import jax
    import jax.numpy as jnp

    def total(q, k, v):
        t, d = q.shape[0], q.shape[-1]
        back = jnp.arange(t)[:, None] - jnp.arange(t)[None, :]
        keep = (back >= 0) & (True if window is None else back < window)
        s = jnp.einsum("tgd,sd->gts", q, k) * d ** -0.5
        p = jax.nn.softmax(jnp.where(keep[None], s, -jnp.inf), axis=-1)
        return jnp.sum(jnp.einsum("gts,sd->tgd", p, v) * do)

    with jax.default_matmul_precision("highest"):
        return jax.grad(total, argnums=(0, 1, 2))(q, k, v)


@pytest.mark.parametrize("window", [None, 5, 48])
def test_the_references_attention_gradients_are_its_forwards(
        window, monkeypatch):
    """`mellum_ref.attention_grads` (one head, one block of rows at a time,
    dk and dv summed) against jax.grad of the whole masked softmax, with
    the rows split into three blocks."""
    import jax
    import numpy as np
    from references import mellum_ref
    monkeypatch.setattr(mellum_ref, "ROW_BLOCK", 16)
    keys = jax.random.split(jax.random.key(3), 4)
    q, do = (jax.random.normal(k, (48, 3, 8)) for k in keys[:2])
    k, v = (jax.random.normal(k, (48, 8)) for k in keys[2:])
    got = mellum_ref.attention_grads(q, k, v, do, window)
    for g, w in zip(got, _dense_grads(q, k, v, do, window)):
        assert g.shape == w.shape
        assert np.abs(np.asarray(g - w)).max() < 1e-5


def test_the_window_backward_check_reads_a_wrong_band_and_a_right_one():
    """`families.mellum.window_backward`: the program's attention
    gradients (off a TPU the dense band mask) against the reference's:
    roundings apart with the same window; a band one key wider or
    narrower on one side is read at once, in dq, dk and dv."""
    import jax.numpy as jnp
    from families import mellum
    from paddle_tpu.models.mellum import mellum_tiny
    cfg = mellum_tiny().config
    assert cfg.sliding_window == 8
    same = mellum.window_backward(cfg, 40, 2 ** 31 + 5, 8, jnp.float32)
    assert sorted(same) == ["dk.window_backward", "dq.window_backward",
                            "dv.window_backward"]
    assert max(same.values()) < 1e-5
    for wrong in (7, 9, None):
        off = mellum.window_backward(cfg, 40, 2 ** 31 + 5, wrong,
                                     jnp.float32)
        assert min(off.values()) > 0.02, (wrong, off)


def _rehearse(mellum_tree, trace, seconds="2", **env):
    """(result line, info line, the family's line of sub-block errors)"""
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env)
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH_DIR, "run.py"), "--workload",
         TINY_CELL, "--seed", "2147483659", "--seconds", seconds, "--trace",
         trace, "--benchmark-json", mellum_tree, "--allow-cpu-rehearsal"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    lines = p.stdout.strip().splitlines()
    tag = "mellum blocks "
    blocks = [l for l in p.stderr.splitlines() if l.startswith(tag)]
    return (json.loads(lines[-1]), json.loads(lines[-2][5:]),
            json.loads(blocks[-1][len(tag):]))


def test_cpu_rehearsal_runs_the_cells_control_flow(mellum_tree):
    line, info, blocks = _rehearse(mellum_tree, "1")
    assert line["correct"] is True and info["problems"] == []
    assert line["rehearsal"] is True and line["metrics"] == {}
    assert info["steps"] > 0 and info["compiles_in_window"] == 0
    assert info["loss_diff"] < 0.001            # float32 both sides
    assert info["reader_errors"] == {}
    values = info["cpu_rehearsal_values"]
    assert values["compiles_in_window"] == 0
    # a count, not a time: the gauge the loader set from the rule's
    # Decision. 40 tokens under a window of 8 in tiles of 128 rows: one
    # tile a kernel, 128 x 128 pairs visited for the 292 the band leaves
    assert values["attn_window_visited_pair_share"] == pytest.approx(
        128 * 128 / (8 * 9 / 2 + 32 * 8))
    # every sub-block of the program was held to the reference's, float32
    # both sides: summation order alone (1e-5 is ~100 roundings)
    assert sorted(blocks["errors"]) == [
        "0.sliding_attention", "0.sparse_moe", "1.sliding_attention",
        "1.sparse_moe", "2.sliding_attention", "2.sparse_moe",
        "3.full_attention", "3.sparse_moe", "dk.window_backward",
        "dq.window_backward", "dv.window_backward"]
    assert max(blocks["errors"].values()) < 1e-5 and blocks["over"] == {}
    # 8 of 16 experts held, top-4: each layer's held assignments
    assert len(blocks["held_sizes"]) == 4
    assert all(len(s) == 8 and 0 < sum(s) < 40 * 4
               for s in blocks["held_sizes"])


@pytest.mark.parametrize("plant,touched", [
    ("window", ("sliding_attention", "window_backward")),
    ("rope", ("full_attention",)), ("routed", ("sparse_moe",)),
    ("bf16", None)])
def test_a_planted_fault_comes_out_not_correct(mellum_tree, plant, touched):
    """MELLUM_PLANT gives the unchanged reference faulty inputs (window
    layers the whole triangle, in the sub-blocks and in the gradients;
    full layers default frequencies; a held expert dropped a token;
    everything in bf16): every reading the fault touches passes its limit
    and no other does, the loader returns NaN for the reference's loss and
    the runner's comparison says `correct` false."""
    line, info, blocks = _rehearse(mellum_tree, "0", seconds="1",
                                   MELLUM_PLANT=plant)
    assert line["correct"] is False
    over = sorted(blocks["over"])
    if touched is None:
        assert over == sorted(blocks["errors"])     # 8 sub-blocks, 3 grads
    else:
        assert over == sorted(k for k in blocks["errors"]
                              if k.endswith(touched))
    assert len(info["problems"]) == 1 and "nan" in info["problems"][0]


def test_an_unknown_plant_is_refused(mellum_tree):
    env = dict(os.environ, JAX_PLATFORMS="cpu", MELLUM_PLANT="nonsense")
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH_DIR, "run.py"), "--workload",
         TINY_CELL, "--seed", "1", "--seconds", "1", "--trace", "0",
         "--benchmark-json", mellum_tree, "--allow-cpu-rehearsal"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode != 0 and "window, rope, routed, bf16" in p.stderr
