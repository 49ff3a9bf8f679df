"""chip_smoke.py cannot rot: every phase function runs here at tiny sizes on
the CPU (Pallas kernels interpreted), the entry refuses a CPU backend, and
the ways a device could hide on that path stay closed — the compile-cache
helper, set_device, the router's measurement branch, autotune candidates
and the mesh worker's hello.
"""

import json
import os

import pytest

import jax
import jax.numpy as jnp

import chip_smoke

TINY_GPT = dict(vocab_size=256, hidden_size=64, num_attention_heads=4,
                max_position_embeddings=32)
TINY_LLAMA = dict(vocab_size=256, hidden_size=64, intermediate_size=128,
                  num_hidden_layers=2, num_attention_heads=4,
                  num_key_value_heads=2, max_position_embeddings=128)


class TestPhasesAtTinySizes:
    def test_entry_exits_nonzero_on_cpu_without_running_a_phase(
            self, monkeypatch, capsys):
        for phase in ("kernel_phase", "server_phase", "trainer_phase"):
            monkeypatch.setattr(
                chip_smoke, phase,
                lambda *a, **k: pytest.fail("a phase ran on the CPU"))
        assert chip_smoke.main([]) == 2
        out = capsys.readouterr()
        assert out.out == "" and "no TPU" in out.err

    def test_kernel_phase(self):
        res = chip_smoke.kernel_phase(
            bh=1, seq=256, small_seq=128, gqa=(4, 2), interpret=True,
            grouped=((512, 160, 256, 4, ((128, 128, 128),) * 3),))
        names = [c["case"] for c in res["cases"]]
        assert names == ["chooser_tiles", "small_tiles", "gqa",
                         "d96_zero_pad", "rms_epilogue", "grouped_matmul"]
        # one causal schedule, whatever the tiles: each case names the
        # tiles it ran and the grid steps they gave
        assert res["cases"][0]["grid_steps"] == {
            "fa_fwd": 1, "fa_bwd_dq": 1, "fa_bwd_dkv": 1}
        assert res["cases"][1]["tiles"]["dkv"] == (128, 256, 128)
        assert res["cases"][1]["grid_steps"]["fa_fwd"] == 2

    def test_grouped_matmul_case_fails_on_a_fall_back_and_a_wrong_kernel(
            self, monkeypatch):
        from paddle_tpu.parallel import moe
        with pytest.raises(AssertionError, match="fell back"):
            chip_smoke._grouped_matmul_case(512, 160, 256, 4)   # no TPU
        real = moe._gmm
        monkeypatch.setattr(moe, "_gmm", lambda *a: real(*a) * 1.2)
        with pytest.raises(AssertionError, match="differs from"):
            chip_smoke._grouped_matmul_case(512, 160, 256, 4,
                                            ((128, 128, 128),) * 3)

    def test_kernel_case_fails_on_a_wrong_kernel(self, monkeypatch):
        from paddle_tpu.ops.pallas import flash_attention as fa
        real = fa._flash_fwd_bhsd

        def skewed(*a, **k):
            out, lse = real(*a, **k)
            return out * 1.2, lse
        monkeypatch.setattr(fa, "_flash_fwd_bhsd", skewed)
        with pytest.raises(AssertionError, match="forward differs"):
            chip_smoke._attention_case("skew", 1, 1, 128, 128, None, True,
                                       0)

    def test_server_phase(self):
        meter = chip_smoke.CompileMeter()
        res = chip_smoke.server_phase(
            config=TINY_LLAMA,
            engine=dict(num_blocks=64, block_size=8, max_batch=4,
                        max_blocks_per_seq=8, prefill_buckets=(8, 16)),
            prompts=(5, 12, 30), new_tokens=5, check=(0, 2),
            dtype="float32", meter=meter)
        assert res["requests"] == 3 and res["tokens_out"] == 15
        # 30 > the largest bucket: the chunked request is one of the checked
        assert [c["prompt"] for c in res["reference"]] == [5, 30]
        assert all(c["max_gap_in_logit_std"] <= chip_smoke.SERVE_LOGIT_TOL
                   for c in res["reference"])
        assert any("serving.decode" in k for k in res["compile_s_by_program"])

    def test_trainer_and_four_chip_phase(self):
        """One device, then mp=2 x sharding=2 on virtual devices: equal
        first-step loss, every device holds a shard, loss falls."""
        res = chip_smoke.four_chip_phase(
            depth_equal=1, depth_full=1, steps=3, widths=TINY_GPT, batch=4,
            seq=32, dtype="float32")
        assert res["first_step_loss_diff"] <= chip_smoke.FOUR_CHIP_LOSS_TOL
        full = res["four_chip_full_depth"]
        assert full["mesh"]["mp"] == 2 and full["mesh"]["sharding"] == 2
        assert full["losses"][-1] < full["losses"][0]
        qkv = full["shards"]["gpt.h.0.attn.qkv_proj.weight"]
        assert sorted(qkv["param_shards"]) == ["0", "1", "2", "3"]
        assert qkv["param_shards"]["0"] == [64, 96]       # mp halves it
        assert qkv["moment1_shards"]["0"] == [32, 96]     # ZeRO halves again
        assert full["attention"]["forward"] == "xla_dense"   # CPU backend
        # XLA's buffer assignment: the only place activations show
        assert full["memory"]["xla_step"]["temp"] > 0


class TestCompileCacheHelper:
    def test_env_set_is_left_alone(self, monkeypatch, tmp_path):
        from paddle_tpu.framework import compile_cache as cc
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        touched = []
        monkeypatch.setattr(jax.config, "update",
                            lambda *a, **k: touched.append(a))
        assert cc.setup_compile_cache() == str(tmp_path)
        assert touched == []

    def test_env_unset_uses_checkout_dir(self, monkeypatch):
        from paddle_tpu.framework import compile_cache as cc
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        updates = {}
        monkeypatch.setattr(jax.config, "update",
                            lambda k, v: updates.__setitem__(k, v))
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        want = os.path.join(repo, ".jax_cache")
        assert cc.setup_compile_cache() == want
        assert updates == {"jax_compilation_cache_dir": want}

    def test_no_other_cache_dir_is_set_in_the_tree(self):
        """Only the helper names jax_compilation_cache_dir."""
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        hits = []
        for root, dirs, files in os.walk(repo):
            dirs[:] = [d for d in dirs if not d.startswith(".")
                       and d not in ("tests", "chiprun_out", "__pycache__")]
            for f in files:
                if f.endswith(".py"):
                    p = os.path.join(root, f)
                    if "jax_compilation_cache_dir" in open(p).read():
                        hits.append(os.path.relpath(p, repo))
        assert hits == ["paddle_tpu/framework/compile_cache.py"]

    def test_cache_record_shows_earlier_processes(self, tmp_path, capsys):
        meter = chip_smoke.CompileMeter()
        chip_smoke._cache_record(str(tmp_path), meter, "one-chip")
        assert "earlier processes on this cache: none" in \
            capsys.readouterr().out
        chip_smoke._cache_record(str(tmp_path), meter, "one-chip")
        assert "'mode': 'one-chip'" in capsys.readouterr().out
        runs = json.load(open(tmp_path / "chip_smoke_compile_seconds.json"))
        assert len(runs) == 2


class TestNoHiddenDevice:
    def test_set_device_tpu_raises_without_a_chip(self):
        import paddle_tpu as paddle
        with pytest.raises(RuntimeError, match="no accelerator"):
            paddle.set_device("tpu")
        with pytest.raises(RuntimeError, match="no accelerator"):
            paddle.set_device("gpu:0")

    def test_set_device_index_out_of_range_raises(self):
        import paddle_tpu as paddle
        with pytest.raises(ValueError, match="out of range"):
            paddle.set_device(f"cpu:{len(jax.devices('cpu'))}")
        assert paddle.set_device("cpu") == "cpu"

    def test_selection_failures_propagate_on_a_tpu_backend(self, monkeypatch):
        from paddle_tpu import generation
        from paddle_tpu.nn.functional import attention as attn
        from paddle_tpu.ops.pallas import attention_router as ar

        def broken(*a, **k):
            raise OSError("kernel module unreadable")
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        monkeypatch.setattr(ar, "route", broken)
        with pytest.raises(OSError):
            attn._use_pallas((2, 512, 4, 64), 64, False, dtype="bfloat16")
        with pytest.raises(OSError):
            generation._prefill_flash_routed(8, 512, 64, "bfloat16")

    def test_refused_autotune_candidates_are_counted_then_raise(self):
        from paddle_tpu.ops.pallas import autotune as at

        def make_runner(cand):
            if cand == "n/a":
                raise ValueError("does not apply")

            def run():
                raise RuntimeError("VMEM exhausted")
            return run
        at.enable_autotune()
        try:
            with pytest.warns(RuntimeWarning, match="VMEM exhausted"):
                with pytest.raises(RuntimeError, match="every applicable"):
                    at.autotune("refused", ["n/a", "a", "b"], make_runner)
        finally:
            at.disable_autotune()
            at.clear_cache()

    def test_worker_hello_reports_its_platform(self):
        from paddle_tpu.inference.mesh.transport import serve_request

        class _Pool:
            block_size = 8

        class _Engine:
            embed_w = jnp.zeros((16, 4))
            pool = _Pool()
        kind, meta, _ = serve_request(_Engine(), "ping", {}, b"")
        assert kind == "ok" and meta["platform"] == "cpu"
        assert meta["vocab"] == 16 and meta["block_size"] == 8
