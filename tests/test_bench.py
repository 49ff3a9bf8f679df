"""bench.py contract tests (CPU paths only — chip runs go through the
chip tool).

Each invocation prints ONE JSON line from the process that ran the arm;
these tests pin that contract, the no-chip failure and the peak table.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_bench(args, timeout=600):
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")] + args,
        capture_output=True, text=True, timeout=timeout, cwd=REPO)
    for line in reversed(p.stdout.strip().splitlines()):
        if line.startswith("{"):
            return json.loads(line)
    raise AssertionError(f"no JSON line: rc={p.returncode} "
                         f"stderr={p.stderr[-300:]}")


class TestBenchWorkers:
    @pytest.mark.slow
    def test_secondary_models_cpu(self):
        """BASELINE rows 2-3: ResNet images/sec + BERT tokens/s emitted in
        one secondary detail dict, with no error field.

        ~45s on one CPU (two full model compiles in a subprocess); out of
        tier-1's wall budget — test_llama_cpu_smoke keeps the JSON row
        contract covered there."""
        obj = _run_bench(["--secondary", "both", "--cpu"])
        assert obj["metric"] == "secondary_models_cpu_smoke"
        d = obj["detail"]
        assert not any(k.endswith("error") for k in d), d
        assert d["resnet_images_per_s"] > 0
        assert d["bert_tokens_per_s"] > 0
        assert d["resnet_loss"] == d["resnet_loss"]  # not NaN
        assert d["bert_loss"] > 0

    @pytest.fixture(scope="class")
    def cpu_smoke_row(self):
        """One bench subprocess shared by the contract assertions below
        (each run costs ~10s; tier-1 runs against a wall clock)."""
        return _run_bench(["--cpu"])

    def test_llama_cpu_smoke(self, cpu_smoke_row):
        obj = cpu_smoke_row
        assert obj["metric"] == "llama_train_tokens_per_s_cpu_smoke"
        assert obj["value"] > 0
        # every row names the device it ran on, as jax reports it
        dev = obj["device"]
        assert (dev["platform"], dev["kind"]) == ("cpu", "cpu")
        assert dev["count"] >= 1

    def test_row_embeds_roundtrippable_metrics_snapshot(self, cpu_smoke_row):
        """Every bench row carries detail.metrics_snapshot — the process's
        registry snapshot (train telemetry + router counters) — and it
        must load back into a registry (self-describing evidence)."""
        snap = cpu_smoke_row["detail"]["metrics_snapshot"]
        from paddle_tpu.observability import metrics as obs_metrics
        reg = obs_metrics.load_snapshot(
            json.loads(json.dumps(snap)))   # through the JSON line
        steps = reg.get("train_step_seconds")
        assert steps is not None and steps.count > 0
        assert reg.get("train_tokens_total").value > 0
        assert obs_metrics.snapshot(reg)["metrics"] == snap["metrics"]


class TestNoHiddenDevice:
    """No chip is a failure, an unknown chip has no peak, and a failed
    arm fails the run — bench.py never substitutes a number."""

    def test_no_chip_without_cpu_flag_exits_nonzero(self, capsys):
        import bench
        with pytest.raises(SystemExit) as exc:
            bench._init_backend(force_cpu=False)   # tests run on the CPU
        assert exc.value.code == 1
        out = capsys.readouterr()
        assert "no accelerator" in out.err and out.out == ""

    def test_detect_peak_raises_on_unknown_device_kind(self):
        import bench
        assert bench.PEAK_BF16["TPU v5 lite"] == 197e12
        with pytest.raises(KeyError, match="device_kind 'cpu'"):
            bench.detect_peak()

    def test_failed_arm_makes_exit_code_nonzero(self, monkeypatch, capsys):
        import bench

        def boom(on_tpu):
            raise RuntimeError("arm blew up")
        monkeypatch.setattr(bench, "_bench_resnet", boom)
        assert bench.secondary_worker(force_cpu=True, which="resnet") == 1
        row = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        assert row["metric"] == "secondary_models_cpu_smoke"
        assert "arm blew up" in row["detail"]["resnet_error"]
