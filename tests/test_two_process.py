"""Two-process distributed integration: the launcher spawns REAL worker
processes that rendezvous through our own stack.

reference pattern: test/collective/test_communication_api_base.py:28 and
test/legacy_test/test_dist_base.py:957 spawn trainer subprocesses and
compare losses across them; this is the TPU-native analog over
jax.distributed (CPU/gloo backend) + the native TCPStore.
"""

import json
import os
import socket
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(REPO, "tests", "two_proc_worker.py")


def _free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class TestTwoProcessIntegration:
    @pytest.fixture(scope="class")
    def results(self, tmp_path_factory):
        """One launch shared by every assertion (the run costs ~1 min)."""
        tmp = tmp_path_factory.mktemp("twoproc")
        out = str(tmp / "result")
        env = {k: v for k, v in os.environ.items()
               if not k.startswith(("PADDLE_", "JAX_COORDINATOR"))}
        # workers must not inherit the in-process CPU override machinery:
        # they force the cpu platform themselves
        env.pop("XLA_FLAGS", None)
        p = subprocess.run(
            [sys.executable, "-m", "paddle_tpu.distributed.launch",
             "--nproc_per_node=2", f"--master=127.0.0.1:{_free_port()}",
             "--max_restart=0", f"--log_dir={tmp}", WORKER, out],
            cwd=REPO, env=env, capture_output=True, text=True, timeout=420)
        logs = ""
        for r in range(2):
            lp = tmp / f"worker.{r}.log"
            if lp.exists():
                logs += f"\n--- worker {r} ---\n" + lp.read_text()[-2000:]
        if p.returncode != 0 and (
                "Multiprocess computations aren't implemented"
                in p.stderr + logs):
            pytest.skip("jaxlib CPU backend on this host lacks "
                        "multiprocess collectives; the two-process drill "
                        "needs a runtime with cross-process all-reduce")
        assert p.returncode == 0, f"launch failed: {p.stderr[-500:]}{logs}"
        res = {}
        for r in range(2):
            with open(f"{out}.rank{r}") as f:
                res[r] = json.load(f)
        res["ckpt_path"] = out + ".ckpt2p"
        return res

    def test_bootstrap_world(self, results):
        for r in range(2):
            assert results[r]["rank"] == r
            assert results[r]["world"] == 2
            assert results[r]["process_count"] == 2
            assert results[r]["global_devices"] == 2

    def test_tcp_store_cross_process(self, results):
        # rank 1 read the value rank 0 set — the KV really crossed
        assert results[1]["store"] == "from-rank0"

    def test_eager_collectives_cross_process(self, results):
        for r in range(2):
            assert results[r]["all_reduce_sum"] == 3.0
            assert results[r]["all_reduce_max"] == 2.0
            assert results[r]["all_gather"] == [0.0, 1.0]
            assert results[r]["broadcast_src1"] == 15.0

    def test_spmd_trainer_parity(self, results):
        # dp=2 over two processes == single-device full-batch training
        for r in range(2):
            assert results[r]["parity"], results[r]
        # and both ranks observed the SAME replicated loss
        assert results[0]["spmd_losses"] == results[1]["spmd_losses"]

    def test_reduce_scatter_cross_process(self, results):
        # contributions [r+1, 10(r+1)] sum to [3, 30]; rank r keeps chunk r
        assert results[0]["reduce_scatter"] == 3.0
        assert results[1]["reduce_scatter"] == 30.0
        assert results[0]["stream_reduce_scatter"] == 3.0
        assert results[1]["stream_reduce_scatter"] == 30.0

    def test_scatter_gather_cross_process(self, results):
        assert results[0]["scatter_from0"] == 100.0
        assert results[1]["scatter_from0"] == 200.0
        assert results[0]["gather_dst1"] == []       # only dst fills
        assert results[1]["gather_dst1"] == [7.0, 8.0]

    def test_send_recv_cross_process(self, results):
        assert results[1]["p2p_recv"] == [41.0, 42.0]
        assert results[0]["p2p_roundtrip"] == [42.0, 43.0]

    def test_batch_isend_irecv_cross_process(self, results):
        assert results[0]["batch_p2p"] == 109.0
        assert results[1]["batch_p2p"] == 9.0

    def test_two_proc_checkpoint_reshard_loads_single_proc(self, results,
                                                           tmp_path_factory):
        """The checkpoint two processes wrote loads in THIS single process
        onto a different (8-device) mesh — reshard-on-load — and matches
        the worker's own trained parameters (verified there against the
        eager reference)."""
        import numpy as np
        import jax
        import jax.numpy as jnp
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        from paddle_tpu.distributed import checkpoint as dck

        for r in range(2):
            assert results[r]["ckpt_saved"]
        ckpt = results["ckpt_path"]
        assert os.path.exists(os.path.join(ckpt, "metadata.json"))
        import json as _json
        with open(os.path.join(ckpt, "metadata.json")) as f:
            meta = _json.load(f)
        mesh = Mesh(np.array(jax.devices()[:2]), ("x",))
        state = {}
        for k, info in meta["arrays"].items():
            shape = tuple(info["shape"])
            # shard the first even-sized dim over 'x' to force resharding
            spec = [None] * len(shape)
            for d, s in enumerate(shape):
                if s % 2 == 0:
                    spec[d] = "x"
                    break
            state[k] = jax.device_put(
                jnp.zeros(shape, jnp.dtype(info["dtype"])),
                NamedSharding(mesh, P(*spec)))
        dck.load_state_dict(state, ckpt)
        # worker trained 3 SGD steps matching its eager reference; recompute
        # that reference here and compare arrays
        ref = _eager_reference_params()
        for k, arr in state.items():
            np.testing.assert_allclose(np.asarray(arr), ref[k],
                                       rtol=1e-4, atol=1e-5)


    def test_parameter_server_cross_process(self, results):
        """rank 0 served a sparse table over RPC; rank 1 pulled/pushed from
        a REAL separate process. Both sides must agree on the rows, the
        miss-init must be deterministic, and the duplicate-id push must
        have pre-aggregated (one rule step for id 3's summed grad)."""
        import numpy as np
        for r in range(2):
            assert results[r]["ps_ok"]
        assert results[1]["ps_init_deterministic"]
        assert results[1]["ps_push_math"]
        np.testing.assert_allclose(np.asarray(results[0]["ps_rows"]),
                                   np.asarray(results[1]["ps_rows"]),
                                   atol=1e-6)


def _eager_reference_params():
    """3 SGD steps on the worker's model/data, eagerly, in this process."""
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu import nn, optimizer

    rng = np.random.RandomState(0)
    X = rng.randn(8, 4).astype(np.float32)
    Y = (X @ rng.randn(4, 1).astype(np.float32))
    paddle.seed(0)
    model = nn.Sequential(nn.Linear(4, 8), nn.Tanh(), nn.Linear(8, 1))
    opt = optimizer.SGD(0.1, parameters=model.parameters())
    for _ in range(3):
        loss = ((model(paddle.to_tensor(X)) - paddle.to_tensor(Y)) ** 2).mean()
        loss.backward()
        opt.step()
        opt.clear_grad()
    return {k: np.asarray(t.numpy()) for k, t in model.state_dict().items()}

