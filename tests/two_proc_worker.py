"""Two-process integration worker (run via paddle_tpu.distributed.launch).

Exercises the REAL multi-process bootstrap end to end, the way the
reference's collective tests spawn actual trainer processes
(test/collective/test_communication_api_base.py:28,
test/legacy_test/test_dist_base.py:957):

  launch --nproc_per_node=2 --master=... -> PADDLE_* env ->
  init_parallel_env -> jax.distributed.initialize (CPU/gloo) + TCPStore
  -> eager cross-process collectives -> 2-process SpmdTrainer parity.

Writes a JSON result file per rank; the pytest wrapper asserts on it.
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

import jax

# a test worker is a pure-CPU process whatever the environment says: a
# chip belongs to one process, and the parent may hold it
jax.config.update("jax_platforms", "cpu")


def main():
    out_path = sys.argv[1]
    import paddle_tpu as paddle
    import paddle_tpu.distributed as dist
    from paddle_tpu.distributed import parallel_env

    dist.init_parallel_env()
    rank = dist.get_rank()
    world = dist.get_world_size()
    results = {"rank": rank, "world": world,
               "process_count": jax.process_count(),
               "global_devices": jax.device_count()}

    # ---- TCPStore: out-of-band KV through our native store ---------------
    store = parallel_env.get_store()
    if store is not None:
        if rank == 0:
            store.set("greeting", b"from-rank0")
        results["store"] = store.get("greeting").decode()

    # ---- eager cross-process collectives ---------------------------------
    x = paddle.to_tensor(np.array([float(rank + 1)], np.float32))
    dist.all_reduce(x)
    results["all_reduce_sum"] = float(x.numpy()[0])  # 1+2 = 3

    mx = paddle.to_tensor(np.array([float(rank + 1)], np.float32))
    dist.all_reduce(mx, op=dist.ReduceOp.MAX)
    results["all_reduce_max"] = float(mx.numpy()[0])  # 2

    gathered = []
    dist.all_gather(gathered, paddle.to_tensor(
        np.array([float(rank)], np.float32)))
    results["all_gather"] = [float(t.numpy()[0]) for t in gathered]  # [0, 1]

    b = paddle.to_tensor(np.array([float(rank * 10 + 5)], np.float32))
    dist.broadcast(b, src=1)
    results["broadcast_src1"] = float(b.numpy()[0])  # 15

    # reduce_scatter: rank r contributes [r+1, (r+1)*10]; reduced sum is
    # [3, 30]; rank r keeps element r
    rs_out = paddle.to_tensor(np.zeros(1, np.float32))
    rs_in = [paddle.to_tensor(np.array([float(rank + 1)], np.float32)),
             paddle.to_tensor(np.array([float((rank + 1) * 10)], np.float32))]
    dist.reduce_scatter(rs_out, rs_in)
    results["reduce_scatter"] = float(rs_out.numpy()[0])  # r0: 3, r1: 30

    # stream flavor, single-Tensor input (chunked internally)
    st_out = paddle.to_tensor(np.zeros(1, np.float32))
    st_in = paddle.to_tensor(
        np.array([rank + 1.0, (rank + 1.0) * 10], np.float32))
    dist.stream.reduce_scatter(st_out, st_in)
    results["stream_reduce_scatter"] = float(st_out.numpy()[0])

    # scatter from src=0: rank r receives 100*(r+1)
    sc_out = paddle.to_tensor(np.zeros(1, np.float32))
    sc_list = ([paddle.to_tensor(np.array([100.0], np.float32)),
                paddle.to_tensor(np.array([200.0], np.float32))]
               if rank == 0 else None)
    dist.scatter(sc_out, sc_list, src=0)
    results["scatter_from0"] = float(sc_out.numpy()[0])

    # gather to dst=1
    ga = []
    dist.gather(paddle.to_tensor(np.array([float(rank + 7)], np.float32)),
                ga, dst=1)
    results["gather_dst1"] = [float(t.numpy()[0]) for t in ga]

    # p2p over the store: 0 -> 1 then 1 -> 0 (two sequenced messages)
    if rank == 0:
        dist.send(paddle.to_tensor(np.array([41.0, 42.0], np.float32)), dst=1)
        back = paddle.to_tensor(np.zeros(2, np.float32))
        dist.recv(back, src=1)
        results["p2p_roundtrip"] = [float(x) for x in back.numpy()]  # [42,43]
    else:
        got = paddle.to_tensor(np.zeros(2, np.float32))
        dist.recv(got, src=0)
        dist.send(paddle.to_tensor(np.asarray(got.numpy()) + 1.0), dst=0)
        results["p2p_recv"] = [float(x) for x in got.numpy()]  # [41,42]

    # batched p2p: symmetric exchange in ONE batch on both ranks
    peer = 1 - rank
    bsend = paddle.to_tensor(np.array([float(rank * 100 + 9)], np.float32))
    brecv = paddle.to_tensor(np.zeros(1, np.float32))
    dist.batch_isend_irecv([dist.P2POp(dist.isend, bsend, peer),
                            dist.P2POp(dist.irecv, brecv, peer)])
    results["batch_p2p"] = float(brecv.numpy()[0])  # r0: 109, r1: 9

    # ---- 2-process SpmdTrainer step parity vs local eager loop -----------
    from jax.sharding import Mesh
    from paddle_tpu import nn, optimizer
    from paddle_tpu.parallel.spmd import SpmdTrainer, DP_ONLY_RULES

    rng = np.random.RandomState(0)
    X = rng.randn(8, 4).astype(np.float32)
    Y = (X @ rng.randn(4, 1).astype(np.float32))

    paddle.seed(0)
    model = nn.Sequential(nn.Linear(4, 8), nn.Tanh(), nn.Linear(8, 1))
    opt = optimizer.SGD(0.1, parameters=model.parameters())
    mesh = Mesh(np.array(jax.devices()).reshape(2), ("dp",))
    trainer = SpmdTrainer(model, opt, mesh, rules=DP_ONLY_RULES,
                          loss_fn=lambda pred, y: ((pred - y) ** 2).mean())
    spmd_losses = [float(trainer.step((X, Y))) for _ in range(3)]
    results["spmd_losses"] = spmd_losses

    # local eager reference: same init, same full batch, one device
    paddle.seed(0)
    ref = nn.Sequential(nn.Linear(4, 8), nn.Tanh(), nn.Linear(8, 1))
    ropt = optimizer.SGD(0.1, parameters=ref.parameters())
    eager_losses = []
    for _ in range(3):
        loss = ((ref(paddle.to_tensor(X)) - paddle.to_tensor(Y)) ** 2).mean()
        loss.backward()
        ropt.step()
        ropt.clear_grad()
        eager_losses.append(float(loss.numpy()))
    results["eager_losses"] = eager_losses
    results["parity"] = bool(np.allclose(spmd_losses, eager_losses,
                                         rtol=1e-4, atol=1e-5))

    # ---- 2-process distributed checkpoint save (owner-computed chunks);
    # the pytest wrapper reshard-loads it in a SINGLE process -------------
    from paddle_tpu.distributed import checkpoint as dck
    dck.save_state_dict(dict(trainer.params), out_path + ".ckpt2p")
    results["ckpt_saved"] = True

    # ---- parameter server across REAL processes: rank 0 serves a sparse
    # table over RPC, rank 1 trains against it (reference pattern:
    # test/ps/ + the_one_ps server/worker roles) -------------------------
    import socket as _socket
    from paddle_tpu.distributed import rpc as _rpc
    from paddle_tpu.distributed import ps as _ps
    from paddle_tpu.distributed.ps.accessor import deterministic_init

    if rank == 0:
        with _socket.socket() as _s:
            _s.bind(("127.0.0.1", 0))
            ps_master = f"127.0.0.1:{_s.getsockname()[1]}"
        store.set("ps_rpc_master", ps_master.encode())
    else:
        ps_master = store.get("ps_rpc_master").decode()
    name = _ps.the_one_ps.server_name(0) if rank == 0 else f"trainer_{rank}"
    _rpc.init_rpc(name, rank=rank, world_size=2, master_endpoint=ps_master)
    cfgs = [_ps.TableConfig(0, 4, _ps.CtrAccessor(
        _ps.SparseNaiveSGDRule(learning_rate=0.5)))]
    eng = _ps.TheOnePs(cfgs, num_servers=1)
    ids = np.array([3, 9, 3], np.uint64)
    if rank == 0:
        server = eng.start_server(0)
        store.set("ps_server_up", b"1")
        store.wait("ps_trainer_done")
        # server-side view after the trainer's push
        results["ps_rows"] = server.pull(0, np.array([3, 9], np.uint64)) \
            .tolist()
    else:
        store.wait("ps_server_up")
        client = eng.connect([_ps.the_one_ps.server_name(0)])
        first = client.pull(0, ids)
        init3 = deterministic_init(3, 4, 0.0001)
        results["ps_init_deterministic"] = bool(
            np.allclose(first[0], init3) and np.allclose(first[2], init3))
        # duplicate id 3 pre-aggregates: one rule step with summed grad
        client.push(0, ids, np.ones((3, 4), np.float32))
        after = client.pull(0, np.array([3, 9], np.uint64))
        results["ps_rows"] = after.tolist()
        results["ps_push_math"] = bool(
            np.allclose(after[0], first[0] - 1.0, atol=1e-6)
            and np.allclose(after[1], first[1] - 0.5, atol=1e-6))
        store.set("ps_trainer_done", b"1")
    _rpc.shutdown()
    results["ps_ok"] = True

    with open(f"{out_path}.rank{rank}", "w") as f:
        json.dump(results, f)
    print(f"rank {rank} OK", flush=True)


if __name__ == "__main__":
    main()
