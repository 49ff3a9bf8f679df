"""Elastic fault-recovery drill worker (run via paddle_tpu.distributed.launch
with --max_restart >= 1).

The end-to-end kill -> detect -> restart -> resume drill the reference
implements across fleet/elastic/manager.py:125 (membership watch),
launch/main.py (pod restart) and test/legacy_test/test_dist_base.py:957
(loss-continuity comparison):

  - both ranks register with ElasticManager (TCPStore leases + heartbeats)
  - SpmdTrainer (dp=2) trains; EVERY step ends with a distributed
    checkpoint (params + opt state + step counter, owner-computed chunks)
  - on the FIRST incarnation, rank 1 hard-crashes (os._exit) before step
    CRASH_AT; rank 0's ElasticManager WATCH detects the lost lease and
    exits for regroup (the reference manager's RESTART signal)
  - the launcher restarts the pod; the new incarnation loads the latest
    checkpoint and continues from the recorded step
  - per-step losses append to a per-rank jsonl; the pytest wrapper splices
    incarnations and compares against an unkilled run
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

import jax

# a test worker is a pure-CPU process whatever the environment says: a
# chip belongs to one process, and the parent may hold it
jax.config.update("jax_platforms", "cpu")

TOTAL_STEPS = 6
CRASH_AT = 3          # rank 1 dies before running this step (incarnation 0)
HB = 0.3              # fast heartbeats so lease expiry fits in a test


def log_event(workdir, rank, payload):
    with open(os.path.join(workdir, f"events.rank{rank}.jsonl"), "a") as f:
        f.write(json.dumps(payload) + "\n")
        f.flush()
        os.fsync(f.fileno())


def main():
    workdir = sys.argv[1]
    import paddle_tpu as paddle
    import paddle_tpu.distributed as dist
    from paddle_tpu.distributed import parallel_env
    from paddle_tpu.distributed import checkpoint as dck
    from paddle_tpu.distributed.fleet.elastic import (ElasticManager,
                                                      ElasticStatus)
    from jax.sharding import Mesh
    from paddle_tpu import nn, optimizer
    from paddle_tpu.parallel.spmd import SpmdTrainer, DP_ONLY_RULES

    dist.init_parallel_env()
    rank = dist.get_rank()
    store = parallel_env.get_store()
    sentinel = os.path.join(workdir, "crashed.sentinel")
    first_incarnation = not os.path.exists(sentinel)
    incarnation = 0 if first_incarnation else 1

    em = ElasticManager(store, node_id=f"rank{rank}-inc{incarnation}",
                        np_range=(2, 2), heartbeat_interval=HB)
    em.register()
    em.start()
    log_event(workdir, rank, {"event": "registered",
                              "incarnation": incarnation,
                              "alive": sorted(em.alive_nodes())})

    # deterministic data + model (same on both incarnations)
    rng = np.random.RandomState(0)
    X = rng.randn(8, 4).astype(np.float32)
    Y = (X @ rng.randn(4, 1).astype(np.float32))
    paddle.seed(0)
    model = nn.Sequential(nn.Linear(4, 8), nn.Tanh(), nn.Linear(8, 1))
    opt = optimizer.SGD(0.1, parameters=model.parameters())
    mesh = Mesh(np.array(jax.devices()).reshape(2), ("dp",))
    trainer = SpmdTrainer(model, opt, mesh, rules=DP_ONLY_RULES,
                          loss_fn=lambda pred, y: ((pred - y) ** 2).mean())

    # ---- resume from the latest distributed checkpoint -------------------
    ckpt = os.path.join(workdir, "ckpt")
    start_step = 0
    if os.path.exists(os.path.join(ckpt, "metadata.json")):
        state = dict(trainer.params)
        for name, st in trainer.opt_state.items():
            for k, v in st.items():
                state[f"__opt__/{name}/{k}"] = v
        state["__step__"] = jax.numpy.zeros((), jax.numpy.int32)
        dck.load_state_dict(state, ckpt)
        trainer.params = {k: state[k] for k in trainer.params}
        trainer.opt_state = {
            name: {k: state[f"__opt__/{name}/{k}"] for k in st}
            for name, st in trainer.opt_state.items()}
        start_step = int(state["__step__"])
        trainer.step_count = start_step
        log_event(workdir, rank, {"event": "resumed",
                                  "incarnation": incarnation,
                                  "from_step": start_step})

    for s in range(start_step, TOTAL_STEPS):
        if first_incarnation and s == CRASH_AT:
            if rank == 1:
                # hard failure: no deregister, no cleanup — the lease must
                # EXPIRE for the manager to notice, as with a real crash
                with open(sentinel, "w") as f:
                    f.write("rank1 crashed\n")
                log_event(workdir, rank, {"event": "crash",
                                          "incarnation": 0, "at_step": s})
                os._exit(17)
            else:
                # rank 0: the peer's lease expires (ttl = 3*HB); WATCH must
                # report the membership change — that detection is the drill
                status = em.watch(poll=HB, max_wait=30 * HB)
                detected = status in (ElasticStatus.RESTART,
                                      ElasticStatus.HOLD)
                log_event(workdir, rank, {
                    "event": "detected_membership_change",
                    "incarnation": 0, "status": status,
                    "alive_after": sorted(em.alive_nodes()),
                    "detected": detected})
                # regroup: exit nonzero so the launcher restarts the pod
                # (the reference manager's RESTART path)
                os._exit(18 if detected else 19)

        loss = float(trainer.step((X, Y)))
        log_event(workdir, rank, {"event": "step", "incarnation": incarnation,
                                  "step": s, "loss": loss})
        # checkpoint AFTER the step: params/opt for step s+1
        state = dict(trainer.params)
        for name, st in trainer.opt_state.items():
            for k, v in st.items():
                state[f"__opt__/{name}/{k}"] = v
        state["__step__"] = jax.numpy.asarray(s + 1, jax.numpy.int32)
        dck.save_state_dict(state, ckpt)

    em.deregister()
    log_event(workdir, rank, {"event": "done", "incarnation": incarnation})
    print(f"rank {rank} inc {incarnation} done", flush=True)


if __name__ == "__main__":
    main()
