"""Compile a cell's largest programs at real widths for a TPU v5e that is
described, not attached (on-chip-measurement guide, section 2.3), and print
XLA's memory analysis. Costs no chip time; run it before a cell's first
chip call:

    JAX_PLATFORMS=cpu python3 benchmark/tools/describe_compile.py \
        --workload gpt3-xl-d12.pretrain-2k

Nothing runs and no time is measured: what this proves is that the chip's
compiler accepts the programs and that they fit its memory. It reaches into
the program (the trainer's jitted step, the engine's program makers) the
way a scratch script has to; it is a tool, not part of the yardstick.
"""

from __future__ import annotations

import argparse
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
for p in (ROOT, BENCH_DIR):
    if p not in sys.path:
        sys.path.insert(0, p)


def _gib(n):
    return f"{n / 2**30:.2f} GiB"


def _report(name, compiled):
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             - m.alias_size_in_bytes + m.temp_size_in_bytes)
    print(f"{name}: arguments {_gib(m.argument_size_in_bytes)} + outputs "
          f"{_gib(m.output_size_in_bytes)} - aliased "
          f"{_gib(m.alias_size_in_bytes)} + temporaries "
          f"{_gib(m.temp_size_in_bytes)} = {_gib(total)} per device",
          flush=True)
    return total


def describe_train(cell, topo):
    """The trainer's jitted step on described devices: SpmdTrainer places
    its arrays with jax.device_put, which a described device cannot take,
    so while the trainer is built device_put hands back shapes."""
    import importlib
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from paddle_tpu.parallel import spmd

    real_devices, real_put = jax.devices, jax.device_put

    def shape_put(a, sharding=None, **kw):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding)

    real_zeros_like = jnp.zeros_like

    def host_zeros_like(p, *a, **kw):    # optimizer moments of a shape
        return real_zeros_like(jax.ShapeDtypeStruct(p.shape, p.dtype),
                               *a, **kw)

    family = importlib.import_module(f"families.{cell.config['family']}")
    jax.devices = lambda *a, **k: list(topo.devices)
    jax.device_put, jnp.zeros_like = shape_put, host_zeros_like
    try:
        trainer, cfg, n = family.build_trainer(cell.config, cell.traffic, 0)
    finally:
        jax.devices, jax.device_put = real_devices, real_put
        jnp.zeros_like = real_zeros_like
    rep = NamedSharding(trainer.mesh, P())
    tr = cell.traffic
    ids = np.zeros((int(tr["batch"]), int(tr["seq"])), np.int32)
    trainer._batch_arrays((ids, ids))
    batch_sh = NamedSharding(trainer.mesh,
                             spmd._pad_spec(trainer.batch_spec, 2))
    sds = jax.ShapeDtypeStruct
    batch = (sds(ids.shape, jnp.int32, sharding=batch_sh),) * 2
    key = jax.random.key_data(jax.random.PRNGKey(0))
    # the attention router asks jax.default_backend() and would take its
    # CPU branch (dense attention, O(S^2) buffers) here: steer it while the
    # step is traced, as the guide's section 2.3 says a script has to
    real_backend = jax.default_backend
    jax.default_backend = lambda: "tpu"
    try:
        compiled = _lower_step(trainer, batch, key, rep, sds, jnp)
    finally:
        jax.default_backend = real_backend

    text = compiled.as_text()
    print(f"train step, {n / 1e6:.1f}M parameters, mesh "
          f"{dict(trainer.mesh.shape)}: tpu_custom_call (Pallas) x "
          f"{text.count('tpu_custom_call')}, all-reduce x "
          f"{text.count('all-reduce(')}, all-gather x "
          f"{text.count('all-gather(')}, reduce-scatter x "
          f"{text.count('reduce-scatter(')}")
    _report("train step", compiled)


def _lower_step(trainer, batch, key, rep, sds, jnp):
    import jax
    with jax.set_mesh(trainer.mesh):
        return trainer._compiled.lower(
            trainer.params, trainer.opt_state, batch,
            sds(key.shape, key.dtype, sharding=rep),
            sds((), jnp.int32, sharding=rep),
            sds((), jnp.float32, sharding=rep)).compile()


def describe_serve(cell, topo):
    """The engine's decode tile and its widest prefill chunk, lowered from
    the engine's own program makers with the shapes of a real engine built
    on the host."""
    import importlib
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    family = importlib.import_module(f"families.{cell.config['family']}")
    eng, cfg, n, _ = family.build_engine(cell.config, 0)
    chip = SingleDeviceSharding(topo.devices[0])

    def shp(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip),
            tree)

    B, MB = eng.max_batch, eng.max_blocks_per_seq
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=chip)  # noqa: E731
    common = [shp(eng.stacked), shp(eng.embed_w), shp(eng.norm_w),
              shp(eng._out_w), shp(eng.pool.k), shp(eng.pool.v)]
    lanes = [i32(B), i32(B),
             jax.ShapeDtypeStruct((B,), jnp.bool_, sharding=chip),
             i32(B), i32(B), i32(B, MB)]
    dec = jax.jit(eng._make_decode(False), donate_argnums=(4, 5)).lower(
        *common, *lanes).compile()
    weights = sum(a.nbytes for a in jax.tree_util.tree_leaves(
        [eng.stacked, eng.embed_w, eng._out_w]))
    pool = eng.pool.k.nbytes + eng.pool.v.nbytes
    print(f"engine: {n / 1e6:.1f}M parameters; stacked weights + embedding "
          f"+ head {_gib(weights)}; pool {_gib(pool)}; {B} lanes x {MB} "
          f"blocks a sequence")
    _report(f"decode tile (K={eng.decode_steps})", dec)
    w = eng.chunk
    pre = jax.jit(eng._make_prefill_chunk()).lower(
        *common, i32(1, w), i32(), i32(), i32(MB)).compile()
    _report(f"prefill chunk b{w} (pool not donated)", pre)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    args = ap.parse_args()
    import jax
    from jax.experimental import topologies
    from harness import cells
    jax.config.update("jax_enable_compilation_cache", False)
    cell = cells.load_cell(args.workload)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    kind = cell.traffic["kind"]
    (describe_train if kind == "train" else describe_serve)(cell, topo)


if __name__ == "__main__":
    main()
