"""The flash kernels compile for a TPU v5e that is described, not attached.

The interpreter (tests/test_flash_attention.py) proves the arithmetic; it
cannot see what Mosaic refuses: a slice off the tiling, a relayout it has
no rule for, more VMEM than a kernel may take. The chip's compiler is
installed here and compiles for a described chip (on-chip-measurement
guide, section 2), so every tile regime the chooser produces at real
widths is compiled here at no chip time. Nothing runs and nothing is
timed. All such compiles live in this one file: only the worker that is
handed it loads the TPU's library, inside the fixture.
"""

import collections
import functools
import math
import os
import re

import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops.pallas import flash_attention as fa


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — whatever the plugin raises
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _no_persistent_cache():
    # a described compile is written to the cache and cannot be read back
    # without a chip: the next one would warn and compile again
    from jax.experimental.compilation_cache import compilation_cache as cc
    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", old)
    cc.reset_cache()


def _small(rows):
    tile = (rows, 4 * rows, rows)
    return fa.Tiles(fwd=tile, dq=tile, dkv=tile)


# (id, bh, seq_q, seq_k, head_dim, q_per_kv, causal, dtype, tiles)
CASES = [
    ("train_cell", 64, 2048, 2048, 128, 1, True, jnp.bfloat16, None),
    ("four_chip_shard", 16, 2048, 2048, 128, 1, True, jnp.bfloat16, None),
    ("gqa", 32, 2048, 2048, 128, 4, True, jnp.bfloat16, None),
    ("ragged", 8, 2000, 2000, 128, 1, True, jnp.bfloat16, None),
    ("ragged_small_tiles", 8, 2176, 2176, 128, 1, True, jnp.bfloat16, None),
    ("cross_length", 8, 1024, 2048, 128, 1, True, jnp.bfloat16, None),
    ("noncausal_ragged", 8, 100, 260, 128, 1, False, jnp.bfloat16, None),
    ("short", 8, 16, 16, 128, 1, True, jnp.bfloat16, None),
    ("float32", 4, 2048, 2048, 128, 1, True, jnp.float32, None),
    ("long_streamed_in_pieces", 2, 65536, 65536, 128, 1, True, jnp.bfloat16,
     None),
    ("clamped_index_maps", 8, 2048, 2048, 128, 1, True, jnp.bfloat16,
     _small(128)),
]


@pytest.mark.parametrize("case", CASES, ids=[c[0] for c in CASES])
def test_forward_and_backward_compile(one_chip, case):
    _, bh, sq, sk, d, rep, causal, dtype, tiles = case

    def sds(*shape, dt=dtype):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    q, kv = sds(bh, sq, d), sds(bh // rep, sk, d)

    def fwd(q_, k_, v_):
        return fa._flash_fwd_bhsd(q_, k_, v_, causal, 0.088, tiles=tiles,
                                  interpret=False, q_per_kv=rep)

    def bwd(q_, k_, v_, o_, lse_, g_):
        return fa._flash_bwd_bhsd(q_, k_, v_, o_, lse_, g_, causal, 0.088,
                                  tiles=tiles, interpret=False, q_per_kv=rep)

    text = jax.jit(fwd).lower(q, kv, kv).compile().as_text()
    assert text.count("tpu_custom_call") >= 1
    text = jax.jit(bwd).lower(q, kv, kv, q, sds(bh, sq, dt=jnp.float32),
                              q).compile().as_text()
    assert "fa_bwd_dq" in text and "fa_bwd_dkv" in text


def _win_tiles(rows, streamed):
    tile = (rows[0], streamed, rows[1])
    return fa.Tiles(fwd=tile, dq=tile, dkv=tile)


# (id, bh, seq_q, seq_k, q_per_kv, window, tiles): the windowed kernels
# (faw_*), whose streamed grid axis is relative to each resident tile's
# band. The cell's shape at the chooser's tiles, then what the chip sweep
# of tools/window_attention.py times, then the edges.
WINDOW_CASES = [
    ("mellum_cell_2x32x16k", 64, 16384, 16384, 8, 1024, None),
    ("at_8k", 64, 8192, 8192, 8, 1024, None),
    ("at_2k", 64, 2048, 2048, 8, 1024, None),
    ("rows_256_streamed_1024", 8, 16384, 16384, 8, 1024,
     _win_tiles((256, 256), 1024)),
    ("rows_1024_512_streamed_2048", 8, 16384, 16384, 8, 1024,
     _win_tiles((1024, 512), 2048)),
    ("rows_1024_streamed_4096", 8, 16384, 16384, 8, 1024,
     _win_tiles((1024, 1024), 4096)),
    ("rows_512_whole_sequence", 8, 16384, 16384, 8, 1024,
     _win_tiles((512, 512), 16384)),
    ("window_under_a_sub_block", 8, 4096, 4096, 1, 100, None),
    ("window_between_tiles", 8, 4096, 4096, 1, 1500, None),
    ("ragged", 8, 3000, 3000, 1, 1024, None),
    ("cross_length", 8, 1024, 4096, 1, 1024, None),
]


@pytest.mark.parametrize("case", WINDOW_CASES,
                         ids=[c[0] for c in WINDOW_CASES])
def test_windowed_forward_and_backward_compile(one_chip, case):
    _, bh, sq, sk, rep, window, tiles = case
    d = 128

    def sds(*shape, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    q, kv = sds(bh, sq, d), sds(bh // rep, sk, d)

    def fwd(q_, k_, v_):
        return fa._flash_fwd_bhsd(q_, k_, v_, True, 0.088, tiles=tiles,
                                  interpret=False, q_per_kv=rep,
                                  window=window)

    def bwd(q_, k_, v_, o_, lse_, g_):
        return fa._flash_bwd_bhsd(q_, k_, v_, o_, lse_, g_, True, 0.088,
                                  tiles=tiles, interpret=False, q_per_kv=rep,
                                  window=window)

    text = jax.jit(fwd).lower(q, kv, kv).compile().as_text()
    assert "faw_fwd" in text and "fa_fwd" not in text
    text = jax.jit(bwd).lower(q, kv, kv, q, sds(bh, sq, dt=jnp.float32),
                              q).compile().as_text()
    assert "faw_bwd_dq" in text and "faw_bwd_dkv" in text
    assert "fa_bwd" not in text


def test_a_window_that_hides_nothing_compiles_the_causal_kernels(one_chip):
    """window >= seq_k is the causal call: the accepted cells' kernel
    names, whatever window a caller passes."""
    x = jax.ShapeDtypeStruct((8, 2048, 128), jnp.bfloat16, sharding=one_chip)

    def fwd(q, k, v):
        return fa._flash_fwd_bhsd(q, k, v, True, 0.088, interpret=False,
                                  window=2048)

    text = jax.jit(fwd).lower(x, x, x).compile().as_text()
    assert "fa_fwd" in text and "faw_" not in text


def test_wrappers_compile(one_chip):
    """head_dim 96 through the public wrapper (zero-padded to 128) with its
    gradient, and the RMSNorm epilogue riding the forward's flush."""
    def sds(*shape, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def grads(q, k, v):
        return jax.grad(lambda a, b, c: jnp.sum(fa.flash_attention_bshd(
            a, b, c, causal=True).astype(jnp.float32)), (0, 1, 2))(q, k, v)

    x = sds(2, 512, 4, 96)
    text = jax.jit(grads).lower(x, x, x).compile().as_text()
    assert "fa_fwd" in text and "fa_bwd_dkv" in text

    def epilogue(q, k, v, res, w):
        return fa.flash_attention_rms_epilogue_bshd(q, k, v, res, w)

    q, kv = sds(2, 2048, 8, 128), sds(2, 2048, 2, 128)
    text = jax.jit(epilogue).lower(q, kv, kv, q, sds(128)).compile().as_text()
    assert "fa_fwd" in text


def test_nope_gqa_at_8k_compiles_and_routes_to_the_kernels(one_chip):
    """The attention layer of granite-4.0-h-small at the benchmark cell's
    shape: causal GQA 32/8, d 128, seq 8192, softmax scale 1/128 (the
    config's attention_multiplier, not 1/sqrt(d)), no rotary. The rule
    names the kernels (dense attention would hold 8 GiB of float32
    scores), and they compile for a described v5e, forward and backward,
    at the tiles the Decision records."""
    from paddle_tpu.ops.pallas.attention_router import (
        clear_routing_cache, route)
    bh, seq, d, rep, scale = 32, 8192, 128, 4, 0.0078125
    clear_routing_cache()
    dec = route(bh, seq, seq, d, jnp.bfloat16, True, platform="tpu")
    assert (dec.fwd, dec.bwd) == ("pallas", "pallas")
    tiles = fa.tiles_for_shape(bh, seq, seq, d, jnp.bfloat16, True)
    assert dec.tiles == tiles
    # rows resident per grid step: 1024 (forward), 512 (backward kernels);
    # the whole sequence streamed past them: 32 x 8192 / rows steps a call
    assert tiles.fwd == (1024, 8192, 1024) and tiles.dq == tiles.dkv \
        == (512, 8192, 512)
    assert dec.grid_steps == {"fa_fwd": 256, "fa_bwd_dq": 512,
                              "fa_bwd_dkv": 512}

    def sds(*shape, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    q, kv = sds(bh, seq, d), sds(bh // rep, seq, d)
    text = jax.jit(lambda q_, k_, v_: fa._flash_fwd_bhsd(
        q_, k_, v_, True, scale, tiles=tiles, interpret=False,
        q_per_kv=rep)).lower(q, kv, kv).compile().as_text()
    assert "fa_fwd" in text
    text = jax.jit(lambda q_, k_, v_, o_, lse_, g_: fa._flash_bwd_bhsd(
        q_, k_, v_, o_, lse_, g_, True, scale, tiles=tiles, interpret=False,
        q_per_kv=rep)).lower(q, kv, kv, q, sds(bh, seq, dt=jnp.float32),
                             q).compile().as_text()
    assert "fa_bwd_dq" in text and "fa_bwd_dkv" in text


def test_grouped_matmul_kernel_keeps_the_name_its_metrics_read(
        one_chip, monkeypatch):
    """`lax.ragged_dot` in parallel/moe.py dropless_moe (what its
    `grouped_matmul` keeps at the granite cell's widths, on a TPU too:
    they have no entry in `_GMM_TILES`) becomes the TPU
    compiler's own grouped-matmul kernel, whose op_name is the kernel's
    name in place of the program's name stack, so the scope `pt.moe` is
    lost on it. benchmark/layer_metrics/moe_time_share.json and
    moe_grouped_matmul_roofline.json find it by that name: if a compiler
    gives it another, this fails here, and the metrics do not go short in
    silence on the chip. One FFN block of the published widths, forward
    and backward. What is not the kernel holds no scatter: the counts, the
    gates' way into expert order and back and the chosen logits' gradient
    are comparisons and sorts, which the chip does not run one index
    after the other."""
    import json
    import os
    from paddle_tpu.parallel.moe import dropless_moe
    # the described chip is not jax.default_backend(): the choice of the
    # grouped matmul is told it is
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def sds(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)

    def routed(x, router, w_in, w_out):
        with jax.named_scope("pt.moe"):
            return dropless_moe(x, router, w_in, w_out, 10, (0, 9))

    def grads(*arrays):
        return jax.value_and_grad(lambda *a: jnp.sum(
            routed(*a).astype(jnp.float32)), (0, 1, 2, 3))(*arrays)

    text = jax.jit(grads).lower(
        sds(2048, 4096), sds(4096, 72), sds(9, 4096, 1536),
        sds(9, 768, 4096)).compile().as_text()
    kernels = [line for line in text.splitlines()
               if "custom-call(" in line and "tpu_custom_call" in line]
    # x W_in and act W_out, and the two products of each in the backward
    assert len(kernels) >= 6
    assert not re.search(r" scatter\(", text)
    metrics = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "layer_metrics")
    for name in ("moe_time_share", "moe_grouped_matmul_roofline"):
        with open(os.path.join(metrics, name + ".json")) as f:
            rx = re.compile(json.load(f)["params"]["regex"])
        for line in kernels:
            op_name = re.search(r'op_name="([^"]*)"', line).group(1)
            assert rx.search(op_name), (name, op_name)
            assert "pt.moe" not in op_name      # or the first alternative
            # of moe_time_share's pattern would count the kernel twice


def test_mellum_moe_compiles_with_the_pallas_grouped_matmul(one_chip,
                                                            monkeypatch):
    """The Mellum cell's routed experts (models/mellum.py MellumSparseMoe:
    hidden 2304, 16 held of 64 of width 896, top-8, the routing not
    differentiated, bf16) over its step's 2 x 16,384 tokens, forward and
    backward with the blocks' recomputation, for a described v5e. At
    these widths `parallel/moe.py grouped_matmul` takes jax's Pallas
    kernels at the tiles of `_GMM_TILES`, which Mosaic has to fit into
    VMEM beside the step: seven products a block (x W_in forward and
    recomputed, act W_out, two gradients each), every one under `pt.moe`,
    none left to XLA's `ragged-dot`. Outside the kernels nothing
    scatters but the kernels' own metadata (a tile count a group)."""
    import paddle_tpu as paddle
    from paddle_tpu.models.mellum import MellumConfig, MellumSparseMoe
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = MellumConfig(num_hidden_layers=1, vocab_size=256, dtype="bfloat16",
                       experts_held=(0, 16), differentiate_routing=False)
    with paddle.LazyGuard():
        sub = MellumSparseMoe(cfg)
    names, tensors = zip(*sub.named_parameters())
    params = {n.replace(".", "_"): jax.ShapeDtypeStruct(
        tuple(t.shape), jnp.bfloat16, sharding=one_chip)
        for n, t in zip(names, tensors)}
    h = jax.ShapeDtypeStruct((2, 16384, cfg.hidden_size), jnp.bfloat16,
                             sharding=one_chip)

    def loss(h_, p_):
        block = jax.checkpoint(lambda x: sub._pure(x, **p_))
        return jnp.sum(sub._over(block, h_).astype(jnp.float32))

    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(
        h, params).compile().as_text()
    kernels = collections.Counter()
    for line in text.splitlines():
        if "custom-call(" in line and "tpu_custom_call" in line:
            op_name = re.search(r'op_name="([^"]*)"', line).group(1)
            assert "pt.moe" in op_name and "ragged" not in op_name, op_name
            kernels[re.search(r"jit\((\w+)\)/pallas_call", op_name).group(1)] \
                += 1
    assert kernels == {"gmm": 5, "tgmm": 2}
    for scattered in re.findall(r"= \w+\[(\d*)\]\S* scatter\(", text):
        assert int(scattered or 1) <= 1024


@pytest.mark.parametrize("rows, weighted", [(5120, True), (1024, False)],
                         ids=["query_side_5120_rows", "key_side_1024_rows"])
def test_retention_kernels_compile_at_the_cell_s_shapes(one_chip, rows,
                                                        weighted):
    """ops/pallas/power_retention.py at the Brumby cell's shapes: a chunk's
    1,024 key rows or its 5 x 1,024 query rows of 128 lanes against a
    state of 65 rotations x 128 x 256 (the normaliser's column beside the
    128 of v), bf16. What the interpreter cannot refuse and Mosaic can: a
    lane rotation by the loop's index, `m_ref[r]`, a product that
    contracts the rows of both operands, a whole float32 state
    accumulated over the row grid with the VMEM limit raised."""
    from paddle_tpu.ops.pallas import power_retention as kernels
    d, e = 128, 256
    features = (d // 2 + 1) * d

    def sds(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)

    u, m, w = sds(rows, d), sds(features, e), sds(rows, e)
    for name, fn, args in (
            ("retn_read", kernels.read, (u, m)),
            ("retn_write", kernels.write, (u, w)),
            ("retn_back", kernels.back, (u, w, m))):
        text = jax.jit(functools.partial(
            fn, weighted=weighted, interpret=False)).lower(
                *args).compile().as_text()
        assert "tpu_custom_call" in text and name in text, name


_COMPILED = {}        # one compile a shape, shared by the tests below


def _kernel_calls(text):
    """[(kernel name, op_name, instruction name)] of a compiled text's
    Pallas calls."""
    out = []
    for line in text.splitlines():
        if "custom-call(" in line and "tpu_custom_call" in line:
            op_name = re.search(r'op_name="([^"]*)"', line).group(1)
            inst = re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = ", line).group(1)
            out.append((op_name.split("/")[-2], op_name, inst))
    return out


def _retention_layer_text(one_chip, monkeypatch):
    """One layer's `power_retention` of the Brumby cell (16,384 positions,
    40 query heads over 8 state heads, d 128, bf16, chunks of 1,024),
    forward and backward with the chunk's recomputation, compiled for a
    described v5e."""
    if "retention" in _COMPILED:
        return _COMPILED["retention"]
    from paddle_tpu.ops.pallas import power_retention as kernels
    from paddle_tpu.ops.power_retention import power_retention
    # the described chip is not jax.default_backend(): the kernels are
    # asked for compiled here, where the program would interpret them
    monkeypatch.setattr(kernels, "_interpret_default", lambda: False)
    seq, heads, groups, d = 16384, 40, 8, 128

    def sds(*shape, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def loss(q, k, v, log_g):
        return jnp.sum(power_retention(q, k, v, log_g, 1024).astype(
            jnp.float32))

    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3))).lower(
        sds(1, seq, heads, d), sds(1, seq, groups, d), sds(1, seq, groups, d),
        sds(1, seq, groups, dt=jnp.float32)).compile().as_text()
    _COMPILED["retention"] = text
    return text


def test_retention_layer_compiles_and_no_expansion_reaches_memory(
        one_chip, monkeypatch):
    """The compiled text of one layer's retention names the three kernels
    as the catalog does, each under the scan's scope in the forward, the
    recomputation and the backward, and holds no array of an expansion's
    shape: neither the query side's bf16[5120,8320] nor the key side's
    bf16[1024,8320]."""
    from paddle_tpu.observability.catalog import KERNEL_NAMES
    text = _retention_layer_text(one_chip, monkeypatch)
    assert not re.search(r"\[(?:5120|1024),8320\]", text)
    calls = _kernel_calls(text)
    names = collections.Counter()
    for kernel, op_name, _ in calls:
        assert "pt.retn.scan" in op_name, op_name
        names[kernel] += 1
    assert set(names) == {n for n in KERNEL_NAMES if n.startswith("retn_")}
    # forward: read, write. Recomputed: read (the recomputed write feeds
    # nothing). Backward: back twice, write for the state's cotangent,
    # read for vw's
    assert names == {"retn_read": 3, "retn_write": 2, "retn_back": 2}
    assert any("transpose(jvp(pt.retn.scan))" in op for _, op, _ in calls)


def test_retention_kernels_run_in_the_passes_the_rule_gives_them(
        one_chip, monkeypatch):
    """catalog.py trace_pass on the same compiled layer: `retn_back` runs
    in the backward alone; `retn_read` once in each of the forward, the
    chunk's recomputation (inside the backward's loop: the pass holds
    through `while/body`) and the backward; `retn_write` in the forward
    and the backward (its recomputation feeds nothing). This is what
    `retention_scan_recompute_time_share` reads."""
    from paddle_tpu.observability.catalog import trace_pass
    passes = collections.defaultdict(collections.Counter)
    for kernel, op_name, inst in _kernel_calls(
            _retention_layer_text(one_chip, monkeypatch)):
        passes[kernel][trace_pass(op_name, inst)] += 1
    assert passes["retn_back"] == {"backward": 2}
    assert passes["retn_read"] == {"forward": 1, "recompute": 1,
                                   "backward": 1}
    assert passes["retn_write"] == {"forward": 1, "backward": 1}


def _rematerialised_loss(sub):
    """h, params -> a scalar through the sub-block's `_pure` under
    jax.checkpoint: with its value and gradients the forward, the
    recomputation and the backward are all in one compiled text (the
    gradient alone drops the first forward: nothing reads it)."""
    def loss(h_, p_):
        block = jax.checkpoint(lambda x: sub._pure(x, **p_))
        return jnp.sum(block(h_).astype(jnp.float32))
    return jax.value_and_grad(loss, argnums=(0, 1))


def _sub_block_operands(sub, hidden, one_chip):
    """(h (1, 16384, hidden), {parameter: shape}) of a sub-block, bf16 on
    the described chip."""
    names, tensors = zip(*sub.named_parameters())
    params = {n.replace(".", "_"): jax.ShapeDtypeStruct(
        tuple(t.shape), jnp.bfloat16, sharding=one_chip)
        for n, t in zip(names, tensors)}
    return jax.ShapeDtypeStruct((1, 16384, hidden), jnp.bfloat16,
                                sharding=one_chip), params


def _mellum_mixer_text(one_chip, monkeypatch, kind):
    """One Mellum mixer of `kind` (16,384 positions, 32 query heads over 4
    key-value heads, d 128, bf16, window 1,024; models/mellum.py
    MellumAttention) compiled for a described v5e, value and gradients
    through the sub-block's rematerialisation."""
    if kind in _COMPILED:
        return _COMPILED[kind]
    import paddle_tpu as paddle
    from paddle_tpu.models.mellum import MellumAttention, MellumConfig
    from paddle_tpu.ops.pallas import attention_router
    # the described chip is not jax.default_backend(): the rules (the
    # attention router's, models/rope.py's) and the kernels' interpret
    # switch are told it is
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    attention_router.clear_routing_cache()
    cfg = MellumConfig(num_hidden_layers=2, layer_types=[
        "sliding_attention", "full_attention"], vocab_size=256,
        dtype="bfloat16")
    with paddle.LazyGuard():
        sub = MellumAttention(cfg, kind)
    _COMPILED[kind] = jax.jit(_rematerialised_loss(sub)).lower(
        *_sub_block_operands(sub, cfg.hidden_size, one_chip)
    ).compile().as_text()
    attention_router.clear_routing_cache()
    return _COMPILED[kind]


def _mellum_mixer_calls(one_chip, monkeypatch, kind):
    """The Pallas calls of that mixer."""
    return _kernel_calls(_mellum_mixer_text(one_chip, monkeypatch, kind))


def _brumby_mixer_text(one_chip, monkeypatch):
    """One retention mixer of the Brumby cell (16,384 positions, 40 query
    heads over 8 state heads, d 128, bf16; models/brumby.py
    BrumbyRetention) compiled the same way."""
    if "brumby_mixer" in _COMPILED:
        return _COMPILED["brumby_mixer"]
    import paddle_tpu as paddle
    from paddle_tpu.models.brumby import BrumbyConfig, BrumbyRetention
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = BrumbyConfig(num_hidden_layers=1, vocab_size=256, dtype="bfloat16")
    with paddle.LazyGuard():
        sub = BrumbyRetention(cfg)
    _COMPILED["brumby_mixer"] = jax.jit(_rematerialised_loss(sub)).lower(
        *_sub_block_operands(sub, cfg.hidden_size, one_chip)
    ).compile().as_text()
    return _COMPILED["brumby_mixer"]


def test_mellum_mixers_compile_with_their_kernels_under_their_scopes(
        one_chip, monkeypatch):
    """One window layer's and one full layer's mixer of the Mellum cell,
    forward and backward with the sub-block's recomputation, for a
    described v5e. The window layer's kernels are the faw_* ones and the
    full layer's the fa_* ones, as the catalog names them, and every call
    is under pt.attn/pt.attn.<kind>: the cell's `attn_window_*` and
    `attn_full_*` metrics tell the kinds apart by these names."""
    from paddle_tpu.observability.catalog import KERNEL_NAMES
    seen = {}
    for kind in ("sliding_attention", "full_attention"):
        seen[kind] = collections.Counter()
        # "pt.attn/pt.attn.<kind>", or with the transformation that
        # wrapped the outer scope, "jvp(pt.attn)/pt.attn.<kind>"
        scope = re.compile(r"pt\.attn\)*/pt\.attn\." + kind.split("_")[0])
        for kernel, op_name, _ in _mellum_mixer_calls(one_chip, monkeypatch,
                                                      kind):
            seen[kind][kernel] += 1
            # the backward's too: the rematerialised sub-block's transpose
            # is traced inside its scopes
            assert scope.search(op_name), op_name
    positions = {"normrope_fwd", "normrope_bwd"}      # models/rope.py
    assert set(seen["sliding_attention"]) - positions == {
        "faw_fwd", "faw_bwd_dq", "faw_bwd_dkv"}
    assert set(seen["full_attention"]) - positions == {
        "fa_fwd", "fa_bwd_dq", "fa_bwd_dkv"}
    assert (set(seen["sliding_attention"]) | set(seen["full_attention"])) \
        - positions == {n for n in KERNEL_NAMES if n.startswith("fa")}


@pytest.mark.parametrize("kind, prefix", [("sliding_attention", "faw"),
                                          ("full_attention", "fa")])
def test_mellum_mixer_kernels_run_in_the_passes_the_rule_gives_them(
        one_chip, monkeypatch, kind, prefix):
    """catalog.py trace_pass on the compiled mixers: the forward kernel is
    found once in the forward and once in the sub-block's recomputation
    (what `attn_window_recompute_time_share` reads, and why
    `attn_window_fwd_roofline` credits half of the kernel's time), the two
    backward kernels in the backward alone."""
    from paddle_tpu.observability.catalog import trace_pass
    passes = collections.defaultdict(collections.Counter)
    for kernel, op_name, inst in _mellum_mixer_calls(one_chip, monkeypatch,
                                                     kind):
        passes[kernel][trace_pass(op_name, inst)] += 1
    assert passes[prefix + "_fwd"] == {"forward": 1, "recompute": 1}
    assert passes[prefix + "_bwd_dq"] == {"backward": 1}
    assert passes[prefix + "_bwd_dkv"] == {"backward": 1}


_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\) -> .* \{\s*$")
_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%?[\w.\-]+ = (.+?) ([\w\-]+)\(")
_CALLED = re.compile(r"(?:calls|to_apply|body|condition)=%?([\w.\-]+)")


def _matmul_fusions(text):
    """[(result shape, opcodes, op_names of its convolutions)] of every
    fusion of an optimised HLO text that holds a convolution, its
    computation's nested calls included; a fusion inside another is the
    outer one's."""
    bodies, current = {}, None
    for line in text.splitlines():
        head = _COMPUTATION.match(line)
        if head:
            current = bodies.setdefault(head.group(1), [])
        elif line.startswith("}"):
            current = None
        elif current is not None:
            m = _INSTRUCTION.match(line)
            if m:
                current.append((m.group(1), m.group(2), line))

    def walk(name, ops, convs):
        for _, opcode, line in bodies.get(name, ()):
            ops[opcode] += 1
            if opcode == "convolution":
                convs.append(re.search(r'op_name="([^"]*)"', line).group(1))
            for callee in _CALLED.findall(line):
                walk(callee, ops, convs)

    fused = {callee for body in bodies.values() for _, opcode, line in body
             if opcode == "fusion" for callee in _CALLED.findall(line)}
    out = []
    for name, body in bodies.items():
        if name in fused:
            continue
        for shape, opcode, line in body:
            if opcode == "fusion":
                ops, convs = collections.Counter(), []
                for callee in _CALLED.findall(line):
                    walk(callee, ops, convs)
                if convs:
                    out.append((shape, ops, convs))
    return out


# (id, tokens, positions, heads): the q and k projections of the Mellum
# cell (2 x 16,384 tokens, 32 and 4 heads) and of the Brumby cell (16,384
# tokens, 40 and 8 heads), d 128, bf16
NORMROPE_CASES = [
    ("mellum_q", 32768, 16384, 32), ("mellum_k", 32768, 16384, 4),
    ("brumby_q", 16384, 16384, 40), ("brumby_k", 16384, 16384, 8)]


@pytest.mark.parametrize("case", NORMROPE_CASES,
                         ids=[c[0] for c in NORMROPE_CASES])
def test_normrope_kernels_compile_at_the_cells_shapes(one_chip, case):
    """ops/pallas/rope_norm.py at the tiles it chooses: what the
    interpreter cannot refuse and Mosaic can is a lane rotation of a
    float32 tile, a block of eight heads' lanes beside two float32 table
    tiles inside Mosaic's default VMEM limit (the calls ask for no other:
    a tiling that needs more fails to compile here), and the weight's
    gradient accumulated over the inner grid axis."""
    from paddle_tpu.ops.pallas import rope_norm
    _, tokens, positions, heads = case
    d = 128

    def sds(*shape, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    x, w = sds(tokens, heads * d), sds(d)
    table = sds(positions, d, dt=jnp.float32)
    text = jax.jit(functools.partial(
        rope_norm.forward, eps=1e-6, interpret=False)).lower(
            x, w, table, table).compile().as_text()
    assert "tpu_custom_call" in text and "normrope_fwd" in text
    text = jax.jit(functools.partial(
        rope_norm.backward, eps=1e-6, interpret=False)).lower(
            x, w, table, table, x).compile().as_text()
    assert "tpu_custom_call" in text and "normrope_bwd" in text


_TOP_LEVEL = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = (.+?) ([\w\-]+)\(")


def _written_under(text, scope):
    """[(pass, opcode, result shape)] of the instructions of a compiled
    text that run as operations of their own (not inside a fusion) under
    `scope`."""
    from paddle_tpu.observability.catalog import trace_pass
    out, fused = [], False
    for line in text.splitlines():
        head = _COMPUTATION.match(line)
        if head:
            fused = "fused_computation" in head.group(1)
            continue
        op_name = re.search(r'op_name="([^"]*)"', line)
        m = _TOP_LEVEL.match(line)
        if fused or not m or not op_name or scope not in op_name.group(1):
            continue
        if m.group(3) not in ("parameter", "constant", "bitcast", "tuple",
                              "get-tuple-element"):
            out.append((trace_pass(op_name.group(1), m.group(1)),
                        m.group(3), m.group(2)))
    return out


def _float32_elements(shape):
    """The largest float32 array of a result shape, in elements."""
    return max([math.prod(int(n) for n in dims.split(",") if n)
                for dims in re.findall(r"f32\[([\d,]*)\]", shape)] or [0])


MIXERS = [("sliding_attention", "pt.attn.sliding/pt.attn.pos", 4),
          ("full_attention", "pt.attn.full/pt.attn.pos", 4),
          ("retention", "pt.retn.pos", 8)]


@pytest.mark.parametrize("kind, scope, kv_heads", MIXERS,
                         ids=[m[0] for m in MIXERS])
def test_the_position_part_is_the_two_kernels_and_no_float32_projection(
        one_chip, monkeypatch, kind, scope, kv_heads):
    """models/rope.py norm_rope in the compiled Mellum mixers and the
    Brumby mixer: under the part's scope `normrope_fwd` runs for q and for
    k in the forward and in the sub-block's recomputation, `normrope_bwd`
    for each in the backward alone (catalog.py trace_pass), and in no pass
    does an operation under that scope write a float32 array of a
    projection's size (k's, the smaller): before PR 37 XLA wrote q's three
    times a pass there. What is left beside the kernels is the tables
    (positions x 128 float32) and the weight's gradient."""
    from paddle_tpu.observability.catalog import trace_pass
    text = _brumby_mixer_text(one_chip, monkeypatch) if kind == "retention" \
        else _mellum_mixer_text(one_chip, monkeypatch, kind)
    passes = collections.defaultdict(collections.Counter)
    for kernel, op_name, inst in _kernel_calls(text):
        if kernel.startswith("normrope"):
            assert scope in op_name.replace("jvp(", "").replace(")", ""), \
                op_name
            passes[kernel][trace_pass(op_name, inst)] += 1
    assert passes == {"normrope_fwd": {"forward": 2, "recompute": 2},
                      "normrope_bwd": {"backward": 2}}
    written = _written_under(text, scope.split("/")[-1])
    assert {p for p, _, _ in written} == {"forward", "recompute", "backward"}
    for pass_, opcode, shape in written:
        assert _float32_elements(shape) < 16384 * kv_heads * 128, (
            pass_, opcode, shape)


def test_gelu_once_leaves_the_activation_in_one_fusion_a_layer(one_chip):
    """Two blocks of LayerNorm -> fc1 -> GELU -> fc2 at the GPT cell's
    widths, forward, backward and a donated update, compiled for a
    described v5e. With jax.nn.gelu (the control) only the pre-activation
    is kept and the activation is evaluated again inside the matmul
    fusions that read it: fc2's forward, fc2's weight gradient and, as its
    derivative, the epilogue of the gradient that flows back to fc1. With
    ops/gelu_once.py the `exponential` (of erfc there, of the density
    here) is in exactly one fusion a layer, the one that holds fc1's
    forward matmul and returns value and derivative, both
    `bf16[4,2048,8192]`."""
    from paddle_tpu.ops.gelu_once import gelu_once
    layers, hidden, inner = 2, 2048, 8192

    def sds(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)

    layer = {"scale": sds(hidden), "shift": sds(hidden),
             "w1": sds(hidden, inner), "b1": sds(inner),
             "w2": sds(inner, hidden), "b2": sds(hidden)}

    def step(act, params, x):
        def loss(params):
            h = x
            for i, p in enumerate(params):
                with jax.named_scope(f"layer{i}"):
                    mean = jnp.mean(h.astype(jnp.float32), -1, keepdims=True)
                    var = jnp.var(h.astype(jnp.float32), -1, keepdims=True)
                    n = ((h - mean) * jax.lax.rsqrt(var + 1e-5)).astype(
                        h.dtype) * p["scale"] + p["shift"]
                    h = h + act(n @ p["w1"] + p["b1"]) @ p["w2"] + p["b2"]
            return jnp.mean(h.astype(jnp.float32) ** 2)
        value, grads = jax.value_and_grad(loss)(params)
        return value, jax.tree_util.tree_map(
            lambda p, g: p - 1e-4 * g.astype(p.dtype), params, grads)

    def compiled(act):
        return _matmul_fusions(
            jax.jit(functools.partial(step, act), donate_argnums=0).lower(
                [layer] * layers, sds(4, 2048, hidden)).compile().as_text())

    once = [f for f in compiled(gelu_once) if f[1]["exponential"]]
    assert len(once) == layers, [(f[0], f[2]) for f in once]
    for shape, _, convs in once:
        assert shape.count(f"bf16[4,2048,{inner}]") == 2, shape
        # fc1's forward product, not a product of the backward
        assert len(convs) == 1 and "transpose(" not in convs[0], convs
    control = [f for f in compiled(
        lambda a: jax.nn.gelu(a, approximate=False)) if f[1]["exponential"]]
    assert len(control) >= 3 * layers, [(f[0], f[2]) for f in control]


@pytest.mark.parametrize("batch, seq, channels", [
    (1, 32768, 5120), (2, 1000, 640)], ids=["phi4flash_cell", "ragged"])
def test_selective_scan_kernels_compile_under_their_scope(
        one_chip, monkeypatch, batch, seq, channels):
    """ops/selective_scan.py with the kernels (ops/pallas/selective_scan.py)
    at the Phi-4-mini-flash cell's one layer (32,768 positions, 5,120
    channels, 16 states, bf16) and at a length that pads and a channel
    count that takes tiles of 128, forward and backward: what the
    interpreter cannot refuse and Mosaic can (a row of time loaded at a
    dynamic sublane offset, a lane taken from B and C by a one-hot select,
    the chunk's states indexed on the leading axis, the reversed grid).
    Both kernels under pt.ssm.sel, the backward's too."""
    from paddle_tpu.ops import selective_scan as op
    from paddle_tpu.ops.pallas import selective_scan as kernels
    # the described chip is not jax.default_backend(): the rule is steered
    # and the kernels asked for compiled
    monkeypatch.setattr(kernels, "supported", lambda u, a: True)
    monkeypatch.setattr(kernels, "_interpret_default", lambda: False)

    def sds(*shape, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def loss(u, delta, a, b, c, d, z, bias):
        return jnp.sum(op.selective_scan(u, delta, a, b, c, d, z, bias
                                         ).astype(jnp.float32))

    big, small = sds(batch, seq, channels), sds(batch, seq, 16)
    vec = sds(channels)
    text = jax.jit(jax.value_and_grad(loss, argnums=tuple(range(8)))).lower(
        big, big, sds(channels, 16, dt=jnp.float32), small, small, vec, big,
        vec).compile().as_text()
    calls = _kernel_calls(text)
    assert sorted(name for name, _, _ in calls) == ["selscan_bwd",
                                                    "selscan_fwd"]
    assert all("pt.ssm.sel" in op_name for _, op_name, _ in calls)
