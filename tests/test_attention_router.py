"""Per-shape attention backend router: ledger dispatch, provenance,
measurement fallback, and numeric parity across all three backends.

reference capability: paddle/phi/kernels/autotune/ (per-signature
algorithm selection) generalized to backend selection; the baked ledger
is generated by tools/bake_flash_blocks.py --ledger from real hardware
artifacts (.flash_vs_xla.json + .bench_tpu_wins.jsonl).
"""

import json
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops.pallas import attention_router as ar
from paddle_tpu.ops.pallas import flash_attention as fa

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
V5E = "TPU v5 lite"


@pytest.fixture(autouse=True)
def _fresh_router():
    ar.clear_routing_cache()
    yield
    from paddle_tpu.framework import flags as _flags
    _flags.set_flags({"FLAGS_attention_ledger_path": "",
                      "FLAGS_attention_router": "auto"})
    ar.clear_routing_cache()


# The hardware A/B shapes (rows of .flash_vs_xla.json) with the winners
# the v5e measured (PR 27, the two-level-tile kernels: flash wins every
# row, forward 2.1-10x and forward+backward 2.6-6x over dense; round 5's
# f32-operand kernels had lost three of the four) — the router MUST
# reproduce these from the shipped ledger (acceptance criterion: routed
# choice == measured winner, asserted from the recorded table).
R5_SHAPES = [
    # (bh, seq, head_dim, fwd_winner, bwd_winner)
    (128, 1024, 128, "pallas", "pallas"),
    (32, 2048, 128, "pallas", "pallas"),
    (8, 4096, 128, "pallas", "pallas"),
    (32, 2048, 96, "pallas", "pallas"),
]


class TestShippedLedger:
    def test_ledger_loads_and_is_versioned(self):
        led = ar.load_ledger()
        assert led is not None, "shipped attention_ledger.json must parse"
        assert led["ledger_format"] == ar.LEDGER_FORMAT
        assert led["device_kind"] == V5E
        assert led["round"] == 27
        assert len(led["entries"]) == 4
        # the ledger regenerates byte-identically from the artifacts
        sys.path.insert(0, os.path.join(REPO, "tools"))
        try:
            import bake_flash_blocks as bake
        finally:
            sys.path.pop(0)
        regen = bake.bake_ledger(
            os.path.join(REPO, ".flash_vs_xla.json"), round_num=27,
            wins_path=os.path.join(REPO, ".bench_tpu_wins.jsonl"))
        shipped = json.load(open(os.path.join(
            REPO, "paddle_tpu", "ops", "pallas", "attention_ledger.json")))
        assert regen == shipped

    @pytest.mark.parametrize("bh,seq,d,fwd,bwd", R5_SHAPES)
    def test_routed_choice_matches_measured_winner(self, bh, seq, d, fwd,
                                                   bwd):
        dec = ar.route(bh, seq, seq, d, "bfloat16", True,
                       platform="tpu", device_kind=V5E)
        assert dec.fwd == fwd and dec.bwd == bwd
        assert dec.source == "ledger"
        # provenance carries the raw measurements for audit
        assert "measured on TPU v5 lite" in dec.provenance
        led = ar.load_ledger()
        row = next(e for e in led["entries"]
                   if e["seq"] == seq and e["head_dim"] == d)
        assert str(row["fwd_ms"][fwd]) in dec.provenance

    def test_end_to_end_entry_outranks_isolated(self):
        """The 535m train shape (bh=64 s2048 d128, the benchmark's training
        cell too): round 5's isolated timing said dense fwd + hybrid bwd
        while the end-to-end hardware A/B measured full-pallas winning
        (0.4261 vs 0.4063 MFU) — the e2e entry takes priority at its exact
        shape."""
        dec = ar.route(64, 2048, 2048, 128, "bfloat16", True,
                       platform="tpu", device_kind=V5E)
        assert dec.fwd == "pallas" and dec.bwd == "pallas"
        assert dec.source == "ledger-e2e"
        assert "llama_535m" in dec.provenance
        # the 780m shape (d 96 padded to 128): round 5's end-to-end row
        # said hybrid backward on kernels that no longer exist; PR 27's A/B
        # with today's measured the Pallas backward at 0.5775 MFU against
        # the hybrid's 0.4137, and the row carries both readings
        dec = ar.route(128, 2048, 2048, 96, "bfloat16", True,
                       platform="tpu", device_kind=V5E)
        assert (dec.fwd, dec.bwd) == ("pallas", "pallas")
        assert dec.source == "ledger-e2e"
        assert "llama_780m" in dec.provenance
        row = next(e for e in ar.load_ledger()["end_to_end"]
                   if e["config"] == "llama_780m")
        assert row["mfu"]["pallas"] > row["mfu"]["xla"] > 0

    def test_ledger_ignored_on_other_device(self):
        dec = ar.route(32, 2048, 2048, 128, "bfloat16", True,
                       platform="cpu", device_kind="TPU v6e")
        assert dec.source != "ledger"

    def test_ledger_rows_carry_no_tiles(self):
        """Tiles come from flash_attention.choose_tiles alone: the rows
        rank backends, and a tile size measured on one generation of
        kernels must not override the chooser of the next."""
        led = ar.load_ledger()
        for row in led["entries"] + led["end_to_end"]:
            assert not {"blocks_fwd", "blocks_bwd"} & set(row), row
        assert "packed_grid_validated" not in led


class TestLedgerRoundTrip:
    """bake -> write -> load -> dispatch, through a tmp ledger selected
    via FLAGS_attention_ledger_path (the satellite round-trip case)."""

    def test_bake_load_dispatch(self, tmp_path):
        from paddle_tpu.framework import flags as _flags
        sys.path.insert(0, os.path.join(REPO, "tools"))
        try:
            import bake_flash_blocks as bake
        finally:
            sys.path.pop(0)
        src = {
            "device_kind": "TestChip", "dtype": "float32", "causal": True,
            "rows": [{"seq": 256, "batch": 2, "heads": 2, "head_dim": 64,
                      "flash_fwd_ms": 1.0, "dense_fwd_ms": 2.0,
                      "fwdbwd_ms_pallas": 3.0, "fwdbwd_ms_hybrid": 2.5,
                      "max_abs_err": 0.001}],
            "autotuned_blocks": {"fwd_s256_d64": [128, 128]},
        }
        p = tmp_path / "ab.json"
        p.write_text(json.dumps(src))
        led = bake.bake_ledger(str(p), round_num=99)
        out = tmp_path / "ledger.json"
        out.write_text(json.dumps(led))

        _flags.set_flags({"FLAGS_attention_ledger_path": str(out)})
        ar.clear_routing_cache()
        dec = ar.route(4, 256, 256, 64, "float32", True,
                       platform="tpu", device_kind="TestChip")
        assert (dec.fwd, dec.bwd) == ("pallas", "xla")  # 1.0<2.0; 2.5<3.0
        assert dec.source == "ledger"
        assert "r99" in dec.provenance

    def test_wrong_format_version_fails_open(self, tmp_path):
        from paddle_tpu.framework import flags as _flags
        bad = tmp_path / "bad.json"
        bad.write_text(json.dumps({"ledger_format": 999, "entries": []}))
        _flags.set_flags({"FLAGS_attention_ledger_path": str(bad)})
        ar.clear_routing_cache()
        assert ar.load_ledger() is None
        dec = ar.route(32, 2048, 2048, 128, "bfloat16", True,
                       platform="cpu", device_kind="cpu")
        assert dec.source in ("proxy", "heuristic")


class TestMeasurementFallback:
    """Forced ledger miss exercises the deterministic CPU fallback."""

    def test_miss_routes_through_proxy_deterministically(self):
        key = (4, 640, 640, 64, "float32", True)
        d1 = ar.route(*key, platform="cpu", device_kind="cpu")
        ar.clear_routing_cache()
        d2 = ar.route(*key, platform="cpu", device_kind="cpu")
        assert d1 == d2                      # no clocks, no randomness
        assert d1.source == "proxy"
        assert "NOT a measurement" in d1.provenance
        # the proxy prefers the flash fwd for causal self-attn (the sweep
        # visits half the pairs, no O(S^2) traffic) — the bf16-kernel
        # hypothesis
        assert d1.fwd == "pallas"

    def test_proxy_math_matches_decision(self):
        bh, s, d = 4, 640, 64
        est = {b: ar._proxy_ms("fwd", bh, s, s, d, "float32", True, b)
               for b in ("pallas", "xla")}
        dec = ar.route(bh, s, s, d, "float32", True, platform="cpu",
                       device_kind="cpu")
        assert dec.fwd == min(est, key=est.get)

    def test_ledger_mode_never_measures(self):
        from paddle_tpu.framework import flags as _flags
        _flags.set_flags({"FLAGS_attention_router": "ledger"})
        ar.clear_routing_cache()
        dec = ar.route(4, 640, 640, 64, "float32", True, platform="cpu",
                       device_kind="cpu")
        assert dec.source == "heuristic"

    def test_decision_log_records(self):
        ar.route(4, 640, 640, 64, "float32", True, platform="cpu",
                 device_kind="cpu")
        log = ar.decision_log()
        assert log and log[-1][0][:2] == (4, 640)


class TestBackendParity:
    """Numeric parity across the three dispatchable backends on the r5
    production shape set (scaled bh for interpreter speed; the (seq, d)
    geometry is the production one for the not-slow subset's s1024)."""

    def _parity(self, bh, sq, d, dtype, tol):
        rs = np.random.RandomState(1)
        q = jnp.asarray(rs.randn(bh, sq, d), dtype)
        k = jnp.asarray(rs.randn(bh, sq, d), dtype)
        v = jnp.asarray(rs.randn(bh, sq, d), dtype)
        scale = d ** -0.5

        def loss_flash(q_, k_, v_):
            return jnp.sum(fa._flash_attention_bhsd(q_, k_, v_, True,
                                                    scale) ** 2)

        def loss_dense(q_, k_, v_):
            return jnp.sum(fa._xla_attention_bhsd(q_, k_, v_, True,
                                                  scale) ** 2)

        from paddle_tpu.framework import flags as _flags
        outs = {}
        for mode in ("pallas", "xla"):      # bwd backends behind flash fwd
            old = _flags.flag_value("flash_attention_bwd")
            _flags.set_flags({"FLAGS_flash_attention_bwd": mode})
            try:
                outs[f"hybrid_{mode}"] = jax.grad(
                    loss_flash, argnums=(0, 1, 2))(q, k, v)
            finally:
                _flags.set_flags({"FLAGS_flash_attention_bwd": old})
        outs["dense"] = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
        base = outs.pop("dense")
        for name, g in outs.items():
            for a, b, nm in zip(g, base, "qkv"):
                np.testing.assert_allclose(
                    np.asarray(a, np.float32), np.asarray(b, np.float32),
                    rtol=tol, atol=tol, err_msg=f"{name} d{nm}")

    def test_parity_production_geometry_s1024(self):
        # full production (seq, d) at reduced bh — the three backends
        # (full-pallas, hybrid, dense) agree fwd+bwd
        self._parity(1, 1024, 128, jnp.float32, 2e-3)

    @pytest.mark.slow
    @pytest.mark.parametrize("bh,sq,d", [(2, 2048, 128), (2, 2048, 96),
                                         (1, 4096, 128)])
    def test_parity_full_r5_shape_set(self, bh, sq, d):
        self._parity(bh, sq, d, jnp.float32, 2e-3)


class TestDecisionCarriesTiles:
    """Every Decision names the tiles the flash kernels take at its shape
    and the grid steps one call of each makes (decision_log() surfaces
    them): the count that says the two-level tiles engaged."""

    @pytest.mark.parametrize("mode,kw", [
        ("auto", dict(platform="tpu", device_kind=V5E)),     # ledger-e2e
        ("auto", dict(platform="cpu", device_kind="cpu")),   # proxy
        ("heuristic", dict(platform="cpu", device_kind="cpu")),
    ])
    def test_every_source_carries_them(self, mode, kw):
        from paddle_tpu.framework import flags as _flags
        _flags.set_flags({"FLAGS_attention_router": mode})
        ar.clear_routing_cache()
        dec = ar.route(64, 2048, 2048, 128, "bfloat16", True, **kw)
        assert dec.tiles == fa.choose_tiles(2048, 2048, 128, 2)
        assert dec.grid_steps == dec.tiles.grid_steps(64, 2048, 2048)
        assert max(dec.grid_steps.values()) <= 1024

    def test_padded_head_dim_and_cross_length(self):
        # head_dim 96 rides zero-padded to the lane width in the kernels
        dec = ar.route(8, 300, 1000, 96, "float32", True, platform="cpu",
                       device_kind="cpu")
        assert dec.tiles == fa.choose_tiles(300, 1000, 128, 4)
        res_q, streamed_k, _ = dec.tiles.fwd
        assert dec.grid_steps["fa_fwd"] == (
            8 * -(-300 // res_q) * -(-1000 // streamed_k))


class TestFusedEpilogue:
    """The rmsnorm(attn+residual)*gamma epilogue fused into the flash
    flush must match the unfused composition exactly (incl. zero-padded
    head dims, where the mean divisor is the TRUE d)."""

    @pytest.mark.parametrize("d", [32, 48])   # 48 exercises lane padding
    def test_matches_unfused(self, d):
        rs = np.random.RandomState(3)
        b, s, h = 1, 200, 2
        q = jnp.asarray(rs.randn(b, s, h, d), jnp.float32)
        k = jnp.asarray(rs.randn(b, s, h, d), jnp.float32)
        v = jnp.asarray(rs.randn(b, s, h, d), jnp.float32)
        res = jnp.asarray(rs.randn(b, s, h, d), jnp.float32)
        w = jnp.asarray(rs.randn(d), jnp.float32)
        fused = fa.flash_attention_rms_epilogue_bshd(q, k, v, res, w)
        att = fa.flash_attention_bshd(q, k, v, causal=True)
        hh = att + res
        ref = hh * jax.lax.rsqrt(
            jnp.mean(hh * hh, axis=-1, keepdims=True) + 1e-6) * w
        np.testing.assert_allclose(np.asarray(fused), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_incubate_functional_unfused_path(self):
        import paddle_tpu as paddle
        from paddle_tpu.incubate.nn import functional as IF
        rs = np.random.RandomState(4)
        b, s, h, kvh, d = 1, 64, 4, 2, 16    # GQA
        q = paddle.Tensor(jnp.asarray(rs.randn(b, s, h, d), jnp.float32))
        k = paddle.Tensor(jnp.asarray(rs.randn(b, s, kvh, d), jnp.float32))
        v = paddle.Tensor(jnp.asarray(rs.randn(b, s, kvh, d), jnp.float32))
        res = paddle.Tensor(jnp.asarray(rs.randn(b, s, h, d), jnp.float32))
        w = paddle.Tensor(jnp.asarray(rs.randn(d), jnp.float32))
        out = IF.fused_attention_rms_epilogue(q, k, v, res, w)
        assert list(out.shape) == [b, s, h, d]
        # parity vs the fused kernel run directly (expanded-kv equal math)
        fused = fa.flash_attention_rms_epilogue_bshd(
            q._data, k._data, v._data, res._data, w._data)
        np.testing.assert_allclose(np.asarray(out._data), np.asarray(fused),
                                   rtol=2e-4, atol=2e-4)

    def test_epilogue_fusion_ledger_gate(self):
        # shipped ledger has no fused_epilogue_wins rows -> never selected
        assert ar.epilogue_fusion_wins(32, 2048, 2048, 128, "bfloat16",
                                       True, device_kind=V5E) is False


class TestSdpaRouting:
    """nn.functional sdpa consults the router for its auto decision."""

    def test_use_pallas_follows_router(self, monkeypatch):
        from paddle_tpu.nn.functional import attention as attn
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        calls = {}

        def fake_route(bh, sq, sk, d, dtype, causal, **kw):
            calls["key"] = (bh, sq, sk, d, str(dtype), causal)
            return ar.Decision(fwd="pallas", bwd="pallas")
        monkeypatch.setattr(ar, "route", fake_route)
        assert attn._use_pallas((2, 512, 4, 64), 64, False,
                                dtype="bfloat16", causal=True) is True
        assert calls["key"] == (8, 512, 512, 64, "bfloat16", True)

        monkeypatch.setattr(
            ar, "route",
            lambda *a, **kw: ar.Decision(fwd="xla", bwd="xla"))
        assert attn._use_pallas((2, 512, 4, 64), 64, False,
                                dtype="bfloat16", causal=True) is False

    def test_bias_still_forces_dense(self):
        from paddle_tpu.nn.functional import attention as attn
        assert attn._use_pallas((2, 2048, 4, 128), 128, True) is False
