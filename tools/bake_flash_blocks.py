"""Bake a hardware A/B session into the attention backend ledger.

`tools/flash_vs_xla.py` on the chip writes a table of the flash kernels
against dense XLA; this turns it into the **attention backend ledger**
consumed by ops/pallas/attention_router.py — per (seq, head_dim, bh,
causal, dtype) the measured fwd winner (pallas flash vs dense XLA) and bwd
winner (FA-2 Pallas kernels vs dense-remat hybrid), with the raw ms on
every row, plus end-to-end train A/B entries merged from
`.bench_tpu_wins.jsonl` (rows carrying attention_backend +
attention_bwd).  End-to-end entries outrank isolated rows in the router:
r5 measured full-pallas bwd WINNING the 535m train step (0.4261 vs 0.4063
MFU) while losing isolated — HBM pressure from the O(S^2) remat buffer
dominates.  The ledger is versioned (`ledger_format`) and device-tagged;
the router ignores tables from other devices or formats.

The ledger ranks BACKENDS and nothing else. Tile sizes are not baked: the
kernels take them from flash_attention.choose_tiles, from the shape (the
rows this tool used to write, `blocks_fwd`/`blocks_bwd`, had been measured
on kernels two generations gone and overrode the chooser; PERF.md section
6, PR 27).

Usage:
  python tools/bake_flash_blocks.py [path] [--ledger out] [--round N]
(default path: .flash_vs_xla.json; default out:
 paddle_tpu/ops/pallas/attention_ledger.json)

Re-bake after every hardware session: run tools/flash_vs_xla.py on the
chip (through the chip tool; the table comes back under chiprun_out/),
copy it to .flash_vs_xla.json, run this, and commit both — every router
call site (nn/functional attention, flash bwd, incubate, serving, bench)
picks the new winners up at next import.
"""

import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# bench ladder configs -> (num_heads, head_dim); needed to key end-to-end
# ledger rows from .bench_tpu_wins.jsonl details (which record config
# name + batch + seq but not the head split)
_LADDER_HEADS = {
    "llama_535m": (16, 128),
    "llama_780m": (16, 96),
    "llama_1.3b": (16, 128),
    "llama_1.3b_small_batch": (16, 128),
}


def _load(path):
    return json.load(open(path))


def bake_ledger(path, round_num=None, wins_path=None):
    """-> the ledger dict for attention_router.py (caller writes it)."""
    doc = _load(path)
    dtype = doc.get("dtype", "bfloat16")
    causal = bool(doc.get("causal", True))
    entries = []
    for row in doc.get("rows", []):
        seq, d = row["seq"], row["head_dim"]
        bh = row["batch"] * row["heads"]
        # fwd: flash kernel vs dense einsum, straight ms comparison
        fwd_ms = {"pallas": row["flash_fwd_ms"], "xla": row["dense_fwd_ms"]}
        # bwd GIVEN a flash fwd: FA-2 Pallas kernels vs dense-remat
        # hybrid — the fwd+bwd totals share the same flash forward, so
        # the total ordering IS the backward ordering
        bwd_ms = {"pallas": row["fwdbwd_ms_pallas"],
                  "xla": row["fwdbwd_ms_hybrid"]}
        entry = {
            "seq": seq, "head_dim": d, "bh": bh, "causal": causal,
            "dtype": dtype,
            "fwd": min(fwd_ms, key=fwd_ms.get),
            "bwd": min(bwd_ms, key=bwd_ms.get),
            "fwd_ms": fwd_ms, "bwd_ms": bwd_ms,
            "max_abs_err": row.get("max_abs_err"),
        }
        entries.append(entry)

    e2e = []
    if wins_path and os.path.exists(wins_path):
        # group hardware train rows by (config, batch, seq); a config that
        # was measured under BOTH bwd modes yields a real A/B — record the
        # winner.  Singletons still ship (they are the only e2e evidence).
        by_cfg = {}
        with open(wins_path) as f:
            for line in f:
                try:
                    obj = json.loads(line)
                except Exception:
                    continue
                if not isinstance(obj, dict) or \
                        obj.get("metric") != "llama_train_mfu_1chip":
                    continue
                det = obj.get("detail") or {}
                cfg = det.get("config")
                if cfg not in _LADDER_HEADS or \
                        det.get("attention_backend") != "pallas_flash":
                    continue
                by_cfg.setdefault((cfg, det.get("batch"),
                                   det.get("seq")), []).append(obj)
        for (cfg, batch, seq), rows in sorted(by_cfg.items()):
            heads, d = _LADDER_HEADS[cfg]
            best = max(rows, key=lambda o: o.get("value") or 0)
            det = best["detail"]
            bwd = str(det.get("attention_bwd", "pallas"))
            bwd = {"auto:pallas": "pallas", "auto:xla": "xla"}.get(bwd, bwd)
            mfu = {str(o["detail"].get("attention_bwd")):
                   o.get("value") for o in rows}
            e2e.append({
                "config": cfg, "seq": seq, "head_dim": d,
                "bh": batch * heads, "causal": True, "dtype": "bfloat16",
                "fwd": "pallas", "bwd": bwd, "mfu": mfu,
                "round": best.get("round"),
                "note": ("end-to-end train-step winner; no dense-XLA "
                         "end-to-end row was measured beside it"),
            })

    return {
        "ledger_format": 1,
        "version": 2,
        "round": round_num,
        "device_kind": doc.get("device_kind"),
        "dtype": dtype,
        "generated_from": [os.path.basename(path)] + (
            [os.path.basename(wins_path)] if wins_path and
            os.path.exists(wins_path) else []),
        "kernel_note": ("isolated rows measured with the two-level-tile "
                        "bf16-operand kernels (one causal sweep, tiles "
                        "from flash_attention.choose_tiles); the rows "
                        "rank backends only, tiles are never baked"),
        "entries": entries,
        "end_to_end": e2e,
    }


def main(argv):
    args = list(argv[1:])
    round_num = None
    if "--round" in args:
        i = args.index("--round")
        round_num = int(args[i + 1])
        del args[i:i + 2]
    ledger_out = os.path.join(REPO, "paddle_tpu", "ops", "pallas",
                              "attention_ledger.json")
    if "--ledger" in args:
        i = args.index("--ledger")
        if i + 1 < len(args) and not args[i + 1].startswith("-"):
            ledger_out = args[i + 1]
            del args[i:i + 2]
        else:
            del args[i]
    path = args[0] if args else os.path.join(REPO, ".flash_vs_xla.json")
    wins = os.path.join(REPO, ".bench_tpu_wins.jsonl")
    led = bake_ledger(path, round_num=round_num, wins_path=wins)
    with open(ledger_out, "w") as f:
        json.dump(led, f, indent=1, sort_keys=False)
        f.write("\n")
    print(f"wrote {ledger_out}: {len(led['entries'])} measured entries, "
          f"{len(led['end_to_end'])} end-to-end entries "
          f"(device {led['device_kind']}, round {led['round']})")


if __name__ == "__main__":
    main(sys.argv)
