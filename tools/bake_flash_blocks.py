"""Bake hardware autotune + A/B results into shipped tables.

Two outputs from one hardware session's artifacts:

1. Block-size literal (the original mode): turn `.flash_vs_xla.json`
   autotune spreads into a `_SHIPPED_BLOCKS` dict to paste into
   ops/pallas/flash_attention.py.  Winners whose margin over the
   (128, 128) baseline is under `MARGIN` are rejected (close timings
   mean noise ranked the candidates).

2. `--ledger [out.json]`: the **attention backend ledger** consumed by
   ops/pallas/attention_router.py — per (seq, head_dim, bh, causal,
   dtype) the measured fwd winner (pallas flash vs dense XLA) and bwd
   winner (FA-2 Pallas kernels vs dense-remat hybrid), with the raw ms
   on every row, plus end-to-end train A/B entries merged from
   `.bench_tpu_wins.jsonl` (rows carrying attention_backend +
   attention_bwd).  End-to-end entries outrank isolated rows in the
   router: r5 measured full-pallas bwd WINNING the 535m train step
   (0.4261 vs 0.4063 MFU) while losing isolated — HBM pressure from the
   O(S^2) remat buffer dominates.  The ledger is versioned
   (`ledger_format`) and device-tagged; the router ignores tables from
   other devices or formats.

Usage:
  python tools/bake_flash_blocks.py [path]               # blocks literal
  python tools/bake_flash_blocks.py [path] --ledger [out] [--round N]
(default path: .flash_vs_xla.json; default out:
 paddle_tpu/ops/pallas/attention_ledger.json)

Re-bake after every hardware session: run tools/flash_vs_xla.py on the
chip (through the chip tool; the table comes back under chiprun_out/),
then this with --ledger, and commit the JSON — every router call site
(nn/functional attention, flash bwd, incubate, serving, bench) picks the
new winners up at next import.
"""

import ast
import json
import os
import sys

MARGIN = 0.97  # winner must be <= 97% of baseline ms

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


def bake_blocks(path):
    """Print the _SHIPPED_BLOCKS literal (original mode)."""
    doc = _load(path)
    tuned = doc.get("autotuned_blocks", {})
    spreads = tuned.get("candidate_ms", {})

    print(f"# from {path} on {doc.get('device_kind')}")
    print("_SHIPPED_BLOCKS = {")
    best_bh = {}   # (kind, seq, d) -> (bh, win, note): prefer the largest bh
    for key, win in sorted(tuned.items()):
        if key == "candidate_ms" or isinstance(win, str):
            continue
        parts = key.split("_")   # fwd_s2048_d128[_bh64]
        kind, seq, d = parts[0], int(parts[1][1:]), int(parts[2][1:])
        bh = int(parts[3][2:]) if len(parts) > 3 else 0
        note = ""
        # find this key's spread: timing_log keys are the _tuned_blocks
        # cache tuples (kind, tb, sq, sk, d, dtype, causal, device) —
        # tb=min(bh,64)
        for sk, ms in spreads.items():
            try:
                tup = ast.literal_eval(sk)
            except Exception:
                continue
            if (tup[0] == kind and tup[2] == seq and tup[4] == d
                    and tup[1] == min(bh, 64)):
                base = ms.get("(128, 128)")
                bw = ms.get(str(tuple(win)))
                if base and bw:
                    if bw > base * MARGIN:
                        win = [128, 128]
                        note = (f"  # win over default <3% "
                                f"({bw} vs {base}ms)")
                    else:
                        note = f"  # {bw}ms vs default {base}ms"
                break
        if not note:
            # no timing spread to validate against (legacy JSON without
            # candidate_ms, or a bh-less key): this winner may be ranked by
            # noise — refuse to ship it, fall back to the default
            win = [128, 128]
            note = "  # UNVALIDATED winner (no candidate_ms spread) -> default"
        cur = best_bh.get((kind, seq, d))
        if cur is None or bh > cur[0]:
            best_bh[(kind, seq, d)] = (bh, win, note)
    for (kind, seq, d), (bh, win, note) in sorted(best_bh.items()):
        print(f'    ("{kind}", {seq}, {d}): {tuple(win)},{note}  # bh={bh}')
    print("}")


def _blocks_for(tuned, kind, seq, d):
    hit = tuned.get(f"{kind}_s{seq}_d{d}")
    return list(hit) if hit else None


def bake_ledger(path, round_num=None, wins_path=None):
    """-> the ledger dict for attention_router.py (caller writes it)."""
    doc = _load(path)
    tuned = doc.get("autotuned_blocks", {})
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
        entries.append({
            "seq": seq, "head_dim": d, "bh": bh, "causal": causal,
            "dtype": dtype,
            "fwd": min(fwd_ms, key=fwd_ms.get),
            "bwd": min(bwd_ms, key=bwd_ms.get),
            "fwd_ms": fwd_ms, "bwd_ms": bwd_ms,
            "max_abs_err": row.get("max_abs_err"),
            "blocks_fwd": _blocks_for(tuned, "fwd", seq, d),
            "blocks_bwd": _blocks_for(tuned, "bwd", seq, d),
        })

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
        "version": 1,
        "round": round_num,
        "device_kind": doc.get("device_kind"),
        "dtype": dtype,
        "generated_from": [os.path.basename(path)] + (
            [os.path.basename(wins_path)] if wins_path and
            os.path.exists(wins_path) else []),
        "kernel_note": ("isolated rows measured with the r5 f32-operand "
                        "kernels (since replaced by bf16-operand); "
                        "RE-BAKE from a fresh tools/flash_vs_xla.py run "
                        "at the next hardware session"),
        # flipped by the re-bake once a chip run shows the
        # triangle-packed causal grid lowers and matches (chip_smoke.py's
        # kernel phase reports it; ROADMAP S4 decides)
        "packed_grid_validated": False,
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
    ledger_out = None
    if "--ledger" in args:
        i = args.index("--ledger")
        if i + 1 < len(args) and not args[i + 1].startswith("-"):
            ledger_out = args[i + 1]
            del args[i:i + 2]
        else:
            ledger_out = os.path.join(REPO, "paddle_tpu", "ops", "pallas",
                                      "attention_ledger.json")
            del args[i]
    path = args[0] if args else os.path.join(REPO, ".flash_vs_xla.json")
    if ledger_out:
        wins = os.path.join(REPO, ".bench_tpu_wins.jsonl")
        led = bake_ledger(path, round_num=round_num, wins_path=wins)
        with open(ledger_out, "w") as f:
            json.dump(led, f, indent=1, sort_keys=False)
            f.write("\n")
        print(f"wrote {ledger_out}: {len(led['entries'])} measured entries, "
              f"{len(led['end_to_end'])} end-to-end entries "
              f"(device {led['device_kind']}, round {led['round']})")
    else:
        bake_blocks(path)


if __name__ == "__main__":
    main(sys.argv)
