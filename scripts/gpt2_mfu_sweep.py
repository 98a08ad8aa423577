#!/usr/bin/env python
"""GPT-2 round MFU sweep: remat policy x microbatch x lm_chunk.

The committed sweep behind VERDICT round-5 "Next round" item 4: the
flagship GPT-2 sketched round sits at 33% MFU (BENCH_r05, flat since
r04), and runs/BREAKDOWN_gpt2.md attributes the gap to the model side —
the bare fwd+bwd at the same config measures ~31% MFU under full remat
(scripts/bench_gpt2_model.py), so the target MFU >= 0.40 is reachable
ONLY by cutting backward recompute (remat policy) or reshaping the
microbatch scan, not by shaving the ~75 ms of federated slices. The two
endpoints are already measured and committed:

- remat=False: compiles post-fused-clients but is SLOWER (69.3k vs
  76.5k tok/s) — saved-activation HBM traffic beats the recompute FLOPs;
- dots_with_no_batch_dims_saveable: catastrophic under the fused round
  (3.1k tok/s, r4) — excluded from the default arm set on purpose.

What was NEVER measured is the middle ground this sweep covers:
``dots_saveable`` (save matmul outputs, recompute elementwise),
microbatch 2/4 (smaller live set => more savable activations per step),
and the chunked-CE granularity 64/256 (chunk loop count vs live logits).
Each arm is one `bench_gpt2.run(...)` — same round, same analytic-FLOPs
MFU definition, retry-wrapped — and lands as one JSON line in the
output file as it finishes (a dead arm costs itself, not the sweep).

Run on the TPU runtime (each arm recompiles; the persistent compile
cache makes repeats cheap):

    python scripts/gpt2_mfu_sweep.py --out runs/gpt2_mfu_sweep.jsonl
    python scripts/gpt2_mfu_sweep.py --arms base,mb4,policy_dots

The verdict rule the sweep encodes: if no arm reaches MFU >= 0.40, the
best arm + the committed endpoint measurements above constitute the
trace-level ceiling proof (the remat recompute is the floor, and every
policy between full remat and none loses more to HBM traffic than it
saves in FLOPs) — recorded in runs/BREAKDOWN_gpt2.md either way.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import traceback

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# arm name -> bench_gpt2.run keyword overrides (base = shipping config:
# full remat, microbatch 8, lm_chunk 128)
ARMS = {
    "base": {},
    # the PR-9 A/B: base now runs the FUSED sketch encode (the
    # microbatch scan carries the table; --sketch_fused_encode auto);
    # this arm forces the pre-fusion round whose ledger documents the
    # dense (d,) gradient materialization — the temp_bytes delta
    # between the two is the committed proof the floor moved
    # (runs/BREAKDOWN_gpt2.md §Round 7)
    "unfused_encode": {"fused_encode": "off"},
    "no_remat": {"remat": False},
    "policy_dots": {"remat_policy": "dots_saveable"},
    "mb4": {"microbatch": 4},
    "mb2": {"microbatch": 2},
    "chunk64": {"lm_chunk": 64},
    "chunk256": {"lm_chunk": 256},
    "mb4_chunk256": {"microbatch": 4, "lm_chunk": 256},
    "policy_dots_mb4": {"remat_policy": "dots_saveable", "microbatch": 4},
    # the measured-catastrophic policy (3.1k tok/s at r4) — opt-in only,
    # kept so the endpoint stays reproducible: --arms +policy_nobatch
    "policy_nobatch": {"remat_policy": "dots_with_no_batch_dims_saveable"},
}
DEFAULT_ARMS = [a for a in ARMS if a != "policy_nobatch"]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", default="runs/gpt2_mfu_sweep.jsonl",
                    help="JSONL output, one line per arm as it finishes")
    ap.add_argument("--arms", default="",
                    help="comma-separated arm names (default: all except "
                         "policy_nobatch); prefix an arm with + to ADD it "
                         "to the default set")
    ap.add_argument("--rounds", type=int, default=8,
                    help="timed rounds per arm")
    ap.add_argument("--compile_cache", default=None,
                    help="persistent XLA compile cache DIR (unset: the "
                         "config default — strongly recommended, every "
                         "arm recompiles the round; empty string "
                         "disables)")
    ap.add_argument("--dryrun", action="store_true",
                    help="run every arm at smoke scale (GPT2Config.small"
                         ", tiny round) so the sweep completes on the "
                         "CPU container: exercises the sweep mechanics "
                         "and records live roofline/memory-ledger "
                         "fields per arm, but the throughput numbers "
                         "are NOT the flagship measurement — each line "
                         "carries dryrun: true")
    ap.add_argument("--ledger_ab", action="store_true",
                    help="append the compile-only fused-vs-unfused "
                         "cohort-ledger A/B at a parameter-dominated "
                         "GPT-2 geometry (bench_gpt2.ledger_ab) — the "
                         "committed dense-gradient-floor proof for "
                         "runs/BREAKDOWN_gpt2.md §Round 7; honors "
                         "--dryrun")
    args = ap.parse_args(argv)

    import bench_gpt2
    from bench_common import log

    names = list(DEFAULT_ARMS)
    if args.arms:
        adds = [a[1:] for a in args.arms.split(",") if a.startswith("+")]
        picks = [a for a in args.arms.split(",") if not a.startswith("+")]
        if picks:
            names = picks
        names += [a for a in adds if a not in names]
    unknown = [a for a in names if a not in ARMS]
    if unknown:
        ap.error(f"unknown arms {unknown}; known: {sorted(ARMS)}")

    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    results = []
    with open(args.out, "a") as f:
        for name in names:
            log(f"=== arm {name}: {ARMS[name] or 'shipping config'}")
            rec = {"arm": name, **{"overrides": ARMS[name]}}
            if args.dryrun:
                rec["dryrun"] = True
            try:
                rec["result"] = bench_gpt2.run(
                    n_rounds=args.rounds, dryrun=args.dryrun,
                    compile_cache=args.compile_cache, **ARMS[name])
            except Exception as e:
                log(traceback.format_exc())
                rec["error"] = f"{type(e).__name__}: {e}"
            # one fsync'd line per arm: a crash mid-sweep keeps every
            # finished measurement (the bench resilience contract)
            f.write(json.dumps(rec) + "\n")
            f.flush()
            os.fsync(f.fileno())
            results.append(rec)
        if args.ledger_ab:
            log("=== ledger_ab: compile-only fused-vs-unfused cohort "
                "ledgers (parameter-dominated geometry)")
            rec = {"arm": "ledger_ab"}
            if args.dryrun:
                rec["dryrun"] = True
            try:
                rec["result"] = bench_gpt2.ledger_ab(dryrun=args.dryrun)
            except Exception as e:
                log(traceback.format_exc())
                rec["error"] = f"{type(e).__name__}: {e}"
            f.write(json.dumps(rec) + "\n")
            f.flush()
            os.fsync(f.fileno())

    ok = [r for r in results if r.get("result", {}).get("mfu") is not None]
    if ok:
        best = max(ok, key=lambda r: r["result"]["mfu"])
        print(json.dumps({
            "metric": "gpt2_mfu_sweep_best",
            "arm": best["arm"],
            "mfu": best["result"]["mfu"],
            "tok_per_s": best["result"]["value"],
            "target_0.40_met": best["result"]["mfu"] >= 0.40,
            "arms_run": len(results),
        }))
        return 0
    print(json.dumps({"metric": "gpt2_mfu_sweep_best", "error":
                      "no arm produced an MFU", "arms_run": len(results)}))
    return 1


if __name__ == "__main__":
    sys.exit(main())
