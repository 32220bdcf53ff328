#!/usr/bin/env python3
"""Find an open-loop cell's knee once, on the chip: one set-up, then one
window at each offered rate. The knee is the highest rate at which the
completions keep up with the arrivals over the window (no failed or
refused request, nothing shed, p95 under the window's length); the cell's
traffic file then fixes 0.8 of it.

    python3 benchmarks/tools/sweep.py --workload <cell> --seed <n> --seconds <s> --rates 10,20,40,80
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args()
    if args.rehearse_cpu:
        import os
        os.environ["JAX_PLATFORMS"] = "cpu"
    from benchmarks import harness, stats
    import jax
    cell = harness.Cell(args.workload, rehearsal=args.rehearse_cpu)
    if jax.devices()[0].platform != "tpu" and not args.rehearse_cpu:
        return 2
    rows = []
    with harness.prepared(cell, args.seed, {}) as env:
        for rate in [float(r) for r in args.rates.split(",")]:
            for st in cell.traffic["streams"]:
                if st["arrivals"]["process"] == "poisson":
                    st["arrivals"]["rate"] = rate
            ctx = harness.window(env, args.seconds, False)
            rec, t0, t1 = ctx["records"], ctx["t_start"], ctx["t_end"]
            lat = stats.latencies_ms(rec, t0, t1)
            att, bad = stats.attempted_failed(rec, t0, t1)
            b, a = ctx["before"]["scheduler"], ctx["after"]["scheduler"]
            launched = a["batches_launched"] - b["batches_launched"]
            row = {"rate": rate, "attempted": att, "failed": bad,
                   "answered_per_s": stats.queries_per_second(rec, t0, t1),
                   "p50_ms": stats.percentile(lat, 50),
                   "p95_ms": stats.percentile(lat, 95),
                   "max_ms": max(lat),
                   "shed": a["shed"] - b["shed"],
                   "declined": a["declined"] - b["declined"],
                   "batch_fill": (a["delivered"] - b["delivered"])
                   / max(launched, 1),
                   "late_p95_ms": stats.percentile(
                       stats.lateness_ms(rec, t0, t1), 95)}
            rows.append(row)
            print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
