#!/usr/bin/env python3
"""Read the control on the chip at a cell's own size: the plain reference
put in the program's place at the nearest precision below the one the
configuration states, on three seeds or more, through the cell's own
comparison. Every seed has to come out as NOT correct under the cell's
limits; the smallest reading is the limit's upper end (PERF.md).

    python3 benchmarks/tools/control.py --workload <cell> --seeds 1,2,3 --queries 16
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--queries", type=int, default=16)
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args()
    if args.rehearse_cpu:
        import os
        os.environ["JAX_PLATFORMS"] = "cpu"
    from benchmarks import harness
    import jax
    cell = harness.Cell(args.workload, rehearsal=args.rehearse_cpu)
    if jax.devices()[0].platform != "tpu" and not args.rehearse_cpu:
        return 2
    mod, limits = cell.corpus_mod, cell.spec["limits"]
    stream = cell.traffic["streams"][0]
    k = int(stream["request"]["size"])
    for seed in [int(s) for s in args.seeds.split(",")]:
        corpus = mod.generate(cell.config, seed, harness.log)
        rng = np.random.default_rng([seed, 13])
        queries = mod.query_pool(
            corpus, {**stream["queries"], "pool": args.queries}, rng, rng)
        ref = mod.Reference(corpus, queries)
        worst = {name: 0.0 for name in limits}
        for q in queries:
            got = mod.compare(ref.scores(q), stream["request"],
                              *mod.control_hits(ref, q, k))
            worst = {n: max(worst[n], got[n]) for n in worst}
        fails = [n for n in limits if worst[n] > limits[n]]
        print(json.dumps({"seed": seed, "k": k, "queries": len(queries),
                          "control": worst, "limits": limits,
                          "correct": not fails, "failed_on": fails}),
              flush=True)
        del corpus, ref
    return 0


if __name__ == "__main__":
    sys.exit(main())
