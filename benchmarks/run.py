#!/usr/bin/env python3
"""Run one cell of the benchmark once.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Loads the corpus from the seed, serves it through ``Node`` + ``RestServer``,
warms every shape, measures for ``--seconds`` from the client's side of
the socket, compares a sample of the window's own replies with the plain
reference, and prints one JSON object as the last line of standard output.
With no TPU, or fewer chips than the cell asks for, it exits non-zero and
prints no result. ``--rehearse-cpu`` walks the same path on the CPU at the
size the files give and can never print a passing line.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="run on the CPU backend; never reports correct")
    args = ap.parse_args(argv)
    if args.rehearse_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"

    from benchmarks import harness
    cell = harness.Cell(args.workload, rehearsal=args.rehearse_cpu)

    import jax
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    if not args.rehearse_cpu and (dev["platform"] != "tpu"
                                  or dev["count"] < cell.chips):
        print(f"[bench] no accelerator for [{cell.name}]: JAX found "
              f"{dev['platform']} ({dev['kind']} x{dev['count']}), the "
              f"cell asks for {cell.chips} TPU chip(s)", file=sys.stderr)
        return 2
    harness.log(f"device: {dev}"
                + (" — REHEARSAL on the CPU" if args.rehearse_cpu else ""))
    line = harness.run_cell(cell, args.seed, args.seconds,
                            bool(args.trace), dev,
                            rehearsal=args.rehearse_cpu)
    for name, value in line["observed"].items():
        print(f"[bench] observed {name}: {value!r} (no limit)",
              file=sys.stderr)
    for name, ent in line["compared"].items():   # the line's last key
        print(f"[bench] compared {name}: {ent['value']!r} "
              f"(limit {ent['limit']!r})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 3 if args.rehearse_cpu else 0


if __name__ == "__main__":
    sys.exit(main())
