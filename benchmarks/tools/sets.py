#!/usr/bin/env python3
"""Run a set of runs of one cell as the driver runs them — a fresh process
a run, a different seed a run — and say how the set spread: per run the
end-to-end metrics and what the run's log says it was (stalled seconds,
staged depth at both ends, held share, rows a batch, full collections),
then each metric's median and its spread in both forms: interquartile
distance ÷ median (the contract's, what a bound is set from) and the runs'
range less the run farthest from the median (the driver's verdicts').

    python3 benchmarks/tools/sets.py --workload <cell> --seeds 11,12,13,14,15,16 --seconds 51

``--checkout`` runs another tree's ``benchmarks/run.py`` (the parent's, or a
copy whose traffic file carries another cap): two sets on one machine are
two invocations in one call. This process never touches JAX: the chip
belongs to the run. Logs go to ``chiprun_out/sets/<tag>.<seed>.{out,err}``.
"""

from __future__ import annotations

import argparse
import json
import re
import statistics
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmarks import stats  # noqa: E402

PACE = re.compile(r"scheduler pace \[(\S+)\]: at the window's start: (.*?); "
                  r"at the window's end: (.*)")


def pairs(text: str) -> dict:
    """``"a 1, b 2.5"`` → ``{"a": 1.0, "b": 2.5}``; ``"nothing"`` → {}."""
    out = {}
    for part in text.split(", "):
        key, _, value = part.partition(" ")
        try:
            out[key] = float(value)
        except ValueError:
            pass
    return out


def read_run(result_line: str, log_text: str) -> dict:
    """One run's row from its result line and its ``[bench]`` log lines
    (``harness.window_log``'s and ``run_cell``'s)."""
    doc = json.loads(result_line)
    row = {"correct": doc["correct"], "failed": doc["failed"],
           **{k: v["value"] for k, v in doc["metrics"].items()}}
    m = re.search(r"scheduler in the window: (.*)", log_text)
    sched = pairs(m.group(1)) if m else {}
    row.update({k: sched[k] for k in ("held_share", "hold_ms_per_held",
                                      "rows_per_batch") if k in sched})
    depth = {}
    for lane, start, end in PACE.findall(log_text):
        depth[lane] = [pairs(t).get("staged_depth") for t in (start, end)]
    if depth:
        row["staged_depth"] = depth
    m = re.search(r"stalled seconds \(under half the median\): (\[.*?\])",
                  log_text)
    if m:
        row["stalled"] = json.loads(m.group(1))
    m = re.search(r"collections by generation (\[.*?\])", log_text)
    if m:
        row["full_collections"] = json.loads(m.group(1))[-1]
    return row


def summarise(values: list) -> dict:
    """A set's median and its spread in both forms, as shares of it."""
    median = statistics.median(values)
    q = statistics.quantiles(values, n=4)
    ranged = stats.driver_spread(values)
    return {"n": len(values), "median": median,
            "iqr_share": (q[2] - q[0]) / median,
            "driver_spread": ranged, "driver_share": ranged / median}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", default="51")
    ap.add_argument("--trace", default="0")
    ap.add_argument("--checkout", default=str(ROOT))
    ap.add_argument("--tag", default="set")
    args = ap.parse_args()
    out_dir = ROOT / "chiprun_out" / "sets"
    out_dir.mkdir(parents=True, exist_ok=True)
    rows, rc = [], 0
    for seed in args.seeds.split(","):
        base = f"{args.tag}.{seed}"
        run = subprocess.run(
            [sys.executable, "benchmarks/run.py", "--workload",
             args.workload, "--seed", seed, "--seconds", args.seconds,
             "--trace", args.trace],
            cwd=args.checkout, capture_output=True, text=True)
        log = "".join(line[:6000] + "\n" for line in run.stderr.splitlines()
                      if line.startswith("[bench]") or run.returncode)
        (out_dir / f"{base}.out").write_text(run.stdout)
        (out_dir / f"{base}.err").write_text(log)
        last = (run.stdout.strip().splitlines() or [""])[-1]
        if run.returncode:
            rc = run.returncode
            print(json.dumps({"tag": args.tag, "seed": seed,
                              "rc": run.returncode}), flush=True)
            continue
        rows.append(read_run(last, log))
        print(json.dumps({"tag": args.tag, "seed": seed, **rows[-1]}),
              flush=True)
    keys = [k for k in (rows[0] if rows else {})
            if isinstance(rows[0][k], float) and k not in (
                "held_share", "hold_ms_per_held", "rows_per_batch")]
    if len(rows) >= 2:
        for key in keys:
            print(json.dumps({"tag": args.tag, "metric": key, **summarise(
                [r[key] for r in rows if key in r])}), flush=True)
    return rc if rc else int(not all(r["correct"] for r in rows))


if __name__ == "__main__":
    sys.exit(main())
