"""Run one cell several times, each run its own process, and print the spread
(run by hand; the parent never touches JAX, so each child gets the chip).

    python benchmark/tests/run_set.py --workload q7.sat --seeds 11,12,13 \
        --seconds 48 --out chiprun_out/q7sat_set1

Writes every run's stdout/stderr under `--out`, the final lines to
`<out>/lines.jsonl`, and prints per metric the values, the median and the
spread as the benchmark's contract defines it: (Q3 - Q1) / median with
`statistics.quantiles(values, n=4)`.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def spread(values: list) -> float | None:
    if len(values) < 2:
        return None
    q = statistics.quantiles(values, n=4)
    med = statistics.median(values)
    return (q[2] - q[0]) / med if med else None


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    os.makedirs(args.out, exist_ok=True)
    lines = []
    for seed in args.seeds.split(","):
        tag = f"{args.workload}.s{seed}.t{args.trace}"
        t0 = time.monotonic()
        with open(os.path.join(args.out, tag + ".out"), "w") as fo, \
                open(os.path.join(args.out, tag + ".err"), "w") as fe:
            rc = subprocess.run(
                [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
                 "--workload", args.workload, "--seed", seed,
                 "--seconds", str(args.seconds), "--trace", str(args.trace)],
                cwd=ROOT, stdout=fo, stderr=fe).returncode
        wall = time.monotonic() - t0
        with open(os.path.join(args.out, tag + ".out")) as f:
            out = f.read().strip().splitlines()
        last = json.loads(out[-1]) if rc == 0 and out else None
        lines.append({"seed": int(seed), "rc": rc, "wall_s": wall,
                      "line": last})
        print(json.dumps({"seed": int(seed), "rc": rc,
                          "wall_s": round(wall, 1),
                          "correct": last and last["correct"],
                          "metrics": last and {k: v["value"] for k, v in
                                               last["metrics"].items()}}),
              flush=True)
    with open(os.path.join(args.out, "lines.jsonl"), "a") as f:
        for ln in lines:
            f.write(json.dumps(ln) + "\n")
    good = [ln["line"] for ln in lines if ln["line"]]
    names = sorted({k for ln in good for k in ln["metrics"]})
    for k in names:
        vals = [ln["metrics"][k]["value"] for ln in good
                if k in ln["metrics"]]
        print(json.dumps({"metric": k, "n": len(vals),
                          "median": statistics.median(vals),
                          "spread": spread(vals),
                          "min": min(vals), "max": max(vals)}), flush=True)
    return 0 if all(ln["rc"] == 0 and ln["line"]["correct"]
                    for ln in lines) else 1


if __name__ == "__main__":
    sys.exit(main())
