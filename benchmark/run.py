"""benchmark/run.py — one process, one cell, once.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Deploys the cell's query durably over Hummock on a fresh directory, warms up
its shapes, measures a window of whole checkpoints, reopens the store from
disk and compares the materialized view with a numpy oracle, and prints one
JSON object as the last line of stdout. Touches JAX itself; starts no child.
Without a TPU (or with fewer chips than the cell asks for) it exits non-zero
and prints no result. The CPU rehearsal is asked for by `JAX_PLATFORMS=cpu`
AND `BENCH_REHEARSAL=1`, takes its sizes from the configuration's
`rehearsal` block, and says `cpu` in its `device`.
"""

from __future__ import annotations

import time

T_PROCESS = time.monotonic_ns()

import argparse          # noqa: E402
import asyncio           # noqa: E402
import json              # noqa: E402
import os                # noqa: E402
import shutil            # noqa: E402
import sys               # noqa: E402
import tempfile          # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def log(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


def parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap


def main(args=None, mutate=None) -> int:
    """`mutate(cell)`: benchmark/tests/control.py breaks one guarantee of
    the configuration before the run; the benchmark's command passes none."""
    args = args or parser().parse_args()

    from benchmark.harness import spec
    try:
        bm = spec.load_benchmark()
        rehearsal = os.environ.get("BENCH_REHEARSAL") == "1"
        env_cpu = os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu"
        if rehearsal and not env_cpu:
            print("benchmark: BENCH_REHEARSAL=1 needs JAX_PLATFORMS=cpu too",
                  file=sys.stderr)
            return 2
        cell = spec.Cell(bm, args.workload, rehearsal=rehearsal)
        if mutate is not None:
            mutate(cell)
    except (spec.SpecError, OSError, KeyError, ValueError) as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2
    if not os.path.isdir(os.path.join(ROOT, "risingwave_tpu")):
        print("benchmark: no risingwave_tpu/ beside benchmark/ — nothing to "
              "measure", file=sys.stderr)
        return 2

    # every program into the persistent cache, however quickly it compiled:
    # the second run of a cell in a checkout compiles nothing
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    import jax

    import risingwave_tpu  # noqa: F401 — enables x64 before any tracing
    from risingwave_tpu.utils.compile_cache import enable_persistent_cache
    cache_dir = enable_persistent_cache()
    devs = jax.devices()
    platform, kind, count = devs[0].platform, devs[0].device_kind, len(devs)
    if platform != "tpu" and not rehearsal:
        print(f"benchmark: jax found no TPU (platform={platform!r}); a "
              "measurement does not fall back. The CPU rehearsal is "
              "JAX_PLATFORMS=cpu BENCH_REHEARSAL=1.", file=sys.stderr)
        return 2
    if platform == "tpu" and rehearsal:
        print("benchmark: BENCH_REHEARSAL=1 on a TPU backend",
              file=sys.stderr)
        return 2
    if count < cell.chips:
        print(f"benchmark: cell {cell.name} needs {cell.chips} chips, jax "
              f"sees {count}", file=sys.stderr)
        return 2
    if platform == "tpu":
        with open(os.path.join(ROOT, "benchmark", "peaks.json")) as f:
            if kind not in json.load(f):
                print(f"benchmark: device kind {kind!r} is not in "
                      "benchmark/peaks.json", file=sys.stderr)
                return 2

    from benchmark.harness import report
    store_path = tempfile.mkdtemp(prefix="bench_hummock_")
    try:
        line = asyncio.run(report.run_cell(
            cell, args.seed, args.seconds, bool(args.trace),
            store_path=store_path, t_process_ns=T_PROCESS, log=log,
            device={"platform": platform, "kind": kind, "count": count},
            cache_dir=cache_dir))
    finally:
        shutil.rmtree(store_path, ignore_errors=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
