"""Record the tiny xplane that `test_harness.py` reduces to known numbers.

    python benchmark/tests/record_testdata.py <out-dir>

Run on the device whose traces the benchmark reads (one TPU chip): three
dispatches of a program named `traced` (what every StateJit program of the
engine is called), one of another program, with the host asleep in between,
inside the harness's own clock-sync annotation and spans. Prints what the
reduction gives, to be written into the test as the known numbers.
"""

from __future__ import annotations

import json
import os
import shutil
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main(out_dir: str) -> int:
    import jax
    import jax.numpy as jnp

    from benchmark import trace_reduce
    from benchmark.harness import tracing

    def traced(x):
        return (x @ x).sum()

    def other(x):
        return jnp.sort(x.ravel())[:8]

    f, g = jax.jit(traced), jax.jit(other)
    x = jnp.ones((1024, 1024), jnp.float32)
    f(x).block_until_ready()
    g(x).block_until_ready()
    tr = tracing.WindowTracer()
    tr._start(0)
    for _ in range(3):
        with tr.span("collect_wait"):
            f(x).block_until_ready()
        with tr.span("quota_wait"):
            time.sleep(0.02)
    with tr.span("inject"):
        g(x).block_until_ready()
    tr._stop(2)
    path = tr.xplane_path()
    os.makedirs(out_dir, exist_ok=True)
    shutil.copy(path, os.path.join(out_dir, "tiny.xplane.pb"))
    side = {"sync_host_ns": tr.sync_ns, "t_start_host_ns": tr.t_start_ns,
            "t_stop_host_ns": tr.t_stop_ns, "spans": tr.spans}
    with open(os.path.join(out_dir, "tiny.side.json"), "w") as fh:
        json.dump(side, fh)
    print(trace_reduce.describe(path))
    print(json.dumps(trace_reduce.reduce_file(path, **side), indent=1))
    print("bytes", os.path.getsize(path))
    tr.cleanup()
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1] if len(sys.argv) > 1
                  else tempfile.mkdtemp(prefix="bench_testdata_")))
