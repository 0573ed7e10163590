"""One run of one cell, from deploy to the final line."""

from __future__ import annotations

import os
import time

from . import check, drive, spec, stats
from .tracing import WindowTracer

mono = time.monotonic_ns


def memory_peak_bytes(chips: int) -> int:
    """Peak bytes in use on the fullest of the cell's chips (0 where the
    backend does not report it, as the CPU)."""
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.local_devices()[:max(1, chips)]]
    return int(max(peaks, default=0))


def end_to_end(cell, run: dict) -> dict:
    """The cell's end-to-end metrics, each from the harness's own clock."""
    win = run["window"]
    fresh = win["freshness_s"]
    values = {
        "setup_s": run["setup_s"],
        "rows_per_s": (len(fresh) * win["rows_per_checkpoint"]
                       / win["window_s"]) if fresh else None,
        "freshness_p50_s": stats.median(fresh) if fresh else None,
        "recovery_s": run["check"].get("recovery_s"),
    }
    out = {}
    for m in cell.end_to_end:
        if m["name"] not in values:
            raise spec.SpecError(f"no arithmetic for end-to-end metric "
                                 f"{m['name']}")
        if values[m["name"]] is not None:
            out[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    return out


def per_layer(cell, run: dict) -> dict:
    """Each per-layer metric through its own reader in benchmark/layers/; a
    reader that finds nothing to read returns None and the metric is left
    out of the line."""
    out = {}
    for m in cell.per_layer:
        mod = spec.load_module("layers", m["name"])
        for attr, key in (("LAYER", "layer"), ("UNIT", "unit"),
                          ("MOVES", "moves")):
            if getattr(mod, attr) != m[key]:
                raise spec.SpecError(
                    f"layers/{m['name']}.py says {attr}="
                    f"{getattr(mod, attr)!r}, BENCHMARK.json {m[key]!r}")
        if mod.NEEDS_TRACE and run.get("trace") is None:
            continue
        v = mod.read(run)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


async def run_cell(cell, seed: int, seconds: float, trace: bool, *,
                   store_path: str, t_process_ns: int, log, device: dict,
                   cache_dir: str) -> dict:
    log({"phase": "start", "cell": cell.name, "seed": seed,
         "seconds": seconds, "trace": trace, "device": device,
         "rehearsal": cell.rehearsal, "compile_cache_dir": cache_dir,
         "compile_cache_warm": any(os.scandir(cache_dir)),
         "config": cell.config["session_set"], "traffic": cell.traffic})
    compiles = drive.BackendCompiles()
    session, deploy_s, deploy_steps = await drive.deploy(cell, seed,
                                                         store_path)
    stamps = drive.Stamps(session.coord)
    warmup_s = await drive.warm_up(session, cell, stamps)
    tracer = WindowTracer() if trace else None
    run = {"cell": cell, "seed": seed, "deploy_s": deploy_s,
           "warmup_s": warmup_s, "trace": None, "device": device}
    try:
        # set-up ends where the first timed barrier is injected
        win = await drive.window(session, cell, stamps, seconds,
                                 compiles, tracer)
        run["setup_s"] = (win["t_open_ns"] - t_process_ns) / 1e9
        run["window"] = win
        if tracer is not None and tracer.xplane_path():
            from benchmark import trace_reduce
            run["trace"] = trace_reduce.reduce_file(
                tracer.xplane_path(), sync_host_ns=tracer.sync_ns,
                t_start_host_ns=tracer.t_start_ns,
                t_stop_host_ns=tracer.t_stop_ns, spans=tracer.spans,
                samples=tracer.samples)
            run["trace"]["checkpoints"] = tracer.n_traced
    finally:
        if tracer is not None:
            tracer.cleanup()
    device["memory_peak_bytes"] = memory_peak_bytes(cell.chips)
    run["hbm_peak_bytes"] = device["memory_peak_bytes"]

    fresh = sorted(win["freshness_s"])
    late = sorted((r["call_ns"] - r["due_ns"]) / 1e9
                  for r in win["checkpoints"])
    log({"phase": "window", "window_s": win["window_s"],
         "checkpoints": win["attempted"], "not_committed": win["failed"],
         "deploy_s": deploy_s, "warmup_s": warmup_s,
         "deploy_steps": deploy_steps,
         "backend_compiles_in_window": win["counters"]["backend_compiles"],
         "backend_compile_s_in_window": win["counters"]["backend_compile_s"],
         "freshness_s_min": fresh[0] if fresh else None,
         "freshness_s_max": fresh[-1] if fresh else None,
         "inject_lateness_s_p50": stats.median(late),
         "inject_lateness_s_max": late[-1],
         "d2h_fetches": win["counters"]["d2h_fetches"],
         "d2h_bytes": win["counters"]["d2h_bytes"],
         "dispatches": win["counters"]["dispatches"],
         "compiled_in_window": win["compiled_in_window"]})

    numbers = check.health(session, cell, win, store_path)
    chk = await check.reopen_and_compare(
        session, cell, seed, store_path, win, compiles,
        timed_recovery=cell.reports("recovery_s"))
    numbers += chk.pop("numbers")
    run["check"] = chk
    correct = all(n["ok"] for n in numbers)
    log({"phase": "check", "correct": correct, "compared": numbers,
         **{k: chk[k] for k in ("offsets", "mv_rows", "reopen_s", "read_s",
                                "oracle_s", "recovery_s", "reopen_steps")}})

    line = {"correct": correct, "attempted": win["attempted"],
            "failed": win["failed"],
            "metrics": per_layer(cell, run) if trace
            else end_to_end(cell, run),
            "device": device}
    if trace and run["trace"] is not None:
        t = run["trace"]
        device["busy_s"], device["window_s"] = t["busy_s"], t["window_s"]
        # the programs that took most device time (all the xplane knows of a
        # StateJit program is a fingerprint), then the operations
        line["breakdown"] = {
            "device_ops": [["program " + n, s] for n, s
                           in t["device_modules"][:4]]
            + [["op " + n, s] for n, s in t["device_ops"][:6]],
            "idle_gaps": t["idle_gaps"]}
    return line
