"""xplane -> device busy and idle time, device time per program, and the idle
gaps labelled by what the host was doing.

Reads the profiler's `.xplane.pb` with `jax.profiler.ProfileData` alone.
A TPU plane (`/device:TPU:<n>`) carries one line per kind of record; the line
"XLA Ops" holds one event per executed HLO operation and "XLA Modules" one
per executed program. Busy time is the union of the op events (modules where
a plane has no op line); idle is the traced interval minus that.

Every StateJit program of the engine is `jax.jit(traced)`, so all of them
are the XLA module `jit_traced`: a single executor step cannot be told from
another by name today (PERF.md, list for the `tracing` issue). What can be
told: the StateJit programs together against the generators and the rest.
"""

from __future__ import annotations

import re

OP_LINES = ("XLA Ops",)
MODULE_LINES = ("XLA Modules",)
STATEJIT_MODULE = re.compile(r"^jit_traced(\(|$)")
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")


def union_ns(intervals) -> tuple:
    """Merged intervals and their total length."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return merged, sum(e - s for s, e in merged)


def _events(line):
    return [(ev.name, float(ev.start_ns), float(ev.start_ns)
             + float(ev.duration_ns)) for ev in line.events]


def read_planes(path: str) -> dict:
    """The parts of an xplane file the reduction uses:
    {"devices": {n: {"ops": [(name, s, e)], "modules": [...]}},
     "host": [(name, s, e)]} — times in ns on the trace's own clock."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    out = {"devices": {}, "host": []}
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = out["devices"].setdefault(
                int(m.group(1)), {"ops": [], "modules": []})
            for line in plane.lines:
                if line.name in OP_LINES:
                    dev["ops"] += _events(line)
                elif line.name in MODULE_LINES:
                    dev["modules"] += _events(line)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                out["host"] += [e for e in _events(line)
                                if e[0].startswith("bench")]
    return out


def _short(op: str) -> str:
    """An op event is named by its whole HLO line: keep `%name = shape`."""
    return op if len(op) <= 72 else op[:69] + "..."


def reduce_planes(planes: dict, *, t_start_ns: float, t_stop_ns: float,
                  spans=(), samples=()) -> dict:
    """`t_start_ns`/`t_stop_ns`: the traced interval on the trace's clock.
    `spans`: (name, s, e) and `samples`: (t, main label, [worker labels]) on
    the same clock."""
    window = t_stop_ns - t_start_ns
    per_dev = {}
    for n, dev in planes["devices"].items():
        evs = dev["ops"] or dev["modules"]
        clipped = [(max(s, t_start_ns), min(e, t_stop_ns))
                   for _, s, e in evs if e > t_start_ns and s < t_stop_ns]
        merged, busy = union_ns(clipped)
        per_dev[n] = {"busy_ns": busy, "merged": merged}
    by_module: dict = {}
    by_op: dict = {}
    for dev in planes["devices"].values():
        for name, s, e in dev["modules"]:
            by_module[name] = by_module.get(name, 0.0) + (e - s)
        for name, s, e in dev["ops"]:
            by_op[_short(name)] = by_op.get(_short(name), 0.0) + (e - s)
    n_dev = max(1, len(per_dev))
    statejit_ns = sum(v for k, v in by_module.items()
                      if STATEJIT_MODULE.match(k)) / n_dev
    worst = max(per_dev.values(), key=lambda d: -d["busy_ns"], default=None)
    gaps = _label_gaps(worst["merged"] if worst else [], t_start_ns,
                       t_stop_ns, spans, samples)
    return {
        "window_s": window / 1e9,
        "busy_s": sum(d["busy_ns"] for d in per_dev.values()) / n_dev / 1e9,
        "busy_s_least": (worst["busy_ns"] / 1e9) if worst else 0.0,
        "statejit_s": statejit_ns / 1e9,
        "devices": len(per_dev),
        "device_modules": sorted(([k, v / n_dev / 1e9]
                                  for k, v in by_module.items()),
                                 key=lambda kv: -kv[1]),
        "device_ops": sorted(([k, v / n_dev / 1e9] for k, v in by_op.items()),
                             key=lambda kv: -kv[1])[:10],
        "idle_gaps": gaps[:10],
    }


def _label_gaps(merged, t0, t1, spans, samples) -> list:
    """Idle gaps of the least busy device, summed by label: the harness span
    that covers most of the gap and the host frame sampled most often in
    it."""
    edges = [t0] + [x for iv in merged for x in iv] + [t1]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] - edges[i] > 1e6]          # over 1 ms
    out: dict = {}
    for s, e in gaps:
        best, cover = "outside_harness_span", 0.0
        for name, ss, se in spans:
            c = min(e, se) - max(s, ss)
            if c > cover:
                best, cover = name, c
        frames: dict = {}
        for t, main, workers in samples:
            if s <= t <= e:
                for lab in [main] + list(workers):
                    frames[lab] = frames.get(lab, 0) + 1
        hot = max(frames, key=frames.get) if frames else "unsampled"
        label = f"{best}|{hot}"
        out[label] = out.get(label, 0.0) + (e - s) / 1e9
    return sorted(([k, v] for k, v in out.items()), key=lambda kv: -kv[1])


def reduce_file(path: str, *, sync_host_ns: int, t_start_host_ns: int,
                t_stop_host_ns: int, spans=(), samples=(),
                sync_name: str = "bench_clock_sync") -> dict:
    """Reduce one xplane file. Host-side times (monotonic ns) are moved onto
    the trace's clock through the `bench_clock_sync` annotation, whose start
    the harness stamped as `sync_host_ns`. A trace without the annotation is
    an error: the traced interval would shrink to first..last device op and
    every idle share would read too low."""
    planes = read_planes(path)
    sync = [s for name, s, _ in planes["host"] if name == sync_name]
    if not sync:
        raise ValueError(f"{path}: no {sync_name!r} annotation on a host "
                         "plane; the traced interval cannot be placed on "
                         "the trace's clock")
    off = sync[0] - sync_host_ns
    return reduce_planes(
        planes, t_start_ns=t_start_host_ns + off,
        t_stop_ns=t_stop_host_ns + off,
        spans=[(n, s + off, e + off) for n, s, e in spans],
        samples=[(t + off, m, w) for t, m, w in samples])


def describe(path: str, top: int = 12) -> str:
    """Planes, lines and the commonest event names of an xplane file: what
    to look at by hand before trusting the reduction."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        out.append(f"PLANE {plane.name}")
        for line in plane.lines:
            evs = list(line.events)
            tot: dict = {}
            for ev in evs:
                n, d = tot.get(ev.name, (0, 0.0))
                tot[ev.name] = (n + 1, d + float(ev.duration_ns))
            out.append(f"  LINE {line.name!r}: {len(evs)} events")
            for name, (n, d) in sorted(tot.items(),
                                       key=lambda kv: -kv[1][1])[:top]:
                out.append(f"    {n:7d} x {d / 1e6:12.3f} ms  {name[:100]}")
    return "\n".join(out)


if __name__ == "__main__":
    import sys
    print(describe(sys.argv[1]))
