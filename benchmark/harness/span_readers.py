"""What the span and program-name readers in benchmark/layers/ share.

The engine keeps one span tree per checkpoint in a process-wide log
(`risingwave_tpu/utils/trace.py` SPAN_LOG: `Span(epoch, name, parent, owner,
t0_ns, t1_ns, sid, count)` on `time.monotonic_ns()`, the harness's own
clock) and one entry per compiled StateJit signature in
`risingwave_tpu/ops/jit_state.py` PROGRAMS (name, static arguments, the
program id of the xplane's `jit_traced(<id>)`). Readers run in the engine's
process, so they read both directly, selecting by the `epoch` of the
window's committed checkpoints. An engine without the log or the registry
(the parent of PR 36), a window epoch the log no longer has, a phase key an
actor does not report: every reader here then returns None, never raises,
and the metric is left out of the line.
"""

from __future__ import annotations

import re

from . import readers, stats

MODULE_ID = re.compile(r"^jit_traced\((\d+)\)$")


def trees(run: dict):
    """The span list of every committed checkpoint of the window, or None
    where the log lacks one of them."""
    try:
        from risingwave_tpu.utils.trace import SPAN_LOG
    except ImportError:
        return None
    out = [SPAN_LOG.spans(r["epoch"]) for r in readers.committed(run)]
    return out if out and all(out) else None


def median_per_tree(run: dict, value):
    """`value(spans)` of each committed checkpoint's tree, the median over
    the window; None where a tree is missing or `value` finds nothing."""
    ts = trees(run)
    if ts is None:
        return None
    per = [value(spans) for spans in ts]
    return None if any(v is None for v in per) else stats.median(per)


def span_s(spans, name: str):
    """Seconds of the one span called `name` (the root, `collect`,
    `flush.queue`, `flush`)."""
    found = [sp for sp in spans if sp.name == name]
    return (found[0].t1_ns - found[0].t0_ns) / 1e9 if len(found) == 1 \
        else None


def flush_wait_s(spans):
    """Seconds the uploader's worker threads spent in `d2h_wait` under the
    checkpoint's `flush`: blocked until the device reached and shipped a
    buffer."""
    if span_s(spans, "flush") is None:
        return None
    return sum(sp.t1_ns - sp.t0_ns for sp in spans
               if sp.name == "d2h_wait" and sp.owner == "uploader") / 1e9


def phase_s_per_ckpt(run: dict, key: str):
    """`readers.phase_s_per_ckpt`, but None unless some actor of every
    committed checkpoint reports `key`."""
    recs = readers.committed(run)
    if not recs or not all(any(key in p for p in (r.get("phases") or {})
                               .values()) for r in recs):
        return None
    return readers.phase_s_per_ckpt(run, key)


def device_s_per_ckpt(run: dict, wanted):
    """Device seconds per traced checkpoint (mean over the chips, as
    `exec_dev_s_per_ckpt`) of the StateJit programs whose registered name
    `wanted(name)` accepts; None where nothing in the trace can be named."""
    t = run.get("trace")
    if not t or not t.get("checkpoints"):
        return None
    try:
        from risingwave_tpu.ops.jit_state import programs_by_id
    except ImportError:
        return None
    by_id = programs_by_id()
    total, found = 0.0, False
    for module, seconds in t["device_modules"]:
        m = MODULE_ID.match(module)
        prog = by_id.get(int(m.group(1))) if m else None
        if prog is not None and wanted(prog.name):
            total, found = total + seconds, True
    return total / t["checkpoints"] if found else None


PERSIST_PROGRAM = re.compile(
    r"(^sorted_join_diff$|_persist_view$|_watchdog_pack$|_wd_pack$"
    r"|_mem_pack$)")
JOIN_PROGRAM = re.compile(r"^(sorted|sharded)_join_")
AGG_PROGRAM = re.compile(r"^(hash|sharded)_agg_")


def is_persist(name: str) -> bool:
    return bool(PERSIST_PROGRAM.search(name))


def is_join(name: str) -> bool:
    return bool(JOIN_PROGRAM.match(name)) and not is_persist(name)


def is_agg(name: str) -> bool:
    return bool(AGG_PROGRAM.match(name)) and not is_persist(name)
