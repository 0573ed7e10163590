"""What decides `correct`: the MV read from the store REOPENED from disk,
against the numpy oracle over rows `[0, committed offset)`, plus the run's
health counters. Runs after the window has closed.
"""

from __future__ import annotations

import gc
import time

import numpy as np

from . import drive

mono = time.monotonic_ns


def exact(what: str, value: int) -> dict:
    """A number compared exactly: it has to be 0."""
    return {"what": what, "value": value, "limit": 0, "ok": value == 0}


def sort_cols(cols: list) -> list:
    order = np.lexsort(tuple(reversed(cols)))
    return [c[order] for c in cols]


def rows_to_cols(rows: list, dtypes) -> list:
    """list of tuples -> per-column arrays."""
    if not rows:
        return [np.zeros(0, dt) for dt in dtypes]
    return [np.asarray([r[j] for r in rows], dtype=dt)
            for j, dt in enumerate(dtypes)]


def compare(got: list, want: list, float_rtol: float) -> list:
    """Compare two relations column by column after sorting their rows.
    Returns one `{"what", "value", "limit", "ok"}` per number compared:
    the difference in row count (limit 0), per integer column the number of
    differing cells (limit 0), per float column the largest relative
    difference (limit `float_rtol`)."""
    got, want = sort_cols(list(got)), sort_cols(list(want))
    n_got, n_want = int(got[0].shape[0]), int(want[0].shape[0])
    out = [exact("mv_rows_minus_oracle_rows", n_got - n_want)]
    if n_got != n_want:
        return out
    for j, (g, w) in enumerate(zip(got, want)):
        if np.issubdtype(w.dtype, np.floating):
            finite = bool(np.all(np.isfinite(g)))
            rel = float(np.max(np.abs(g - w) / np.maximum(np.abs(w), 1e-300),
                               initial=0.0)) if finite else float("inf")
            out.append({"what": f"col{j}_max_rel_diff", "value": rel,
                        "limit": float_rtol, "ok": rel <= float_rtol})
        else:
            out.append(exact(f"col{j}_cells_differing",
                             int(np.count_nonzero(g != w))))
    return out


def health(session, cell, win: dict, store_path: str) -> list:
    """The window's guarantees as numbers with the limit 0."""
    errs = drive.join_error_counters(session, cell.query.MV)
    disk = drive.store_on_disk(store_path)
    c = win["counters"]
    numbers = [
        ("checkpoints_not_committed", win["failed"]),
        ("statejit_compiles_in_window", sum(win["compiled_in_window"]
                                            .values())),
        ("recoveries", int(session.recoveries)),
        ("barrier_stalls_in_window", c["barrier_stalls"]),
        ("mesh_shuffle_dropped_rows", c["mesh_shuffle_dropped"]),
        ("join_error_counters", sum(sum(v) for v in errs.values())),
        ("manifest_missing", 0 if disk["manifest"] and disk["ssts"] else 1),
    ]
    return [exact(k, v) for k, v in numbers]


def _mv_layout(session, cell) -> dict:
    """What reading the MV's table from a bare store needs."""
    t = session.catalog.mvs[cell.query.MV].table
    names = list(t.schema.names)
    return {"table_id": t.table_id, "schema": t.schema,
            "pk_indices": t.pk_indices,
            "dist_key_indices": t.dist_key_indices,
            "pk_descending": t.pk_descending,
            "columns": [names.index(c) for c in cell.query.COLUMNS]}


def _read_mv_from_store(store, layout: dict) -> list:
    from risingwave_tpu.state.storage_table import StorageTable
    cols = layout.pop("columns")
    return [tuple(row[j] for j in cols)
            for row in StorageTable(store, **layout).batch_iter()]


async def reopen_and_compare(session, cell, seed: int, store_path: str,
                             win: dict, compiles, timed_recovery: bool
                             ) -> dict:
    """Process death (`Session.crash()`: no stop protocol), then the store
    reopened from disk. The MV read from it must equal the oracle at the
    committed offsets. With `timed_recovery` a fresh Session recovers over
    the reopened store, commits one more checkpoint (`recovery_s` is reopen
    -> that commit) and the MV is read through its SQL; otherwise the MV's
    table is scanned from the reopened store itself — `recover()` would
    cost minutes there (PERF.md) and nothing timed needs it."""
    from risingwave_tpu.frontend import Session
    mv, quotas = cell.query.MV, cell.quotas
    offs = drive.committed_offsets(session, mv)
    numbers = [exact(f"committed_offset_{t}_minus_expected", offs[t] - n)
               for t, n in win["expected_offsets"].items()]
    layout = _mv_layout(session, cell)
    await session.crash()
    del session
    gc.collect()
    compiles0 = drive.read_counters(compiles)
    t0 = mono()
    store2 = drive.open_store(store_path, reopen=True)
    t_opened = mono()
    out = {"recovery_s": None}
    steps = {"store_open_s": (t_opened - t0) / 1e9}
    if timed_recovery:
        s2 = Session(store=store2)
        await s2.recover()
        t_recovered = mono()
        start = drive.committed_offsets(s2, mv)
        numbers += [exact(f"reopened_offset_{t}_minus_committed",
                          start[t] - offs[t]) for t in offs]
        stamps = drive.Stamps(s2.coord)
        rec = await drive.checkpoint(
            s2, mv, stamps, {t: offs[t] + q for t, q in quotas.items()})
        await s2.coord.drain_uploads()
        out["recovery_s"] = (rec["commit_ns"] - t0) / 1e9
        offs = drive.committed_offsets(s2, mv)
        numbers += [exact(f"resumed_offset_{t}_minus_expected",
                          offs[t] - start[t] - quotas[t]) for t in offs]
        numbers.append(exact("recoveries_after_restart",
                             int(s2.recoveries)))
        compiles1 = drive.read_counters(compiles)
        steps.update(
            session_recover_s=(t_recovered - t_opened) / 1e9,
            first_checkpoint_s=(mono() - t_recovered) / 1e9,
            backend_compiles=(compiles1["backend_compiles"]
                              - compiles0["backend_compiles"]),
            backend_compile_s=(compiles1["backend_compile_s"]
                               - compiles0["backend_compile_s"]),
            statejit_compiles=(compiles1["jit_compiles"]
                               - compiles0["jit_compiles"]))
        t1 = mono()
        rows = cell.query.read_mv(s2)
        await s2.crash()
    else:
        t1 = mono()
        rows = _read_mv_from_store(store2, layout)
    got = rows_to_cols(rows, cell.query.DTYPES)
    t2 = mono()
    want = cell.query.oracle(offs, cell.config, seed)
    numbers += compare(got, want, cell.query.FLOAT_RTOL)
    out.update(numbers=numbers, offsets=offs, mv_rows=int(got[0].shape[0]),
               reopen_steps=steps, reopen_s=(t1 - t0) / 1e9,
               read_s=(t2 - t1) / 1e9, oracle_s=(mono() - t2) / 1e9)
    return out
