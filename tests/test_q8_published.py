"""NEXMark q8 'Monitor New Users' AS PUBLISHED (a VARCHAR column projected
and grouped by, two hash aggregates that are DISTINCTs whose every group the
watermark kills within a checkpoint or two, a join cleaned on both sides),
through `Session` -> binder -> plan -> actors with no option of its own,
against the benchmark's numpy oracle (`benchmark/queries/q8.py`, which takes
nothing from the engine and states the name as TEXT) on seeded offsets; the
connector's `hot_seller_bucket` option and its default; the zombie purge of
a hash aggregate under churn (nothing compiled after the first barrier, no
overflow, no recovery); the names across `crash()` + `recover()` and across
a REAL process restart; and the oracle catching one altered name.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmark.harness import check, drive
from benchmark.queries import q8
from benchmark.reference import nexmark_q8
from risingwave_tpu.common.types import GLOBAL_DICT, DataType
from risingwave_tpu.connectors import nexmark as nx
from risingwave_tpu.connectors.nexmark import NexmarkConfig, NexmarkGenerator
from risingwave_tpu.frontend import Session
from risingwave_tpu.frontend.binder import BindError
from risingwave_tpu.state import HummockStateStore, LocalFsObjectStore
from risingwave_tpu.stream.hash_agg import (
    ZOMBIE_PURGE_MARK, HashAggExecutor)
from risingwave_tpu.stream.sorted_join import SortedJoinExecutor
from risingwave_tpu.stream.monitor import _state_tables_of
from risingwave_tpu.utils.metrics import (
    GLOBAL_METRICS, HASH_AGG_PURGES, HASH_AGG_REHASH_ROWS, STATE_WRITE_KEYS)
from risingwave_tpu.utils.trace import SPAN_LOG

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEED = 2147483659
# NEXMark's 1 : 3, as the cell's traffic: both sources cover the same 25,600
# events every checkpoint; at 1 ms between events that is 25.6 s = 2.56 of
# the 10 s windows, so every group of a checkpoint is dead one or two
# checkpoints later, as in the cell (16.4 windows a checkpoint there)
PERSONS, AUCTIONS = 512, 1536
TRAFFIC = {"chunk_size": {"person": PERSONS, "auction": AUCTIONS},
           "chunks_per_interval": {"person": 1, "auction": 1}}
WINDOW_US = 10_000_000


def _config(agg: int = 4096, join: int = 8192, bucket: int = 100) -> dict:
    return {"generator": {"inter_event_us": 1000, "emit_watermarks": 1,
                          "watermark_lag_us": 0,
                          "hot_seller_bucket": bucket},
            "window_us": WINDOW_US,
            "session_set": {"streaming_join_capacity": join,
                            "streaming_join_match_factor": 2,
                            "streaming_agg_capacity": agg,
                            "streaming_watchdog": 1}}


async def _deploy(s: Session, cfg: dict) -> None:
    for stmt in q8.ddl(cfg, TRAFFIC, SEED):
        await s.execute(stmt)


def _aggs(s: Session) -> dict:
    """'person' / 'auction' -> that side's hash aggregate (the person side
    groups by four keys, one of them the VARCHAR's int32 id)."""
    found = [ex for ex in drive.executors_of(s, q8.MV)
             if isinstance(ex, HashAggExecutor)]
    assert len(found) == 2
    return {"person" if len(ex.group_key_indices) == 4 else "auction": ex
            for ex in found}


def _read(s: Session) -> list:
    return check.rows_to_cols(q8.read_mv(s), q8.DTYPES)


def _offsets(k: int) -> dict:
    return {"person": k * PERSONS, "auction": k * AUCTIONS}


def _numbers(got: list, offsets: dict, cfg: dict) -> list:
    return check.compare(got, q8.oracle(offsets, cfg, SEED), q8.FLOAT_RTOL)


def _assert_is_the_oracles(got: list, offsets: dict, cfg: dict) -> None:
    numbers = _numbers(got, offsets, cfg)
    assert all(n["ok"] for n in numbers), numbers
    assert got[0].shape[0] > 0, "oracle vacuous"
    assert got[1].dtype.kind == "U", "the name is compared as text"


def _compiles(program: str) -> int:
    return int(GLOBAL_METRICS.counter("jit_compile_count",
                                      program=program).value)


# ------------------------------------------------------------ the statement

def test_the_ddl_is_upstreams_statement_and_its_sources_declare_no_key():
    *sets, person, auction, mv = q8.ddl(_config(), TRAFFIC, SEED)
    assert all(s.startswith("SET ") for s in sets)
    for src in (person, auction):
        assert "primary_key" not in src
        assert "hot_seller_bucket=100" in src
    # RisingWave ci/scripts/sql/nexmark/q8.sql, column for column and
    # predicate for predicate; INTERVAL '10' SECOND written in microseconds
    assert " ".join(mv.split()) == (
        "CREATE MATERIALIZED VIEW q8 AS "
        "SELECT P.id, P.name, P.starttime "
        "FROM (SELECT id, name, window_start AS starttime, "
        "window_end AS endtime "
        "FROM TUMBLE(person, date_time, 10000000) "
        "GROUP BY id, name, window_start, window_end) P "
        "JOIN (SELECT seller, window_start AS starttime, "
        "window_end AS endtime "
        "FROM TUMBLE(auction, date_time, 10000000) "
        "GROUP BY seller, window_start, window_end) A "
        "ON P.id = A.seller AND P.starttime = A.starttime "
        "AND P.endtime = A.endtime")
    assert q8.COLUMNS == ("id", "name", "starttime")
    assert q8.DTYPES[1].kind == "U" and q8.FLOAT_RTOL == 0.0


def test_the_oracle_imports_nothing_of_the_engine():
    for mod in (q8, nexmark_q8):
        src = open(mod.__file__).read()
        # the query module looks at the connector's options once, before
        # its DDL; its oracle and the reference take nothing
        body = src.split("def ddl(")[1] if mod is q8 else src
        assert "import risingwave_tpu" not in body
        assert "from risingwave_tpu" not in body


# ------------------------------------------- the generator and its one option

def _engine_rows(table: str, n: int, cols: list, **cfg) -> list:
    gen = NexmarkGenerator(table, chunk_size=n, cfg=NexmarkConfig(
        inter_event_us=1000, base_time_us=nexmark_q8.base_time_us(SEED),
        **cfg))
    got, _ = gen.next_chunk().to_numpy()
    return [got[i] for i in cols]


@pytest.mark.parametrize("bucket", [4, 100])
def test_the_references_events_are_the_connectors(bucket):
    n = 6000
    kw = dict(inter_event_us=1000, base_time=nexmark_q8.base_time_us(SEED))
    pid, name, pt = _engine_rows("person", n, [0, 1, 6])
    want = nexmark_q8.persons(0, n, **kw)
    assert np.array_equal(pid, want["id"])
    assert np.array_equal(pt, want["date_time"])
    # the engine's cell is a dictionary id; the reference's is the text
    assert GLOBAL_DICT.decode_many(name) == want["name"].tolist()
    seller, at = _engine_rows("auction", 3 * n, [7, 5],
                              hot_seller_bucket=bucket)
    want = nexmark_q8.auctions(0, 3 * n, hot_seller_bucket=bucket, **kw)
    assert np.array_equal(seller, want["seller"])
    assert np.array_equal(at, want["date_time"])
    # three auctions in four go to the first person of the current bucket
    # (and one cold seller in `bucket` happens to be such a person)
    hot = (seller - nx.FIRST_PERSON_ID) % bucket == 0
    assert 0.74 < hot.mean() < (0.79 if bucket == 100 else 0.84)


def test_the_bucket_defaults_to_the_connectors_four():
    assert NexmarkConfig().hot_seller_bucket == nx.HOT_SELLER_RATIO == 4
    a = _engine_rows("auction", 4096, [7])
    b = _engine_rows("auction", 4096, [7], hot_seller_bucket=4)
    assert np.array_equal(a[0], b[0])


async def test_a_bucket_below_one_is_refused():
    s = Session()
    with pytest.raises(BindError):
        await s.execute(
            "CREATE SOURCE auction WITH (connector='nexmark', "
            "table='auction', hot_seller_bucket=0)")


# ------------------------------------------------------------------ the MV

@pytest.mark.parametrize("bucket", [100, 4])
async def test_the_mv_is_the_oracles_names_as_text(bucket):
    cfg = _config(bucket=bucket)
    s = Session()
    await _deploy(s, cfg)
    joins = [ex for ex in drive.executors_of(s, q8.MV)
             if isinstance(ex, SortedJoinExecutor)]
    assert len(joins) == 1
    assert all(c is not None for c in joins[0].clean_specs), \
        "both sides of the join are cleaned by the window's watermark"
    aggs = _aggs(s)
    # the name travels as its int32 id: a width of its own in the person
    # side's keys and values, which the batch codec lays out like any other
    assert DataType.VARCHAR in aggs["person"].state_table.schema.data_types
    assert DataType.VARCHAR not in \
        aggs["auction"].state_table.schema.data_types
    await s.tick(5)
    _assert_is_the_oracles(_read(s), _offsets(5), cfg)
    assert aggs["person"].state_table.row_path_rows == 0
    assert s.recoveries == 0
    await s.drop_all()


# --------------------------------------------------- the purge under churn

async def test_a_table_whose_groups_all_die_is_purged_and_compiles_nothing():
    """Every checkpoint brings 512 new person groups and kills all but the
    open window's: zombies fill the 4,096-slot table, and the barrier drops
    them whenever one more interval would take it past ZOMBIE_PURGE_MARK.
    Both purge programs were compiled by the first barrier (on a worker
    thread, from the INITIAL one): the purges compile nothing; nothing
    overflows; no in-process recovery."""
    cfg = _config()
    s = Session()
    await _deploy(s, cfg)
    await s.tick(1)
    person = _aggs(s)["person"]
    before = {p: _compiles(p) for p in ("hash_agg_live_zombie",
                                        "hash_agg_rehash")}
    assert all(n > 0 for n in before.values()), \
        "compiled by the executor's first barrier, not run"
    assert person.rebuilds == 0
    purges, fills, spans = 0, [], 0
    for _ in range(12):
        await s.tick(1)
        fills.append(person._occ_known)
        tr = s.coord.tracer._ring[-1]
        n = sum(ph.get("agg_purges", 0) for ph in tr.phases.values())
        purges += n
        found = [sp for sp in SPAN_LOG.spans(tr.epoch)
                 if sp.name == "agg.purge"]
        spans += len(found)
        if n:
            # the purge is a child of an actor's barrier poll and has its
            # dispatch and its awaited readback as children
            by_sid = {sp.sid: sp for sp in SPAN_LOG.spans(tr.epoch)}
            assert all(by_sid[sp.parent].name == "actor.persist"
                       for sp in found)
            kids = {sp.name for sp in by_sid.values()
                    if sp.parent in {f.sid for f in found}}
            assert {"dispatch:hash_agg_rehash", "d2h_wait"} <= kids
    assert person.rebuilds >= 2 and person.capacity == 4096
    assert purges >= person.rebuilds and spans >= purges
    assert max(fills) <= ZOMBIE_PURGE_MARK * 4096 + PERSONS
    assert {p: _compiles(p) for p in before} == before
    assert s.recoveries == 0
    evicted = [ph["agg_evict_groups"] for tr in list(s.coord.tracer._ring)[-6:]
               for ph in tr.phases.values() if "agg_evict_groups" in ph]
    assert sum(evicted) > 4 * PERSONS, "both aggregates evict every window"
    label_purges = sum(
        c.value for (name, _l), c in GLOBAL_METRICS.counters.items()
        if name == HASH_AGG_PURGES)
    assert label_purges >= purges
    _assert_is_the_oracles(_read(s), _offsets(13), cfg)
    await s.drop_all()


async def test_a_rebuild_says_how_many_groups_it_reinserted():
    """What a rebuild costs is the groups that survive it. The actor's
    phase dict carries `agg_rehash_rows` at a barrier that rebuilt a table
    and at no other: the live groups of the fetch that decided the purge,
    which is what the rebuilt table then holds; the rendered trace prints
    it beside the purge, and `hash_agg_rehash_rows_total` moves by it."""
    cfg = _config()
    s = Session()
    await _deploy(s, cfg)
    await s.tick(1)
    aggs = _aggs(s)

    def series() -> float:
        return sum(c.value for (name, _l), c in GLOBAL_METRICS.counters.items()
                   if name == HASH_AGG_REHASH_ROWS)

    start, rehashed, rebuilds = series(), 0, 0
    for _ in range(12):
        built = {name: agg.rebuilds for name, agg in aggs.items()}
        await s.tick(1)
        tr = s.coord.tracer._ring[-1]
        rebuilt = [agg for name, agg in aggs.items()
                   if agg.rebuilds > built[name]]
        with_rows = [ph for ph in tr.phases.values()
                     if "agg_rehash_rows" in ph]
        assert len(with_rows) == len(rebuilt)
        assert [ph for ph in tr.phases.values() if "agg_purges" in ph] \
            == with_rows, "every rebuild here is a same-capacity purge"
        # each aggregate is its actor's only one: the count is its table's
        assert sorted(ph["agg_rehash_rows"] for ph in with_rows) \
            == sorted(agg._occ_known for agg in rebuilt)
        for ph in with_rows:
            assert 0 < ph["agg_rehash_rows"] <= 2 * PERSONS
            assert (f"zombie purge(s), rehashed {ph['agg_rehash_rows']} "
                    f"groups]") in tr.render()
        if not with_rows:
            assert "rehashed" not in tr.render()
        rehashed += sum(ph["agg_rehash_rows"] for ph in with_rows)
        rebuilds += len(rebuilt)
    assert rebuilds >= 2 and series() - start == rehashed
    assert s.recoveries == 0
    await s.drop_all()


async def test_the_row_form_writes_are_counted_per_actor():
    """The person aggregate's table, the join's left table and the MV hold
    the name's int32 id, and their writes are columnar segments all the
    same: the batch codec takes any fixed-width schema. Every actor whose
    chain holds a state table says how many rows took the row form in a
    checkpoint, 0 included; what is left is a source's offset row."""
    cfg = _config()
    s = Session(store=None)
    await _deploy(s, cfg)
    await s.tick(1)
    tables = [t for ex in drive.executors_of(s, q8.MV)
              for t in _state_tables_of(ex)]
    with_name = [t for t in tables
                 if DataType.VARCHAR in t.schema.data_types]
    assert len(with_name) == 3
    before = {t.table_id: t.row_path_rows for t in tables}
    columnar0, row0 = (STATE_WRITE_KEYS[c].value for c in (True, False))
    await s.tick(3)
    for t in with_name:
        assert t.row_path_rows == 0
    # at least the checkpoint's 512 person groups in, as many out, and the
    # same through the join and the MV: none of them a key of the row form
    assert STATE_WRITE_KEYS[True].value - columnar0 >= 3 * 3 * 2 * PERSONS
    in_rows = sum(t.row_path_rows - before[t.table_id] for t in tables)
    assert STATE_WRITE_KEYS[False].value - row0 == in_rows <= 3 * 2
    phases = s.coord.tracer._ring[-1].phases
    rows = [ph["row_path_rows"] for ph in phases.values()
            if "row_path_rows" in ph]
    assert len(rows) >= 5 and rows.count(0) >= 3
    assert sum(rows) == in_rows // 3
    # the rendered trace names the row form only where it took a row
    assert s.coord.tracer._ring[-1].render().count(
        "state rows in row form") == len(rows) - rows.count(0) == 2
    await s.drop_all()


# ------------------------------------------------- a crash, and a real one

async def test_the_names_survive_crash_and_recover(tmp_path):
    root = str(tmp_path / "hummock")
    cfg = _config()
    s = Session(store=HummockStateStore(LocalFsObjectStore(root)))
    await _deploy(s, cfg)
    await s.tick(4)
    offsets = drive.committed_offsets(s, q8.MV)
    assert offsets == _offsets(4)
    _assert_is_the_oracles(_read(s), offsets, cfg)
    await s.crash()
    del s
    s2 = Session(store=HummockStateStore.open(LocalFsObjectStore(root)))
    await s2.recover()
    _assert_is_the_oracles(_read(s2), offsets, cfg)
    await s2.tick(1)
    offsets = drive.committed_offsets(s2, q8.MV)
    assert offsets == _offsets(5)
    _assert_is_the_oracles(_read(s2), offsets, cfg)
    assert s2.recoveries == 0
    await s2.crash()


async def test_a_recovery_does_not_wait_for_its_purge_programs(
        tmp_path, monkeypatch):
    """A restarted process loads every executable where it is first asked
    for, on the event loop. The purge pair is not called for many
    checkpoints: a recovering aggregate asks for it on a worker thread at
    its INITIAL barrier and waits for it at the end of the first barrier
    after that, not in `recover()`. The purges that follow find jax's
    trace and executable: each program is traced once an aggregate."""
    import threading

    from risingwave_tpu.ops.jit_state import StateJit
    asked = []
    real = StateJit.precompile

    def precompile(self, *args, **kwargs):
        asked.append((self.name, threading.current_thread()
                      is threading.main_thread()))
        return real(self, *args, **kwargs)

    monkeypatch.setattr(StateJit, "precompile", precompile)
    root = str(tmp_path / "hummock")
    cfg = _config()
    s = Session(store=HummockStateStore(LocalFsObjectStore(root)))
    await _deploy(s, cfg)
    await s.tick(3)
    await s.crash()
    del s
    pair = ("hash_agg_live_zombie", "hash_agg_rehash")
    before = {p: _compiles(p) for p in pair}
    del asked[:]
    s2 = Session(store=HummockStateStore.open(LocalFsObjectStore(root)))
    await s2.recover()
    await s2.tick(1)
    assert sorted(asked) == sorted((p, False) for p in pair * 2), \
        "two aggregates, the pair each, neither asked for on the loop"
    assert {p: _compiles(p) - before[p] for p in pair} == {p: 2 for p in pair}
    person = _aggs(s2)["person"]
    for _ in range(8):
        await s2.tick(1)
    assert person.rebuilds >= 1, "a purge ran after the recovery"
    assert {p: _compiles(p) - before[p] for p in pair} == {p: 2 for p in pair}
    _assert_is_the_oracles(_read(s2), _offsets(12), cfg)
    assert s2.recoveries == 0
    await s2.crash()


_WRITER = """
import asyncio, json, sys
sys.path.insert(0, {root!r})
import risingwave_tpu
from risingwave_tpu.common.types import GLOBAL_DICT, DataType
# a string of this process alone, minted before any vocabulary: every
# name's id is one higher than a fresh process would give it
GLOBAL_DICT.get_or_insert("minted-by-the-writer-alone")
from benchmark.queries import q8
from risingwave_tpu.frontend import Session
from risingwave_tpu.state import HummockStateStore, LocalFsObjectStore

async def main():
    s = Session(store=HummockStateStore(LocalFsObjectStore({store!r})))
    for stmt in q8.ddl({cfg!r}, {traffic!r}, {seed}):
        await s.execute(stmt)
    await s.tick(3)
    await s.crash()

asyncio.run(main())
"""

_READER = """
import asyncio, json, sys
sys.path.insert(0, {root!r})
import risingwave_tpu
from risingwave_tpu.common.types import GLOBAL_DICT, DataType
assert len(GLOBAL_DICT) == 0
from benchmark.harness import drive
from benchmark.queries import q8
from risingwave_tpu.frontend import Session
from risingwave_tpu.state import HummockStateStore, LocalFsObjectStore

async def main():
    s = Session(store=HummockStateStore.open(LocalFsObjectStore({store!r})))
    assert GLOBAL_DICT.decode(0) == "minted-by-the-writer-alone"
    await s.recover()
    await s.tick(1)
    print(json.dumps({{"rows": q8.read_mv(s),
                      "offsets": drive.committed_offsets(s, q8.MV),
                      "recoveries": s.recoveries}}))
    await s.crash()

asyncio.run(main())
"""


def test_the_names_survive_a_real_process_restart(tmp_path):
    """One Python process writes the store and dies; another, whose
    GLOBAL_DICT starts EMPTY, opens it (`load_dict_log`), recovers, commits
    one more checkpoint and reads the names. Inside one process
    `Session.crash()` leaves the dictionary in memory, so neither the test
    above nor the benchmark's cell can show this. The writer mints a string
    of its own first, so an id decoded without the log would name the
    person before."""
    cfg = _config()
    fill = dict(root=ROOT, store=str(tmp_path / "hummock"), cfg=cfg,
                traffic=TRAFFIC, seed=SEED)
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    outs = []
    for script in (_WRITER, _READER):
        p = subprocess.run([sys.executable, "-c", script.format(**fill)],
                           env=env, capture_output=True, text=True,
                           timeout=200)
        assert p.returncode == 0, p.stderr[-3000:]
        outs.append(p.stdout)
    out = json.loads(outs[1].strip().splitlines()[-1])
    assert out["offsets"] == _offsets(4) and out["recoveries"] == 0
    got = check.rows_to_cols([tuple(r) for r in out["rows"]], q8.DTYPES)
    _assert_is_the_oracles(got, out["offsets"], cfg)


# ----------------------------------------------------- the oracle catches

async def test_one_altered_name_is_not_correct_by_the_name_column_alone(
        monkeypatch):
    """The source alters one name where it is produced (another person's,
    so a valid dictionary id): the row count and both integer columns still
    agree, `col1_cells_differing` alone says no."""
    cfg = _config()
    want = q8.oracle(_offsets(3), cfg, SEED)
    victim = int(want[0][0]) - nx.FIRST_PERSON_ID   # a person the MV shows
    assert victim < PERSONS
    other = GLOBAL_DICT.get_or_insert("person_999")
    assert nexmark_q8.person_name(np.asarray([want[0][0]]))[0] != "person_999"
    real = nx.NexmarkGenerator.next_chunk

    def altered(self):
        at = self.offset
        chunk = real(self)
        if self.table == "person" and at == 0:
            col = chunk.columns[1]
            col.data = col.data.at[victim].set(other)
        return chunk

    monkeypatch.setattr(nx.NexmarkGenerator, "next_chunk", altered)
    s = Session()
    await _deploy(s, cfg)
    await s.tick(3)
    numbers = _numbers(_read(s), _offsets(3), cfg)
    assert [n["what"] for n in numbers if not n["ok"]] == [
        "col1_cells_differing"]
    assert next(n for n in numbers
                if n["what"] == "col1_cells_differing")["value"] == 1
    await s.drop_all()
