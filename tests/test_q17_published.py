"""TPC-H Q17 'Small-Quantity-Order Revenue' AS PUBLISHED (clause 2.4.17,
Brand#23 / MED BOX) as a streaming MV over a generator that follows clause
4.2.3, through `Session` -> binder (the fusion to the snapshot join-agg, the
fact side pruned to the three columns read, the threshold folded to one INT64
a group) -> actors -> durable state, against the benchmark's numpy oracle
(`benchmark/queries/q17.py`, written from the published text, taking nothing
from the engine); the generator against the spec's own numbers and against
the benchmark's numpy copy; the tie a NUMERIC threshold leaves out; and what
the executor's barrier fetches and publishes.
"""

import json
import threading
from fractions import Fraction

import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness import check, drive
from benchmark.queries import q17
from benchmark.reference import tpch
from risingwave_tpu.common.types import GLOBAL_DICT, DataType
from risingwave_tpu.connectors import tpch as connector
from risingwave_tpu.connectors.tpch import TPCH_SCHEMAS, TpchGenerator
from risingwave_tpu.frontend import Session
from risingwave_tpu.state import HummockStateStore, LocalFsObjectStore
from risingwave_tpu.stream.snapshot_join_agg import SnapshotJoinAggExecutor
from risingwave_tpu.utils import d2h

SEED = 15                      # at SF 0.001 two of the 200 parts qualify
LINEITEMS, PARTS = 960, 256    # a checkpoint, near TPC-H's 30 : 1
TRAFFIC = {"chunk_size": {"lineitem": LINEITEMS, "part": PARTS},
           "chunks_per_interval": {"lineitem": 1, "part": 1}}
# where each reference column's small integers index into
STRINGS = {"p_name": connector.COLOURS, "p_mfgr": tpch.MFGRS,
           "p_brand": tpch.BRANDS, "p_type": connector.TYPES,
           "p_container": tpch.CONTAINERS, "p_comment": connector.NOUNS,
           "l_returnflag": tpch.RETURNFLAGS, "l_linestatus": tpch.LINESTATUS,
           "l_shipinstruct": connector.INSTRUCTIONS,
           "l_shipmode": connector.MODES, "l_comment": connector.NOUNS}


def _config(capacity: int = 1 << 14, sf: float = 0.001) -> dict:
    return {"generator": {"scale_factor": sf, "brand": "Brand#23",
                          "container": "MED BOX"},
            "session_set": {"streaming_join_capacity": capacity,
                            "streaming_agg_capacity": 64,
                            "streaming_watchdog": 1}}


async def _deploy(s: Session, cfg: dict, seed: int = SEED):
    for stmt in q17.ddl(cfg, TRAFFIC, seed):
        await s.execute(stmt)
    snap, = [ex for ex in drive.executors_of(s, q17.MV)
             if isinstance(ex, SnapshotJoinAggExecutor)]
    return snap


def _engine_rows(table: str, start: int, n: int, **kw) -> dict:
    gen = TpchGenerator(table, chunk_size=n, start_offset=start, **kw)
    return {f.name: np.asarray(c.data) for f, c in
            zip(gen.schema, gen.next_chunk().columns)}


def _assert_is_the_oracles(rows: list, offsets: dict, cfg: dict,
                           seed: int = SEED) -> dict:
    got = check.rows_to_cols(rows, q17.DTYPES)
    numbers = check.compare(got, q17.oracle(offsets, cfg, seed),
                            q17.FLOAT_RTOL)
    assert all(n["ok"] for n in numbers), numbers
    return numbers


# ------------------------------------------------- the generator, by the spec

@pytest.mark.parametrize("partkey, dollars", [
    (1, "901.00"), (10, "910.01"), (1000, "901.00"), (200000, "1100.00")])
def test_retail_price_is_clause_4_2_3s(partkey, dollars):
    """(90000 + ((P_PARTKEY / 10) mod 20001) + 100 x (P_PARTKEY mod 1000))
    / 100, worked by hand: 901.00 is dbgen's own price of part 1."""
    want = int(Fraction(dollars) * 100)
    row = _engine_rows("part", partkey - 1, 256)
    assert row["p_partkey"][0] == partkey
    assert row["p_retailprice"][0] == want
    assert tpch.retail_price_cents(np.asarray([partkey]))[0] == want


def test_brands_and_containers_each_near_their_share():
    """SF 1's 200,000 parts: 25 brands and 40 containers, every one within
    five standard deviations of its share, every one of the 1,000 pairs
    present, and a brand's M is its manufacturer's."""
    p = _engine_rows("part", 0, 200_000, seed=7)
    assert (p["p_partkey"] == np.arange(1, 200_001)).all()
    for name, vocab in (("p_brand", tpch.BRANDS),
                        ("p_container", tpch.CONTAINERS)):
        ids = np.asarray([GLOBAL_DICT.get_or_insert(s) for s in vocab])
        counts = np.asarray([(p[name] == i).sum() for i in ids])
        assert counts.sum() == 200_000, f"{name}: a string outside the list"
        share = 200_000 / len(vocab)
        assert np.abs(counts - share).max() < 5 * np.sqrt(share), counts
    pairs = np.unique(np.stack([p["p_brand"], p["p_container"]]), axis=1)
    assert pairs.shape[1] == 25 * 40
    brand = np.asarray([GLOBAL_DICT.decode(int(i)) for i in p["p_brand"][:500]])
    mfgr = np.asarray([GLOBAL_DICT.decode(int(i)) for i in p["p_mfgr"][:500]])
    assert all(b[6] == m[-1] for b, m in zip(brand, mfgr))
    assert 1 <= p["p_size"].min() and p["p_size"].max() == 50


def test_lineitems_name_every_part_uniformly_and_price_by_its_part():
    sf = 0.01                                       # 2,000 parts
    li = _engine_rows("lineitem", 0, 200_000, scale_factor=sf, seed=7)
    assert li["l_partkey"].min() == 1 and li["l_partkey"].max() == 2000
    per_part = np.bincount(li["l_partkey"], minlength=2001)[1:]
    assert np.abs(per_part - 100).max() < 5 * np.sqrt(100), per_part
    per_q = np.bincount(li["l_quantity"], minlength=51)[1:]
    assert li["l_quantity"].min() == 1 and per_q.shape[0] == 50
    assert np.abs(per_q - 4000).max() < 5 * np.sqrt(4000)
    assert (li["l_extendedprice"] == li["l_quantity"]
            * tpch.retail_price_cents(li["l_partkey"])).all()
    assert 1 <= li["l_suppkey"].min() and li["l_suppkey"].max() <= 100
    assert (li["l_receiptdate"] > li["l_shipdate"]).all()
    assert li["l_shipdate"].min() > tpch.STARTDATE
    assert li["l_receiptdate"].max() <= tpch.ENDDATE
    # "N" exactly where the line was received after CURRENTDATE
    n_id = GLOBAL_DICT.get_or_insert("N")
    assert ((li["l_returnflag"] == n_id)
            == (li["l_receiptdate"] > tpch.CURRENTDATE)).all()
    assert (tpch.STARTDATE, tpch.ENDDATE, tpch.CURRENTDATE) == (
        connector.STARTDATE, connector.ENDDATE, connector.CURRENTDATE)


@pytest.mark.parametrize("table", ["part", "lineitem"])
@pytest.mark.parametrize("start", [0, 6_001_215 - 100])
def test_the_numpy_reference_is_the_engines_generator(table, start):
    """Prefix for prefix, every one of the 9 and 16 columns, strings decoded
    through the dictionary, at two seeds (one past 2^31)."""
    assert [len(TPCH_SCHEMAS[t]) for t in ("part", "lineitem")] == [9, 16]
    for seed in (3, 2147483659):
        got = _engine_rows(table, start, 1000, scale_factor=0.05, seed=seed)
        want = (tpch.part(start, 1000, seed=seed) if table == "part"
                else tpch.lineitem(start, 1000, seed=seed,
                                   scale_factor=0.05))
        assert list(got) == list(want)
        for f in TPCH_SCHEMAS[table]:
            g, w = got[f.name], want[f.name]
            assert g.dtype == f.data_type.np_dtype
            if f.data_type is DataType.VARCHAR:
                g = np.asarray([GLOBAL_DICT.decode(int(i)) for i in g])
                w = np.asarray(STRINGS[f.name])[w]
            assert (g == w).all(), (f.name, seed)


def test_a_seed_is_other_data_from_the_same_program():
    """`seed` is a dynamic argument: two seeds, one compile (a static seed
    recompiled the nexmark generator for 46 s a run: PERF.md)."""
    n = 1234                                   # a shape no other test uses
    before = connector.gen_lineitem_columns._cache_size()
    a = _engine_rows("lineitem", 0, n, scale_factor=0.25, seed=1)
    b = _engine_rows("lineitem", 0, n, scale_factor=0.25, seed=2**31 + 11)
    assert connector.gen_lineitem_columns._cache_size() == before + 1
    assert (a["l_orderkey"] == b["l_orderkey"]).all()
    assert (a["l_partkey"] != b["l_partkey"]).mean() > 0.99
    assert (a["l_quantity"] != b["l_quantity"]).mean() > 0.9


# ------------------------------------------------------------ the statement

def test_the_statement_is_the_published_text_decorrelated():
    *sets, part, lineitem, mv = q17.ddl(_config(), TRAFFIC, SEED)
    assert all(s.startswith("SET ") for s in sets)
    assert "primary_key='p_partkey'" in part and "scale_factor=0.001" in part
    assert f"seed={SEED}" in part and f"seed={SEED}" in lineitem
    assert "primary_key" not in lineitem
    text = " ".join(mv.split())
    # clause 2.4.17's predicates, each once: the equi join, the two
    # substitution parameters, the correlated threshold (as a join on the
    # correlation key), and the select list
    for predicate in (
            "SELECT sum(L.l_extendedprice) / 7.0 AS avg_yearly",
            "P.p_partkey = L.l_partkey",
            "P.p_brand = 'Brand#23'", "P.p_container = 'MED BOX'",
            "0.2 * avg(l_quantity)", "FROM lineitem GROUP BY l_partkey",
            "A.agg_partkey = L.l_partkey",
            "L.l_quantity < A.avg_quantity"):
        assert text.count(predicate) == 1, predicate
    # and tests/test_tpch_q17.py runs the same words
    import test_tpch_q17
    assert test_tpch_q17.Q17 == mv


async def test_the_plan_prunes_the_fact_side_and_folds_the_threshold():
    """lineitem's 16 columns reach the store as the 3 the plan reads (the
    store reserves `capacity` rows for every column it is given), part's 9
    as key + the two filter columns, and `l_quantity < 0.2 * avg(...)` is
    one INT64 comparison against ceil(sum / (5 x count))."""
    s = Session()
    snap = await _deploy(s, _config())
    assert [f.name for f in snap._fact_schema] == [
        "l_partkey", "l_quantity", "l_extendedprice"]
    assert [f.name for f in snap.inputs[1].schema] == [
        "p_partkey", "p_brand", "p_container"]
    assert len(snap._fcols) == 3 and snap.capacity == 1 << 14
    assert snap.dim_capacity == 64
    assert [(c.kind.name, c.ret_type.name) for c in snap.sub_agg_calls] == [
        ("SUM", "INT64"), ("COUNT", "INT64")]
    assert repr(list(snap.sub_items)) == (
        "[neg(divide(neg(multiply(lit(1), $0)), multiply(lit(5), $1)))]")
    assert repr(snap.residue) == "less_than($1, $3)"
    assert all(e.ret_type is DataType.INT64 for e in snap.sub_items)
    await s.drop_all()


# ------------------------------------------- the view, durable, and a crash

async def test_the_view_is_the_oracles_and_survives_a_crash(tmp_path):
    """The configuration's own DDL at SF 0.001, durable: the MV equals the
    oracle at the committed offsets read live, after `crash()` + `recover()`
    over the store reopened from disk, and after two more checkpoints."""
    root = str(tmp_path / "hummock")
    cfg = _config()
    s = Session(store=HummockStateStore(LocalFsObjectStore(root)))
    await _deploy(s, cfg)
    await s.tick(4)
    offsets = drive.committed_offsets(s, q17.MV)
    assert offsets == {"lineitem": 4 * LINEITEMS, "part": 4 * PARTS}
    _assert_is_the_oracles(q17.read_mv(s), offsets, cfg)
    await s.crash()
    del s
    s2 = Session(store=HummockStateStore.open(LocalFsObjectStore(root)))
    await s2.recover()
    _assert_is_the_oracles(q17.read_mv(s2), offsets, cfg)
    await s2.tick(2)
    offsets = drive.committed_offsets(s2, q17.MV)
    assert offsets == {"lineitem": 6 * LINEITEMS, "part": 6 * PARTS}
    _assert_is_the_oracles(q17.read_mv(s2), offsets, cfg)
    await s2.crash()


def test_the_oracle_prints_its_tie_count(capsys):
    cfg = _config()
    q17.oracle({"lineitem": 4000, "part": 200}, cfg, SEED)
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["phase"] == "oracle" and line["ties_met"] >= 0
    assert line["rows_summed"] > 0 and line["qualifying_parts"] == 2
    assert line["avg_yearly_dollars"] == line["sum_cents"] / 100 / 7.0


# ------------------------------------------------------------------ the tie

def _jsonl_sources(tmp_path, parts: list, lineitems: list) -> list:
    files = {}
    for name, rows in (("part", parts), ("lineitem", lineitems)):
        files[name] = tmp_path / f"{name}.jsonl"
        files[name].write_text("".join(json.dumps(r) + "\n" for r in rows))
    return [
        f"CREATE SOURCE part WITH (connector='jsonl', path='{files['part']}'"
        ", columns='p_partkey int64, p_brand varchar, p_container varchar',"
        " primary_key='p_partkey')",
        f"CREATE SOURCE lineitem WITH (connector='jsonl', "
        f"path='{files['lineitem']}', columns='l_partkey int64, "
        "l_quantity int64, l_extendedprice int64')"]


def _line(partkey: int, quantity: int) -> dict:
    return {"l_partkey": partkey, "l_quantity": quantity,
            "l_extendedprice": quantity * 100_000 + partkey}


PARTS_ROWS = [
    {"p_partkey": 1, "p_brand": "Brand#23", "p_container": "MED BOX"},
    {"p_partkey": 2, "p_brand": "Brand#23", "p_container": "MED BOX"},
    {"p_partkey": 3, "p_brand": "Brand#12", "p_container": "MED BOX"}]
# part 1: quantities 1 and 9, average 5, threshold 1.0: the row with 1 is a
# TIE and must not count. part 2: 1, 9, 9, 11: average 7.5, threshold 1.5:
# the row with 1 counts. part 3 does not pass the filter.
TIE_LINES = ([_line(1, 1), _line(1, 9)]
             + [_line(2, q) for q in (1, 9, 9, 11)] + [_line(3, 1)] * 3)


async def test_a_tie_does_not_count(tmp_path):
    """`<` is strict and upstream's threshold is NUMERIC: 5 x 1 x 2 = 10 is
    not under the sum 10. The planner decides it in INT64 (a FLOAT64 `0.2 x
    5.0` happens to round to 1.0 on the CPU; on a TPU's pair of f32 it need
    not): ceil(10 / (5 x 2)) = 1 and 1 < 1 is false."""
    s = Session()
    for stmt in _jsonl_sources(tmp_path, PARTS_ROWS, TIE_LINES):
        await s.execute(stmt)
    await s.execute(q17.STATEMENT.format(brand="Brand#23",
                                         container="MED BOX"))
    snap, = [ex for ex in drive.executors_of(s, q17.MV)
             if isinstance(ex, SnapshotJoinAggExecutor)]
    assert repr(snap.residue) == "less_than($1, $3)"
    await s.tick(3)
    got, = q17.read_mv(s)
    only_part_2s = _line(2, 1)["l_extendedprice"]
    assert got[0] == only_part_2s / 7.0
    # the oracle's arithmetic on the same rows: one tie met, one row summed
    part = {"p_partkey": np.asarray([1, 2, 3]),
            "p_brand": np.asarray([tpch.BRANDS.index("Brand#23")] * 2
                                  + [tpch.BRANDS.index("Brand#12")]),
            "p_container": np.asarray(
                [tpch.CONTAINERS.index("MED BOX")] * 3)}
    li = {k: np.asarray([r[k] for r in TIE_LINES]) for k in TIE_LINES[0]}
    r = q17.small_quantity_revenue(part, li, "Brand#23", "MED BOX")
    assert (r["ties"], r["rows"], r["cents"]) == (1, 1, only_part_2s)
    await s.drop_all()


@pytest.mark.parametrize("sql_op, holds", [
    ("<", lambda q, r: q < r), ("<=", lambda q, r: q <= r),
    (">", lambda q, r: q > r), (">=", lambda q, r: q >= r)])
async def test_every_ordering_against_an_exact_threshold(tmp_path, sql_op,
                                                         holds):
    """`q OP 0.3 * avg(q)` over groups that hold ties (3/10 of 10, 20, 30
    and of the non-integer 25/3, 55/3), written both ways round: the rows
    kept are those an exact rational comparison keeps."""
    groups = {1: [3, 17], 2: [6, 34, 20], 3: [9, 51, 30, 30],
              4: [2, 3, 20], 5: [5, 6, 44]}
    parts = [{"p_partkey": k, "p_brand": "Brand#23",
              "p_container": "MED BOX"} for k in groups]
    lines = [_line(k, q) for k, qs in groups.items() for q in qs]
    want = sum(_line(k, q)["l_extendedprice"]
               for k, qs in groups.items() for q in qs
               if holds(q, Fraction(3, 10) * Fraction(sum(qs), len(qs))))
    flipped = {"<": ">", "<=": ">=", ">": "<", ">=": "<="}[sql_op]
    s = Session()
    for stmt in _jsonl_sources(tmp_path, parts, lines):
        await s.execute(stmt)
    body = ("SELECT sum(L.l_extendedprice) AS v FROM lineitem L "
            "JOIN part P ON P.p_partkey = L.l_partkey "
            "JOIN (SELECT l_partkey AS k, avg(l_quantity) * 0.3 AS thr "
            "FROM lineitem GROUP BY l_partkey) A ON A.k = L.l_partkey AND ")
    await s.execute(f"CREATE MATERIALIZED VIEW a AS {body}"
                    f"L.l_quantity {sql_op} A.thr")
    await s.execute(f"CREATE MATERIALIZED VIEW b AS {body}"
                    f"A.thr {flipped} L.l_quantity")
    for mv in ("a", "b"):
        snap, = [ex for ex in drive.executors_of(s, mv)
                 if isinstance(ex, SnapshotJoinAggExecutor)]
        assert all(e.ret_type is DataType.INT64 for e in snap.sub_items)
    await s.tick(3)
    assert s.query("SELECT v FROM a") == [(want,)]
    assert s.query("SELECT v FROM b") == [(want,)]
    assert 0 < want < sum(ln["l_extendedprice"] for ln in lines)
    await s.drop_all()


def test_the_limit_lies_between_its_two_readings():
    """FLOAT_RTOL 1e-12: a division in one f32 and one row on the wrong side
    of `<` both fail it; the last bit of a pair-of-f32 quotient passes."""
    cfg = _config()
    offsets = {"lineitem": 4000, "part": 200}
    want = q17.oracle(offsets, cfg, SEED)
    ok = lambda got: check.compare(got, want, q17.FLOAT_RTOL)[-1]  # noqa
    assert ok(want)["ok"] and ok(want)["what"] == "col0_max_rel_diff"
    f32 = ok([want[0].astype(np.float32).astype(np.float64)])
    assert not f32["ok"] and 1e-9 < f32["value"] < 1e-7
    assert ok([want[0] * (1 + 2.0 ** -46)])["ok"]
    part, li = q17.events(offsets, cfg, SEED)
    r = q17.small_quantity_revenue(part, li, "Brand#23", "MED BOX")
    one_row_more = (r["cents"] + int(li["l_extendedprice"].min())) / 7.0
    off = ok([np.asarray([one_row_more])])
    assert not off["ok"] and off["value"] > 1e-4
    assert not ok([np.asarray([np.nan])])["ok"]        # a NULL sum


# -------------------------------------- what the barrier fetches and counts

async def test_the_phase_dict_carries_the_stores_counts_from_one_fetch(
        monkeypatch):
    """`snapshot_rows` / `snapshot_capacity` / `snapshot_dim_rows` come from
    the counts fetch the barrier makes anyway: a checkpoint of the executor
    is that fetch and (durable) one packed fetch of the new rows, both on
    worker threads, whatever the phase dict carries."""
    s = Session()
    snap = await _deploy(s, _config())
    await s.tick(2)
    fetches = []
    real = d2h._in_wait_span

    def spy(fetch, nbytes):
        host = real(fetch, nbytes)
        fetches.append((threading.get_ident(), nbytes(host)))
        return host

    monkeypatch.setattr(d2h, "_in_wait_span", spy)
    loop_thread = threading.get_ident()
    before = (snap._counts.dispatches, snap._persist_pack.dispatches)
    await s.tick(1)
    monkeypatch.undo()
    assert (snap._counts.dispatches - before[0],
            snap._persist_pack.dispatches - before[1]) == (1, 1)
    mine = [f for f in fetches if f[1] == 5 * 4
            or f[1] == (4 * 1024 + 64) * 8]
    assert len(mine) == 2, fetches       # int32[5]; 3 columns + vbits + dim
    assert all(thread != loop_thread for thread, _ in fetches)
    ph, = [p for p in s.coord.tracer._ring[-1].phases.values()
           if "snapshot_rows" in p]
    assert ph["snapshot_rows"] == 3 * LINEITEMS
    assert ph["snapshot_capacity"] == 1 << 14
    assert ph["snapshot_dim_rows"] == 2            # the two parts that pass
    assert snap.take_phase_counts() == {}, "taken once an interval"
    text = s.coord.tracer._ring[-1].render()
    assert f"snapshot holds {3 * LINEITEMS} of {1 << 14} rows, 2 dim" in text
    await s.drop_all()


class _Table:
    def __init__(self):
        self.rows, self.commits = [], 0

    def write_chunk_columns(self, ops, cols, vis):
        assert ops.shape == vis.shape == cols[0].shape
        self.rows.append([np.asarray(c) for c in cols])

    def commit(self, epoch):
        self.commits += 1


class _Input:
    def __init__(self, schema):
        self.schema = schema

    def fence_tokens(self):
        return []


async def test_a_persist_window_past_the_stores_end_is_shifted_back():
    """The pack slices a power-of-two window at a dynamic offset; where the
    rows gained start so late that the window would pass the store's end it
    starts earlier and the host skips what the last checkpoint wrote — with
    a FLOAT64, a FLOAT32 and a NULL-carrying column in the store."""
    from risingwave_tpu.common.types import schema
    from risingwave_tpu.common.epoch import EpochPair
    from risingwave_tpu.stream.message import Barrier
    sch = schema(("k", DataType.INT64), ("x", DataType.FLOAT64),
                 ("y", DataType.FLOAT32))
    tables = (_Table(), _Table())
    ex = SnapshotJoinAggExecutor(
        _Input(sch), _Input(schema(("k", DataType.INT64))),
        fact_key=0, dim_key=0, sub_agg_calls=[], sub_items=[], residue=None,
        final_agg_calls=[], final_items=[], out_names=[], out_types=[],
        capacity=256, dim_capacity=64, state_tables=tables)
    n, lo = 170, 30                  # 140 rows gained: a 256-row window
    k = np.arange(256, dtype=np.int64) * 7
    ex._fcols = (jnp.asarray(k), jnp.asarray(k * 0.5),
                 jnp.asarray((k * 0.25).astype(np.float32)))
    ex._fvalids = (jnp.ones(256, bool), jnp.asarray(k % 3 != 0),
                   jnp.ones(256, bool))
    ex._dkeys = jnp.arange(64, dtype=jnp.int64) + 1000
    ex._persist_cursor = [lo, 60]
    pack = ex._dispatch_persist(n, 63)
    assert pack[1] == (256, lo, n) and pack[2] == (64, 60, 63)
    barrier = Barrier(EpochPair(2, 1))
    await ex._persist(barrier, pack)
    pos, kk, x, y, vbits = tables[0].rows[0]
    assert (pos == np.arange(lo, n)).all() and (kk == k[lo:n]).all()
    assert x.dtype == np.float64 and (x == k[lo:n] * 0.5).all()
    assert y.dtype == np.float32 and (y == k[lo:n] * 0.25).all()
    assert (vbits == 0b101 + 2 * (k[lo:n] % 3 != 0)).all()
    dpos, dk = tables[1].rows[0]
    assert (dpos == [60, 61, 62]).all() and (dk == [1060, 1061, 1062]).all()
    assert ex._persist_cursor == [n, 63]
    assert [t.commits for t in tables] == [1, 1]
