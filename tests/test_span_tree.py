"""The span tree of one checkpoint (utils/trace.py), the StateJit program
registry (ops/jit_state.py) and the benchmark readers over both
(benchmark/layers/, benchmark/harness/span_readers.py): NEXMark q7 at the
benchmark cell's rehearsal size, durable over Hummock, two checkpoints.
"""

import math
import re

import jax
import jax.numpy as jnp
import pytest

from benchmark import trace_reduce
from benchmark.harness import drive, span_readers, spec
from risingwave_tpu.frontend import Session
from risingwave_tpu.ops.jit_state import (
    PROGRAMS, Program, StateJit, programs_by_id)
from risingwave_tpu.utils import trace
from risingwave_tpu.utils.metrics import (
    GLOBAL_METRICS, JIT_COMPILES, TRACE_SPANS_DROPPED)
from risingwave_tpu.utils.trace import (
    SPAN_LOG, EpochTrace, Span, SpanLog, traces_to_chrome)

SPAN_READERS = ("inject_to_commit_p50_s", "flush_queue_s_per_ckpt",
                "flush_wait_s_per_ckpt", "flush_host_s_per_ckpt",
                "dispatch_host_s_per_ckpt", "persist_wait_s_per_ckpt",
                "fence_s_per_ckpt")
DEV_READERS = ("join_dev_s_per_ckpt", "agg_dev_s_per_ckpt",
               "persist_dev_s_per_ckpt")
_Q7: dict = {}


async def _q7(tmp_path_factory) -> dict:
    """Two checkpoints of the q7.sat cell at rehearsal size, once per
    process: the harness's records, the tracer's traces and the trees."""
    if not _Q7:
        cell = spec.Cell(spec.load_benchmark(), "q7.sat", rehearsal=True)
        path = str(tmp_path_factory.mktemp("q7_spans"))
        s, _, _ = await drive.deploy(cell, 2147483659, path)
        stamps = drive.Stamps(s.coord)
        recs = [await drive.checkpoint(
            s, cell.query.MV, stamps,
            {t: (i + 1) * q for t, q in cell.quotas.items()})
            for i in range(2)]
        await s.coord.drain_uploads()
        traces = {t.epoch: t for t in s.coord.tracer.recent()}
        _Q7.update(recs=recs, traces=[traces[r["epoch"]] for r in recs],
                   trees=[SPAN_LOG.spans(r["epoch"]) for r in recs],
                   run={"window": {"checkpoints": recs}, "trace": None})
        await s.crash()
    return _Q7


def _one(spans, name):
    found = [sp for sp in spans if sp.name == name]
    assert len(found) == 1, (name, found)
    return found[0]


# ------------------------------------------------------------ the tree

async def test_every_span_has_its_epoch_and_a_parent_in_it(
        tmp_path_factory):
    q7 = await _q7(tmp_path_factory)
    for rec, spans in zip(q7["recs"], q7["trees"]):
        sids = {sp.sid for sp in spans}
        assert len(sids) == len(spans)
        roots = [sp for sp in spans if sp.parent == 0]
        assert [sp.name for sp in roots] == ["checkpoint"]
        for sp in spans:
            assert sp.epoch == rec["epoch"]
            assert sp.t1_ns >= sp.t0_ns
            assert sp.parent == 0 or sp.parent in sids, sp


async def test_children_lie_inside_their_parents(tmp_path_factory):
    """All of them end inside; the actors' polls and waits, children of
    `collect`, may begin before the inject: they are the interval's work."""
    q7 = await _q7(tmp_path_factory)
    for spans in q7["trees"]:
        by_sid = {sp.sid: sp for sp in spans}
        collect = _one(spans, "collect")
        for sp in spans:
            if sp.parent == 0:
                continue
            par = by_sid[sp.parent]
            assert sp.t1_ns <= par.t1_ns + 1000, (sp, par)
            if par is not collect:
                assert sp.t0_ns >= par.t0_ns - 1000, (sp, par)


async def test_collect_queue_and_flush_tile_the_checkpoint(
        tmp_path_factory):
    q7 = await _q7(tmp_path_factory)
    for spans in q7["trees"]:
        root, collect = _one(spans, "checkpoint"), _one(spans, "collect")
        queue, flush = _one(spans, "flush.queue"), _one(spans, "flush")
        assert {collect.parent, queue.parent, flush.parent} == {root.sid}
        assert collect.t0_ns == root.t0_ns and flush.t1_ns == root.t1_ns
        assert collect.t1_ns - 5e6 <= queue.t0_ns <= collect.t1_ns
        assert queue.t1_ns == flush.t0_ns
        parts = sum(sp.t1_ns - sp.t0_ns for sp in (collect, queue, flush))
        assert abs(parts - (root.t1_ns - root.t0_ns)) < 5e6


async def test_the_tree_names_what_the_issue_names(tmp_path_factory):
    q7 = await _q7(tmp_path_factory)
    for spans in q7["trees"]:
        by_sid = {sp.sid: sp for sp in spans}
        names = {sp.name for sp in spans}
        assert {"actor.apply", "actor.persist", "actor.fence",
                "actor.input_wait", "d2h_wait", "flush.seal",
                "flush.upload", "flush.commit"} <= names
        assert {"dispatch:hash_agg_apply", "dispatch:sorted_join_diff",
                "dispatch:sorted_join_apply_counted"} <= names
        stages = [sp for sp in spans if sp.name.startswith("flush.stage:")]
        assert stages and all(by_sid[sp.parent].name == "flush"
                              for sp in stages)
        for sp in spans:
            if sp.name.startswith("dispatch:"):
                assert by_sid[sp.parent].name in ("actor.apply",
                                                  "actor.persist", "collect")
                assert isinstance(sp.owner, int)
            if sp.name == "d2h_wait":
                assert sp.count > 0
                assert by_sid[sp.parent].name.startswith(
                    ("actor.", "flush.stage:"))
            if sp.name.startswith("actor."):
                assert isinstance(sp.owner, int)
        # the join's diff is fetched on the loop thread, the agg's dirty
        # groups by the uploader's worker thread
        owners = {sp.owner for sp in spans if sp.name == "d2h_wait"}
        assert "uploader" in owners and len(owners) > 1


async def test_the_new_phase_keys_are_parts_of_the_old(tmp_path_factory):
    q7 = await _q7(tmp_path_factory)
    for rec, spans in zip(q7["recs"], q7["trees"]):
        assert rec["phases"]
        for actor, ph in rec["phases"].items():
            polls = sum(sp.t1_ns - sp.t0_ns for sp in spans
                        if sp.owner == actor
                        and sp.name in ("actor.apply", "actor.persist"))
            assert ph["align_ns"] == ph["input_wait_ns"] + ph["fence_ns"]
            assert ph["apply_ns"] + ph["persist_ns"] <= polls
            assert ph["dispatch_ns"] <= polls
            assert ph["apply_wait_ns"] + ph["persist_wait_ns"] <= polls
            by_sid = {sp.sid: sp for sp in spans}
            if not [sp for sp in spans if sp.owner == actor
                    and sp.name == "actor.input_wait"
                    and by_sid[sp.parent].name == "collect"]:
                # no wait that began before its poll: a join's two pulls
                # wait beside each other's compute, and a poll then takes
                # off more waiting than it held
                assert ph["dispatch_ns"] <= ph["apply_ns"] \
                    + ph["persist_ns"]
                assert ph["persist_wait_ns"] <= ph["persist_ns"]
                assert ph["apply_wait_ns"] <= ph["apply_ns"]
        assert max(p["dispatch_ns"] for p in rec["phases"].values()) > 0
        assert max(p["persist_wait_ns"] for p in rec["phases"].values()) > 0


async def test_a_trace_in_the_ring_holds_the_logs_spans(tmp_path_factory):
    q7 = await _q7(tmp_path_factory)
    for t, spans in zip(q7["traces"], q7["trees"]):
        assert {sp.sid for sp in t.spans} == {sp.sid for sp in spans}
        assert _one(spans, "checkpoint").sid == t.root_sid
        assert _one(spans, "collect").sid == t.collect_sid
        txt = t.render()
        assert "inject -> commit" in txt and "flush.stage:" in txt
        assert re.search(r"sorted_join_apply_counted \dx \d", txt)


async def test_round_trip_through_dict_and_chrome(tmp_path_factory):
    q7 = await _q7(tmp_path_factory)
    for t in q7["traces"]:
        back = EpochTrace.from_dict(t.to_dict())
        assert back.to_dict() == t.to_dict()
        assert (back.root_sid, back.collect_sid) == (t.root_sid,
                                                     t.collect_sid)
        # offsets from inject, as every other time in the wire form
        assert _one(back.spans, "collect").t0_ns == 0
        assert [(sp.name, sp.parent, sp.owner, sp.sid, sp.count,
                 sp.t1_ns - sp.t0_ns) for sp in back.spans] == [
            (sp.name, sp.parent, sp.owner, sp.sid, sp.count,
             sp.t1_ns - sp.t0_ns) for sp in t.spans]
    events = traces_to_chrome(q7["traces"])
    assert all(e["ts"] >= 0 and e["dur"] >= 0 for e in events)
    for t in q7["traces"]:
        mine = [e for e in events if e["args"].get("epoch") == t.epoch
                and "sid" in e["args"]]
        assert {e["args"]["sid"] for e in mine} == set(t.span_map)
        for e in mine:
            sp = t.span_map[e["args"]["sid"]]
            assert e["tid"] == (sp.owner if isinstance(sp.owner, int)
                                else 0)
        # the flush's real spans stand in for the slices laid end to end
        assert not [e for e in events
                    if e["name"] == f"seal {t.epoch}"]


# ------------------------------------------------------- the log's bound

def test_the_log_drops_whole_epochs_from_its_old_end_and_counts_them():
    log = SpanLog(max_spans=10)
    before = TRACE_SPANS_DROPPED.value
    for epoch in (1, 2, 3):
        log.put(*(Span(epoch, "x", 0, 1, 0, 1, 100 * epoch + i)
                  for i in range(4)))
    assert log.epochs() == [2, 3]
    assert log.spans(1) == [] and len(log.spans(2)) == 4
    assert TRACE_SPANS_DROPPED.value - before == 4
    # one epoch larger than the bound stays whole
    log.put(*(Span(4, "y", 0, 1, 0, 1, 1000 + i) for i in range(12)))
    assert log.epochs() == [4] and len(log.spans(4)) == 12
    assert TRACE_SPANS_DROPPED.value - before == 12
    # a span whose sid the epoch has replaces it
    log.put(Span(4, "y", 0, 1, 0, 99, 1000))
    assert len(log.spans(4)) == 12 and log.spans(4)[0].t1_ns == 99


def test_the_default_log_holds_a_benchmark_window():
    """q7.sat commits 83 checkpoints a window and a few at warm-up; a tree
    at bench widths is under 100 spans."""
    assert SPAN_LOG.max_spans >= 3 * (83 + 8) * 100


def test_a_scope_keeps_an_intervals_first_spans_and_counts_the_rest():
    sc = trace.SpanScope(7)
    for i in range(trace.MAX_PENDING + 5):
        sc.leaf("x", i, i + 1)
    before = TRACE_SPANS_DROPPED.value
    log = SpanLog()
    sc.flush(11, 3, log)
    assert len(log.spans(11)) == trace.MAX_PENDING
    assert all(sp.parent == 3 and sp.owner == 7 for sp in log.spans(11))
    assert TRACE_SPANS_DROPPED.value - before == 5
    assert sc.pending == [] and sc.dropped == 0


async def test_metric_level_off_records_no_span():
    s = Session()
    await s.execute("SET metric_level = off")
    await s.execute("CREATE SOURCE bid WITH (connector='nexmark', "
                    "table='bid', chunk_size=128, rate_limit=128)")
    await s.execute("CREATE MATERIALIZED VIEW off_m AS SELECT auction, "
                    "count(*) AS n FROM bid GROUP BY auction")
    await s.tick(2)
    for t in s.coord.tracer.recent():
        assert t.spans == [] and t.root_sid == 0
        assert SPAN_LOG.spans(t.epoch) == []
    await s.execute("SET metric_level = info")
    await s.tick(2)
    t = s.coord.tracer.recent()[-1]
    assert {"checkpoint", "collect", "actor.persist"} <= {
        sp.name for sp in t.spans}
    await s.drop_all()


# ------------------------------------------------- the program registry

def test_a_statejit_is_still_the_module_the_benchmark_sums():
    """`benchmark/trace_reduce.py` finds the StateJit programs by their XLA
    module's name; JAX derives it from the jitted function's `__name__`."""
    sj = StateJit(lambda x: x + 1, name="renamed_step")
    x = jnp.arange(4)
    sj(x)
    module = sj._jitted.lower(x).compile().runtime_executable() \
        .hlo_modules()[0].name
    assert module == "jit_traced"
    assert trace_reduce.STATEJIT_MODULE.match(module)
    assert trace_reduce.STATEJIT_MODULE.match(module + "(1234567890)")
    assert span_readers.MODULE_ID.match(module + "(1234567890)")


def test_every_op_carries_its_statejits_name():
    sj = StateJit(lambda x: jnp.cumsum(x) * 2, name="scoped_step")
    hlo = sj._jitted.lower(jnp.arange(8)).compile().as_text()
    ops = re.findall(r'op_name="([^"]*)"', hlo)
    assert [op for op in ops if "/scoped_step/" in op], ops


def test_two_static_signatures_are_two_programs_and_no_compile():
    sj = StateJit(lambda st, x, side: (st + x.sum() * (side + 1), x),
                  static_argnames=("side",), donate_argnums=(0,),
                  name="two_sided_step")
    x = jnp.arange(4.0)
    n0, jit0 = len(PROGRAMS), JIT_COMPILES.value
    st, _ = sj(jnp.zeros(()), x, side=0)
    st, _ = sj(st, x, side=1)
    for _ in range(3):
        st, _ = sj(st, x, side=0)
    assert float(st) == 6.0 * 6
    mine = PROGRAMS[n0:]
    assert [(p.name, p.statics) for p in mine] == [
        ("two_sided_step", (("side", 0),)),
        ("two_sided_step", (("side", 1),))]
    assert [p.label for p in mine] == ["two_sided_step[side=0]",
                                       "two_sided_step[side=1]"]
    # one trace a signature: registering ran `traced` no second time
    assert sj.compiles == 2 and JIT_COMPILES.value - jit0 == 2
    assert sj.dispatches == 5


def test_registering_a_signature_compiles_and_traces_nothing():
    compiles = []

    def on(event, _duration, **_kw):
        if event.endswith("backend_compile_duration"):
            compiles.append(event)

    jax.monitoring.register_event_duration_secs_listener(on)
    x = jnp.arange(16.0)
    sts = [jnp.ones(16) + k for k in range(2)]
    # what a call that compiles costs without the registry...
    plain = StateJit(lambda st, x: st * 2 + x, donate_argnums=(0,),
                     name="unregistered_step")
    plain._register = lambda args, kwargs: None
    n = len(compiles)
    plain(sts[0], x)
    cost = len(compiles) - n
    # ... is what it costs with it
    sj = StateJit(lambda st, x: st * 3 + x, donate_argnums=(0,),
                  name="registered_step")
    n, n0 = len(compiles), len(PROGRAMS)
    sj(sts[1], x)
    assert cost == 1 and len(compiles) - n == 1
    assert sj.compiles == 1 and len(PROGRAMS) == n0 + 1
    assert PROGRAMS[-1].name == "registered_step"


def test_programs_by_id_names_what_has_an_id():
    n0 = len(PROGRAMS)
    PROGRAMS.extend([Program("hash_agg_apply", (), 123),
                     Program("sorted_join_diff", (), None)])
    try:
        by_id = programs_by_id()
        assert by_id[123].name == "hash_agg_apply" and None not in by_id
    finally:
        del PROGRAMS[n0:]


# ------------------------------------------------------------ the readers

def _synthetic_run(log_epochs=(5, 6)) -> dict:
    """Two committed checkpoints with known spans and phases; the spans of
    `log_epochs` go into the log."""
    ms = 1_000_000
    recs = []
    for k, epoch in enumerate((5, 6)):
        base = 10_000 * ms * (k + 1)
        sid = 9_000_000 + 100 * epoch
        spans = [
            Span(epoch, "checkpoint", 0, "coord", base, base + 300 * ms, sid),
            Span(epoch, "collect", sid, "coord", base, base + 100 * ms,
                 sid + 1),
            Span(epoch, "flush.queue", sid, "uploader", base + 100 * ms,
                 base + (120 + 20 * k) * ms, sid + 2),
            Span(epoch, "flush", sid, "uploader", base + (120 + 20 * k) * ms,
                 base + 300 * ms, sid + 3),
            Span(epoch, "flush.stage:7", sid + 3, "uploader",
                 base + 150 * ms, base + 250 * ms, sid + 4),
            Span(epoch, "d2h_wait", sid + 4, "uploader", base + 150 * ms,
                 base + 200 * ms, sid + 5, 4096),
            Span(epoch, "d2h_wait", sid + 4, "uploader", base + 210 * ms,
                 base + 220 * ms, sid + 6, 64),
            Span(epoch, "d2h_wait", sid + 1, 3, base + 10 * ms,
                 base + 90 * ms, sid + 7, 36),
        ]
        if epoch in log_epochs:
            trace.SPAN_LOG.put(*spans)
        recs.append({"epoch": epoch, "commit_ns": base + 300 * ms,
                     "phases": {
                         1: {"apply_ns": 50 * ms, "persist_ns": 40 * ms,
                             "align_ns": 30 * ms, "dispatch_ns": 20 * ms,
                             "persist_wait_ns": 5 * ms, "fence_ns": 8 * ms},
                         2: {"apply_ns": 10 * ms, "persist_ns": 90 * ms,
                             "align_ns": 70 * ms, "dispatch_ns": 6 * ms,
                             "persist_wait_ns": (60 + 10 * k) * ms,
                             "fence_ns": 1 * ms}}})
    return {"window": {"checkpoints": recs + [{"epoch": 7}]},
            "trace": None}


SYNTHETIC = {"inject_to_commit_p50_s": 0.300,
             "flush_queue_s_per_ckpt": 0.030,
             "flush_wait_s_per_ckpt": 0.060,
             "flush_host_s_per_ckpt": 0.110,
             "dispatch_host_s_per_ckpt": 0.020,
             "persist_wait_s_per_ckpt": 0.065,
             "fence_s_per_ckpt": 0.008}


@pytest.fixture
def clean_log(monkeypatch):
    """The synthetic epochs go into a log of their own."""
    monkeypatch.setattr(trace, "SPAN_LOG", SpanLog())


@pytest.mark.parametrize("name", SPAN_READERS)
def test_a_span_reader_on_a_synthetic_run(name, clean_log):
    mod = spec.load_module("layers", name)
    assert mod.NEEDS_TRACE is False and mod.MOVES == "freshness_p50_s"
    assert mod.read(_synthetic_run()) == pytest.approx(SYNTHETIC[name])


@pytest.mark.parametrize("name", SPAN_READERS)
def test_a_span_reader_reads_nothing_where_an_epoch_is_missing(
        name, clean_log):
    mod = spec.load_module("layers", name)
    run = _synthetic_run(log_epochs=(5,))
    for rec in run["window"]["checkpoints"][1:2]:
        for ph in rec["phases"].values():
            for key in ("dispatch_ns", "persist_wait_ns", "fence_ns"):
                del ph[key]
    assert mod.read(run) is None
    assert mod.read({"window": {"checkpoints": []}, "trace": None}) is None


@pytest.mark.parametrize("name", SPAN_READERS)
async def test_a_span_reader_on_a_rehearsal_run(name, tmp_path_factory):
    q7 = await _q7(tmp_path_factory)
    v = spec.load_module("layers", name).read(q7["run"])
    assert v is not None and math.isfinite(v) and v >= 0
    if name == "inject_to_commit_p50_s":
        fresh = [(r["commit_ns"] - r["inject_ns"]) / 1e9
                 for r in q7["recs"]]
        assert v == pytest.approx(sum(fresh) / 2, rel=0.05)


async def test_the_flush_readers_add_up_to_the_checkpoint_less_collect(
        tmp_path_factory):
    q7 = await _q7(tmp_path_factory)
    for spans in q7["trees"]:
        root = span_readers.span_s(spans, "checkpoint")
        parts = (span_readers.span_s(spans, "flush.queue")
                 + span_readers.flush_wait_s(spans)
                 + (span_readers.span_s(spans, "flush")
                    - span_readers.flush_wait_s(spans)))
        assert parts == pytest.approx(
            root - span_readers.span_s(spans, "collect"), abs=5e-3)


@pytest.mark.parametrize("name,want", [
    ("join_dev_s_per_ckpt", (0.50 + 0.25) / 2),
    ("agg_dev_s_per_ckpt", (0.125 + 0.0625) / 2),
    ("persist_dev_s_per_ckpt", (1.0 + 2.0 + 4.0) / 2)])
def test_a_device_reader_names_modules_through_the_registry(name, want):
    mod = spec.load_module("layers", name)
    assert mod.NEEDS_TRACE is True
    n0 = len(PROGRAMS)
    PROGRAMS.extend([
        Program("sorted_join_apply_counted", (("side", 0),), 901),
        Program("sharded_join_apply_fused_s1", (), 902),
        Program("hash_agg_apply_scan2", (), 903),
        Program("sharded_agg_flush", (), 904),
        Program("sorted_join_diff", (), 905),
        Program("hash_agg_persist_view", (("n_slots", 128),), 906),
        Program("sharded_join_watchdog_pack", (), 907),
        Program("project_step", (), 908),
        Program("sorted_join_evict", (), None)])
    try:
        trace_ = {"checkpoints": 2, "device_modules": [
            ["jit_traced(901)", 0.50], ["jit_traced(902)", 0.25],
            ["jit_traced(903)", 0.125], ["jit_traced(904)", 0.0625],
            ["jit_traced(905)", 1.0], ["jit_traced(906)", 2.0],
            ["jit_traced(907)", 4.0], ["jit_traced(908)", 8.0],
            ["jit_traced(999)", 16.0], ["jit__unnamed_function_", 32.0]]}
        run = {"window": {"checkpoints": []}, "trace": trace_}
        assert mod.read(run) == pytest.approx(want)
        # nothing the registry can name, no trace: nothing to read
        del PROGRAMS[n0:]
        assert mod.read(run) is None
        assert mod.read({"window": {"checkpoints": []},
                         "trace": None}) is None
    finally:
        del PROGRAMS[n0:]


def test_the_benchmark_lists_the_new_readers():
    bm = spec.load_benchmark()
    by_name = {m["name"]: m for m in bm["per_layer"]}
    for name in SPAN_READERS:
        assert by_name[name]["source"] == "program_span"
        assert "workloads" not in by_name[name]
    for name in DEV_READERS:
        assert by_name[name]["source"] == "device_trace"
    assert "q5.sat" not in by_name["join_dev_s_per_ckpt"]["workloads"]
    # every cell runs a hash agg but q17.sat, whose one stateful executor is
    # the snapshot join-agg (its own reader: snapshot_dev_s_per_ckpt), and
    # q19.sat, whose one is the group top-N (topn_dev_s_per_ckpt)
    assert set(by_name["agg_dev_s_per_ckpt"]["workloads"]) == {
        w["name"] for w in bm["workloads"]} - {"q17.sat", "q19.sat"}
    assert by_name["snapshot_dev_s_per_ckpt"]["workloads"] == ["q17.sat"]
    assert by_name["topn_dev_s_per_ckpt"]["workloads"] == ["q19.sat"]
    assert by_name["topn_dev_s_per_ckpt"]["source"] == "device_trace"
    assert GLOBAL_METRICS.counter("trace_spans_dropped_total") \
        is TRACE_SPANS_DROPPED
