"""The benchmark's own tests — run by hand, not by tier-1:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_harness.py -q

Everything runs on the CPU at the configurations' `rehearsal` sizes.
"""

from __future__ import annotations

import asyncio
import copy
import json
import os
import subprocess
import sys
import tempfile

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import trace_reduce                       # noqa: E402
from benchmark.harness import check, spec, stats         # noqa: E402

BM = spec.load_benchmark()
CELLS = [w["name"] for w in BM["workloads"]]
TESTDATA = os.path.join(ROOT, "benchmark", "testdata")


def _env(chips: int) -> dict:
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "BENCH_REHEARSAL": "1"}
    env.pop("BENCH_RUN", None)
    if chips > 1:
        env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count="
                            f"{chips}")
    return env


def _run(script: str, cell: str, *extra: str, trace: int = 0):
    chips = next(w["chips"] for w in BM["workloads"] if w["name"] == cell)
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", script),
         "--workload", cell, "--seed", "2147483659", "--seconds", "3",
         "--trace", str(trace), *extra],
        cwd=ROOT, env=_env(chips), capture_output=True, text=True)
    return p, (json.loads(p.stdout.strip().splitlines()[-1])
               if p.returncode == 0 else None)


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_ends_in_a_wellformed_correct_line(cell, trace):
    p, line = _run("run.py", cell, trace=trace)
    assert p.returncode == 0, p.stderr[-2000:]
    assert set(line) >= {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert line["correct"] is True
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"
    c = spec.Cell(BM, cell)
    want = c.per_layer if trace else c.end_to_end
    names = {m["name"]: m["unit"] for m in want}
    assert line["metrics"], line
    for k, v in line["metrics"].items():
        assert names[k] == v["unit"] and isinstance(v["value"], (int, float))
    if not trace:
        assert set(line["metrics"]) == set(names)
    else:
        assert {"busy_s", "window_s"} <= set(line["device"])


def test_no_tpu_and_no_rehearsal_flag_exits_nonzero():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("BENCH_REHEARSAL", None)
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", CELLS[0], "--seed", "1", "--seconds", "1"],
        cwd=ROOT, env=env, capture_output=True, text=True)
    assert p.returncode != 0 and p.stdout.strip() == ""


@pytest.mark.parametrize("cell", CELLS)
def test_control_rare_checkpoint_is_not_correct(cell):
    """The control: one guarantee of the configuration broken by the
    engine's own switch (every second barrier a checkpoint)."""
    p, line = _run("tests/control.py", cell, "--control", "rare_checkpoint")
    assert p.returncode == 0, p.stderr[-2000:]
    assert line["correct"] is False and line["failed"] > 0


def test_broken_timed_path_is_not_correct(monkeypatch):
    """The rest of a run driven with the timed path broken underneath: the
    source alters one bid where it is produced (a price no oracle row has),
    and `correct` comes out false."""
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    import risingwave_tpu  # noqa: F401
    from risingwave_tpu.connectors import nexmark as nx
    from benchmark.harness import report

    real = nx.NexmarkGenerator.next_chunk

    def altered(self):
        at = self.offset
        chunk = real(self)
        if at == 0:
            col = chunk.columns[2]
            col.data = col.data.at[7].set(10 ** 9)
        return chunk

    monkeypatch.setattr(nx.NexmarkGenerator, "next_chunk", altered)
    cell = spec.Cell(BM, "q7.sat", rehearsal=True)
    store = tempfile.mkdtemp(prefix="bench_test_")
    line = asyncio.run(report.run_cell(
        cell, 5, 2.0, False, store_path=store, t_process_ns=0,
        log=lambda _o: None,
        device={"platform": "cpu", "kind": "cpu", "count": 1},
        cache_dir=tempfile.mkdtemp(prefix="bench_test_cache_")))
    assert line["correct"] is False


def _q7_oracle(n=20000):
    cell = spec.Cell(BM, "q7.sat", rehearsal=True)
    return cell.query.oracle({"bid": n}, cell.config, 3)


def test_oracle_equal_is_correct():
    want = _q7_oracle()
    assert len(want[0]) > 3
    shuffled = [c[::-1].copy() for c in want]
    assert all(n["ok"] for n in check.compare(shuffled, want, 0.0))


def test_oracle_catches_a_dropped_row():
    want = _q7_oracle()
    got = [c[1:] for c in want]
    assert not all(n["ok"] for n in check.compare(got, want, 0.0))


def test_oracle_catches_a_price_off_by_one():
    want = _q7_oracle()
    got = [c.copy() for c in want]
    got[1][len(got[1]) // 2] += 1
    assert not all(n["ok"] for n in check.compare(got, want, 0.0))


def test_float_columns_compare_by_relative_difference():
    want = [np.arange(4, dtype=np.int64), np.array([1.0, 2.0, 3.0, 4.0])]
    near = [want[0], want[1] * (1 + 1e-14)]
    far = [want[0], want[1] * (1 + 1e-9)]
    assert all(n["ok"] for n in check.compare(near, want, 1e-12))
    assert not all(n["ok"] for n in check.compare(far, want, 1e-12))


def test_reference_generator_equals_the_engines():
    """The oracle's events are the benchmark's own numpy copy of what the
    engine's connector makes (its 99% hot-key skew included, which is not
    NEXMark's); here it is held against the engine's device generator."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import risingwave_tpu  # noqa: F401
    from risingwave_tpu.connectors.nexmark import (NexmarkConfig,
                                                   NexmarkGenerator)
    from benchmark.reference import nexmark
    seed = 2 ** 31 + 77
    base = nexmark.base_time_us(seed)
    gen = NexmarkGenerator("bid", chunk_size=4096, start_offset=4096 * 999,
                           cfg=NexmarkConfig(inter_event_us=250,
                                             base_time_us=base))
    chunk = gen.next_chunk()
    ref = nexmark.bids(4096 * 999, 4096, inter_event_us=250, base_time=base)
    for name, j in (("auction", 0), ("bidder", 1), ("price", 2),
                    ("date_time", 5)):
        assert np.array_equal(np.asarray(chunk.columns[j].data), ref[name])
    assert nexmark.base_time_us(seed) != nexmark.base_time_us(seed + 1)


def test_trace_reduce_on_the_recorded_xplane():
    """benchmark/testdata/tiny.xplane.pb: recorded on one TPU v5e chip by
    record_testdata.py (my chip run, PR 23) — three dispatches of a program
    named `traced`, one of `other`, the host asleep 20 ms between them."""
    with open(os.path.join(TESTDATA, "tiny.side.json")) as f:
        side = json.load(f)
    r = trace_reduce.reduce_file(
        os.path.join(TESTDATA, "tiny.xplane.pb"), **side)
    assert r["devices"] == 1
    assert r["window_s"] == pytest.approx(0.068936509, rel=1e-6)
    assert r["busy_s"] == pytest.approx(0.001514973, rel=1e-6)
    assert r["statejit_s"] == pytest.approx(4.1589e-05, rel=1e-6)
    assert 100 * (1 - r["busy_s_least"] / r["window_s"]) == pytest.approx(
        97.8024, abs=1e-3)
    mods = dict(r["device_modules"])
    assert [k for k in mods if k.startswith("jit_traced")]
    assert [k for k in mods if k.startswith("jit_other")]
    # the idle time is the host asleep inside the harness's quota_wait span
    assert r["idle_gaps"][0][0].startswith("quota_wait|")
    assert r["idle_gaps"][0][1] > 0.055


def test_trace_without_the_clock_sync_annotation_is_an_error():
    with open(os.path.join(TESTDATA, "tiny.side.json")) as f:
        side = json.load(f)
    with pytest.raises(ValueError, match="no_such_annotation"):
        trace_reduce.reduce_file(os.path.join(TESTDATA, "tiny.xplane.pb"),
                                 sync_name="no_such_annotation", **side)


def test_hot_key_share_is_the_connectors_not_nexmarks():
    """99% of bids on the hot auction / bidder, where public NEXMark has 50%
    / 75%: the deviation every configuration states."""
    from benchmark.reference import nexmark
    ev = nexmark.bids(0, 46_000, inter_event_us=250,
                      base_time=nexmark.BASE_TIME_US)
    hot_a = (ev["auction"] - nexmark.FIRST_AUCTION_ID) % 100 == 0
    hot_b = (ev["bidder"] - nexmark.FIRST_PERSON_ID) % 100 == 1
    assert 0.985 < hot_a.mean() < 0.995 and 0.985 < hot_b.mean() < 0.995
    for cfg in BM["configs"]:
        with open(os.path.join(ROOT, cfg["file"])) as f:
            assert "99%" in json.load(f)["deviations"]["key_skew"]
        assert "99%" in cfg["source"]


def test_union_of_intervals():
    merged, total = trace_reduce.union_ns([(0, 10), (5, 20), (30, 40)])
    assert merged == [[0, 20], [30, 40]] and total == 30


def test_percentiles():
    xs = list(range(1, 46))
    assert stats.percentile(xs, 0.95) == 43       # the third largest of 45
    assert stats.median([3, 1, 2]) == 2 and stats.median([1, 2, 3, 4]) == 2.5


@pytest.mark.parametrize("field,value", [
    ("name", "q7 sat"), ("name", "q7,sat"), ("name", "a/b"),
    ("name", "x" * 65), ("config", "nexmark q7"), ("chips", 2)])
def test_a_bad_cell_fails(field, value):
    bm = copy.deepcopy(BM)
    bm["workloads"][0][field] = value
    with pytest.raises(spec.SpecError):
        spec.validate(bm)


@pytest.mark.parametrize("field,value", [
    ("unit", "rows per s"), ("unit", "µs"), ("unit", ""),
    ("name", "rows per s"), ("better", "more"), ("source", "stopwatch"),
    ("bound", 0.5)])
def test_a_bad_metric_fails(field, value):
    bm = copy.deepcopy(BM)
    bm["end_to_end"][0][field] = value
    with pytest.raises(spec.SpecError):
        spec.validate(bm)


def test_extra_keys_fail():
    bm = copy.deepcopy(BM)
    bm["per_layer"][0]["why"] = "because"
    with pytest.raises(spec.SpecError):
        spec.validate(bm)
    bm = copy.deepcopy(BM)
    bm["workloads"][0]["metrics"] = ["rows_per_s"]
    with pytest.raises(spec.SpecError):
        spec.validate(bm)


def test_every_layer_reader_agrees_with_benchmark_json():
    for m in BM["per_layer"]:
        mod = spec.load_module("layers", m["name"])
        assert (mod.LAYER, mod.UNIT, mod.MOVES) == (
            m["layer"], m["unit"], m["moves"])
        assert mod.NEEDS_TRACE == (m["source"] == "device_trace")
