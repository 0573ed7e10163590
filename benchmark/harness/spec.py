"""BENCHMARK.json and the files it names.

A cell is `{name, config, traffic, chips, why}`; the three names resolve to
`benchmark/configs/<config>.json`, `benchmark/traffic/<traffic>.json` and,
through the configuration's `query`, `benchmark/queries/<query>.py`. A
per-layer metric `<m>` is read by `benchmark/layers/<m>.py`. Adding a cell,
a configuration, a traffic mix, a query or a per-layer metric is adding files
and `BENCHMARK.json` entries; nothing here is edited.
"""

from __future__ import annotations

import importlib.util
import json
import os
import re

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH_DIR = os.path.join(ROOT, "benchmark")

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


class SpecError(ValueError):
    pass


def load_benchmark(path: str | None = None) -> dict:
    with open(path or os.path.join(ROOT, "BENCHMARK.json")) as f:
        bm = json.load(f)
    validate(bm)
    return bm


def _check_name(what: str, s) -> None:
    if not isinstance(s, str) or not NAME_RE.match(s):
        raise SpecError(f"{what}: {s!r} is not a name (letters, digits, "
                        "'_', '.', '-'; at most 64; no space, comma, slash)")


def _check_line(what: str, s) -> None:
    if (not isinstance(s, str) or not 1 <= len(s) <= 200
            or "\n" in s or "\t" in s):
        raise SpecError(f"{what}: 1 to 200 characters on one line, no tab")


def validate(bm: dict) -> None:
    """The part of the contract a file can be held to without a run."""
    keys = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
    if set(bm) != keys:
        raise SpecError(f"BENCHMARK.json keys {sorted(bm)} != {sorted(keys)}")
    if not (isinstance(bm["run_seconds"], int)
            and 1 <= bm["run_seconds"] <= 51):
        raise SpecError("run_seconds: a whole number from 1 to 51")
    configs, cells = {}, {}
    for c in bm["configs"]:
        if set(c) != {"name", "source", "file", "reduced", "why"}:
            raise SpecError(f"config keys {sorted(c)}")
        _check_name("config name", c["name"])
        _check_line("config source", c["source"])
        _check_line("config why", c["why"])
        for k in c["reduced"]:
            _check_name("reduced key", k)
        if c["name"] in configs:
            raise SpecError(f"config {c['name']} twice")
        if not any(c["file"].startswith(p.rstrip("/") + "/")
                   for p in bm["paths"]):
            raise SpecError(f"config file {c['file']} not under paths")
        configs[c["name"]] = c
    pairs = set()
    for w in bm["workloads"]:
        if set(w) != {"name", "config", "traffic", "chips", "why"}:
            raise SpecError(f"cell keys {sorted(w)}")
        for k in ("name", "config", "traffic"):
            _check_name(f"cell {k}", w[k])
        _check_line("cell why", w["why"])
        if w["chips"] not in (1, 4):
            raise SpecError(f"cell {w['name']}: chips must be 1 or 4")
        if w["config"] not in configs:
            raise SpecError(f"cell {w['name']}: unknown config {w['config']}")
        if w["name"] in cells or (w["config"], w["traffic"]) in pairs:
            raise SpecError(f"cell {w['name']} twice")
        cells[w["name"]] = w
        pairs.add((w["config"], w["traffic"]))
    unused = set(configs) - {w["config"] for w in bm["workloads"]}
    if unused:
        raise SpecError(f"configs used by no cell: {sorted(unused)}")
    seen = set()
    e2e = {}
    for m in bm["end_to_end"]:
        if not {"name", "unit", "better", "bound", "source"} <= set(m) \
                or set(m) - {"name", "unit", "better", "bound", "source",
                             "workloads"}:
            raise SpecError(f"end_to_end keys {sorted(m)}")
        _metric_common(m, seen, cells)
        if m["source"] not in ("host_clock", "device_trace"):
            raise SpecError(f"{m['name']}: end-to-end source {m['source']}")
        if not 0 < m["bound"] <= 0.25:
            raise SpecError(f"{m['name']}: bound {m['bound']}")
        e2e[m["name"]] = m
    if "setup_s" not in e2e or "workloads" in e2e["setup_s"]:
        raise SpecError("setup_s must be an end-to-end metric of every cell")
    for m in bm["per_layer"]:
        if not {"name", "unit", "better", "source", "layer", "moves"} \
                <= set(m) or set(m) - {"name", "unit", "better", "source",
                                       "layer", "moves", "workloads"}:
            raise SpecError(f"per_layer keys {sorted(m)}")
        _metric_common(m, seen, cells)
        _check_line("layer", m["layer"])
        if m["moves"] not in e2e:
            raise SpecError(f"{m['name']}: moves unknown {m['moves']}")
        for c in m.get("workloads", cells):
            if c not in cells_of(bm, e2e[m["moves"]]):
                raise SpecError(f"{m['name']}: cell {c} does not report "
                                f"{m['moves']}")
    for c in cells:
        if len([m for m in e2e.values() if c in cells_of(bm, m)]) < 2:
            raise SpecError(f"cell {c}: needs setup_s and one more metric")
        if not any(c in cells_of(bm, m) for m in bm["per_layer"]):
            raise SpecError(f"cell {c}: no per-layer metric")


def _metric_common(m: dict, seen: set, cells: dict) -> None:
    _check_name("metric name", m["name"])
    if m["name"] in seen:
        raise SpecError(f"metric {m['name']} twice")
    seen.add(m["name"])
    if not isinstance(m["unit"], str) or not UNIT_RE.match(m["unit"]):
        raise SpecError(f"{m['name']}: unit {m['unit']!r}")
    if m["better"] not in ("lower", "higher"):
        raise SpecError(f"{m['name']}: better {m['better']!r}")
    if m["source"] not in SOURCES:
        raise SpecError(f"{m['name']}: source {m['source']!r}")
    for c in m.get("workloads", ()):
        if c not in cells:
            raise SpecError(f"{m['name']}: unknown cell {c}")


def cells_of(bm: dict, metric: dict) -> list:
    """Names of the cells that report `metric`."""
    return metric.get("workloads", [w["name"] for w in bm["workloads"]])


def _load_json(*parts: str) -> dict:
    with open(os.path.join(BENCH_DIR, *parts)) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """`benchmark/<kind>/<name>.py` as a module (names may hold '.', '-')."""
    path = os.path.join(BENCH_DIR, kind, name + ".py")
    if not os.path.isfile(path):
        raise SpecError(f"no {kind}/{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"benchmark.{kind}.{re.sub(r'[^A-Za-z0-9_]', '_', name)}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One entry of `workloads`, with everything its names resolve to."""

    def __init__(self, bm: dict, name: str, rehearsal: bool = False):
        by_name = {w["name"]: w for w in bm["workloads"]}
        if name not in by_name:
            raise SpecError(f"unknown cell {name!r}; BENCHMARK.json has "
                            f"{sorted(by_name)}")
        w = by_name[name]
        self.name, self.chips, self.why = name, w["chips"], w["why"]
        cfg_entry = next(c for c in bm["configs"] if c["name"] == w["config"])
        with open(os.path.join(ROOT, cfg_entry["file"])) as f:
            self.config = json.load(f)
        self.traffic = _load_json("traffic", w["traffic"] + ".json")
        if self.config["chips"] != self.chips:
            raise SpecError(f"cell {name}: {self.chips} chips, its "
                            f"configuration {self.config['chips']}")
        self.rehearsal = rehearsal
        if rehearsal:
            self._shrink()
        self.query = load_module("queries", self.config["query"])
        self.end_to_end = [m for m in bm["end_to_end"]
                           if name in cells_of(bm, m)]
        self.per_layer = [m for m in bm["per_layer"]
                          if name in cells_of(bm, m)]

    def _shrink(self) -> None:
        """CPU rehearsal: the configuration's own `rehearsal` block."""
        r = self.config["rehearsal"]
        self.config = {**self.config, "session_set": {
            **self.config["session_set"], **r.get("session_set", {})}}
        d = r.get("chunk_size_divisor", 1)
        self.traffic = {**self.traffic, "chunk_size": {
            t: max(256, n // d)
            for t, n in self.traffic["chunk_size"].items()}}

    @property
    def quotas(self) -> dict:
        """table -> rows each source emits per barrier interval."""
        return {t: n * self.traffic["chunks_per_interval"][t]
                for t, n in self.traffic["chunk_size"].items()}

    def reports(self, metric: str) -> bool:
        return any(m["name"] == metric for m in self.end_to_end)
