"""Data types of the engine.

Re-design of the reference's `DataType` enum (src/common/src/types/mod.rs:110-165)
for a TPU columnar engine: every type has a fixed-width device representation
(jnp dtype); variable-width types (Varchar/Bytea/Jsonb) are dictionary-encoded
on the host and appear on device as int32 ids. Decimal is a scaled int64
(fixed-point) — TPU has no decimal unit, and Nexmark/TPC-H money columns fit.
"""

from __future__ import annotations

import enum
from dataclasses import dataclass

import jax.numpy as jnp
import numpy as np


class DataType(enum.Enum):
    BOOLEAN = "boolean"
    INT16 = "int16"
    INT32 = "int32"
    INT64 = "int64"
    FLOAT32 = "float32"
    FLOAT64 = "float64"
    # Fixed-point decimal: int64 mantissa with per-column scale (digits after
    # the point). Matches reference Decimal semantics for the benchmark
    # workloads; scale is carried in the Field, not the array.
    DECIMAL = "decimal"
    DATE = "date"            # int32 days since unix epoch
    TIME = "time"            # int64 microseconds since midnight
    TIMESTAMP = "timestamp"  # int64 microseconds since unix epoch (naive)
    TIMESTAMPTZ = "timestamptz"  # int64 microseconds since unix epoch (UTC)
    INTERVAL = "interval"    # int64 microseconds (months/days folded; subset)
    VARCHAR = "varchar"      # int32 dictionary id (host-side StringDictionary)
    BYTEA = "bytea"          # int32 dictionary id
    JSONB = "jsonb"          # int32 dictionary id
    SERIAL = "serial"        # int64 (vnode-prefixed row ids)

    # ------------------------------------------------------------------
    @property
    def np_dtype(self) -> np.dtype:
        return np.dtype(_NP_DTYPE[self])

    @property
    def jnp_dtype(self):
        return _NP_DTYPE[self]

    @property
    def is_dict_encoded(self) -> bool:
        return self in (DataType.VARCHAR, DataType.BYTEA, DataType.JSONB)

    @property
    def is_float(self) -> bool:
        return self in (DataType.FLOAT32, DataType.FLOAT64)

    @property
    def is_integral(self) -> bool:
        return self in (
            DataType.INT16, DataType.INT32, DataType.INT64, DataType.SERIAL,
            DataType.DECIMAL, DataType.DATE, DataType.TIME, DataType.TIMESTAMP,
            DataType.TIMESTAMPTZ, DataType.INTERVAL,
        )

    def zero_value(self):
        if self is DataType.BOOLEAN:
            return False
        if self.is_float:
            return 0.0
        return 0


_NP_DTYPE = {
    DataType.BOOLEAN: np.bool_,
    DataType.INT16: np.int16,
    DataType.INT32: np.int32,
    DataType.INT64: np.int64,
    DataType.FLOAT32: np.float32,
    DataType.FLOAT64: np.float64,
    DataType.DECIMAL: np.int64,
    DataType.DATE: np.int32,
    DataType.TIME: np.int64,
    DataType.TIMESTAMP: np.int64,
    DataType.TIMESTAMPTZ: np.int64,
    DataType.INTERVAL: np.int64,
    DataType.VARCHAR: np.int32,
    DataType.BYTEA: np.int32,
    DataType.JSONB: np.int32,
    DataType.SERIAL: np.int64,
}


@dataclass(frozen=True)
class Field:
    """A named, typed column of a schema (reference: catalog Field)."""

    name: str
    data_type: DataType
    # decimal scale (digits after the point) when data_type == DECIMAL
    scale: int = 0


@dataclass(frozen=True)
class Schema:
    fields: tuple[Field, ...]

    def __post_init__(self):
        object.__setattr__(self, "fields", tuple(self.fields))

    def __len__(self) -> int:
        return len(self.fields)

    def __iter__(self):
        return iter(self.fields)

    def __getitem__(self, i: int) -> Field:
        return self.fields[i]

    @property
    def names(self) -> tuple[str, ...]:
        return tuple(f.name for f in self.fields)

    @property
    def data_types(self) -> tuple[DataType, ...]:
        return tuple(f.data_type for f in self.fields)

    def index_of(self, name: str) -> int:
        for i, f in enumerate(self.fields):
            if f.name == name:
                return i
        raise KeyError(name)

    def select(self, indices) -> "Schema":
        return Schema(tuple(self.fields[i] for i in indices))

    def concat(self, other: "Schema") -> "Schema":
        return Schema(self.fields + other.fields)


def schema(*pairs) -> Schema:
    """schema(("a", DataType.INT64), ("b", DataType.FLOAT64))"""
    return Schema(tuple(Field(n, t) for n, t in pairs))


class StringDictionary:
    """Host-side append-only string<->id mapping for dict-encoded columns.

    The device only ever sees int32 ids; equality/group-by/join on strings is
    exact on ids. Ordering on dict-encoded columns is NOT id order — ordered
    ops on strings must go through the host path.
    """

    __slots__ = ("_strings", "_ids", "_mint_lock")

    def __init__(self):
        import threading
        self._strings: list[str] = []
        self._ids: dict[str, int] = {}
        # serving queries bind literals on worker threads; minting must
        # be atomic or two threads can hand out the same id for two
        # different strings. Reads (decode, the hit path below) stay
        # lock-free — the structures are append-only.
        self._mint_lock = threading.Lock()

    def __len__(self):
        return len(self._strings)

    def get_or_insert(self, s: str) -> int:
        i = self._ids.get(s)
        if i is None:
            with self._mint_lock:
                i = self._ids.get(s)
                if i is None:
                    i = len(self._strings)
                    self._strings.append(s)
                    self._ids[s] = i
        return i

    def lookup(self, s: str) -> "int | None":
        """The id of `s`, or None: a read that never mints one."""
        return self._ids.get(s)

    def encode_many(self, strings) -> np.ndarray:
        return np.asarray([self.get_or_insert(s) for s in strings], dtype=np.int32)

    def decode(self, i: int) -> str:
        return self._strings[i]

    def decode_many(self, ids) -> list[str]:
        return [self._strings[int(i)] for i in np.asarray(ids).ravel()]


# A process-global dictionary: ids are consistent across all columns, which
# lets dict-encoded values flow between operators without re-encoding.
GLOBAL_DICT = StringDictionary()


# ------------------------------------------------------- dict durability
# Open-vocabulary sources (connectors/file_source.py) mint dict ids at
# parse time; MV state then stores those ids. The dictionary is
# append-only with stable ids, so durability is an append-only DELTA LOG
# in the object store: each checkpoint persists the strings minted since
# the last one (meta/barrier_manager.py calls persist_dict_delta before
# the epoch's manifest commit), and recovery replays the log IN ORDER
# before anything re-encodes (frontend/session.py calls load_dict_log at
# store-open). Reference: the dictionary the reference never needs —
# its VARCHAR cells are inline bytes; dict encoding is the TPU design's
# device representation, so its durability is a TPU-design obligation.

_DICT_LOG_PREFIX = "dict/"


def persist_dict_delta(objects, cursor: int) -> int:
    """Append strings [cursor, len) to the log; returns the new cursor."""
    import json as _json
    n = len(GLOBAL_DICT)
    if n > cursor:
        blob = _json.dumps(GLOBAL_DICT._strings[cursor:n]).encode()
        objects.upload(f"{_DICT_LOG_PREFIX}{cursor:012d}-{n:012d}", blob)
        cursor = n
    return cursor


def load_dict_log(objects) -> int:
    """Replay the delta log into GLOBAL_DICT; returns the restored
    length. Tolerates overlapping ranges (re-persisted prefixes) but
    REQUIRES content agreement — a mismatch means two incompatible
    dictionaries and must fail loudly, not decode garbage."""
    import json as _json
    paths = sorted(objects.list(_DICT_LOG_PREFIX))
    covered = 0      # ids the LOG covers — pre-existing in-process
    #                  strings beyond it still need a first delta
    for p in paths:
        name = p[len(_DICT_LOG_PREFIX):] if p.startswith(_DICT_LOG_PREFIX) \
            else p.rsplit("/", 1)[-1]
        start = int(name.split("-")[0])
        covered = max(covered, int(name.split("-")[1]))
        strings = _json.loads(objects.read(p))
        have = len(GLOBAL_DICT)
        if start > have:
            raise RuntimeError(
                f"dict log gap: segment starts at {start}, have {have}")
        for k, s in enumerate(strings):
            i = start + k
            if i < have:
                if GLOBAL_DICT._strings[i] != s:
                    raise RuntimeError(
                        f"dict log mismatch at id {i}: "
                        f"{GLOBAL_DICT._strings[i]!r} != {s!r}")
            else:
                got = GLOBAL_DICT.get_or_insert(s)
                assert got == i, f"dict id drift: {got} != {i}"
                have = got + 1
    return covered
