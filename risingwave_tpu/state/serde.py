"""Row serde: memcomparable key encoding + value encoding.

Reference: src/common/src/util/memcmp_encoding.rs and util/value_encoding/ —
primary keys are serialized so that byte order == row order (LSM range scans
give pk order for free), values are a compact fixed-layout encoding.

Subset choices for the TPU engine: all device types are fixed-width ints/
floats (types.py), so encoding is per-field:
  null flag byte (0x00 null / 0x01 value, nulls-first like the reference
  default) ++ order-preserving bytes:
    signed int  -> big-endian with sign bit flipped
    float       -> big-endian IEEE; if negative flip all bits else flip sign
    bool        -> single byte
    dict ids    -> int32 rule (NOTE: id order, not lexicographic string
                   order — ordered ops on strings take the host path)
Descending order flips all bytes (used by TopN/OverWindow orderings).
"""

from __future__ import annotations

import struct
from typing import Optional, Sequence

import numpy as np

from ..common.types import DataType, Schema

_INT_WIDTH = {
    DataType.INT16: 2, DataType.DATE: 4, DataType.INT32: 4,
    DataType.VARCHAR: 4, DataType.BYTEA: 4, DataType.JSONB: 4,
    DataType.INT64: 8, DataType.TIME: 8, DataType.TIMESTAMP: 8,
    DataType.TIMESTAMPTZ: 8, DataType.INTERVAL: 8, DataType.DECIMAL: 8,
    DataType.SERIAL: 8,
}


def _enc_int(v: int, width: int) -> bytes:
    bias = 1 << (8 * width - 1)
    return (int(v) + bias).to_bytes(width, "big")


def _dec_int(b: bytes) -> int:
    bias = 1 << (8 * len(b) - 1)
    return int.from_bytes(b, "big") - bias


def _enc_float(v: float, fmt: str) -> bytes:
    raw = struct.pack(">" + fmt, v)
    n = int.from_bytes(raw, "big")
    top = 1 << (8 * len(raw) - 1)
    n = (n ^ ((1 << (8 * len(raw))) - 1)) if (n & top) else (n | top)
    return n.to_bytes(len(raw), "big")


def _dec_float(b: bytes, fmt: str) -> float:
    n = int.from_bytes(b, "big")
    top = 1 << (8 * len(b) - 1)
    n = (n ^ top) if (n & top) else (n ^ ((1 << (8 * len(b))) - 1))
    return struct.unpack(">" + fmt, n.to_bytes(len(b), "big"))[0]


def encode_memcomparable(
    values: Sequence, types: Sequence[DataType], descending: Optional[Sequence[bool]] = None,
) -> bytes:
    out = bytearray()
    for i, (v, t) in enumerate(zip(values, types)):
        desc = bool(descending[i]) if descending is not None else False
        if v is None:
            field = b"\x00"
        else:
            if t is DataType.BOOLEAN:
                body = b"\x01" if v else b"\x00"
            elif t in (DataType.FLOAT32, DataType.FLOAT64):
                body = _enc_float(float(v), "f" if t is DataType.FLOAT32 else "d")
            else:
                body = _enc_int(int(v), _INT_WIDTH[t])
            field = b"\x01" + body
        if desc:
            field = bytes(0xFF - b for b in field)
        out += field
    return bytes(out)


def decode_memcomparable(
    data: bytes, types: Sequence[DataType], descending: Optional[Sequence[bool]] = None,
) -> tuple:
    vals = []
    pos = 0
    for i, t in enumerate(types):
        desc = bool(descending[i]) if descending is not None else False
        if t is DataType.BOOLEAN:
            width = 1
        elif t is DataType.FLOAT32:
            width = 4
        elif t is DataType.FLOAT64:
            width = 8
        else:
            width = _INT_WIDTH[t]
        flag = data[pos]
        if desc:
            flag = 0xFF - flag
        pos += 1
        if flag == 0x00:
            vals.append(None)
            continue
        body = data[pos:pos + width]
        if desc:
            body = bytes(0xFF - b for b in body)
        pos += width
        if t is DataType.BOOLEAN:
            vals.append(body[0] != 0)
        elif t in (DataType.FLOAT32, DataType.FLOAT64):
            vals.append(_dec_float(body, "f" if t is DataType.FLOAT32 else "d"))
        else:
            vals.append(_dec_int(body))
    return tuple(vals)


# ----------------------------------------------------------- value encoding

def _fmt_char(t: DataType) -> str:
    if t is DataType.BOOLEAN:
        return "?"
    if t is DataType.FLOAT32:
        return "f"
    if t is DataType.FLOAT64:
        return "d"
    w = _INT_WIDTH[t]
    return {2: "h", 4: "i", 8: "q"}[w]


class RowSerde:
    """Fixed-layout value encoding with a null bitmap prefix."""

    def __init__(self, schema: Schema):
        self.schema = schema
        self._fmt = "<" + "".join(_fmt_char(f.data_type) for f in schema)
        self._nbytes_nulls = (len(schema) + 7) // 8
        self._zeros = tuple(f.data_type.zero_value() for f in schema)

    def encode(self, values: Sequence) -> bytes:
        nulls = 0
        clean = []
        for i, v in enumerate(values):
            if v is None:
                nulls |= 1 << i
                clean.append(self._zeros[i])
            else:
                clean.append(v)
        return nulls.to_bytes(self._nbytes_nulls, "little") + struct.pack(self._fmt, *clean)

    def decode(self, data: bytes) -> tuple:
        nulls = int.from_bytes(data[: self._nbytes_nulls], "little")
        vals = struct.unpack(self._fmt, data[self._nbytes_nulls:])
        return tuple(None if (nulls >> i) & 1 else v for i, v in enumerate(vals))


# -------------------------------------------------------------- batch codec

class BatchCodec:
    """`encode_memcomparable` and `RowSerde.encode` over whole columns: the
    same bytes, made by numpy for a batch at once. Every type of the engine
    is fixed-width on the host (dictionary types are their int32 id), so a
    table's keys are one `[n, key_width]` and its values one
    `[n, value_width]` uint8 matrix whatever its schema, each laid out by a
    packed structured dtype: a field store is one strided pass that swaps
    the bytes where the layout asks. A NULL pk value is the one thing with
    no fixed width (flag 0x00 and no body): such rows are the caller's to
    write in row form. `key_prefix` bytes at the head of every key are left
    to the caller (the table id and the vnode)."""

    def __init__(self, schema: Schema, pk_indices: Sequence[int],
                 descending: Optional[Sequence[bool]] = None,
                 key_prefix: int = 0):
        self._dtypes = [np.dtype(f.data_type.np_dtype) for f in schema]
        self._nbytes_nulls = (len(schema) + 7) // 8
        # value layout: null bitmap, then the `<`-packed fields
        offsets, off = [], self._nbytes_nulls
        for dt in self._dtypes:
            offsets.append(off)
            off += dt.itemsize
        self._value_layout = np.dtype({
            "names": [f"v{j}" for j in range(len(self._dtypes))],
            "formats": [dt.newbyteorder("<") for dt in self._dtypes],
            "offsets": offsets, "itemsize": off})
        # key layout: per pk column the flag byte, then the big-endian body
        self._pk = []              # (column, first byte, width, descending)
        names, formats, offsets, off = [], [], [], key_prefix
        for j, i in enumerate(pk_indices):
            w = self._dtypes[i].itemsize
            names += [f"flag{j}", f"body{j}"]
            formats += ["u1", f">u{w}"]
            offsets += [off, off + 1]
            self._pk.append((i, off, w, bool(descending[j])
                             if descending is not None else False))
            off += 1 + w
        self._key_layout = np.dtype({"names": names, "formats": formats,
                                     "offsets": offsets, "itemsize": off})

    @property
    def key_width(self) -> int:
        return self._key_layout.itemsize

    def typed(self, cols: Sequence[np.ndarray],
              valids: Optional[Sequence[Optional[np.ndarray]]] = None
              ) -> list[np.ndarray]:
        """The columns at the schema's dtypes, a NULL lane as the type's
        zero (what `RowSerde.encode` writes and the vnode hash reads)."""
        out = []
        for j, (c, dt) in enumerate(zip(cols, self._dtypes)):
            c = np.ascontiguousarray(c, dtype=dt)
            v = None if valids is None else valids[j]
            out.append(c if v is None else np.where(v, c, dt.type(0)))
        return out

    def encode_keys(self, cols: Sequence[np.ndarray]) -> np.ndarray:
        """`[n, key_width]`: behind the prefix, the memcomparable pk of
        each row of `cols` (from `typed`; no NULL among the pk lanes)."""
        n = len(cols[0])
        out = np.empty((n, self.key_width), dtype=np.uint8)
        fields = out.view(self._key_layout).reshape(n)
        for j, (i, off, w, desc) in enumerate(self._pk):
            bits = cols[i].view(f"u{w}")
            if cols[i].dtype.kind != "b":
                top = bits.dtype.type(1 << (8 * w - 1))
                # `_enc_float`: a negative flips every bit, else the sign
                bits = (np.where(bits & top, ~bits, bits | top)
                        if cols[i].dtype.kind == "f" else bits ^ top)
            fields[f"flag{j}"] = 1
            fields[f"body{j}"] = bits
            if desc:
                field = out[:, off:off + 1 + w]
                np.bitwise_xor(field, 0xFF, out=field)    # 0xFF - byte
        return out

    def encode_values(self, cols: Sequence[np.ndarray],
                      valids: Optional[Sequence[Optional[np.ndarray]]] = None
                      ) -> np.ndarray:
        """`[n, value_width]`: `RowSerde.encode` of each row of `cols`
        (from `typed`, so a NULL lane already holds its zero)."""
        n = len(cols[0])
        out = np.empty((n, self._value_layout.itemsize), dtype=np.uint8)
        out[:, :self._nbytes_nulls] = 0
        fields = out.view(self._value_layout).reshape(n)
        for j, c in enumerate(cols):
            fields[f"v{j}"] = c
            v = None if valids is None else valids[j]
            if v is not None:
                out[:, j // 8] |= (~v).astype(np.uint8) << np.uint8(j % 8)
        return out
