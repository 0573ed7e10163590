"""Numpy interpreter over the Expr IR — the batch/serving evaluator.

Reference: batch expressions evaluate with the same vectorized
`Expression::eval` as streaming; here the SERVING path deliberately stays
off the accelerator (results leave the system anyway, and a blocking
device->host transfer serialises with the dispatch of the streaming
dataflow sharing the process), so the same Expr tree is interpreted over
numpy columns.
Returns (values, valid) pairs with strict NULL propagation.
"""

from __future__ import annotations

import numpy as np

from ..common.types import GLOBAL_DICT
from ..expr.ir import Expr, FuncCall, InputRef, Literal

_BINOPS = {
    "add": np.add, "subtract": np.subtract, "multiply": np.multiply,
    "equal": np.equal, "not_equal": np.not_equal,
    "less_than": np.less, "less_than_or_equal": np.less_equal,
    "greater_than": np.greater, "greater_than_or_equal": np.greater_equal,
}


def eval_numpy(e: Expr, cols: list[np.ndarray], valids=None):
    """-> (values ndarray, valid ndarray bool). `valids` threads per-column
    NULL masks from the storage layer (ADVICE r2 #2); None = all valid."""
    n = len(cols[0]) if cols else 0
    if isinstance(e, InputRef):
        v = (valids[e.index] if valids is not None
             and valids[e.index] is not None else np.ones(n, dtype=bool))
        return cols[e.index], v
    if isinstance(e, Literal):
        if e.value is None:
            return np.zeros(n), np.zeros(n, dtype=bool)
        v = e.value
        if isinstance(v, str):
            v = GLOBAL_DICT.get_or_insert(v)
        return np.full(n, v), np.ones(n, dtype=bool)
    if isinstance(e, FuncCall):
        args = [eval_numpy(a, cols, valids) for a in e.args]
        name = e.name
        if name in _BINOPS:
            (a, av), (b, bv) = args
            return _BINOPS[name](a, b), av & bv
        if name == "divide":
            # match streaming semantics (functions.py _div): integer
            # division floors; division by zero is NULL
            (a, av), (b, bv) = args
            safe = np.where(b == 0, 1, b)
            if (np.issubdtype(np.asarray(a).dtype, np.integer)
                    and np.issubdtype(np.asarray(b).dtype, np.integer)):
                val = np.floor_divide(a, safe)
            else:
                val = np.divide(a, safe)
            return val, av & bv & (b != 0)
        if name == "modulus":
            # streaming _mod: x % 0 is NULL
            (a, av), (b, bv) = args
            return np.mod(a, np.where(b == 0, 1, b)), av & bv & (b != 0)
        if name == "neg":
            (a, av), = args
            return -a, av
        if name == "not":
            (a, av), = args
            return ~a.astype(bool), av
        if name == "and":
            (a, av), (b, bv) = args
            a = a.astype(bool)
            b = b.astype(bool)
            # Kleene: False AND NULL = False
            val = a & b
            valid = (av & bv) | (av & ~a) | (bv & ~b)
            return val, valid
        if name == "or":
            (a, av), (b, bv) = args
            a = a.astype(bool)
            b = b.astype(bool)
            val = a | b
            valid = (av & bv) | (av & a) | (bv & b)
            return val, valid
        if name == "abs":
            (a, av), = args
            return np.abs(a), av
        if name == "is_null":
            (a, av), = args
            return ~av, np.ones_like(av)
        if name == "is_not_null":
            (a, av), = args
            return av, np.ones_like(av)
        if name == "case":
            n_args = len(args)
            has_else = n_args % 2 == 1
            if has_else:
                v, valid = args[-1]
                v = np.asarray(v).copy()
                valid = np.asarray(valid).copy()
            else:
                # the default branch must carry the expression's TYPE:
                # float64 zeros would leak "5.0" for an INT64 CASE
                v = np.zeros(n, dtype=e.ret_type.np_dtype)
                valid = np.zeros(n, dtype=bool)
            v, valid = np.broadcast_to(v, (n,)).copy(), \
                np.broadcast_to(valid, (n,)).copy()
            for i in reversed(range(n_args // 2)):
                c, cv = args[2 * i]
                rv, rvv = args[2 * i + 1]
                hit = np.broadcast_to(
                    np.asarray(c, dtype=bool) & cv, (n,))
                v = np.where(hit, rv, v)
                valid = np.where(hit, np.broadcast_to(rvv, (n,)), valid)
            return v, valid
        if name == "coalesce":
            v, valid = args[0]
            for (b, bv) in args[1:]:
                v = np.where(valid, v, b)
                valid = valid | bv
            return v, valid
        if name in ("lower", "upper", "trim", "ltrim", "rtrim",
                    "reverse", "md5", "length", "char_length", "ascii",
                    "like", "starts_with", "ends_with", "contains",
                    "substr"):
            # PURE NUMPY gather through the same host-built dictionary
            # mapping the streaming kernels use — the serving path must
            # stay off the accelerator (module docstring)
            from ..expr.strings import numpy_string_eval
            (a, av) = args[0]
            return numpy_string_eval(e, np.asarray(a, dtype=np.int64)), av
        if name in ("tumble_start", "tumble_end"):
            (a, av), (w, _) = args
            start = a - a % w
            return (start if name == "tumble_start" else start + w), av
        raise NotImplementedError(f"numpy eval for {name}")
    raise NotImplementedError(f"numpy eval for {type(e).__name__}")
