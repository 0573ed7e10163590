"""Monotone moves of dense lanes — a compaction and an expansion by
log-step shifts.

A set of capacity-sized lanes (one row per position, struct-of-arrays)
whose occupied entries must all move the same way, by amounts that never
let one entry overtake another, needs no index per slot: write each
entry's amount in binary and move it one bit at a time. A stage shifts the
WHOLE lane by 2^k and selects, per position, the shifted or the resting
value — one slice at an offset and an elementwise select, which the TPU
runs at memory speed. `x.at[tgt].set(col, mode="drop")` states the same
move as a general scatter (nothing tells XLA the targets are ordered), and
a v5e prices that at ~67 ns a slot: 0.28 s for one int64 column of 2^22
rows that its HBM reads and writes in 0.1 ms (PERF.md §6, PR 35).

  compact  entries with `keep` slide LEFT over the dropped ones: entry t
           moves by the number of dropped entries before it. Stages go
           from the LOWEST bit up; no two entries meet: for kept a < b,
           b - a >= 1 + c[b] - c[a] >= 1 + lo_k(c[b]) - lo_k(c[a]).
  expand   occupied entries slide RIGHT by `amount`, nondecreasing in
           the position and at most `bound` (static). Stages go from the
           HIGHEST bit of `bound` down; no two meet: a + hi_k(d[a]) <
           b + hi_k(d[b]). An entry pushed to or past the capacity is
           dropped — `mode="drop"`'s meaning.

The amounts travel through the stages ONCE per call, as one int32 lane
that also says which positions are occupied, and give each stage its
mask; every data lane reuses the masks: per lane a stage is one shifted
read and one select. What a stage leaves behind where an entry moved out
is never read again: the closing select puts `fill` wherever no entry came
to rest, so the result equals the scatter's, padding included.
"""

from __future__ import annotations

from functools import partial
from typing import Sequence

import jax
import jax.numpy as jnp
from jax import lax

# Marks a position of the amount lane as occupied (an amount of 0 is an
# entry at rest, not an empty position); capacities stay below it.
_OCCUPIED = 1 << 30


def _shifted(x: jnp.ndarray, k, toward: int, fill: jnp.ndarray) -> jnp.ndarray:
    """x moved k positions (k traced, 0 <= k <= C) toward the higher
    indices (`toward` > 0) or the lower: what leaves at one end is
    dropped, the other end holds `fill`."""
    C = x.shape[0]
    if toward > 0:
        return lax.dynamic_slice(lax.pad(x, fill, [(C, 0, 0)]), (C - k,), (C,))
    return lax.dynamic_slice(lax.pad(x, fill, [(0, C, 0)]), (k,), (C,))


@partial(jax.jit, static_argnames=("fills", "steps", "toward"))
def _move(occupied: jnp.ndarray, amount: jnp.ndarray,
          lanes: Sequence[jnp.ndarray], fills: tuple, steps: tuple,
          toward: int):
    """Every occupied entry moves `amount` positions in direction `toward`
    (+1 / -1), one power of two of `steps` per stage; the caller's order
    of `steps` is what keeps two entries from meeting. Returns (lanes',
    occupied').

    The stages are ONE loop over the table of steps, its body a stage
    with a traced shift: a program holds one stage per call however many
    the capacity asks for (an apply with three moves of 13 lanes is ~40
    fused ops, not ~800: what a compiler, a compile cache and a cold
    `recover()` pay by the op). Bare `lax` primitives and a jit of its
    own, so a process traces a move once per signature however many
    programs and sessions hold it."""
    C = occupied.shape[0]
    assert C < _OCCUPIED
    fills = [jnp.asarray(f, x.dtype) for x, f in zip(lanes, fills)]
    zero, zeros = jnp.int32(0), jnp.zeros(C, jnp.int32)
    s = lax.select(occupied, amount.astype(jnp.int32) | _OCCUPIED, zeros)
    ks = jnp.asarray(steps, jnp.int32)

    def stage(i, carry):
        s, lanes = carry
        k = ks[i]
        out = lax.ne(s & k, zeros)
        inn = _shifted(out, k, toward, jnp.asarray(False))
        # an entry that moved out leaves 0 behind: empty, at rest for good
        s = lax.select(inn, _shifted(s, k, toward, zero),
                       lax.select(out, zeros, s))
        return s, [lax.select(inn, _shifted(x, k, toward, f), x)
                   for x, f in zip(lanes, fills)]

    if steps:
        # inside a shard_map a loop's carry keeps its type: a lane that is
        # the same on every shard (a chunk's all-true validity) becomes
        # per-shard with the first mask, so it enters the loop as such
        per_shard = jax.typeof(s).vma
        lanes = [lax.pcast(x, tuple(per_shard - jax.typeof(x).vma),
                           to="varying") for x in lanes]
        s, lanes = lax.fori_loop(0, len(steps), stage, (s, lanes))
    occupied = lax.ne(s, zeros)
    return ([lax.select(occupied, x, lax.full_like(x, f))
             for x, f in zip(lanes, fills)], occupied)


def compact(keep: jnp.ndarray, lanes: Sequence[jnp.ndarray],
            fills: Sequence) -> list:
    """The entries with `keep`, in their order, at the front of each lane;
    `fill` behind them. Equals `full(C, fill).at[where(keep, cumsum(keep)
    - 1, C)].set(lane, mode="drop")`, lane for lane."""
    C = keep.shape[0]
    dropped_before = jnp.cumsum((~keep).astype(jnp.int32))
    steps = tuple(1 << b for b in range((C - 1).bit_length()))
    return _move(keep, dropped_before, list(lanes), tuple(fills), steps,
                 -1)[0]


def expand(occupied: jnp.ndarray, amount: jnp.ndarray, bound: int,
           lanes: Sequence[jnp.ndarray], fills: Sequence):
    """Each occupied entry `amount` positions to the right: `amount` is
    nondecreasing over the occupied positions and at most `bound`. Returns
    (lanes', occupied'): `fill` where no entry landed. Equals
    `full(C, fill).at[where(occupied, arange(C) + amount, C)].set(lane,
    mode="drop")`, lane for lane."""
    C = occupied.shape[0]
    # an entry bound for a position past the end is dropped at once: those
    # are the last ones, the rest still move by nondecreasing amounts
    lands = jnp.arange(C, dtype=jnp.int32) + amount.astype(jnp.int32) < C
    bound = min(bound, C - 1)
    steps = tuple(1 << b for b in reversed(range(bound.bit_length())))
    return _move(occupied & lands, amount, list(lanes), tuple(fills), steps,
                 +1)
