"""What the per-layer readers in benchmark/layers/ share: the window's
committed checkpoints and the reduction of the actors' phase split."""

from __future__ import annotations

from . import stats  # noqa: F401


def committed(run: dict) -> list:
    return [r for r in run["window"]["checkpoints"] if "commit_ns" in r]


def per_checkpoint(run: dict, total: float):
    n = len(committed(run))
    return total / n if n else None


def phase_s_per_ckpt(run: dict, key: str):
    """`EpochTrace.phases[actor][key]`: the largest over the actors of one
    checkpoint, the median of that over the window's checkpoints."""
    per = [max(p.get(key, 0) for p in r["phases"].values()) / 1e9
           for r in committed(run) if r.get("phases")]
    return stats.median(per) if per else None
