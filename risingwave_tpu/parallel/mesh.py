"""Device mesh + vnode placement.

Reference analogue: meta's parallel-unit scheduling (`ParallelUnitMapping`,
src/common/src/hash/consistent_hash/mapping.rs:200-266) assigns the 256
vnodes to parallel units; here vnodes map to *mesh shards*. The mapping is
contiguous ranges (minimal-movement rebalance on scale, like the reference's
rebalancer) and lives on host as a [256] int array, shipped to device as a
routing table for the all_to_all exchange.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import numpy as np
from jax import shard_map
from jax.sharding import Mesh

from ..common.vnode import VNODE_COUNT

VNODE_AXIS = "vnode"

__all__ = ["VNODE_AXIS", "make_mesh", "shard_map", "shard_vnode_bitmaps",
           "vnode_to_shard"]


def make_mesh(n_devices: Optional[int] = None, devices: Optional[Sequence] = None,
              axis: str = VNODE_AXIS) -> Mesh:
    """1-D mesh over the vnode (data-parallel) axis. Higher-D meshes (e.g.
    separating ICI rings) reshape here without touching executors."""
    if devices is None:
        devices = jax.devices()
        if n_devices is not None:
            assert len(devices) >= n_devices, \
                f"need {n_devices} devices, default platform has {len(devices)}; " \
                f"pass devices= explicitly (e.g. jax.devices('cpu') with " \
                f"xla_force_host_platform_device_count) for a virtual mesh"
            devices = devices[:n_devices]
    elif n_devices is not None:
        assert len(devices) >= n_devices, \
            f"need {n_devices} devices, given {len(devices)}"
        devices = devices[:n_devices]
    return Mesh(np.asarray(devices), (axis,))


def vnode_to_shard(n_shards: int) -> np.ndarray:
    """Contiguous range placement: vnode v -> shard v * n / 256 (int32 [256]).

    Contiguity means scaling from n to n' moves only boundary ranges —
    the same minimal-movement property the reference's rebalancer targets
    (src/meta/src/stream/scale.rs).
    """
    return ((np.arange(VNODE_COUNT, dtype=np.int64) * n_shards) // VNODE_COUNT).astype(np.int32)


def shard_vnode_bitmaps(n_shards: int) -> list[np.ndarray]:
    """Per-shard ownership bitmaps (reference StreamActor.vnode_bitmap)."""
    owner = vnode_to_shard(n_shards)
    return [(owner == s) for s in range(n_shards)]
