"""Pallas TPU kernel: segmented in-VMEM bitonic sort (the base case).

The paper's base case is insertion sort run while the bucket is
cache-resident (§4.7: "on the last level, we perform the base case sorting
immediately after the bucket has been completely filled ... more
cache-friendly").  The TPU analogue of "cache-resident small sort" is a
branch-free **bitonic sorting network** executed entirely inside VMEM on one
window of W elements: O(W log^2 W) compare-exchanges, every one a dense
(rows, lanes) VPU select with zero data-dependent control flow — insertion
sort's data-dependent inner loop would be poison on a vector unit.

The sort key is the lexicographic pair (bucket_id, key): this makes the
window sort *segmented* — bucket boundaries inside the window are respected
automatically — which is what lets IPS4o's overlapped-window base case fix
bucket-straddling tiles (DESIGN.md §4.3).  A payload index rides along so
the wrapper can permute arbitrary payload pytrees.

Each compare-exchange round at distance d fetches every lane's partner
(idx XOR d) with two lane rotations of the (8, W) block and keeps the
minimum or maximum by the direction bit (idx AND 2*size).  All shapes
static.
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

__all__ = ["bitonic_sort_windows"]


# windows per grid step: one (8, W) block, the 32-bit sublane tile
_ROWS = 8


def _cmp_exchange(b, k, v, lane, size: int, d: int, W: int):
    """One bitonic round on (rows, W) windows: partner = lane ^ d, fetched
    with two lane rotations (the rotated lane index picks the one that
    holds the partner, so the sign convention does not matter); runs are
    ascending iff (lane & 2*size) == 0."""
    roll = lambda x, sh: pltpu.roll(x, sh, 1)
    fwd = roll(lane, d) == (lane ^ d)
    bp, kp, vp = (jnp.where(fwd, roll(x, d), roll(x, W - d)) for x in (b, k, v))
    # lexicographic (bucket, key) order; equal pairs never move, so the
    # payload of a tie stays put on both lanes
    p_less = (bp < b) | ((bp == b) & (kp < k))
    p_more = (bp > b) | ((bp == b) & (kp > k))
    # the lower lane of an ascending pair keeps the min, as does the upper
    # lane of a descending one
    want_min = ((lane & (2 * size)) != 0) == ((lane & d) != 0)
    take = (want_min & p_less) | (jnp.logical_not(want_min) & p_more)
    return tuple(jnp.where(take, xp, x) for x, xp in ((b, bp), (k, kp), (v, vp)))


def _kernel(b_ref, k_ref, v_ref, bo_ref, ko_ref, vo_ref, *, W: int):
    b, k, v = b_ref[...], k_ref[...], v_ref[...]
    lane = jax.lax.broadcasted_iota(jnp.int32, b.shape, 1)
    for s in range(int(math.log2(W))):
        size = 1 << s  # ascending runs of length 2*size after this stage
        for dp in range(s, -1, -1):
            b, k, v = _cmp_exchange(b, k, v, lane, size, 1 << dp, W)
    bo_ref[...] = b
    ko_ref[...] = k
    vo_ref[...] = v


@functools.partial(jax.jit, static_argnames=("interpret",))
def bitonic_sort_windows(
    bucket: jax.Array,
    keys: jax.Array,
    idx: jax.Array,
    *,
    interpret: Optional[bool] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Sort each window (row) of (num_w, W) arrays by (bucket, key).

    W must be a power of two (a multiple of 128 on the chip).  Returns
    permuted (bucket, keys, idx).  Windows go eight per grid step; VMEM
    per step: 3 arrays * 8 * W * 4 B (W=8192 -> 768 KiB).
    ``interpret=None`` resolves through the shared off-TPU policy
    (``kernels.resolve_interpret``).
    """
    interpret = resolve_interpret(interpret)
    num_w, W = keys.shape
    if W & (W - 1):
        raise ValueError(f"W={W} must be a power of two")
    rows = -(-num_w // _ROWS) * _ROWS  # pad windows sort among themselves
    pad = lambda x: jnp.pad(x, ((0, rows - num_w), (0, 0)))
    spec = lambda: pl.BlockSpec((_ROWS, W), lambda i: (i, 0))
    shapes = [jax.ShapeDtypeStruct((rows, W), x.dtype) for x in (bucket, keys, idx)]
    out = pl.pallas_call(
        functools.partial(_kernel, W=W),
        grid=(rows // _ROWS,),
        in_specs=[spec(), spec(), spec()],
        out_specs=[spec(), spec(), spec()],
        out_shape=shapes,
        interpret=interpret,
    )(pad(bucket), pad(keys), pad(idx))
    return tuple(x[:num_w] for x in out)
