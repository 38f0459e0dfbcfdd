"""Pallas TPU kernel: branchless merge-path stable 2-way merge.

The streaming subsystem (DESIGN.md §7) decomposes an out-of-core sort into
IPS4o-sorted runs plus k-way merging; this kernel is the merge half.  The
classic CPU merge is a data-dependent two-pointer walk — poison on a VPU
for the same reason insertion sort is (every step is a branch on data).
The TPU formulation splits the work in two branch-free stages:

  1. **Diagonal partition** (`merge_path_partition`, plain XLA): for every
     output-tile boundary d = t*T, a binary search on the merge-path
     diagonal finds i(d) = #A-elements among the first d outputs of the
     *stable* merge (ties go to A).  All diagonals search in parallel —
     one fori_loop of ceil(log2 nA)+1 dense gather steps, no kernel needed.
  2. **In-tile merge** (the Pallas kernel): tile t owns output range
     [d_t, d_{t+1}) which merge-path guarantees is exactly
     A[ia:ia+la] ++ B[ja:ja+lb].  The wrapper gathers each tile's two
     windows into one row of (key, src) pairs — window A ascending ++
     window B *reversed*, a bitonic sequence of 2T pairs — and the kernel
     sorts eight such rows per grid step with a branchless **bitonic
     merger**: log2(2T) compare-exchange rounds, each two lane rotations
     and a dense VPU select at distance d = T..1.  Ranking is
     lexicographic on (key, src) with every A source index (< nA) below
     every B source index (>= nA), which realizes the stable tie rule
     *exactly* (ties to A, order preserved within runs) with no
     tie-epsilon.  Lanes beyond la/lb mask to (sentinel key, 2^30 src)
     and sink to the tail.  The merger does O(T log T) work per tile.

The kernel emits a *permutation* (int32 source index into ``A ++ B``), not
merged keys: the wrapper layers (``repro.stream.merge``) gather keys and
arbitrary payload pytrees through it, which is also what makes the merge
trivially stable for (key, payload) rows.

The windows are gathered in XLA (from the per-tile starts and lengths),
so every kernel block is a dense (8, 2T) tile with T a multiple of 128.
The default T comes from the unified ``launch.roofline.KernelLaunchSpec``
(kind ``"merge"``); the stream plan cache sweeps the spec's candidate
tiles.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

__all__ = ["merge_path_partition", "merge_path_perm", "merge_rows"]


def _sentinel_np(dtype):
    """Largest representable value as a *numpy* scalar (static kernel
    parameter — a traced ``sampling.sentinel_for`` would be a captured
    constant, which pallas_call rejects)."""
    dtype = np.dtype(dtype)
    if np.issubdtype(dtype, np.floating):
        return dtype.type(np.finfo(dtype).max)
    return dtype.type(np.iinfo(dtype).max)


def merge_rows(key_bytes: int) -> int:
    """Default merge tile rows from the unified launch spec."""
    from repro.launch.roofline import launch_spec

    return launch_spec("merge", key_bytes).rows


def merge_path_partition(a: jax.Array, b: jax.Array, d: jax.Array) -> jax.Array:
    """#A-elements among the first ``d`` outputs of the stable merge of
    sorted runs ``a`` and ``b`` (ties to A), for every diagonal in ``d``.

    For each d the answer i is the largest value in
    [max(0, d-nB), min(d, nA)] with ``a[i-1] <= b[d-i]`` (the merge-path
    cut condition with the stable tie rule); the predicate is monotone in
    i, so a clamped binary search over all diagonals at once resolves in
    ceil(log2(nA+1))+1 dense steps.  Keys must be totally ordered under
    ``<=`` (the stream layer passes keyspace-encoded uints).
    """
    nA, nB = a.shape[0], b.shape[0]
    d = d.astype(jnp.int32)
    lo = jnp.maximum(0, d - nB)
    hi = jnp.minimum(d, nA)
    steps = int(nA).bit_length() + 1

    def body(_, state):
        lo, hi = state
        active = lo < hi
        mid = (lo + hi + 1) // 2  # candidate i in (lo, hi]
        am = jnp.take(a, jnp.clip(mid - 1, 0, nA - 1))
        bj = jnp.take(b, jnp.clip(d - mid, 0, nB - 1))
        q = am <= bj  # Q(mid): A[mid-1] still precedes the first unchosen B
        lo2 = jnp.where(q, mid, lo)
        hi2 = jnp.where(q, hi, mid - 1)
        return (jnp.where(active, lo2, lo), jnp.where(active, hi2, hi))

    lo, hi = jax.lax.fori_loop(0, steps, body, (lo, hi))
    return lo


# masked lanes sink past every real (key, src) pair: the key is the dtype
# sentinel (>= all keys) and the src outranks any real source index
_PAD_SRC = 1 << 30

# merge tiles per grid step: one (8, 2T) block, the 32-bit sublane tile
_TILES_PER_STEP = 8


def _merge_exchange(k, s, lane, d: int, W: int):
    """One always-ascending merger round at distance ``d`` on (rows, W)
    blocks: partner = lane ^ d, fetched with two lane rotations; the
    lower lane keeps the lexicographic (key, src) minimum.  The rotated
    lane index picks which rotation holds the partner, so the round does
    not depend on the rotation's sign convention."""
    roll = lambda x, sh: pltpu.roll(x, sh, 1)
    fwd = roll(lane, d) == (lane ^ d)
    kp = jnp.where(fwd, roll(k, d), roll(k, W - d))
    sp = jnp.where(fwd, roll(s, d), roll(s, W - d))
    p_less = (kp < k) | ((kp == k) & (sp < s))
    take = jnp.logical_xor(p_less, (lane & d) != 0)  # upper lane keeps the max
    return jnp.where(take, kp, k), jnp.where(take, sp, s)


def _merge_kernel(k_ref, s_ref, perm_ref, *, T: int):
    k = k_ref[...]  # (rows, 2T): A window ++ reversed B window, per tile
    s = s_ref[...]
    W = 2 * T
    lane = jax.lax.broadcasted_iota(jnp.int32, k.shape, 1)
    for dp in range(int(math.log2(W)) - 1, -1, -1):
        k, s = _merge_exchange(k, s, lane, 1 << dp, W)
    # first T sorted srcs are each tile's outputs (slots >= la+lb — final
    # tile only — hold pad srcs and are sliced off by the wrapper)
    perm_ref[...] = s[:, :T]


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def merge_path_perm(
    a: jax.Array,
    b: jax.Array,
    *,
    tile: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Stable-merge permutation of two sorted runs.

    Args:
      a, b: 1-D sorted arrays of one dtype, totally ordered under ``<=``
        (raw NaNs are the callers' concern — ``repro.stream`` passes
        keyspace-encoded keys, exactly like the sort entry points).
      tile: output elements per merge tile (the merge-path T; a power of
        two and a multiple of 128 for the chip — the in-tile bitonic
        merger runs log2(2T) rounds).  None derives the
        ``KernelLaunchSpec`` default for this key width.
      interpret: shared off-TPU policy via ``kernels.resolve_interpret``.

    Returns ``perm`` (nA+nB,) int32 with ``concat(a, b)[perm]`` equal to
    the *stable* merge: ties keep all of ``a`` before ``b`` and preserve
    order within each run — bit-identical to
    ``jnp.argsort(concat, stable=True)`` whenever a and b are themselves
    stably sorted prefixes of the concatenation.
    """
    interpret = resolve_interpret(interpret)
    nA, nB = a.shape[0], b.shape[0]
    n = nA + nB
    if tile is None:
        tile = merge_rows(a.dtype.itemsize) * 128
    if tile & (tile - 1):
        raise ValueError(f"tile={tile} must be a power of two")
    if n >= _PAD_SRC:
        raise ValueError("runs too long for the int32 source encoding")
    if nA == 0 or nB == 0:  # nothing to interleave
        return jnp.arange(n, dtype=jnp.int32)
    num_tiles = -(-n // tile)
    d = jnp.minimum(jnp.arange(num_tiles + 1, dtype=jnp.int32) * tile, n)
    part = merge_path_partition(a, b, d).astype(jnp.int32)
    ia = part[:-1, None]  # A window start
    la = jnp.diff(part)[:, None]  # A elements owned by each tile
    ja = d[:-1, None] - ia  # B window start
    lb = jnp.diff(d)[:, None] - la
    # gather each tile's (key, src) windows: A ascending ++ B reversed
    # (descending) is bitonic in (key, src) — within a run src ascends
    # with key, and the masked tails sit at the sequence's two ends
    sent = _sentinel_np(a.dtype)
    p = jnp.arange(tile, dtype=jnp.int32)[None, :]
    q = tile - 1 - p  # reversed B window index
    ap = jnp.pad(a, (0, tile))
    bp = jnp.pad(b, (0, tile))
    keys = jnp.concatenate([
        jnp.where(p < la, jnp.take(ap, ia + p), sent),
        jnp.where(q < lb, jnp.take(bp, ja + q), sent),
    ], axis=1)
    srcs = jnp.concatenate([
        jnp.where(p < la, ia + p, _PAD_SRC),
        jnp.where(q < lb, nA + ja + q, _PAD_SRC),
    ], axis=1)
    rows = -(-num_tiles // _TILES_PER_STEP) * _TILES_PER_STEP
    keys = jnp.pad(keys, ((0, rows - num_tiles), (0, 0)), constant_values=sent)
    srcs = jnp.pad(srcs, ((0, rows - num_tiles), (0, 0)), constant_values=_PAD_SRC)

    block = lambda w: pl.BlockSpec((_TILES_PER_STEP, w), lambda t: (t, 0))
    perm = pl.pallas_call(
        functools.partial(_merge_kernel, T=tile),
        grid=(rows // _TILES_PER_STEP,),
        in_specs=[block(2 * tile), block(2 * tile)],
        out_specs=block(tile),
        out_shape=jax.ShapeDtypeStruct((rows, tile), jnp.int32),
        interpret=interpret,
    )(keys, srcs)
    return perm.reshape(-1)[:n]
