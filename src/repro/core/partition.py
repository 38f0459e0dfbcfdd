"""Stable block-structured distribution (paper §4.1–§4.3), TPU formulation.

The paper's three partition phases map to:

  local classification  -> per-tile grouping: each tile (= the VMEM-resident
                           analogue of a thread's stripe-walk with k buffer
                           blocks) groups its elements by bucket id.
  prefix sum            -> per-tile histograms + exclusive scans over tiles
                           (the paper's "prefix sum over stripes"), giving
                           every tile's write offset inside every bucket.
  block permutation +   -> a single gather by the precomputed permutation;
  cleanup                  under jit the input buffer is donated, so XLA
                           reuses it (the in-place property).  The faithful
                           cycle-following variant lives in
                           ``repro.kernels.permute_inplace``.

The resulting permutation is *stable* (tiles in order, stable grouping within
a tile), which the higher levels rely on.

Two interchangeable engines produce that same permutation (DESIGN.md §2):

  "xla"     per-tile stable ``argsort`` grouping + prefix sums + one gather
            (O(tile·log tile) comparison sort inside the distribution pass);
  "pallas"  the fused rank+histogram kernel
            (``kernels.level_fused.rank_hist``): one non-sequential grid
            pass emits tile-local ranks and the per-tile histogram, and a
            tiny prefix epilogue closes dest[i] = offsets[b_i] +
            tile_off[t_i, b_i] + rank[i] — branchless, no comparison sort,
            no bincount glue, and no running counters to serialize the
            grid (DESIGN.md §10).  The sequential counting-rank kernel
            (``kernels.dispatch_rank``) remains as the MoE dispatch engine
            and a tested oracle.  The payload move is a scatter by dest;
            when the caller can guarantee block-homogeneous buckets
            (``partition_blocks``) the faithful in-place block-permutation
            kernel carries the move instead.

Both engines emit the *identical* stable permutation, so they are
bit-exact interchangeable — the plan cache picks per (n, dtype, hardware).

This module is also the engine of MoE token dispatch (``repro.models.moe``):
there the "classifier" output is the router's expert id.
"""
from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import obs

__all__ = [
    "tile_histogram",
    "stable_partition",
    "batched_stable_partition",
    "partition_permutation",
    "partition_blocks",
    "ENGINES",
]

Pytree = Any

ENGINES = ("xla", "pallas")


def _default_interpret() -> bool:
    """Pallas kernels lower natively on TPU; everywhere else interpret.

    Delegates to the one shared policy (``kernels.resolve_interpret``) so
    every kernel call site in the repo resolves identically.
    """
    from repro.kernels import resolve_interpret

    return resolve_interpret()


def tile_histogram(bucket_tiles: jax.Array, nb: int) -> jax.Array:
    """(T, tile) int bucket ids -> (T, nb) histogram."""
    return jax.vmap(lambda row: jnp.bincount(row, length=nb))(bucket_tiles)


def partition_permutation(
    bucket: jax.Array, nb: int, tile: int
) -> Tuple[jax.Array, jax.Array]:
    """Compute the stable partition permutation.

    Args:
      bucket: (n,) int32 bucket ids in [0, nb); n must be a multiple of tile.
      nb: number of buckets (static).
      tile: tile size (static) — the VMEM block granularity.

    Returns:
      (perm, offsets): ``sorted_x = x[perm]`` groups any payload by bucket,
      stably; ``offsets`` (nb+1,) int32 bucket boundaries.
    """
    n = bucket.shape[0]
    if n % tile:
        raise ValueError(f"n={n} not a multiple of tile={tile}")
    num_tiles = n // tile
    bt = bucket.reshape(num_tiles, tile)

    # Local classification: stable grouping within each tile.
    # int32 keeps the scatter below typed against its int32 zeros operand
    # when x64 is enabled (argsort then returns int64 indices)
    order = jnp.argsort(bt, axis=1, stable=True).astype(jnp.int32)  # (T, tile)
    bt_g = jnp.take_along_axis(bt, order, axis=1)

    # Prefix sums (paper: over stripes).
    hist = tile_histogram(bt, nb)  # (T, nb)
    totals = hist.sum(axis=0)  # (nb,)
    offsets = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(totals).astype(jnp.int32)]
    )
    tile_off = (jnp.cumsum(hist, axis=0) - hist).astype(jnp.int32)  # excl, (T, nb)
    run_start = (jnp.cumsum(hist, axis=1) - hist).astype(jnp.int32)  # excl, (T, nb)

    # Block permutation: destination of each grouped element.
    pos = jnp.arange(tile, dtype=jnp.int32)[None, :]
    dest = (
        jnp.take(offsets[:-1], bt_g, axis=0)
        + jnp.take_along_axis(tile_off, bt_g, axis=1)
        + (pos - jnp.take_along_axis(run_start, bt_g, axis=1))
    )  # (T, tile)

    src = (order + (jnp.arange(num_tiles, dtype=jnp.int32) * tile)[:, None]).reshape(-1)
    perm = (
        jnp.zeros((n,), jnp.int32).at[dest.reshape(-1)].set(src, mode="promise_in_bounds")
    )
    return perm, offsets


def stable_partition(
    bucket: jax.Array,
    arrays: Pytree,
    nb: int,
    tile: int,
    engine: str = "xla",
    *,
    offsets: Optional[jax.Array] = None,
    interpret: Optional[bool] = None,
) -> Tuple[Pytree, jax.Array]:
    """Stably reorder every leaf of ``arrays`` so buckets are contiguous.

    ``engine`` selects how the stable placement is computed:

      "xla"     per-tile stable argsort + prefix sums + gather (default);
      "pallas"  the fused rank+histogram kernel + scatter — no comparison
                sort inside the distribution pass, no bincount glue (the
                kernel's histogram yields the boundaries as a by-product).
                ``offsets`` is accepted for API compatibility but ignored
                on this path: the fused kernel recomputes identical
                boundaries for free.

    Both engines produce bit-identical results.  Returns
    (reordered pytree, offsets (nb+1,)).
    """
    if engine == "pallas":
        from repro.kernels.level_fused import rank_hist

        dest, offsets = rank_hist(
            bucket.astype(jnp.int32), nb=nb, interpret=interpret
        )
        with obs.layer("move"):
            out = jax.tree.map(
                lambda a: jnp.zeros_like(a).at[dest].set(a, mode="promise_in_bounds"),
                arrays,
            )
        return out, offsets
    if engine != "xla":
        raise ValueError(f"unknown partition engine {engine!r}; expected {ENGINES}")
    perm, offsets = partition_permutation(bucket, nb, tile)
    with obs.layer("move"):
        out = jax.tree.map(lambda a: jnp.take(a, perm, axis=0), arrays)
    return out, offsets


def batched_stable_partition(
    bucket: jax.Array,
    arrays: Pytree,
    nb: int,
    tile: int,
    engine: str = "xla",
    *,
    offsets: Optional[jax.Array] = None,
    interpret: Optional[bool] = None,
) -> Tuple[Pytree, jax.Array]:
    """Per-row stable partition over a leading batch dimension (DESIGN.md §6).

    ``bucket`` is (B, n); every leaf of ``arrays`` is (B, n, ...).  Each row
    is partitioned independently — elements never cross rows — producing
    per-row bucket boundaries ``offsets`` (B, nb+1).

    Engines mirror :func:`stable_partition`:

      "xla"     the per-tile-argsort permutation, vmapped over rows (dense
                jnp ops batch natively);
      "pallas"  ONE launch of the batch-grid fused rank+histogram kernel
                (``kernels.level_fused.rank_hist_batched``) — rows are
                fully independent, no counter resets exist — followed by
                a flat scatter.  ``offsets`` is ignored on this path (the
                kernel recomputes identical boundaries for free).

    Both produce the bit-identical per-row stable permutation.
    """
    B, n = bucket.shape
    if engine == "pallas":
        from repro.kernels.level_fused import rank_hist_batched

        dest, offsets = rank_hist_batched(
            bucket.astype(jnp.int32), nb=nb, interpret=interpret
        )
        # flatten the per-row destinations into one scatter over (B*n, ...)
        flat_dest = (dest + n * jnp.arange(B, dtype=jnp.int32)[:, None]).reshape(-1)

        def move(a):
            fa = a.reshape((B * n,) + a.shape[2:])
            out = jnp.zeros_like(fa).at[flat_dest].set(fa, mode="promise_in_bounds")
            return out.reshape(a.shape)

        return jax.tree.map(move, arrays), offsets
    if engine != "xla":
        raise ValueError(f"unknown partition engine {engine!r}; expected {ENGINES}")
    perm, offsets = jax.vmap(lambda b: partition_permutation(b, nb, tile))(bucket)
    out = jax.tree.map(
        lambda a: jax.vmap(lambda row, p: jnp.take(row, p, axis=0))(a, perm), arrays
    )
    return out, offsets


def partition_blocks(
    arrays: Pytree,
    block_bucket: jax.Array,
    nb: int,
    block_elems: int,
    *,
    interpret: Optional[bool] = None,
) -> Tuple[Pytree, jax.Array]:
    """Group *block-homogeneous* data with the in-place Pallas kernel.

    The faithful payload move (paper §4.2): when the caller guarantees each
    consecutive run of ``block_elems`` elements shares one bucket (the
    block_bucket (N,) array gives that bucket per block — e.g. MoE capacity
    blocks, distributed chunk exchange), whole blocks move HBM-in-place via
    the stable swap-cycle kernel (``kernels.block_permute``): the *stable*
    block destinations are computed up front (``stable_block_dest``) and
    the kernel chases the permutation cycles over aliased input/output
    refs — no second n-sized buffer.  The kernel path requires every leaf
    to be 1-D with ``block_elems`` a multiple of 128; if any leaf is
    ineligible the whole pytree falls back to a gather by the stable block
    order.  Both paths realize the SAME stable permutation, so they are
    interchangeable per call (the legacy bucket-pointer kernel in
    ``kernels.permute_inplace``, which is not stable, remains as the
    faithful-§4.2 reference).

    Returns (grouped pytree, (nb+1,) *block*-boundary offsets).
    """
    from repro.kernels.block_permute import permute_blocks_by_dest, stable_block_dest

    if interpret is None:
        interpret = _default_interpret()
    hist = jnp.bincount(block_bucket, length=nb)
    d = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(hist).astype(jnp.int32)]
    )

    leaves = jax.tree.leaves(arrays)
    kernel_ok = block_elems % 128 == 0 and all(
        a.ndim == 1 and a.shape[0] % block_elems == 0 for a in leaves
    )

    if kernel_ok:
        dst = stable_block_dest(block_bucket)
        move = lambda a: permute_blocks_by_dest(
            a, dst, block_elems=block_elems, interpret=interpret
        )
    else:
        block_order = jnp.argsort(block_bucket, stable=True)
        nblocks = block_bucket.shape[0]

        def move(a):
            blocks = a.reshape((nblocks, block_elems) + a.shape[1:])
            return jnp.take(blocks, block_order, axis=0).reshape(a.shape)

    return jax.tree.map(move, arrays), d
