"""Pallas TPU kernels: fused counting-rank placement (IPS4o distribution).

Token->expert dispatch is the paper's distribution problem with the router
as classifier (DESIGN.md §3).  These kernels fuse, in ONE pass over the
element stream, what XLA would otherwise do with sort+cumsum+scatter chains:

  dest[i] = start[b_i] + (#elements with bucket b_i before i)

i.e. the *stable* counting placement — rank = prefix count of equal-bucket
lanes, branchless, no comparison sort anywhere in the distribution pass.
The cross-tile running counters persist across the sequential TPU grid —
the same "running bucket pointers on one core" idea as the block
permutation kernel (§4.2), at element granularity.

One kernel body serves every variant.  The counters are a VMEM
(nbp, 128) block — one sublane per bucket, every lane equal — set from
``start`` at the first tile and advanced tile by tile by the level
kernel's row-ranking helper (``kernels.level_fused.rank_rows``: one MXU
prefix per 128-lane row, so nothing unrolls over the buckets).

  * ``dispatch_ranks``: MoE experts (``ops.group_by(method="pallas")``);
    n must be a multiple of the tile.
  * ``partition_ranks``: nb up to hundreds of buckets (the sort hot path's
    2k+1), self-padding to the tile.  Formerly the "pallas" partition
    engine of ``core.partition.stable_partition``; the fused level kernel
    (``kernels.level_fused``, DESIGN.md §10) demoted it to a
    sequential-counter oracle — its running counters serialize the grid,
    where the fused kernel's tile-local ranks + prefix epilogue do not.

``partition_ranks_batched`` (DESIGN.md §6) lifts the kernel over a
leading batch dimension with a *batch grid dimension*: grid =
(B, num_tiles).  The TPU grid iterates sequentially, minor dimension last,
so the running counters simply reset at tile 0 of every row (instead of
only at program 0) and each row's placement stays independent — B stable
per-row partitions in one kernel launch.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret
from repro.kernels.level_fused import padded_buckets, rank_rows

__all__ = ["dispatch_ranks", "partition_ranks", "partition_ranks_batched"]

LANES = 128


def _default_rank_rows(nb: int) -> int:
    """Tile rows from the unified launch spec (kind ``"rank"``; the spec's
    ``k`` is nb here), floored at the legacy 8 for degenerate budgets."""
    from repro.launch.roofline import launch_spec

    return launch_spec("rank", 4, nb).rows or 8


def _rank_kernel(start_ref, bid_ref, dest_ref, run_ref, *, rows: int):
    # the minor grid dim walks the tiles of one row: counters restart at
    # each row's first tile
    @pl.when(pl.program_id(1) == 0)
    def _init():
        run_ref[...] = start_ref[0]

    run_ref[...] = rank_rows(bid_ref, dest_ref, rows, run_ref.shape[0], run_ref[...])


def _counting_dest(bucket: jax.Array, start: jax.Array, nb: int, rows: int,
                   interpret: bool) -> jax.Array:
    """(B, n_pad) ids, (B, nb) starts -> (B, n_pad) stable destinations;
    n_pad a multiple of the rows*128 tile."""
    B, n_pad = bucket.shape
    nbp = padded_buckets(nb)
    num_tiles = n_pad // (rows * LANES)
    start = jnp.pad(start.astype(jnp.int32), ((0, 0), (0, nbp - nb)))
    start = jnp.broadcast_to(start[:, :, None], (B, nbp, LANES))
    bid2 = bucket.reshape(B * n_pad // LANES, LANES)
    tile = lambda b, i: (b * num_tiles + i, 0)
    dest = pl.pallas_call(
        functools.partial(_rank_kernel, rows=rows),
        grid=(B, num_tiles),
        in_specs=[
            pl.BlockSpec((1, nbp, LANES), lambda b, i: (b, 0, 0)),  # starts
            pl.BlockSpec((rows, LANES), tile),
        ],
        out_specs=pl.BlockSpec((rows, LANES), tile),
        out_shape=jax.ShapeDtypeStruct(bid2.shape, jnp.int32),
        scratch_shapes=[pltpu.VMEM((nbp, LANES), jnp.int32)],  # running counters
        interpret=interpret,
    )(start, bid2)
    return dest.reshape(B, n_pad)


@functools.partial(jax.jit, static_argnames=("num_experts", "rows", "interpret"))
def dispatch_ranks(
    expert_id: jax.Array,
    expert_start: jax.Array,
    *,
    num_experts: int,
    rows: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Destination slot per token for expert-major grouping.

    Args:
      expert_id: (n,) int32 in [0, num_experts); n multiple of rows*128.
      expert_start: (num_experts,) int32 exclusive prefix of expert counts.
      rows: tile rows; None takes the largest unified-launch-spec
        candidate whose tile divides n (legacy 8 when none does).

    Returns (n,) int32 destinations (a permutation when starts come from the
    true histogram).
    """
    interpret = resolve_interpret(interpret)
    n = expert_id.shape[0]
    if rows is None:
        from repro.launch.roofline import launch_spec

        rows = launch_spec("rank", 4, num_experts, n=n).rows or 8
    if n % (rows * LANES):
        raise ValueError(f"n={n} not a multiple of tile={rows * LANES}")
    return _counting_dest(
        expert_id.astype(jnp.int32)[None], expert_start[None], num_experts,
        rows, interpret,
    )[0]


def _pad_to_tile(bucket: jax.Array, nb: int, tile: int) -> jax.Array:
    """Pad axis 1 of (B, n) ids to the kernel tile with the trash id nb."""
    n = bucket.shape[1]
    n_pad = -(-n // tile) * tile
    return jnp.pad(bucket.astype(jnp.int32), ((0, 0), (0, n_pad - n)),
                   constant_values=nb)


@functools.partial(jax.jit, static_argnames=("nb", "rows", "interpret"))
def partition_ranks(
    bucket: jax.Array,
    start: jax.Array,
    *,
    nb: int,
    rows: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Stable counting destination per element, vectorized over buckets.

    Args:
      bucket: (n,) int32 bucket ids; ids outside [0, nb) are ignored (their
        dest is unspecified and they never touch a real bucket's counter —
        the wrapper layers use id ``nb`` as alignment padding).
      start: (nb,) int32 exclusive prefix of bucket counts.
      nb: number of buckets (static).
      rows: tile rows; None derives the unified launch spec's candidate
        (the kernel self-pads, so any tile fits any n).

    Returns (n,) int32 destinations: ``start[b_i]`` + the number of earlier
    elements with the same bucket — the stable partition permutation's
    scatter index (identical to the XLA per-tile-argsort placement).
    """
    interpret = resolve_interpret(interpret)
    n = bucket.shape[0]
    if rows is None:
        rows = _default_rank_rows(nb)
    bid = _pad_to_tile(bucket[None], nb, rows * LANES)
    return _counting_dest(bid, start[None], nb, rows, interpret)[0, :n]


@functools.partial(jax.jit, static_argnames=("nb", "rows", "interpret"))
def partition_ranks_batched(
    bucket: jax.Array,
    start: jax.Array,
    *,
    nb: int,
    rows: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Per-row stable counting destinations, batch grid dimension (B, tiles).

    Args:
      bucket: (B, n) int32 bucket ids per row; ids outside [0, nb) are
        ignored (their dest is unspecified; used for alignment padding).
      start: (B, nb) int32 per-row exclusive prefix of bucket counts.
      nb: number of buckets per row (static).

    Returns (B, n) int32 destinations *within each row*: row b's element i
    goes to ``start[b, bucket[b, i]]`` + the number of earlier row-b
    elements in the same bucket — B independent stable partitions computed
    by one kernel, counters resetting at each row's first tile.
    """
    interpret = resolve_interpret(interpret)
    n = bucket.shape[1]
    if rows is None:
        rows = _default_rank_rows(nb)
    bid = _pad_to_tile(bucket, nb, rows * LANES)
    return _counting_dest(bid, start, nb, rows, interpret)[:, :n]
