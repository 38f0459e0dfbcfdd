"""Pallas TPU kernel: the fused single-pass level kernel (DESIGN.md §10).

One grid pass over the keys replaces the level pass's former three HBM
round-trips (classify kernel -> XLA histogram glue -> counting-rank
kernel).  Each grid step, on one VMEM-resident tile:

  1. **classifies** the tile — the dense lane-parallel compare against the
     splitters+sentinel block (tree), or the shift+mask extractor (radix),
     with pad positions (>= ``n_real``) routed to the dedicated pad bucket
     *in-kernel* (the host-side positional reroute disappears);
  2. **accumulates the per-tile bucket histogram** via the one-hot
     reduction (the paper's "count per bucket as a side effect");
  3. **ranks every element within its tile-local bucket run** — the
     exclusive one-hot prefix along the tile, i.e. the paper's
     block-local bucket runs expressed as (bucket, rank-in-run) pairs.

The per-tile outputs are all O(tile): bucket ids, in-run ranks, and the
(num_tiles, nb) histogram.  The *global* placement then closes in a tiny
XLA epilogue with no second pass over the data:

    dest[i] = offsets[b_i] + tile_off[t_i, b_i] + rank[i]

where ``offsets``/``tile_off`` are prefix sums of the histogram (O(T*nb)
work, not O(n)).  The composition is bit-identical to the XLA oracle's
stable partition permutation (``core.partition.partition_permutation``):
tiles in order, stable grouping within a tile — tiling-independent.

Unlike the counting-rank kernel (``dispatch_rank``), nothing here carries
running counters across the sequential grid: every grid step is
independent, so the same body serves the batched form (grid (B, tiles))
with zero reset logic, and a future multi-core stripe split needs no
cross-step state at all.

``rank_hist`` is the classify-free mode for callers that already hold
bucket ids (the segmented/composite level pass, ``stable_partition``'s
pallas engine): same fused rank+histogram pass, same epilogue, self-
padding with the out-of-range trash id like ``partition_ranks``.

Tile shapes come from the unified ``launch.roofline.KernelLaunchSpec``
(kind ``"level_fused"``); the plan cache sweeps the candidate rows.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.classify.radix import radix_bucket_ids
from repro.core.sampling import sentinel_for
from repro.kernels import resolve_interpret

__all__ = [
    "level_fused",
    "level_fused_batched",
    "rank_hist",
    "rank_hist_batched",
    "fused_rows",
]

LANES = 128


def fused_rows(n: int, key_bytes: int, k: int) -> int:
    """Largest spec row candidate whose tile divides ``n`` (0 if none —
    callers then stay on the XLA classifier, exactly like
    ``kernels.classify.default_rows``)."""
    from repro.launch.roofline import launch_spec

    return launch_spec("level_fused", key_bytes, k, n=n).rows


def padded_buckets(nb: int) -> int:
    """Sublanes of the in-kernel one-hot: nb rounded up to the bf16 tile
    (16 rows), so the one-hot feeds the MXU unpadded."""
    return -(-nb // 16) * 16


def rank_rows(bucket_ref, rank_ref, rows: int, nbp: int, run):
    """Stable in-bucket ranks of a (rows, 128) tile of bucket ids, row by
    row in flat order, written to ``rank_ref`` as ``run[b] + #(earlier
    elements of the tile with id b)``.

    ``run`` is a (nbp, 128) int32 per-bucket count with every lane equal
    (the bucket offsets at the tile start).  Returns ``run`` plus the tile
    histogram, same layout.  Each row is one MXU product: its (nbp, 128)
    one-hot against ``[strictly-upper | ones]`` (128, 256) gives, per
    bucket, the count at earlier lanes and the row total.  0/1 operands in
    bf16 with f32 accumulation are exact (no sum exceeds 128).  Ids
    outside [0, nbp) match no one-hot row: they count nowhere and their
    rank is unspecified (callers trim them).
    """
    ids = jax.lax.broadcasted_iota(jnp.int32, (nbp, LANES), 0)
    tri = (
        jax.lax.broadcasted_iota(jnp.int32, (LANES, 2 * LANES), 0)
        < jax.lax.broadcasted_iota(jnp.int32, (LANES, 2 * LANES), 1)
    ).astype(jnp.bfloat16)

    def body(r, run):
        hit = ids == bucket_ref[pl.ds(r, 1), :]  # (nbp, 128)
        cnt = jnp.dot(
            hit.astype(jnp.bfloat16), tri, preferred_element_type=jnp.float32
        ).astype(jnp.int32)  # (nbp, 256): [earlier lanes | row total]
        before = cnt[:, :LANES] + run
        rank_ref[pl.ds(r, 1), :] = jnp.sum(
            jnp.where(hit, before, 0), axis=0, keepdims=True, dtype=jnp.int32
        )
        return run + cnt[:, LANES:]

    return jax.lax.fori_loop(0, rows, body, run)


def column_to_row(col, nb: int):
    """(nbp, 128) lanes-equal int32 per-bucket counts -> (1, nb) row.

    Exact and VPU-only: each 128-bucket chunk selects its diagonal against
    an identity mask and reduces over sublanes."""
    parts = []
    for lo in range(0, col.shape[0], LANES):
        c = col[lo:lo + LANES, :]
        eye = jax.lax.broadcasted_iota(
            jnp.int32, c.shape, 0
        ) == jax.lax.broadcasted_iota(jnp.int32, c.shape, 1)
        parts.append(
            jnp.sum(jnp.where(eye, c, 0), axis=0, keepdims=True, dtype=jnp.int32)
        )
    row = parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)
    return row[:, :nb]


def tile_histogram(bucket_ref, rows: int, nb: int):
    """(1, nb) int32 histogram of a (rows, 128) tile of ids in
    [0, nb): per-lane one-hot counts accumulate row by row, then one lane
    reduction per bucket."""
    nbp = padded_buckets(nb)
    ids = jax.lax.broadcasted_iota(jnp.int32, (nbp, LANES), 0)

    def body(r, acc):
        return acc + jnp.where(ids == bucket_ref[pl.ds(r, 1), :], 1, 0)

    acc = jax.lax.fori_loop(0, rows, body, jnp.zeros((nbp, LANES), jnp.int32))
    col = jnp.sum(acc, axis=1, keepdims=True, dtype=jnp.int32)
    return column_to_row(jnp.broadcast_to(col, (nbp, LANES)), nb)


def _rank_and_hist(bucket_ref, rank_ref, hist_ref, *, nb: int, rows: int):
    """Tile-local (rank-in-bucket-run, histogram) of the ids in
    ``bucket_ref``: ranks to ``rank_ref``, the (1, 1, nb) histogram to
    ``hist_ref``."""
    nbp = padded_buckets(nb)
    run = rank_rows(
        bucket_ref, rank_ref, rows, nbp, jnp.zeros((nbp, LANES), jnp.int32)
    )
    hist_ref[0] = column_to_row(run, nb)


def classify_tile(keys, spl_ref, *, k: int, classifier: str, consumed: int):
    """Local bucket ids in [0, 2k) for one (rows, LANES) tile.

    Tree mode compares the whole tile against one splitter per step;
    ``spl_ref`` is a (1, k, 128) block whose row i holds upper i (the k-1
    splitters, then the dtype sentinel) on every lane.  ``j`` counts only
    the k-1 real splitters (a key above the sentinel, e.g. +inf, still
    lands in bucket k-1); ``eq`` compares against all k uppers."""
    if classifier == "radix":
        return radix_bucket_ids(keys, k, consumed)

    def body(i, carry):
        j, eq = carry
        s = spl_ref[0, pl.ds(i, 1), :]  # (1, 128)
        j = j + jnp.where((keys > s) & (i < k - 1), 1, 0)
        eq = jnp.maximum(eq, jnp.where(keys == s, 1, 0))
        return j, eq

    zero = jnp.zeros(keys.shape, jnp.int32)
    j, eq = jax.lax.fori_loop(0, k, body, (zero, zero))
    return 2 * j + eq


def splitter_block(splitters: jax.Array, k: int) -> jax.Array:
    """(..., k-1) splitters -> (B, k, 128) kernel operand: the dtype
    sentinel appended as the last bucket's upper, each upper broadcast
    across the lanes."""
    spl = splitters.reshape((-1, k - 1))
    sent = jnp.full((spl.shape[0], 1), sentinel_for(spl.dtype), spl.dtype)
    upper = jnp.concatenate([spl, sent], axis=1)
    return jnp.broadcast_to(upper[:, :, None], upper.shape + (LANES,))


def _fused_kernel(
    *refs, k: int, nb: int, rows: int, tiles_per_row: int, n_real: int,
    classifier: str, consumed: int,
):
    if classifier == "radix":
        keys_ref, bucket_ref, rank_ref, hist_ref = refs
        spl_ref = None
    else:
        keys_ref, spl_ref, bucket_ref, rank_ref, hist_ref = refs
    tile_id = pl.program_id(1) if tiles_per_row else pl.program_id(0)
    keys = keys_ref[...]  # (rows, 128)
    bucket = classify_tile(
        keys, spl_ref, k=k, classifier=classifier, consumed=consumed
    )
    # in-kernel pad routing: positions >= n_real (within the row, for the
    # batched grid) belong to the dedicated pad bucket 2k
    tile = rows * LANES
    pos = (
        tile_id * tile
        + jax.lax.broadcasted_iota(jnp.int32, (rows, LANES), 0) * LANES
        + jax.lax.broadcasted_iota(jnp.int32, (rows, LANES), 1)
    )
    bucket_ref[...] = jnp.where(pos >= n_real, 2 * k, bucket)
    _rank_and_hist(bucket_ref, rank_ref, hist_ref, nb=nb, rows=rows)


def _ids_kernel(bid_ref, rank_ref, hist_ref, *, nb: int, rows: int):
    _rank_and_hist(bid_ref, rank_ref, hist_ref, nb=nb, rows=rows)


def _close_placement(bucket, rank, hist, nb: int, tile: int):
    """The XLA epilogue: prefix-sum the histogram and place every element.

    O(num_tiles * nb) prefix work plus one fused elementwise gather —
    never a second pass of classify/one-hot over the data.  1-D form;
    callers vmap it for the batched grid (everything batches natively).
    """
    n = bucket.shape[0]
    num_tiles = hist.shape[0]
    totals = hist.sum(axis=0, dtype=jnp.int32)
    offsets = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(totals, dtype=jnp.int32)]
    )
    tile_off = (jnp.cumsum(hist, axis=0, dtype=jnp.int32) - hist)  # (T, nb)
    base = (offsets[:-1][None, :] + tile_off).reshape(num_tiles * nb)
    t_idx = jnp.arange(n, dtype=jnp.int32) // tile
    dest = jnp.take(base, t_idx * nb + bucket, mode="clip") + rank
    return dest, offsets


@functools.partial(
    jax.jit,
    static_argnames=("k", "n_real", "classifier", "consumed_bits", "rows", "interpret"),
)
def level_fused(
    keys: jax.Array,
    splitters: Optional[jax.Array] = None,
    *,
    k: int,
    n_real: Optional[int] = None,
    classifier: str = "tree",
    consumed_bits: int = 0,
    rows: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> Tuple[jax.Array, jax.Array]:
    """One fused level pass over ``keys`` (n,): classify + histogram + rank
    in a single kernel launch, placement closed by the prefix epilogue.

    Args:
      keys: (n,) totally ordered under ``>``/``==``; n a multiple of the
        rows*128 tile.
      splitters: (k-1,) sorted splitters (tree mode); None for radix.
      k: buckets per level; nb = 2k+1 with bucket 2k dedicated to pads.
      n_real: positions >= n_real are pads and route to bucket 2k
        in-kernel (default n: no pads).
      classifier: "tree" (dense compare) or "radix" (shift+mask, with
        ``consumed_bits`` already fixed by earlier levels).
      rows: tile rows; None derives the largest ``KernelLaunchSpec``
        candidate dividing n.

    Returns (dest (n,) int32, offsets (nb+1,) int32): scattering
    ``a[i] -> dest[i]`` reproduces the stable partition, bit-identical to
    the XLA oracle; ``offsets`` are the bucket boundaries (last bucket =
    the pads).
    """
    interpret = resolve_interpret(interpret)
    n = keys.shape[0]
    if n_real is None:
        n_real = n
    if rows is None:
        rows = fused_rows(n, keys.dtype.itemsize, k)
    tile = rows * LANES
    if not rows or n % tile:
        raise ValueError(f"n={n} must be a multiple of a rows*{LANES} tile")
    num_tiles = n // tile
    nb = 2 * k + 1
    keys2 = keys.reshape(num_tiles * rows, LANES)

    kern = functools.partial(
        _fused_kernel, k=k, nb=nb, rows=rows, tiles_per_row=0,
        n_real=n_real, classifier=classifier, consumed=consumed_bits,
    )
    in_specs = [pl.BlockSpec((rows, LANES), lambda i: (i, 0))]
    operands = [keys2]
    if classifier != "radix":
        in_specs.append(pl.BlockSpec((1, k, LANES), lambda i: (0, 0, 0)))
        operands.append(splitter_block(splitters, k))

    bucket, rank, hist = pl.pallas_call(
        kern,
        grid=(num_tiles,),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((rows, LANES), lambda i: (i, 0)),
            pl.BlockSpec((rows, LANES), lambda i: (i, 0)),
            pl.BlockSpec((1, 1, nb), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((num_tiles * rows, LANES), jnp.int32),
            jax.ShapeDtypeStruct((num_tiles * rows, LANES), jnp.int32),
            jax.ShapeDtypeStruct((num_tiles, 1, nb), jnp.int32),
        ],
        interpret=interpret,
        name="level_fused",
    )(*operands)
    return _close_placement(
        bucket.reshape(n), rank.reshape(n), hist.reshape(num_tiles, nb), nb, tile
    )


@functools.partial(
    jax.jit,
    static_argnames=("k", "n_real", "classifier", "consumed_bits", "rows", "interpret"),
)
def level_fused_batched(
    keys: jax.Array,
    splitters: Optional[jax.Array] = None,
    *,
    k: int,
    n_real: Optional[int] = None,
    classifier: str = "tree",
    consumed_bits: int = 0,
    rows: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Per-row fused level pass over ``keys`` (B, n): batch grid dimension
    (B, num_tiles), row ``b`` classifying against its own splitter block
    (tree) or the shared extractor (radix).  No cross-step state exists in
    the fused body, so rows need no counter resets at all.

    Returns (dest (B, n) int32 within each row, offsets (B, nb+1) int32).
    """
    interpret = resolve_interpret(interpret)
    B, n = keys.shape
    if n_real is None:
        n_real = n
    if rows is None:
        rows = fused_rows(n, keys.dtype.itemsize, k)
    tile = rows * LANES
    if not rows or n % tile:
        raise ValueError(f"n={n} must be a multiple of a rows*{LANES} tile")
    num_tiles = n // tile
    nb = 2 * k + 1
    keys2 = keys.reshape(B * num_tiles * rows, LANES)

    kern = functools.partial(
        _fused_kernel, k=k, nb=nb, rows=rows, tiles_per_row=num_tiles,
        n_real=n_real, classifier=classifier, consumed=consumed_bits,
    )
    in_specs = [pl.BlockSpec((rows, LANES), lambda b, i: (b * num_tiles + i, 0))]
    operands = [keys2]
    if classifier != "radix":
        in_specs.append(pl.BlockSpec((1, k, LANES), lambda b, i: (b, 0, 0)))
        operands.append(splitter_block(splitters, k))

    bucket, rank, hist = pl.pallas_call(
        kern,
        grid=(B, num_tiles),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((rows, LANES), lambda b, i: (b * num_tiles + i, 0)),
            pl.BlockSpec((rows, LANES), lambda b, i: (b * num_tiles + i, 0)),
            pl.BlockSpec((1, 1, nb), lambda b, i: (b * num_tiles + i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * num_tiles * rows, LANES), jnp.int32),
            jax.ShapeDtypeStruct((B * num_tiles * rows, LANES), jnp.int32),
            jax.ShapeDtypeStruct((B * num_tiles, 1, nb), jnp.int32),
        ],
        interpret=interpret,
        name="level_fused_batched",
    )(*operands)
    close = jax.vmap(functools.partial(_close_placement, nb=nb, tile=tile))
    return close(
        bucket.reshape(B, n), rank.reshape(B, n), hist.reshape(B, num_tiles, nb)
    )


@functools.partial(jax.jit, static_argnames=("nb", "rows", "interpret"))
def rank_hist(
    bucket: jax.Array,
    *,
    nb: int,
    rows: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Fused rank + histogram over precomputed bucket ids (n,) int32.

    The classify-free mode of the fused level kernel, for callers that
    computed ids elsewhere (composite/segmented buckets, MoE expert ids,
    the learned classifier): one kernel pass yields tile ranks and the
    histogram, the prefix epilogue closes placement.  Self-pads to the
    kernel tile with the out-of-range trash id ``nb`` (all-zero one-hot:
    no histogram or counter pollution; trash dests are sliced off).

    Returns (dest (n,) int32, offsets (nb+1,) int32), the stable
    counting placement — bit-identical to ``partition_permutation``.
    """
    interpret = resolve_interpret(interpret)
    n = bucket.shape[0]
    if rows is None:
        from repro.launch.roofline import launch_spec

        rows = launch_spec("rank", 4, nb).rows or 8
    tile = rows * LANES
    n_pad = -(-n // tile) * tile
    if n_pad != n:
        bucket = jnp.concatenate([bucket, jnp.full((n_pad - n,), nb, jnp.int32)])
    num_tiles = n_pad // tile
    bid2 = bucket.reshape(num_tiles * rows, LANES)

    rank, hist = pl.pallas_call(
        functools.partial(_ids_kernel, nb=nb, rows=rows),
        grid=(num_tiles,),
        in_specs=[pl.BlockSpec((rows, LANES), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((rows, LANES), lambda i: (i, 0)),
            pl.BlockSpec((1, 1, nb), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((num_tiles * rows, LANES), jnp.int32),
            jax.ShapeDtypeStruct((num_tiles, 1, nb), jnp.int32),
        ],
        interpret=interpret,
        name="rank_hist",
    )(bid2)
    dest, offsets = _close_placement(
        bucket.reshape(n_pad), rank.reshape(n_pad), hist.reshape(num_tiles, nb),
        nb, tile,
    )
    return dest[:n], offsets


@functools.partial(jax.jit, static_argnames=("nb", "rows", "interpret"))
def rank_hist_batched(
    bucket: jax.Array,
    *,
    nb: int,
    rows: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Per-row fused rank + histogram over bucket ids (B, n) int32.

    Rows are fully independent (no cross-step state), so the batched form
    is the unbatched kernel over the flattened rows — tiles never straddle
    rows because each row self-pads to the kernel tile first.

    Returns (dest (B, n) within each row, offsets (B, nb+1)).
    """
    interpret = resolve_interpret(interpret)
    B, n = bucket.shape
    if rows is None:
        from repro.launch.roofline import launch_spec

        rows = launch_spec("rank", 4, nb).rows or 8
    tile = rows * LANES
    n_pad = -(-n // tile) * tile
    if n_pad != n:
        bucket = jnp.concatenate(
            [bucket, jnp.full((B, n_pad - n), nb, jnp.int32)], axis=1
        )
    num_tiles = n_pad // tile
    bid2 = bucket.reshape(B * num_tiles * rows, LANES)

    rank, hist = pl.pallas_call(
        functools.partial(_ids_kernel, nb=nb, rows=rows),
        grid=(B, num_tiles),
        in_specs=[pl.BlockSpec((rows, LANES), lambda b, i: (b * num_tiles + i, 0))],
        out_specs=[
            pl.BlockSpec((rows, LANES), lambda b, i: (b * num_tiles + i, 0)),
            pl.BlockSpec((1, 1, nb), lambda b, i: (b * num_tiles + i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * num_tiles * rows, LANES), jnp.int32),
            jax.ShapeDtypeStruct((B * num_tiles, 1, nb), jnp.int32),
        ],
        interpret=interpret,
        name="rank_hist_batched",
    )(bid2)
    close = jax.vmap(functools.partial(_close_placement, nb=nb, tile=tile))
    dest, offsets = close(
        bucket.reshape(B, n_pad), rank.reshape(B, n_pad), hist.reshape(B, num_tiles, nb)
    )
    return dest[:, :n], offsets
