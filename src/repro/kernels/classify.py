"""Pallas TPU kernel: fused branchless classification + per-tile histogram.

This is the hot loop of the paper's *local classification* phase (§4.1).

Hardware adaptation (DESIGN.md §2): the paper's scalar search-tree descent
(`i <- 2i + (e > a_i)`, one conditional-increment per level) exists to avoid
*branch mispredictions* on a superscalar CPU.  A TPU VPU has no branch
predictor and hates serialized gathers; the idiomatic equivalent of
"branch-free" is "lane-parallel dense compare": we classify a whole
(rows, 128) tile against **all** k-1 splitters with broadcast compares,

    j  = sum_i (key > u_i)          (the rank of the key among splitters)
    eq = any_i (key == u_i)         (equality-bucket test, paper §4.4)
    bucket = 2*j + eq

where u = splitters + the dtype sentinel (the paper's s_k = +inf upper
splitter of the last bucket — comparing against it leaves j unchanged but
makes keys equal to the sentinel land in the last *equality* bucket,
exactly like the tree descent's ``e == upper_j`` test),

and which is mathematically identical to the tree descent (j = |{s : s < key}|)
but runs as k dense VPU ops with zero gathers and zero divergence.  The
per-tile histogram (the paper's "count elements per bucket as a side effect
of maintaining buffer blocks") is fused into the same VMEM pass via a
one-hot reduction.

VMEM budget per grid step: tile keys (rows*128*4 B) + splitters (k*4 B) +
one-hot reduction tile.  The row count is not hard-coded: ``rows=None``
derives it from the VMEM roofline model (``launch.roofline.
classify_tile_rows`` — the largest power-of-two tile whose working set
fits the budget, e.g. 32 rows at f32/k=128), and the plan cache sweeps
the leading candidates (``SortConfig.classify_rows``).

The radix form (``radix_histogram`` — the IPS2Ra extractor of DESIGN.md
§9) replaces the dense compare with one shift + mask per element
(``repro.classify.radix`` is the id contract); no splitter operand at
all, same fused per-tile histogram.

The batched variant (``classify_histogram_batched``, DESIGN.md §6) adds a
*batch grid dimension*: grid = (B, num_tiles), each program classifying
tile ``i`` of row ``b`` against row ``b``'s own splitter set.  The kernel
body is unchanged — only the BlockSpec index maps route per-row blocks —
so B independent rows classify in one ``pallas_call`` instead of B
dispatches of the unbatched kernel.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.classify.radix import radix_bucket_ids
from repro.kernels import resolve_interpret
from repro.kernels.level_fused import classify_tile, splitter_block, tile_histogram

__all__ = [
    "classify_histogram",
    "classify_histogram_batched",
    "radix_histogram",
    "radix_histogram_batched",
    "default_rows",
]

LANES = 128


def default_rows(n: int, key_bytes: int, k: int) -> int:
    """Largest launch-spec row candidate whose tile (rows*128) divides
    ``n``, or 0 when no candidate does (callers then stay on the XLA
    path).  One ``KernelLaunchSpec`` resolution, shared with every other
    sort kernel (``launch.roofline.launch_spec``)."""
    from repro.launch.roofline import launch_spec

    return launch_spec("classify", key_bytes, k, n=n).rows


def _kernel(keys_ref, spl_ref, bucket_ref, hist_ref, *, k: int, nb: int, rows: int):
    # the level kernel's tile classifier (one splitter compare per step)
    bucket_ref[...] = classify_tile(
        keys_ref[...], spl_ref, k=k, classifier="tree", consumed=0
    )
    # Fused per-tile histogram: one-hot counts over the tile.
    hist_ref[0] = tile_histogram(bucket_ref, rows, nb)


@functools.partial(jax.jit, static_argnames=("k", "rows", "interpret"))
def classify_histogram(
    keys: jax.Array,
    splitters: jax.Array,
    *,
    k: int,
    rows: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Classify ``keys`` (n,) against ``splitters`` (k-1,).

    Returns (bucket ids (n,) int32 in [0, 2k), per-tile histogram
    (num_tiles, 2k) int32).  n must be a multiple of rows*128;
    ``rows=None`` takes the largest roofline candidate dividing n.
    """
    interpret = resolve_interpret(interpret)
    n = keys.shape[0]
    if rows is None:
        rows = default_rows(n, keys.dtype.itemsize, k)
    tile = rows * LANES
    if not rows or n % tile:
        raise ValueError(f"n={n} must be a multiple of a rows*{LANES} tile")
    num_tiles = n // tile
    nb = 2 * k
    keys2 = keys.reshape(num_tiles * rows, LANES)
    # The dtype sentinel is the upper splitter of the last bucket: it
    # never changes j (no key is > it) but keys *equal* to it get eq = 1 and
    # land in equality bucket 2(k-1)+1, matching the tree classifier.
    bucket, hist = pl.pallas_call(
        functools.partial(_kernel, k=k, nb=nb, rows=rows),
        grid=(num_tiles,),
        in_specs=[
            pl.BlockSpec((rows, LANES), lambda i: (i, 0)),
            pl.BlockSpec((1, k, LANES), lambda i: (0, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((rows, LANES), lambda i: (i, 0)),
            pl.BlockSpec((1, 1, nb), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((num_tiles * rows, LANES), jnp.int32),
            jax.ShapeDtypeStruct((num_tiles, 1, nb), jnp.int32),
        ],
        interpret=interpret,
    )(keys2, splitter_block(splitters, k))
    return bucket.reshape(n), hist.reshape(num_tiles, nb)


@functools.partial(jax.jit, static_argnames=("k", "rows", "interpret"))
def classify_histogram_batched(
    keys: jax.Array,
    splitters: jax.Array,
    *,
    k: int,
    rows: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Classify ``keys`` (B, n) against per-row ``splitters`` (B, k-1).

    The batch-grid form of :func:`classify_histogram`: grid (B, num_tiles),
    row ``b``'s tiles compare against row ``b``'s splitter block.  Returns
    (bucket ids (B, n) int32 in [0, 2k), per-tile histograms
    (B, num_tiles, 2k) int32).  n must be a multiple of rows*128;
    ``rows=None`` takes the largest roofline candidate dividing n.
    """
    interpret = resolve_interpret(interpret)
    B, n = keys.shape
    if rows is None:
        rows = default_rows(n, keys.dtype.itemsize, k)
    tile = rows * LANES
    if not rows or n % tile:
        raise ValueError(f"n={n} must be a multiple of a rows*{LANES} tile")
    num_tiles = n // tile
    nb = 2 * k
    keys2 = keys.reshape(B * num_tiles * rows, LANES)
    bucket, hist = pl.pallas_call(
        functools.partial(_kernel, k=k, nb=nb, rows=rows),
        grid=(B, num_tiles),
        in_specs=[
            pl.BlockSpec((rows, LANES), lambda b, i: (b * num_tiles + i, 0)),
            # per-row splitters + the dtype sentinel upper
            pl.BlockSpec((1, k, LANES), lambda b, i: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((rows, LANES), lambda b, i: (b * num_tiles + i, 0)),
            pl.BlockSpec((1, 1, nb), lambda b, i: (b * num_tiles + i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * num_tiles * rows, LANES), jnp.int32),
            jax.ShapeDtypeStruct((B * num_tiles, 1, nb), jnp.int32),
        ],
        interpret=interpret,
    )(keys2, splitter_block(splitters, k))
    return bucket.reshape(B, n), hist.reshape(B, num_tiles, nb)


def _radix_kernel(keys_ref, bucket_ref, hist_ref, *, k: int, nb: int, consumed: int,
                  rows: int):
    # the extractor is elementwise (one shift + one mask — the IPS2Ra
    # classifier), so the id computation is shared verbatim with the XLA
    # engine: repro.classify.radix is the single source of truth
    bucket_ref[...] = radix_bucket_ids(keys_ref[...], k, consumed)  # (rows, 128)
    hist_ref[0] = tile_histogram(bucket_ref, rows, nb)


@functools.partial(
    jax.jit, static_argnames=("k", "consumed_bits", "rows", "interpret")
)
def radix_histogram(
    keys: jax.Array,
    *,
    k: int,
    consumed_bits: int = 0,
    rows: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Fused radix extract + per-tile histogram over ``keys`` (n,).

    The radix twin of :func:`classify_histogram` with no splitter operand:
    bucket ``2 * ((key >> shift) & (k-1)) + (key == sentinel)`` where the
    static shift skips ``consumed_bits`` already fixed by earlier levels
    (``repro.classify.radix.radix_shift``).  Keys must be keyspace-encoded
    (unsigned).  Returns (bucket ids (n,) int32 in [0, 2k), per-tile
    histogram (num_tiles, 2k) int32); n must be a multiple of rows*128,
    ``rows=None`` takes the largest roofline candidate dividing n.
    """
    interpret = resolve_interpret(interpret)
    n = keys.shape[0]
    if rows is None:
        rows = default_rows(n, keys.dtype.itemsize, k)
    tile = rows * LANES
    if not rows or n % tile:
        raise ValueError(f"n={n} must be a multiple of a rows*{LANES} tile")
    num_tiles = n // tile
    nb = 2 * k
    keys2 = keys.reshape(num_tiles * rows, LANES)

    bucket, hist = pl.pallas_call(
        functools.partial(
            _radix_kernel, k=k, nb=nb, consumed=consumed_bits, rows=rows
        ),
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
    )(keys2)
    return bucket.reshape(n), hist.reshape(num_tiles, nb)


@functools.partial(
    jax.jit, static_argnames=("k", "consumed_bits", "rows", "interpret")
)
def radix_histogram_batched(
    keys: jax.Array,
    *,
    k: int,
    consumed_bits: int = 0,
    rows: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Per-row fused radix extract + histogram over ``keys`` (B, n).

    The extractor has no per-row state (the shift is data-independent, so
    every row uses the same one — nothing like the per-row splitter blocks
    of :func:`classify_histogram_batched` is needed): the rows concatenate
    into one longer unbatched launch and the tile histograms reshape back.
    Returns (bucket ids (B, n), per-tile histograms (B, n/tile, 2k));
    n must be a multiple of rows*128 so tiles never straddle rows.
    """
    B, n = keys.shape
    if rows is None:
        rows = default_rows(n, keys.dtype.itemsize, k)
    if not rows or n % (rows * LANES):
        raise ValueError(f"n={n} must be a multiple of a rows*{LANES} tile")
    bucket, hist = radix_histogram(
        keys.reshape(B * n),
        k=k, consumed_bits=consumed_bits, rows=rows, interpret=interpret,
    )
    return bucket.reshape(B, n), hist.reshape(B, n // (rows * LANES), 2 * k)
