"""Batched independent-segment sort via the segmented level pass.

``segmented_sort`` sorts each ``keys[offsets[i]:offsets[i+1]]`` range
independently, in place of the per-window ``jnp.argsort`` fallback that
batched consumers (windowed attention, per-request serving state, bucketed
data pipelines) would otherwise use.  It is exactly recursion level 2 of
the full sort (``core.ips4o.segmented_level_pass``) promoted to a public
op: per-segment splitters -> flattened ``classify_segmented`` -> composite
bucket ids (seg * 2k + local, monotone in segment) -> one stable block
partition -> one shared base case over all segments' windows.

Segment boundaries may be traced (data-dependent); only the segment
*count* is static.  Pads go into an extra trailing segment; the robustness
fallback is a stable lexicographic (segment, key) sort.
"""
from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.core.ips4o import (
    SortConfig,
    base_case_or_fallback,
    pad_with_sentinel,
    segment_ids,
    segmented_level_pass,
)
from repro.ops import keyspace

__all__ = ["segmented_sort"]


def _pow2_clamp(x: int, lo: int, hi: int) -> int:
    p = 1
    while p < x:
        p *= 2
    return max(lo, min(p, hi))


def _stable_segmented_sort(arrays: Any, seg: jax.Array) -> Any:
    """Fallback: stable lexicographic (segment, key) sort via two passes."""
    o1 = jnp.argsort(arrays["k"], stable=True)
    o2 = jnp.argsort(jnp.take(seg, o1, axis=0), stable=True)
    order = jnp.take(o1, o2, axis=0)
    return jax.tree.map(lambda a: jnp.take(a, order, axis=0), arrays)


def segmented_sort(
    keys: jax.Array,
    offsets: jax.Array,
    num_segments: int,
    values: Any = None,
    *,
    k: Optional[int] = None,
    cfg: SortConfig = SortConfig(),
    engine: Optional[str] = None,
    classifier: Optional[str] = None,
):
    """Sort each segment of ``keys`` independently, ascending, NaN-safe.

    Args:
      keys: (n,) key array.
      offsets: (num_segments + 1,) nondecreasing int32 segment boundaries
        with offsets[0] == 0 and offsets[-1] == n; may be traced.
      num_segments: static segment count.
      values: optional payload pytree (leaves with leading dim n) permuted
        alongside, per segment.
      k: buckets per segment (power of two); default sizes buckets to the
        average segment like ``plan_levels`` does globally.
      engine: partition-engine override ("xla" | "pallas" | "auto").
      classifier: accepted for API symmetry with ``sort``, but "radix" and
        "learned" are mapped to "tree" here: user segments are arbitrary
        key ranges, not the bit-aligned ranges a radix level 1 produces,
        so the shared bit extractor is not monotone within them, and the
        global CDF model has no per-segment form.  The per-segment
        sampled tree is the only engine whose contract covers this op.

    Returns sorted keys, or (keys, values) when a payload is given.

    >>> import jax.numpy as jnp
    >>> keys = jnp.asarray([3.0, 1.0, 2.0, 2.0, 0.0])
    >>> offsets = jnp.asarray([0, 3, 5], jnp.int32)
    >>> segmented_sort(keys, offsets, 2).tolist()  # segments stay apart
    [1.0, 2.0, 3.0, 0.0, 2.0]
    """
    from repro.ops.sort import with_engine

    cfg = with_engine(cfg, engine, keys, classifier)
    if cfg.classifier != "tree":
        # see the ``classifier`` arg note: only the per-segment tree is
        # valid over arbitrary user segments
        from dataclasses import replace

        cfg = replace(cfg, classifier="tree")
    n = keys.shape[0]
    if keys.ndim != 1:
        raise ValueError("keys must be 1-D")
    if n <= 1:
        return keys if values is None else (keys, values)

    enc = keyspace.encode(keys)
    arrays = {"k": enc}
    if values is not None:
        arrays["v"] = values
    W = cfg.base_case
    unit = max(W, cfg.tile)
    arrays = pad_with_sentinel(arrays, unit)
    n_pad = arrays["k"].shape[0]

    # Pads form one extra trailing segment; sentinel keys make its buckets
    # equality buckets, so it is skipped by the base case for free.
    off_ext = jnp.concatenate(
        [
            jnp.asarray(offsets, jnp.int32),
            jnp.full((1,), n_pad, jnp.int32),
        ]
    )
    num_seg_ext = num_segments + 1
    if k is None:
        avg = max(1, n // max(num_segments, 1))
        k = _pow2_clamp(-(-cfg.slack * avg // W), 2, cfg.kmax)

    rng = jax.random.PRNGKey(cfg.seed)
    arrays, boffs, nb = segmented_level_pass(
        arrays, off_ext, num_seg_ext, n_pad, k, cfg, rng
    )

    # the composite partition is stable and monotone in segment, so each
    # segment keeps its input index range — fallback can recompute seg ids
    seg = segment_ids(off_ext, n_pad)
    arrays = base_case_or_fallback(
        arrays, boffs, nb, None, cfg, lambda a: _stable_segmented_sort(a, seg)
    )

    out = keyspace.decode(arrays["k"][:n], keys.dtype)
    if values is None:
        return out
    return out, jax.tree.map(lambda a: a[:n], arrays["v"])
