"""Splitter-based partial sort: top-k / bottom-k cheaper than a full sort.

The full sort is (level passes) + (base case over *every* window).  For
rank-k queries only the buckets covering ranks [0, k) need their base case:
after the level passes buckets are contiguous and in key order, so the k
smallest elements all live inside the prefix that ends with the bucket
containing rank k-1.  We therefore run the same classify/partition passes
and then base-case-sort only a static, W-aligned prefix

    P = ceil((k + W) / W) * W        (W = cfg.base_case)

which is guaranteed to cover that bucket whenever the base-case
precondition holds (every non-trivial bucket <= W/2: a bucket starting
before rank k ends before k + W/2 <= P - W/2; equality buckets may cross P
but hold identical keys and need no sorting).  The data-dependent
robustness fallback (``lax.cond`` -> full stable sort) guards the
precondition exactly as in the full sort, restricted to buckets that
intersect the prefix.  Work saved: all base-case windows beyond P — the
dominant term for k << n (see ``benchmarks/sort_ops.py``).

``topk`` (largest-k) reuses the ascending machinery through the keyspace
complement: ``~encode(x)`` reverses the total order, so the bottom-k of
the complemented keys are the top-k of the originals.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.ips4o import (
    SortConfig,
    _per_row,
    _sort_padded,
    pad_with_sentinel,
    plan_levels,
    replicated,
)
from repro.ops import keyspace

__all__ = ["topk", "bottomk", "smallest_encoded"]


def _prefix_limit(k: int, W: int, n_pad: int) -> int:
    """Static W-aligned prefix length covering the bucket of rank k-1."""
    return min(n_pad, -(-(k + W) // W) * W)


def _smallest(arrays: Any, kk: int, cfg: SortConfig, lift=_per_row) -> Tuple[Any, Any]:
    """(sorted kk smallest keys, their payload) of each row of the arrays
    dict, by the partial sort: the shared pipeline with ``limit = P``.
    ``lift`` carries the per-row stages over the rows, as in
    ``core.ips4o._sort_padded``: the identity for (n,), ``jax.vmap`` for
    (B, n)."""
    n = arrays["k"].shape[-1]
    arrays = lift(partial(pad_with_sentinel, unit=max(cfg.base_case, cfg.tile)))(arrays)
    n_pad = arrays["k"].shape[-1]
    levels = plan_levels(n_pad, cfg)
    P = _prefix_limit(kk, cfg.base_case, n_pad)
    arrays = _sort_padded(arrays, n, cfg, levels, limit=P, lift=lift)
    return arrays["k"][..., :kk], arrays["v"][..., :kk]


def smallest_encoded(
    enc: jax.Array, kk: int, cfg: SortConfig
) -> Tuple[jax.Array, jax.Array]:
    """(sorted k smallest encoded keys, their original indices).

    ``enc`` must be in the ordered-uint keyspace; ``0 < kk <= n`` static.
    This is the splitter-filter primitive ``repro.dist`` reuses as the
    per-shard candidate filter of the distributed rank-k query.
    """
    n = enc.shape[0]
    return _smallest({"k": replicated(enc), "v": jnp.arange(n, dtype=jnp.int32)}, kk, cfg)


def bottomk(
    keys: jax.Array,
    k: int,
    *,
    cfg: SortConfig = SortConfig(),
    engine: Optional[str] = None,
    classifier: Optional[str] = None,
) -> Tuple[jax.Array, jax.Array]:
    """The ``k`` smallest keys in ascending order, with their indices.

    Returns (values, indices), each of length ``min(k, n)`` (k >= n degrades
    to a full sort).  NaN-safe via the keyspace encoding: NaN is the
    *maximum* of the total order, so ``bottomk`` only yields NaNs once
    every non-NaN key is taken (and, symmetrically, ``topk`` yields them
    first — the ``lax.top_k`` convention).

    >>> import jax.numpy as jnp
    >>> vals, idx = bottomk(jnp.asarray([4.0, 1.0, 3.0]), 2)
    >>> vals.tolist()
    [1.0, 3.0]
    >>> idx.tolist()
    [1, 2]
    """
    n = keys.shape[0]
    if keys.ndim != 1:
        raise ValueError("keys must be 1-D")
    from repro.ops.sort import with_engine

    kk = max(0, min(int(k), n))
    if kk == 0:
        return keys[:0], jnp.zeros((0,), jnp.int32)
    out, idx = smallest_encoded(
        keyspace.encode(keys), kk, with_engine(cfg, engine, keys, classifier)
    )
    return keyspace.decode(out, keys.dtype), idx


def topk(
    keys: jax.Array,
    k: int,
    *,
    cfg: SortConfig = SortConfig(),
    engine: Optional[str] = None,
    classifier: Optional[str] = None,
) -> Tuple[jax.Array, jax.Array]:
    """The ``k`` largest keys in descending order, with their indices.

    Same contract as ``jax.lax.top_k`` (modulo tie order); implemented as
    bottom-k of the complemented encoded keys — ``~u`` reverses the
    keyspace total order, so no descending variant of the engine is needed.

    >>> import jax.numpy as jnp
    >>> vals, idx = topk(jnp.asarray([1.0, 9.0, 3.0, 7.0]), 2)
    >>> vals.tolist()
    [9.0, 7.0]
    >>> idx.tolist()
    [1, 3]
    """
    n = keys.shape[0]
    if keys.ndim != 1:
        raise ValueError("keys must be 1-D")
    from repro.ops.sort import with_engine

    kk = max(0, min(int(k), n))
    if kk == 0:
        return keys[:0], jnp.zeros((0,), jnp.int32)
    out, idx = smallest_encoded(
        ~keyspace.encode(keys), kk, with_engine(cfg, engine, keys, classifier)
    )
    return keyspace.decode(~out, keys.dtype), idx
