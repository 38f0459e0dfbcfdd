"""The sampled comparison-tree classifier (paper §3 + equality buckets §4.4).

Classification of element ``e`` against k-1 sorted splitters is a descent of
the implicit BFS tree: ``i <- 2i + (e > tree[i])`` repeated log2(k) times.
Afterwards ``j = i - k`` is the bucket index: bucket j holds (s_{j-1}, s_j].

Equality buckets (paper §4.4): one extra branch-free comparison against the
*upper* splitter of the landing bucket.  Final local bucket id = ``2j + (e ==
s_j)`` — even ids are regular range-buckets, odd ids are equality buckets
(all elements identical), which are skipped by deeper levels and by the base
case.  We keep equality buckets enabled unconditionally: the paper enables
them at runtime when duplicate splitters are detected, but a jitted program
cannot branch on data, so we pay the one extra comparison statically (noted
in DESIGN.md as a changed assumption).

This module is the "tree" engine of the ``repro.classify`` seam (DESIGN.md
§9); it is the only engine that *needs* the sampling pass — its splitters
come from a sorted sample — which is also what makes it distribution-
adaptive.  The sibling engines trade that adaptivity away ("radix", no
sample at all) or replace it with a model ("learned", CDF fit on the same
sample).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.core.sampling import build_tree, sentinel_for

__all__ = ["classify", "classify_segmented", "num_local_buckets"]


def num_local_buckets(k: int) -> int:
    """2j + eq with j in [0,k) -> ids in [0, 2k)."""
    return 2 * k


def classify(keys: jax.Array, splitters: jax.Array, k: int) -> jax.Array:
    """Classify ``keys`` (n,) against sorted ``splitters`` (k-1,).

    Returns int32 local bucket ids in [0, 2k): ``2j + (key == upper_j)``.
    """
    tree = build_tree(splitters, k)
    upper = jnp.concatenate(
        [splitters, jnp.full((1,), sentinel_for(keys.dtype), keys.dtype)]
    )
    idx = jnp.ones(keys.shape, jnp.int32)
    for _ in range(int(math.log2(k))):
        node = jnp.take(tree, idx, axis=0)
        idx = 2 * idx + (keys > node).astype(jnp.int32)
    j = idx - k
    eq = (keys == jnp.take(upper, j, axis=0)).astype(jnp.int32)
    return 2 * j + eq


def classify_segmented(
    keys: jax.Array, seg: jax.Array, splitters: jax.Array, k: int
) -> jax.Array:
    """Per-segment classification (recursion level 2, flattened).

    ``seg`` (n,) int32 assigns each element its segment; ``splitters``
    (num_seg, k-1) holds each segment's sorted splitters.  Returns local
    bucket ids in [0, 2k) — the caller forms the composite bucket
    ``seg * 2k + local``.
    """
    num_seg = splitters.shape[0]
    tree = build_tree(splitters, k).reshape(num_seg * k)
    upper = jnp.concatenate(
        [
            splitters,
            jnp.full((num_seg, 1), sentinel_for(keys.dtype), keys.dtype),
        ],
        axis=-1,
    ).reshape(num_seg * k)
    base = seg.astype(jnp.int32) * k
    idx = jnp.ones(keys.shape, jnp.int32)
    for _ in range(int(math.log2(k))):
        node = jnp.take(tree, base + idx, axis=0)
        idx = 2 * idx + (keys > node).astype(jnp.int32)
    j = idx - k
    eq = (keys == jnp.take(upper, base + j, axis=0)).astype(jnp.int32)
    return 2 * j + eq
