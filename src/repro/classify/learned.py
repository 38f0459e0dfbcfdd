"""The learned-CDF classifier (arXiv 2208.06902, *Towards Parallel Learned
Sorting*), fitted per level pass on the same sample the tree engine uses.

Instead of equidistant sample order statistics becoming *splitters*, the
whole sorted sample becomes a model: a monotone piecewise-linear CDF with
``P`` equal-probability segments whose knots are the sample quantiles

    knots[i] = sample[round(i * (m-1) / P)],   CDF(knots[i]) = i / P.

Classification is model evaluation instead of a tree descent or a
searchsorted against k-1 splitters — one searchsorted against P-1 interior
knots (P << k) plus a fused multiply:

    seg  = |{interior knots <= key}|
    frac = clip((key - knots[seg]) / (knots[seg+1] - knots[seg]), 0, 1)
    j    = clip(floor((seg + frac) / P * k), 0, k-1)

``j`` is monotone nondecreasing in the key (each term is: ``seg`` is a
rank, ``frac`` interpolates within a segment, duplicate knots collapse to
frac = 0 or 1, and the uint -> f32 cast rounds monotonically), so the
stable-partition + (bucket, key) base-case contract holds exactly as for
sampled splitters.  Equality buckets degrade to the sentinel-only rule of
the radix engine (odd bucket iff key == sentinel) — the model has no
per-bucket upper splitter to compare against.

**Fallback rule** (the paper's guard against model mispredictions, made
jit-compatible): the fit is scored on its own training sample — the
largest predicted bucket load, normalised so a perfect fit scores 1.0:

    imbalance = max_j |{model(sample) = j}| * k / m

When it exceeds ``IMBALANCE_THRESHOLD`` the level classifies with the
comparison tree instead, via one ``lax.cond`` (the splitters come from the
same sample, so the fallback costs nothing extra when not taken).  The
threshold sits well below ``slack / 2`` — the load factor at which a
bucket would overflow W/2 and trip the full-sort robustness fallback — so
a bad fit reroutes to the tree *before* it can cost a stable full sort.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.classify.tree import classify
from repro.core.sampling import sentinel_for

__all__ = [
    "NUM_KNOTS",
    "IMBALANCE_THRESHOLD",
    "fit_cdf_knots",
    "eval_cdf_buckets",
    "sample_imbalance",
    "learned_bucket_ids",
]

# P: piecewise-linear segments of the CDF.  Few segments keep the fit and
# the per-element searchsorted cheap (P is independent of k); 64 matches
# the paper's observation that splitter *precision* matters less than
# splitter *balance* once buckets are oversampled.
NUM_KNOTS = 64

# Sample-measured load factor above which the level falls back to the
# comparison tree.  A perfectly balanced fit scores 1.0; the full-sort
# robustness fallback only trips near slack/2 (= 4.0 at the default
# slack=8), so 3.0 reroutes bad fits one stage earlier.
IMBALANCE_THRESHOLD = 3.0


def _to_float(x: jax.Array) -> jax.Array:
    """Monotone cast into the model's evaluation space (f32 is enough:
    rounding is monotone nondecreasing, and both keys and knots round
    through the same map, so bucket boundaries stay consistent)."""
    return x.astype(jnp.float32)


def fit_cdf_knots(sorted_sample: jax.Array, num_knots: int = NUM_KNOTS) -> jax.Array:
    """(m,) sorted sample -> (P+1,) f32 knots at sample quantiles."""
    m = sorted_sample.shape[-1]
    idx = np.clip(
        np.round(np.arange(num_knots + 1) * (m - 1) / max(num_knots, 1)), 0, m - 1
    ).astype(np.int32)
    return _to_float(jnp.take(sorted_sample, jnp.asarray(idx), axis=-1))


def eval_cdf_buckets(keys: jax.Array, knots: jax.Array, k: int) -> jax.Array:
    """Bucket index j in [0, k) per key (n,) under knots (P+1,) — the
    model evaluation."""
    P = knots.shape[-1] - 1
    kf = _to_float(keys)
    inner = knots[1:-1]  # (P-1,) interior knots
    seg = jnp.searchsorted(inner, kf, side="right").astype(jnp.int32)
    lo = jnp.take(knots, seg, axis=0)
    hi = jnp.take(knots, seg + 1, axis=0)
    # duplicate knots (heavy sample duplicates) give hi == lo: the segment
    # carries zero probability mass, frac pins to 0 — still monotone
    span = hi - lo
    frac = jnp.clip(
        jnp.where(span > 0, (kf - lo) / jnp.where(span > 0, span, 1.0), 0.0),
        0.0,
        1.0,
    )
    cdf = (seg.astype(jnp.float32) + frac) / max(P, 1)
    return jnp.clip((cdf * k).astype(jnp.int32), 0, k - 1)


def sample_imbalance(sorted_sample: jax.Array, knots: jax.Array, k: int) -> jax.Array:
    """Largest predicted bucket load on the training sample, normalised so
    a perfect fit scores 1.0 (a scalar).

    The model is monotone and the sample sorted, so the predicted bucket
    ids are sorted too and per-bucket counts are rank differences — no
    scatter, just k+1 searchsorteds against the (tiny) sample.
    """
    m = sorted_sample.shape[-1]
    jb = eval_cdf_buckets(sorted_sample, knots, k)
    edges = jnp.arange(k + 1, dtype=jnp.int32)
    counts = jnp.diff(jnp.searchsorted(jb, edges, side="left"))
    return jnp.max(counts).astype(jnp.float32) * k / m


def _with_eq(keys: jax.Array, j: jax.Array) -> jax.Array:
    eq = (keys == sentinel_for(keys.dtype)).astype(jnp.int32)
    return 2 * j + eq


def learned_bucket_ids(
    keys: jax.Array,
    sorted_sample: jax.Array,
    splitters: jax.Array,
    k: int,
    threshold: float = IMBALANCE_THRESHOLD,
) -> Tuple[jax.Array, jax.Array]:
    """Local bucket ids in [0, 2k) for ``keys`` (n,), with the tree fallback.

    ``sorted_sample`` (m,) trains the CDF; ``splitters`` (k-1,) are the
    tree's equidistant order statistics of the *same* sample, so the
    ``lax.cond`` fallback branch needs no extra sampling pass.  Returns
    (bucket ids, fell_back flag).  Under ``jax.vmap`` (the batched sort)
    the flag is per row and the ``cond`` becomes a ``select`` that runs
    both classifiers.
    """
    knots = fit_cdf_knots(sorted_sample)
    fell_back = sample_imbalance(sorted_sample, knots, k) > threshold
    b = jax.lax.cond(
        fell_back,
        lambda: classify(keys, splitters, k),
        lambda: _with_eq(keys, eval_cdf_buckets(keys, knots, k)),
    )
    return b, fell_back
