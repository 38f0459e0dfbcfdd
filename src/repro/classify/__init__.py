"""repro.classify — pluggable classifier engines behind one seam (DESIGN.md §9).

IPS4o's partition pipeline is classifier-agnostic: every level pass needs
one function ``keys -> local bucket ids in [0, 2k)`` that is monotone
nondecreasing in the key, with odd ids reserved for equality buckets
(runs of identical keys, skipped by deeper levels and the base case).
This package is that seam, with three interchangeable engines:

  tree     the paper's sampled comparison tree (§3 + §4.4): splitters
           from a sorted sample, branchless BFS descent, per-bucket
           equality test.  Distribution-adaptive; the always-correct
           default.
  radix    IPS2Ra (arXiv 2009.13569): bucket on the next log2(k) bits of
           the keyspace-encoded key — no sampling pass, one shift + mask
           per element, a per-level shift for level 2.  Fastest on
           uniform-ish keyspaces; overflows (and falls back) on heavy
           duplicates.
  learned  arXiv 2208.06902: a monotone piecewise-linear CDF fitted on
           the sample, classification by model evaluation, with a
           measured-imbalance fallback to the tree inside one
           ``lax.cond``.
  auto     (``SortConfig.classifier``) the racing router: the plan cache
           races the engines per (n, dtype, distribution label) and
           routes to the persisted winner (``router.resolve_classifier``,
           ``PlanCache.classifier_plan``).

The fused Pallas forms of the tree and radix classifiers live in
``kernels/classify.py``; the engines here are their XLA formulations and
the single source of truth for the bucket-id contract.
"""
from repro.classify.learned import (
    IMBALANCE_THRESHOLD,
    NUM_KNOTS,
    eval_cdf_buckets,
    fit_cdf_knots,
    learned_bucket_ids,
    sample_imbalance,
)
from repro.classify.radix import radix_bucket_ids, radix_shift
from repro.classify.router import (
    CLASSIFIERS,
    classifier_for,
    distribution_moments,
    resolve_classifier,
)
from repro.classify.tree import (
    classify,
    classify_segmented,
    num_local_buckets,
)

__all__ = [
    "CLASSIFIERS",
    # tree
    "classify",
    "classify_segmented",
    "num_local_buckets",
    # radix
    "radix_bucket_ids",
    "radix_shift",
    # learned
    "NUM_KNOTS",
    "IMBALANCE_THRESHOLD",
    "fit_cdf_knots",
    "eval_cdf_buckets",
    "sample_imbalance",
    "learned_bucket_ids",
    # router
    "resolve_classifier",
    "distribution_moments",
    "classifier_for",
]
