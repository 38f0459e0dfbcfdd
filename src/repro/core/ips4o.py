"""IPS4o: In-place Parallel Super Scalar Samplesort, TPU/JAX formulation.

Structure (see DESIGN.md §4 for the full mapping from the paper):

  * recursion is flattened into at most two *level passes* (the paper's
    "adaptive number of buckets on the last two levels", §4.7, combined with
    the strictly-in-place recursion elimination, §4.6);
  * each level pass = sample -> branchless classification -> stable
    block-structured partition (``core.partition``);
  * equality buckets (§4.4) are always on: odd local bucket ids hold runs of
    identical keys and are skipped by deeper levels and the base case;
  * base case = segmented overlapped-window sort: two passes of
    per-window (bucket, key) lexicographic sorts at window offsets 0 and W/2.
    Every non-trivial bucket has size <= W/2 (checked!), so it is interior to
    a window of one of the two passes and ends up fully sorted;
  * a *robustness fallback* (data-dependent, via ``lax.cond``) runs a plain
    stable sort in the (w.h.p. impossible) event a bucket exceeds W/2 — the
    static-shape analogue of the paper's recursion-until-small guarantee;
  * padding to a multiple of W uses the key-type sentinel and a dedicated
    final bucket — the analogue of the paper's overflow block.

The returned permutation is value-exact vs. ``ref_sort`` (stable) for keys;
payload association is exact per element.  The permutation is **stable**:
every stage preserves the relative order of equal keys — the block
partition is stable by construction, equality buckets keep their input
order, the base-case ``_window_perm`` is a stable lexicographic
(bucket, key) sort and the overlapped windows never exchange equal
elements, and the robustness fallback is ``jnp.argsort(stable=True)``.
``tiebreak_passes`` (multi-word keys, DESIGN.md §11) and the differential
fuzz harness (``tests/test_fuzz_differential.py``) rely on this and pin it
against the numpy stable-argsort oracle.

Keys must form a total order under ``>`` / ``==`` at this level (raw NaNs
are rejected by that contract); the ``repro.ops`` entry points remove the
limitation by bijecting keys into the ordered uint keyspace
(``ops/keyspace.py``) before calling in, so NaN / -0.0 handling is their
concern, not this module's.

The classify+partition hot loops run on one of two engines
(``SortConfig.engine``): "xla" (dense jnp classification + per-tile-argsort
partition) or "pallas" (the fused single-pass level kernel
``kernels.level_fused`` — classify + histogram + rank in ONE grid sweep,
the paper's §4.1/§4.2 loops as one real kernel); "auto" lets the plan
cache / backend pick.  Both engines are bit-exact interchangeable
(DESIGN.md §4.8, §10).

Orthogonally, ``SortConfig.classifier`` picks the bucket-id function each
level pass uses (``repro.classify``, DESIGN.md §9): "tree" (the paper's
sampled comparison tree), "radix" (IPS2Ra bit extraction — no sampling
pass; level 2 shifts past the level-1 bits), "learned" (piecewise-linear
CDF model with an imbalance fallback to the tree), or "auto" (the plan
cache races them).  All engines honour the same contract — monotone local
ids in [0, 2k) with odd ids as equality buckets — so the partition, the
base case, and the robustness fallback are untouched by the choice.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, replace
from functools import partial
from typing import Any, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.classify import (
    classify,
    classify_batched,
    classify_segmented,
    learned_bucket_ids,
    learned_bucket_ids_batched,
    radix_bucket_ids,
    resolve_classifier,
)
from repro.core import sampling
from repro.core.partition import ENGINES, batched_stable_partition, stable_partition
from repro.kernels import resolve_interpret

__all__ = [
    "SortConfig",
    "ips4o_sort",
    "is4o_sort",
    "plan_levels",
    "fit_window",
    "make_sorter",
    "resolve_engine",
    # level-pass internals, consumed by ``repro.ops`` (DESIGN.md §5)
    "pad_with_sentinel",
    "level_pass",
    "segmented_level_pass",
    "partition_passes",
    "base_case",
    "bucket_violations",
    "segment_ids",
    "stable_full_sort",
    "tiebreak_passes",
    # batch-axis-native pipeline, consumed by ``repro.ops.batched`` (§6)
    "ips4o_sort_batched",
    "batched_pad_with_sentinel",
    "batched_level_pass",
    "batched_segmented_level_pass",
    "batched_partition_passes",
    "batched_base_case",
    "batched_bucket_violations",
    "batched_segment_ids",
    "batched_stable_full_sort",
]


@dataclass(frozen=True)
class SortConfig:
    """Tuning parameters (paper §4.7 defaults, adapted to VMEM sizes)."""

    base_case: int = 8192          # W: base-case window (VMEM-resident)
    kmax: int = 128                # max buckets per level (paper: 256)
    tile: int = 4096               # distribution tile (the paper's stripe walk)
    slack: int = 8                 # target expected bucket size = W / slack
    max_sample: int = 8192         # cap on per-level sample size
    seed: int = 0xC0FFEE
    fallback: bool = True          # robustness fallback via lax.cond
    engine: str = "xla"            # partition engine: "xla" | "pallas" | "auto"
    classifier: str = "tree"       # "tree" | "radix" | "learned" | "auto" (§9)
    classify_rows: int = 0         # fused-kernel tile rows; 0 = roofline-derived


def plan_levels(n: int, cfg: SortConfig) -> List[int]:
    """Choose the k for each of (at most two) level passes."""
    if n <= cfg.base_case:
        return []
    target = -(-cfg.slack * n // cfg.base_case)  # ceil
    k1 = max(2, 1 << math.ceil(math.log2(target)))
    if k1 <= cfg.kmax:
        return [k1]
    k1 = cfg.kmax
    k2 = max(2, 1 << math.ceil(math.log2(-(-target // k1))))
    if k2 > cfg.kmax:
        raise ValueError(
            f"n={n} too large for 2 levels with kmax={cfg.kmax}, "
            f"base_case={cfg.base_case}"
        )
    return [k1, k2]


def fit_window(n: int, cfg: SortConfig) -> SortConfig:
    """``cfg`` with the base-case window W doubled until two levels of
    ``kmax`` buckets cover ``n`` keys padded to ``max(W, tile)``: past
    ``kmax² × W / slack`` keys the buckets grow instead of the level count.
    ``cfg`` itself wherever ``plan_levels`` plans n under it already (up
    to 2^24 keys at the defaults), so those sorts run as before.

    >>> fit_window(1 << 24, SortConfig()).base_case
    8192
    >>> fit_window(29_993_472, SortConfig()).base_case
    16384
    """
    W = cfg.base_case
    while True:
        unit = max(W, cfg.tile)
        try:
            plan_levels(-(-n // unit) * unit, replace(cfg, base_case=W))
        except ValueError:
            W *= 2
            continue
        return cfg if W == cfg.base_case else replace(cfg, base_case=W)


def _auto_tile(n: int, nb: int, cfg: SortConfig) -> int:
    """Grow the tile so the (T, nb) histogram stays bounded (<= 2^26 ints)."""
    tile = cfg.tile
    while (n // tile) * nb > (1 << 26) and tile < cfg.base_case:
        tile *= 2
    return tile


def _obs_level_stats(offsets, nb: int, pad_bucket: Optional[int], level: str) -> None:
    """Bucket-balance stats for one completed level pass, as pure
    functions of the partition offsets, delivered through the obs side
    channel (unordered debug callback — ``repro.obs``, DESIGN.md §12).
    Stages nothing — zero added jaxpr equations — unless obs is enabled
    at trace time.  Accepts (nb+1,) and batched (B, nb+1) offsets."""
    if not obs.enabled():
        return
    sizes = jnp.diff(offsets, axis=-1)
    ids = np.arange(nb)
    mask = ids % 2 == 0  # odd ids = equality buckets, sized by the data
    if pad_bucket is not None:
        mask &= ids != pad_bucket
    k_eff = int(mask.sum())
    if k_eff == 0:
        return
    rows = int(np.prod(sizes.shape[:-1], dtype=np.int64)) if sizes.ndim > 1 else 1
    szs = jnp.where(jnp.asarray(mask), sizes, 0)
    largest = jnp.max(szs)
    mean = jnp.maximum(jnp.sum(szs) / (k_eff * max(rows, 1)), 1.0)
    obs.jit_observe(
        "sort.bucket_imbalance", largest.astype(jnp.float32) / mean, level=level
    )
    obs.jit_observe("sort.largest_bucket", largest, level=level)


def _obs_base_stats(violated: jax.Array) -> None:
    """Base-case vs robustness-fallback counters (pure in-jit stats;
    staged only when obs is enabled at trace time — emitted *before* the
    ``lax.cond`` so the callback never sits inside a branch)."""
    if not obs.enabled():
        return
    v = violated.astype(jnp.int32)
    obs.jit_count("sort.fallback_engaged", v)
    obs.jit_count("sort.base_case", 1 - v)


# Largest bucket count the fused rank kernel takes on: its per-tile
# one-hot is (rows*128, nb) in VMEM, so the segmented pass (nb = seg*2k)
# must drop back to the XLA engine past this.
_PALLAS_NB_MAX = 1024


def resolve_engine(cfg: SortConfig, n: int, dtype=None, batch: Optional[int] = None) -> str:
    """Concrete engine for this (cfg, n): "auto" consults the plan cache's
    persisted choice for a same-shape sort — the (batch, n) shape when
    ``batch`` is given — else picks by backend (the kernels lower natively
    only on TPU)."""
    if cfg.engine in ENGINES:
        return cfg.engine
    if cfg.engine != "auto":
        raise ValueError(
            f"unknown engine {cfg.engine!r}; expected one of {ENGINES + ('auto',)}"
        )
    if dtype is not None:
        from repro.ops.plan import default_cache  # lazy: ops layers on core

        hint = default_cache.engine_hint(n, dtype, batch=batch)
        if hint is not None:
            return hint
    return "pallas" if jax.default_backend() == "tpu" else "xla"


def _classify_rows(n: int, cfg: SortConfig, dtype, k: int) -> int:
    """Fused level-kernel tile rows for this level, or 0 if no candidate
    tile divides n (the caller then stays on the XLA classifier).
    ``cfg.classify_rows`` pins a swept value (the plan-cache autotune
    dimension); 0 derives the largest ``KernelLaunchSpec`` candidate for
    the ``"level_fused"`` kernel kind (``launch.roofline.launch_spec``)."""
    from repro.kernels.level_fused import fused_rows

    if cfg.classify_rows:
        return cfg.classify_rows if n % (cfg.classify_rows * 128) == 0 else 0
    return fused_rows(n, jnp.dtype(dtype).itemsize, k)


def segment_ids(offsets: jax.Array, n: int) -> jax.Array:
    """Per-position bucket/segment id from (nb+1,) sorted boundary offsets.

    Position p gets the number of offsets <= p, minus one, which is
    ``searchsorted(offsets, p, side="right") - 1`` bit for bit, in
    O(n + nb): a 1 is scatter-added at each offset and the marks are
    summed up.  Offsets >= n are dropped (no position counts them) and
    offsets below 0 count at position 0 (every position counts them);
    repeated offsets (empty buckets) add up at one position; positions
    before a first offset above 0 get -1.  The offsets stay sorted once
    clamped, so the scatter is told so and XLA does not sort them again.
    """
    at = jnp.maximum(offsets, 0)
    marks = jnp.zeros((n,), jnp.int32).at[at].add(1, mode="drop", indices_are_sorted=True)
    return jnp.cumsum(marks, dtype=jnp.int32) - 1


def _window_perm(keys_w: jax.Array, fb_w: jax.Array) -> jax.Array:
    """Stable lexicographic (bucket, key) sort permutation per window."""
    o1 = jnp.argsort(keys_w, axis=1, stable=True)
    o2 = jnp.argsort(jnp.take_along_axis(fb_w, o1, axis=1), axis=1, stable=True)
    return jnp.take_along_axis(o1, o2, axis=1)


def _apply_window_perm(perm: jax.Array, a: jax.Array) -> jax.Array:
    return jax.vmap(lambda row, p: jnp.take(row, p, axis=0))(a, perm)


def base_case(arrays: Any, fb: jax.Array, W: int, limit: Optional[int] = None) -> Any:
    """Two overlapped segmented window-sort passes (DESIGN.md §4.3).

    ``limit`` (static, multiple of W) restricts both passes to the index
    range [0, limit) — used by the partial sorts in ``repro.ops.topk``,
    which only need the buckets covering the first ``k`` ranks sorted.
    """
    n = fb.shape[0] if limit is None else limit

    def one_pass(arrays, fb, lo, hi):
        keys = arrays["k"][lo:hi]
        m = hi - lo
        kw = keys.reshape(m // W, W)
        fw = fb[lo:hi].reshape(m // W, W)
        perm = _window_perm(kw, fw)

        def fix(a):
            aw = a[lo:hi].reshape((m // W, W) + a.shape[1:])
            sw = _apply_window_perm(perm, aw).reshape((m,) + a.shape[1:])
            return a.at[lo:hi].set(sw)

        with obs.layer("move"):
            arrays = jax.tree.map(fix, arrays)
        fb = fb.at[lo:hi].set(
            _apply_window_perm(perm, fw).reshape(m)
        )
        return arrays, fb

    arrays, fb = one_pass(arrays, fb, 0, n)
    if n > W:  # offset pass: windows at W/2 (ends need no second pass)
        arrays, fb = one_pass(arrays, fb, W // 2, n - W // 2)
    return arrays


def stable_full_sort(arrays: Any) -> Any:
    """Plain stable sort of the arrays dict by key — the robustness fallback."""
    order = jnp.argsort(arrays["k"], stable=True)
    with obs.layer("move"):
        return jax.tree.map(lambda a: jnp.take(a, order, axis=0), arrays)


def replicated(tree: Any) -> Any:
    """Reshard every leaf whose type carries an Explicit-axis sharding
    (``jax.make_mesh``'s default) to replicated.  The engine pads, scatters
    and gathers across whole arrays, which sharding-in-types cannot resolve
    for a sharded operand; on a one-device mesh no data moves.  Unsharded
    leaves pass through untouched."""

    def one(a):
        s = jax.typeof(a).sharding
        if all(p is None for p in s.spec):
            return a
        return jax.sharding.reshard(a, s.update(spec=jax.sharding.PartitionSpec()))

    return jax.tree.map(one, tree)


def _payload_words(values: Any) -> int:
    """32-bit words a row over the leaves of a payload pytree (leading dim
    n); a leaf narrower than a word counts as one."""
    return sum(
        math.prod(a.shape[1:]) * max(1, a.dtype.itemsize // 4)
        for a in jax.tree.leaves(values)
    )


def pad_with_sentinel(arrays: Any, unit: int) -> Any:
    """Pad every leaf of the arrays dict to a multiple of ``unit``; pad keys
    get the dtype sentinel so they sort to the tail (the overflow-block
    analogue).  Non-key leaves are zero-padded."""
    n = arrays["k"].shape[0]
    n_pad = -(-n // unit) * unit
    if n_pad == n:
        return arrays
    pad_n = n_pad - n
    sent = sampling.sentinel_for(arrays["k"].dtype)

    def pad(a):
        padding = [(0, pad_n)] + [(0, 0)] * (a.ndim - 1)
        return jnp.pad(a, padding)

    arrays = jax.tree.map(pad, arrays)
    arrays["k"] = arrays["k"].at[n:].set(sent)
    return arrays


def level_pass(
    arrays: Any,
    n_real: int,
    k: int,
    cfg: SortConfig,
    rng: jax.Array,
    consumed_bits: int = 0,
) -> Tuple[Any, jax.Array, int, int]:
    """One *global* level pass: sample -> branchless classify -> stable
    block partition.  Pads (positions >= n_real) go to a dedicated final
    bucket.  Returns (arrays, offsets, nb, pad_bucket) with nb = 2k + 1.

    The classifier engine comes from ``cfg.classifier`` (DESIGN.md §9):
    "tree" samples splitters, "radix" extracts the next log2(k) key bits
    (skipping ``consumed_bits`` fixed by earlier radix levels — no sample
    at all), "learned" fits a CDF on the sample with a measured-imbalance
    ``lax.cond`` fallback to the tree; "auto" at this depth means "tree"
    (the plan-cache routing happens at the ``repro.ops`` boundary).

    On the "pallas" engine the whole level runs as ONE fused kernel pass
    (``kernels.level_fused``): classify + per-tile histogram + in-tile
    rank in a single grid sweep — one HBM read of the keys instead of the
    former three (classify kernel, histogram glue, counting-rank kernel)
    — with pads routed to the dedicated bucket in-kernel and a prefix
    epilogue closing the destinations.  Offsets and the permutation are
    bit-identical to the "xla" engine (DESIGN.md §10).
    """
    keys = arrays["k"]
    n = keys.shape[0]
    clf = resolve_classifier(cfg.classifier)

    nb = 2 * k + 1  # +1: dedicated pad bucket (the overflow-block analogue)
    pad_n = n - n_real
    engine = resolve_engine(cfg, n, keys.dtype)
    # the fused classify kernels need a 128-aligned n (tree and radix have
    # fused forms; learned classifies on XLA); the counting-rank partition
    # self-pads, so a pallas engine keeps its partition either way
    rows = (
        _classify_rows(n, cfg, keys.dtype, k)
        if engine == "pallas" and clf in ("tree", "radix")
        else 0
    )
    interpret = resolve_interpret()

    if clf != "radix":
        with obs.layer("sample", k=k, n=n_real):
            m1 = min(
                max(sampling.oversampling_factor(n_real) * k, k), cfg.max_sample, n_real
            )
            sample_pos = jax.random.randint(rng, (m1,), 0, n_real)
            sample = jnp.sort(jnp.take(keys, sample_pos, axis=0))
            spl = sampling.select_splitters(sample, k)

    if rows:
        # the fused single-pass level kernel: classify + histogram + rank
        # in one grid sweep; pads route to bucket 2k in-kernel; the prefix
        # epilogue yields the stable destinations and bucket boundaries
        from repro.kernels.level_fused import level_fused

        with obs.layer("classify", engine="pallas", fused=True, classifier=clf, k=k):
            dest, off = level_fused(
                keys, None if clf == "radix" else spl, k=k, n_real=n_real,
                classifier=clf, consumed_bits=consumed_bits, rows=rows,
                interpret=interpret,
            )
        with obs.layer("partition", engine="pallas", fused=True, nb=nb), obs.layer("move"):
            arrays = jax.tree.map(
                lambda a: jnp.zeros_like(a).at[dest].set(a, mode="promise_in_bounds"),
                arrays,
            )
        return arrays, off, nb, 2 * k
    with obs.layer("classify", engine=engine, classifier=clf, k=k):
        if clf == "radix":
            b = radix_bucket_ids(keys, k, consumed_bits)
        elif clf == "learned":
            b, _ = learned_bucket_ids(keys, sample, spl, k)
        else:
            b = classify(keys, spl, k)
        if pad_n:
            is_pad = jnp.arange(n, dtype=jnp.int32) >= n_real
            b = jnp.where(is_pad, 2 * k, b)
    with obs.layer("partition", engine=engine, nb=nb):
        arrays, off = stable_partition(
            b, arrays, nb, _auto_tile(n, nb, cfg), engine=engine,
            interpret=interpret,
        )
    return arrays, off, nb, 2 * k


def segmented_level_pass(
    arrays: Any,
    seg_offsets: jax.Array,
    num_seg: int,
    n_real: int,
    k: int,
    cfg: SortConfig,
    rng: jax.Array,
    sample_cap: int = 2048,
    classifier: str = "tree",
    consumed_bits: int = 0,
) -> Tuple[Any, jax.Array, int]:
    """One *segmented* level pass: per-segment splitters, flattened
    classification, composite-bucket partition.  This is recursion level 2
    of the full sort and the whole of ``repro.ops.segmented_sort``.

    ``seg_offsets`` (num_seg+1,) bounds each segment; segments keep their
    index ranges (the composite id is monotone in segment and the partition
    is stable).  Returns (arrays, offsets, nb) with nb = num_seg * 2k.

    ``classifier`` accepts "tree" (per-segment sampled splitters) or
    "radix" (the shared per-level shift extractor — valid ONLY when the
    segments are radix-aligned key ranges, i.e. when level 1 was a radix
    level too, which is why ``partition_passes`` is the only caller that
    passes it; the "learned" engine has no per-segment form and maps to
    "tree" one layer up).

    Classification stays on the XLA path (the composite-bucket classifier
    has no fused kernel; the radix extractor is one shift + mask, already
    as cheap as a kernel); the *partition* honours ``cfg.engine`` as long
    as nb fits the fused rank kernel's VMEM one-hot (past
    ``_PALLAS_NB_MAX`` composite buckets it drops back to "xla").
    """
    keys = arrays["k"]
    n = keys.shape[0]
    with obs.layer("sort.segment_ids"):
        seg = segment_ids(seg_offsets, n)
    if classifier == "radix":
        # no sampling pass: within a radix-aligned segment the next
        # log2(k) bits are monotone, and the shift is segment-independent
        with obs.layer("classify", segmented=True, classifier="radix", k=k):
            local = radix_bucket_ids(keys, k, consumed_bits)
    else:
        with obs.layer("sample", segmented=True, k=k, segments=num_seg):
            m = min(max(sampling.oversampling_factor(n_real) * k, k), sample_cap)
            seg_rngs = jax.random.split(rng, num_seg)
            pos = jax.vmap(lambda r, lo, hi: sampling.sample_indices(r, m, lo, hi))(
                seg_rngs, seg_offsets[:-1], seg_offsets[1:]
            )
            svals = jnp.sort(
                jnp.take(keys, pos.reshape(-1), axis=0).reshape(num_seg, m), axis=-1
            )
            spl = sampling.select_splitters(svals, k)  # (num_seg, k-1)
        with obs.layer("classify", segmented=True, classifier="tree", k=k):
            local = classify_segmented(keys, seg, spl, k)
    comp = seg * (2 * k) + local
    nb = num_seg * 2 * k
    engine = resolve_engine(cfg, n, keys.dtype)
    if engine == "pallas" and nb > _PALLAS_NB_MAX:
        engine = "xla"
    with obs.layer("partition", segmented=True, nb=nb, engine=engine):
        arrays, offsets = stable_partition(
            comp, arrays, nb, _auto_tile(n, nb, cfg), engine=engine
        )
    return arrays, offsets, nb


def partition_passes(
    arrays: Any, n_real: int, cfg: SortConfig, levels: Sequence[int]
) -> Tuple[Any, jax.Array, int, Optional[int]]:
    """Run the (at most two) level passes of the flattened recursion.

    Returns (arrays, offsets, nb, pad_bucket); after this every bucket is
    contiguous, buckets are in key order, odd ids are equality buckets, and
    pads are at the tail (in ``pad_bucket`` after one level, in an odd
    sentinel-equality bucket after two).

    Classifier threading: level 1 takes ``cfg.classifier`` as resolved by
    ``level_pass``; level 2 reuses "radix" only when level 1 was radix (the
    segments are then bit-aligned key ranges and the next log2(k2) bits
    stay monotone per segment, with ``consumed_bits = log2(k1)``) and maps
    "learned" back to "tree" (the CDF model is global; per-segment refits
    would cost more than the per-segment tree they'd replace).
    """
    clf = resolve_classifier(cfg.classifier)
    rng = jax.random.PRNGKey(cfg.seed)
    r1, r2 = jax.random.split(rng)
    with obs.layer("sort.level1", k=levels[0]):
        arrays, off1, nb1, pad_bucket = level_pass(arrays, n_real, levels[0], cfg, r1)
    _obs_level_stats(off1, nb1, pad_bucket, level="1")
    if len(levels) == 1:
        return arrays, off1, nb1, pad_bucket
    with obs.layer("sort.level2", k=levels[1], segmented=True):
        arrays, offsets, nb = segmented_level_pass(
            arrays, off1, nb1, n_real, levels[1], cfg, r2,
            classifier="radix" if clf == "radix" else "tree",
            consumed_bits=int(math.log2(levels[0])),
        )
    _obs_level_stats(offsets, nb, None, level="2")
    return arrays, offsets, nb, None  # pads now sit in an odd equality bucket


def bucket_violations(
    offsets: jax.Array,
    nb: int,
    W: int,
    pad_bucket: Optional[int] = None,
    limit: Optional[jax.Array] = None,
) -> jax.Array:
    """True iff some non-trivial bucket exceeds W/2 (base-case precondition).

    Equality buckets (odd ids) hold identical keys and never need sorting,
    so their size is unbounded.  ``limit`` restricts the check to buckets
    that intersect [0, limit) — partial sorts only care about those.
    """
    sizes = jnp.diff(offsets)
    ids = jnp.arange(nb, dtype=jnp.int32)
    nontrivial = (ids % 2) == 0  # odd ids = equality buckets (all-equal)
    if pad_bucket is not None:
        nontrivial = nontrivial & (ids != pad_bucket)
    if limit is not None:
        nontrivial = nontrivial & (offsets[:-1] < limit)
    return jnp.any(jnp.where(nontrivial, sizes, 0) > W // 2)


def _sort_padded(arrays: Any, n_real: int, cfg: SortConfig, levels: Sequence[int]) -> Any:
    """Sort padded arrays dict (pads = sentinel keys at the tail)."""
    n = arrays["k"].shape[0]
    W = cfg.base_case

    if not levels:
        # Single window: plain stable base case (the paper's smallSort).
        return stable_full_sort(arrays)

    arrays, offsets, nb, pad_bucket = partition_passes(arrays, n_real, cfg, levels)

    # ---- Base case + robustness fallback ---------------------------------
    with obs.layer("sort.segment_ids"):
        fb = segment_ids(offsets, n)
    # the scope wraps the cond from outside, so its branches' op_names
    # keep ``cond/branch_<i>_fun/jit(argsort)`` as they were
    with obs.layer("sort.base_case", W=W, fallback=cfg.fallback):
        violated = bucket_violations(offsets, nb, W, pad_bucket)
        _obs_base_stats(violated)
        if cfg.fallback:
            return jax.lax.cond(
                violated,
                stable_full_sort,
                lambda a: base_case(a, fb, W),
                arrays,
            )
        return base_case(arrays, fb, W)


# --------------------------------------------------------------------------
# Batch-axis-native pipeline (DESIGN.md §6): every stage of the 1-D sort
# lifted over a leading batch dimension (B, n) in ONE trace.  Rows never
# exchange elements; each row gets its own splitter set, its own bucket
# offsets, and its own stable partition.  The Pallas engine runs the
# batch-grid kernels (grid = (B, tiles)); the XLA engine vmaps its dense
# formulation, which batches natively.


def batched_segment_ids(offsets: jax.Array, n: int) -> jax.Array:
    """Per-position bucket id per row from (B, nb+1) boundary offsets."""
    return jax.vmap(lambda off: segment_ids(off, n))(offsets)


def batched_stable_full_sort(arrays: Any) -> Any:
    """Per-row stable sort by key — the batched robustness fallback."""
    order = jnp.argsort(arrays["k"], axis=1, stable=True)
    take = jax.vmap(lambda a, p: jnp.take(a, p, axis=0))
    return jax.tree.map(lambda a: take(a, order), arrays)


def batched_pad_with_sentinel(arrays: Any, unit: int) -> Any:
    """Pad axis 1 of every (B, n, ...) leaf to a multiple of ``unit``; pad
    keys get the dtype sentinel (each row's overflow-block analogue)."""
    n = arrays["k"].shape[1]
    n_pad = -(-n // unit) * unit
    if n_pad == n:
        return arrays
    pad_n = n_pad - n
    sent = sampling.sentinel_for(arrays["k"].dtype)

    def pad(a):
        padding = [(0, 0), (0, pad_n)] + [(0, 0)] * (a.ndim - 2)
        return jnp.pad(a, padding)

    arrays = jax.tree.map(pad, arrays)
    arrays["k"] = arrays["k"].at[:, n:].set(sent)
    return arrays


def batched_base_case(
    arrays: Any, fb: jax.Array, W: int, limit: Optional[int] = None
) -> Any:
    """The two overlapped window-sort passes (§4.3) over (B, n, ...) leaves.

    Rows share no window: the per-row index range [lo, hi) reshapes to
    B * (hi-lo)/W independent windows, so the same ``_window_perm``
    machinery sorts every row's windows in one pass.  ``limit`` (static,
    multiple of W) restricts both passes to [0, limit) *per row*.
    """
    B = fb.shape[0]
    n = fb.shape[1] if limit is None else limit

    def one_pass(arrays, fb, lo, hi):
        m = hi - lo
        nw = B * (m // W)
        kw = arrays["k"][:, lo:hi].reshape(nw, W)
        fw = fb[:, lo:hi].reshape(nw, W)
        perm = _window_perm(kw, fw)

        def fix(a):
            aw = a[:, lo:hi].reshape((nw, W) + a.shape[2:])
            sw = _apply_window_perm(perm, aw).reshape((B, m) + a.shape[2:])
            return a.at[:, lo:hi].set(sw)

        arrays = jax.tree.map(fix, arrays)
        fb = fb.at[:, lo:hi].set(_apply_window_perm(perm, fw).reshape(B, m))
        return arrays, fb

    arrays, fb = one_pass(arrays, fb, 0, n)
    if n > W:  # offset pass: per-row windows at W/2
        arrays, fb = one_pass(arrays, fb, W // 2, n - W // 2)
    return arrays


def batched_bucket_violations(
    offsets: jax.Array,
    nb: int,
    W: int,
    pad_bucket: Optional[int] = None,
    limit: Optional[jax.Array] = None,
) -> jax.Array:
    """True iff ANY row has a non-trivial bucket exceeding W/2.  The
    fallback is batch-wide (one ``lax.cond`` for the whole trace), so a
    single violating row reroutes every row through the stable sort."""
    sizes = jnp.diff(offsets, axis=1)  # (B, nb)
    ids = jnp.arange(nb, dtype=jnp.int32)
    nontrivial = (ids % 2) == 0
    if pad_bucket is not None:
        nontrivial = nontrivial & (ids != pad_bucket)
    nontrivial = jnp.broadcast_to(nontrivial[None, :], sizes.shape)
    if limit is not None:
        nontrivial = nontrivial & (offsets[:, :-1] < limit)
    return jnp.any(jnp.where(nontrivial, sizes, 0) > W // 2)


def batched_level_pass(
    arrays: Any, n_real: int, k: int, cfg: SortConfig, rng: jax.Array
) -> Tuple[Any, jax.Array, int, int]:
    """One global level pass per row: per-row sample -> per-row splitters ->
    batched branchless classify -> per-row stable partition.

    Returns (arrays, offsets (B, nb+1), nb, pad_bucket) with nb = 2k + 1.
    On the "pallas" engine the whole level runs as ONE batch-grid launch
    of the fused level kernel (``kernels.level_fused``) for all B rows.

    Classifier dispatch mirrors ``level_pass``: "radix" skips the per-row
    sampling entirely (the shift mask is row-independent), "learned" fits
    one CDF model per row and falls back batch-wide to the per-row trees
    when any row's measured imbalance trips the threshold, "auto" at this
    depth means "tree" (the data-aware router is eager-side).
    """
    keys = arrays["k"]
    B, n = keys.shape
    clf = resolve_classifier(cfg.classifier)
    nb = 2 * k + 1  # +1: dedicated pad bucket per row
    pad_n = n - n_real
    engine = resolve_engine(cfg, n, keys.dtype)
    rows = (
        _classify_rows(n, cfg, keys.dtype, k)
        if engine == "pallas" and clf in ("tree", "radix")
        else 0
    )
    interpret = resolve_interpret()

    if clf != "radix":
        with obs.trace("sample", batched=True, k=k, n=n_real):
            m1 = min(
                max(sampling.oversampling_factor(n_real) * k, k), cfg.max_sample, n_real
            )
            row_rngs = jax.random.split(rng, B)
            sample_pos = jax.vmap(lambda r: jax.random.randint(r, (m1,), 0, n_real))(
                row_rngs
            )
            sample = jnp.sort(jnp.take_along_axis(keys, sample_pos, axis=1), axis=1)
            spl = sampling.select_splitters(sample, k)  # (B, k-1) per-row splitters

    if rows:
        # one batch-grid launch of the fused level kernel for all B rows
        from repro.kernels.level_fused import level_fused_batched

        with obs.trace("classify", batched=True, engine="pallas", fused=True, k=k):
            dest, off = level_fused_batched(
                keys, None if clf == "radix" else spl, k=k, n_real=n_real,
                classifier=clf, rows=rows, interpret=interpret,
            )
        with obs.trace("partition", batched=True, engine="pallas", fused=True, nb=nb):
            flat_dest = (
                dest + n * jnp.arange(B, dtype=jnp.int32)[:, None]
            ).reshape(-1)

            def move(a):
                fa = a.reshape((B * n,) + a.shape[2:])
                out = jnp.zeros_like(fa).at[flat_dest].set(
                    fa, mode="promise_in_bounds"
                )
                return out.reshape(a.shape)

            return jax.tree.map(move, arrays), off, nb, 2 * k
    with obs.trace("classify", batched=True, engine=engine, classifier=clf, k=k):
        if clf == "radix":
            b = radix_bucket_ids(keys, k)
        elif clf == "learned":
            b, _ = learned_bucket_ids_batched(keys, sample, spl, k)
        else:
            b = classify_batched(keys, spl, k)
        if pad_n:
            is_pad = jnp.arange(n, dtype=jnp.int32)[None, :] >= n_real
            b = jnp.where(is_pad, 2 * k, b)
    with obs.trace("partition", batched=True, engine=engine, nb=nb):
        arrays, off = batched_stable_partition(
            b, arrays, nb, _auto_tile(n, nb, cfg), engine=engine,
            interpret=interpret,
        )
    return arrays, off, nb, 2 * k


def batched_segmented_level_pass(
    arrays: Any,
    seg_offsets: jax.Array,
    num_seg: int,
    n_real: int,
    k: int,
    cfg: SortConfig,
    rng: jax.Array,
    sample_cap: int = 2048,
    classifier: str = "tree",
    consumed_bits: int = 0,
) -> Tuple[Any, jax.Array, int]:
    """Recursion level 2 per row: per-(row, segment) splitters, flattened
    classification, per-row composite-bucket partition.

    ``seg_offsets`` (B, num_seg+1) bounds each row's segments.  The
    composite id ``seg * 2k + local`` stays row-local, so the partition is
    the per-row one (nb = num_seg * 2k buckets per row) — rows still never
    exchange elements.

    ``classifier`` accepts "tree" or "radix" under the same contract as the
    1-D ``segmented_level_pass``: radix is only valid when level 1 was
    radix (bit-aligned segments), and it skips the per-(row, segment)
    sampling entirely.
    """
    keys = arrays["k"]
    B, n = keys.shape
    seg = batched_segment_ids(seg_offsets, n)  # (B, n)
    if classifier == "radix":
        local = radix_bucket_ids(keys, k, consumed_bits)
    else:
        m = min(max(sampling.oversampling_factor(n_real) * k, k), sample_cap)
        seg_rngs = jax.random.split(rng, B * num_seg).reshape(B, num_seg, -1)
        pos = jax.vmap(
            jax.vmap(lambda r, lo, hi: sampling.sample_indices(r, m, lo, hi))
        )(seg_rngs, seg_offsets[:, :-1], seg_offsets[:, 1:])  # (B, num_seg, m)
        svals = jnp.sort(
            jnp.take_along_axis(keys, pos.reshape(B, num_seg * m), axis=1).reshape(
                B, num_seg, m
            ),
            axis=-1,
        )
        spl = sampling.select_splitters(svals, k)  # (B, num_seg, k-1)
        # flatten (row, segment) -> global segment for the shared classifier
        gseg = (seg + num_seg * jnp.arange(B, dtype=jnp.int32)[:, None]).reshape(B * n)
        local = classify_segmented(
            keys.reshape(B * n), gseg, spl.reshape(B * num_seg, k - 1), k
        ).reshape(B, n)
    comp = seg * (2 * k) + local  # row-local composite bucket
    nb = num_seg * 2 * k
    engine = resolve_engine(cfg, n, keys.dtype)
    if engine == "pallas" and nb > _PALLAS_NB_MAX:
        engine = "xla"
    arrays, offsets = batched_stable_partition(
        comp, arrays, nb, _auto_tile(n, nb, cfg), engine=engine
    )
    return arrays, offsets, nb


def batched_partition_passes(
    arrays: Any, n_real: int, cfg: SortConfig, levels: Sequence[int]
) -> Tuple[Any, jax.Array, int, Optional[int]]:
    """The (at most two) batched level passes of the flattened recursion.

    Returns (arrays, offsets (B, nb+1), nb, pad_bucket); per row, buckets
    are contiguous and in key order, odd local ids are equality buckets,
    pads sit at the row tail.  Classifier threading matches the 1-D
    ``partition_passes``: radix carries to level 2 with the consumed-bit
    shift, learned maps back to tree there.
    """
    clf = resolve_classifier(cfg.classifier)
    rng = jax.random.PRNGKey(cfg.seed)
    r1, r2 = jax.random.split(rng)
    with obs.trace("level_pass", level=1, k=levels[0], batched=True):
        arrays, off1, nb1, pad_bucket = batched_level_pass(
            arrays, n_real, levels[0], cfg, r1
        )
    _obs_level_stats(off1, nb1, pad_bucket, level="1")
    if len(levels) == 1:
        return arrays, off1, nb1, pad_bucket
    with obs.trace("level_pass", level=2, k=levels[1], batched=True, segmented=True):
        arrays, offsets, nb = batched_segmented_level_pass(
            arrays, off1, nb1, n_real, levels[1], cfg, r2,
            classifier="radix" if clf == "radix" else "tree",
            consumed_bits=int(math.log2(levels[0])),
        )
    _obs_level_stats(offsets, nb, None, level="2")
    return arrays, offsets, nb, None  # pads now sit in odd equality buckets


def _sort_padded_batched(
    arrays: Any, n_real: int, cfg: SortConfig, levels: Sequence[int]
) -> Any:
    """Sort padded (B, n_pad, ...) arrays dict, all rows in one trace."""
    n = arrays["k"].shape[1]
    W = cfg.base_case

    if not levels:
        return batched_stable_full_sort(arrays)

    arrays, offsets, nb, pad_bucket = batched_partition_passes(
        arrays, n_real, cfg, levels
    )

    fb = batched_segment_ids(offsets, n)
    violated = batched_bucket_violations(offsets, nb, W, pad_bucket)
    _obs_base_stats(violated)

    with obs.trace("base_case", W=W, fallback=cfg.fallback, batched=True):
        if cfg.fallback:
            return jax.lax.cond(
                violated,
                batched_stable_full_sort,
                lambda a: batched_base_case(a, fb, W),
                arrays,
            )
        return batched_base_case(arrays, fb, W)


def ips4o_sort_batched(
    keys: jax.Array,
    values: Any = None,
    cfg: SortConfig = SortConfig(),
):
    """Sort every row of ``keys`` (B, n) independently, ascending, in ONE
    trace (DESIGN.md §6) — no vmap over the 1-D sort, no python loop.

    Optionally permutes a ``values`` pytree (leaves with leading dims
    (B, n)) alongside, row by row.  Same key contract as
    :func:`ips4o_sort`: keys must form a total order under ``>`` / ``==``
    (the ``repro.ops.batched`` entry points keyspace-encode first and are
    NaN-safe).  Jit-compatible; static shapes.
    """
    if keys.ndim != 2:
        raise ValueError("keys must be 2-D (B, n)")
    B, n = keys.shape
    if n <= 1 or B == 0:
        return keys if values is None else (keys, values)

    arrays = {"k": keys}
    if values is not None:
        arrays["v"] = values

    unit = max(cfg.base_case, cfg.tile)
    with obs.trace("ips4o_sort_batched", B=B, n=n, engine=cfg.engine):
        arrays = batched_pad_with_sentinel(arrays, unit)
        levels = plan_levels(arrays["k"].shape[1], cfg)
        arrays = _sort_padded_batched(arrays, n, cfg, levels)

    out_k = arrays["k"][:, :n]
    if values is None:
        return out_k
    return out_k, jax.tree.map(lambda a: a[:, :n], arrays["v"])


def ips4o_sort(
    keys: jax.Array,
    values: Any = None,
    cfg: SortConfig = SortConfig(),
):
    """Sort ``keys`` (n,) ascending; optionally permute a ``values`` pytree
    (leaves with leading dim n) alongside.  Jit-compatible; static shapes.

    Keys must form a total order under ``>`` / ``==``, which raw float NaNs
    do not — use the ``repro.ops`` entry points (``ops.sort`` etc.), which
    biject keys through ``ops/keyspace.py`` first and are NaN-safe (NaNs
    sort last, -0.0 before +0.0), or canonicalize NaNs yourself before
    calling this low-level engine directly.

    A payload of two or more 32-bit words a row is deferred (DESIGN.md
    §4.9): the rounds sort (key, int32 row index) and every leaf is
    gathered once by the sorted index; a one-word payload moves with the
    keys.  Either way the result is the same, bit for bit.
    """
    n = keys.shape[0]
    if keys.ndim != 1:
        raise ValueError("keys must be 1-D")
    if n <= 1:
        return keys if values is None else (keys, values)

    cfg = fit_window(n, cfg)
    arrays = {"k": keys}
    if values is not None:
        arrays["v"] = values
    arrays = replicated(arrays)
    words = 0 if values is None else _payload_words(arrays["v"])
    deferred = words >= 2
    if deferred:
        values = arrays["v"]
        arrays["v"] = jnp.arange(n, dtype=jnp.int32)
        obs.count("sort.payload_deferred", words)

    unit = max(cfg.base_case, cfg.tile)
    with obs.trace(
        "ips4o_sort", n=n, engine=cfg.engine, classifier=cfg.classifier
    ):
        arrays = pad_with_sentinel(arrays, unit)
        levels = plan_levels(arrays["k"].shape[0], cfg)
        arrays = _sort_padded(arrays, n, cfg, levels)

    out_k = arrays["k"][:n]
    if values is None:
        return out_k
    if deferred:
        # stability keeps every pad behind the real keys, sentinel-valued
        # ones included, so the first n indices are a permutation of [0, n)
        with obs.layer("sort.payload", words=words), obs.layer("move"):
            idx = arrays["v"][:n]
            return out_k, jax.tree.map(lambda a: jnp.take(a, idx, axis=0), values)
    return out_k, jax.tree.map(lambda a: a[:n], arrays["v"])


def tiebreak_passes(
    cols: Sequence[jax.Array],
    values: Any = None,
    cfg: SortConfig = SortConfig(),
) -> Tuple[List[jax.Array], Any]:
    """MSD tie-break level schedule over multi-word keys (DESIGN.md §11).

    ``cols`` is the word decomposition of each row's key, most significant
    first (word 0): W arrays of shape (n,) whose dtypes form a total order
    under ``>`` / ``==`` (the ``repro.ops`` callers pass keyspace-encoded
    uint words).  Rows end up in **stable lexicographic order** — the
    permutation is bit-identical to ``np.lexsort`` over the columns —
    relying on the stability of :func:`ips4o_sort` (module docstring).

    Schedule: level 0 sorts word 0 outright.  Level l re-sorts only the
    runs that still tie on words 0..l-1: tie runs are the
    ``group_by``-style boundary runs of the already-sorted prefix, and the
    segmented re-sort is two stable passes (sort by word l, then by run
    id — the run id is nondecreasing before the pass, so the second sort
    restores every run's index range with word l ordered inside it).
    Words 0..l-1 are *not* threaded through the re-sort: they are constant
    within a tie run by definition, and the composed permutation never
    moves an element across runs.  A level with no surviving ties is
    skipped at runtime via ``lax.cond``.

    Returns ``(sorted cols, values)``; ``values`` leaves (leading dim n)
    are permuted alongside through every pass.
    """
    cols = [c for c in cols]
    if not cols:
        raise ValueError("tiebreak_passes: need at least one word column")
    n = cols[0].shape[0]
    if any(c.shape != (n,) for c in cols):
        raise ValueError("tiebreak_passes: word columns must share shape (n,)")
    if n <= 1:
        return cols, values

    # level 0: plain sort on the most significant word
    key, state = ips4o_sort(cols[0], {"rest": cols[1:], "v": values}, cfg=cfg)
    out: List[jax.Array] = [key]
    boundary = jnp.concatenate(
        [jnp.ones((1,), jnp.bool_), key[1:] != key[:-1]]
    )

    for lvl in range(1, len(cols)):
        rest = state["rest"]
        col, rest = rest[0], rest[1:]
        # tie-run ids of the sorted prefix (words 0..lvl-1): nondecreasing,
        # one id per maximal equal-prefix run (the group_by boundary scan)
        seg = (jnp.cumsum(boundary.astype(jnp.int32)) - 1).astype(jnp.uint32)
        has_ties = jnp.any(~boundary)

        def _resort(args):
            col, rest, v, seg = args
            # stable segmented sort by (run, word lvl) as two stable passes
            col_a, st_a = ips4o_sort(col, {"seg": seg, "rest": rest, "v": v}, cfg=cfg)
            seg_b, st_b = ips4o_sort(
                st_a["seg"], {"col": col_a, "rest": st_a["rest"], "v": st_a["v"]},
                cfg=cfg,
            )
            return st_b["col"], st_b["rest"], st_b["v"], seg_b

        col, rest, v, seg = jax.lax.cond(
            has_ties, _resort, lambda args: args, (col, rest, state["v"], seg)
        )
        state = {"rest": rest, "v": v}
        out.append(col)
        boundary = boundary | jnp.concatenate(
            [jnp.ones((1,), jnp.bool_), col[1:] != col[:-1]]
        )

    return out, state["v"]


def is4o_sort(keys: jax.Array, values: Any = None, cfg: SortConfig = SortConfig()):
    """IS4o — the sequential (single-core) instantiation; on TPU a single
    core runs the same pass pipeline, so this is an alias with one stripe."""
    return ips4o_sort(keys, values, cfg)


def make_sorter(n: int, dtype, cfg: SortConfig = SortConfig(), donate: bool = True):
    """Build a jitted sorter for shape (n,); ``donate=True`` gives the
    in-place property (XLA reuses the input HBM buffer)."""
    f = partial(ips4o_sort, cfg=cfg)
    return jax.jit(f, donate_argnums=(0,) if donate else ())
