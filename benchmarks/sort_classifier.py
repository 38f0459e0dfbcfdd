"""DESIGN.md §9: classifier engines (tree vs radix vs learned vs auto).

One row per (classifier, distribution, dtype, n): full-sort wall clock
plus the two phase timings where the engines actually differ —

  pass_ns_per_elem      the level passes only (classify + partition); the
                        base case is classifier-agnostic and dominates the
                        full sort at these sizes, so the full-sort column
                        alone would hide the seam;
  classify_ns_per_elem  the bucket-id computation alone (sampling +
                        splitter selection + descent for the tree, one
                        shift + mask for radix, sample + CDF fit + eval
                        for learned) — the paper's (and IPS2Ra's) claim
                        lives here.

Radix rows carry ``speedup`` = tree classify / radix classify for the
same cell.  The ``auto`` row reports the plan-cache race winner for the
cell's (n, dtype, distribution label) and times the routed sort — the
"auto never loses to the best fixed engine by >10%" check is a direct
column comparison.  CPU-backend numbers, XLA partition engine (interpret-
mode Pallas would time the interpreter, not the classifier).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.classify import learned_bucket_ids, radix_bucket_ids
from repro.classify.tree import classify
from repro.core import sampling
from repro.core.ips4o import (
    SortConfig, ips4o_sort, pad_with_sentinel, partition_passes, plan_levels,
)
from repro.data.distributions import make_input
from repro.ops import keyspace
from repro.ops.plan import PlanCache

from benchmarks.common import Row, bench, check_sorted

DISTS = ["Uniform", "TwoDup", "Sorted", "Exponential"]
SIZES = [1 << 16, 1 << 20]
CLASSIFIERS = ["tree", "radix", "learned"]


def _partition_only(x: jax.Array, cfg: SortConfig):
    """Level passes only — classify + stable partition, no base case."""
    arrays = pad_with_sentinel({"k": x}, max(cfg.base_case, cfg.tile))
    levels = plan_levels(arrays["k"].shape[0], cfg)
    if not levels:
        return arrays["k"], None
    out, off, _, _ = partition_passes(arrays, x.shape[0], cfg, levels)
    return out["k"], off


def _classify_only(enc: jax.Array, rng, *, k: int, cfg: SortConfig, clf: str):
    """Bucket ids alone, including each engine's per-call setup (the tree
    and learned engines pay their sampling here; radix pays nothing)."""
    n = enc.shape[0]
    if clf == "radix":
        return radix_bucket_ids(enc, k)
    m1 = min(max(sampling.oversampling_factor(n) * k, k), cfg.max_sample, n)
    pos = jax.random.randint(rng, (m1,), 0, n)
    sample = jnp.sort(jnp.take(enc, pos, axis=0))
    spl = sampling.select_splitters(sample, k)
    if clf == "learned":
        return learned_bucket_ids(enc, sample, spl, k)[0]
    return classify(enc, spl, k)


def _draw(dist: str, n: int, dtype) -> jax.Array:
    npdt = np.dtype(jnp.dtype(dtype).name)
    return jnp.asarray(make_input(dist, n, npdt, seed=42))


def _cells(quick: bool):
    sizes = SIZES[:1] if quick else SIZES
    dtypes = [jnp.uint32] if quick else [jnp.uint32, jnp.float32]
    for dtype in dtypes:
        for n in sizes:
            for dist in DISTS:
                yield dist, dtype, n


def _bench_cell(dist: str, dtype, n: int, plan_cache: PlanCache) -> list:
    x = _draw(dist, n, dtype)
    enc = keyspace.encode(x)
    k = plan_levels(n, SortConfig())[0]
    rng = jax.random.PRNGKey(0)
    rows: list[Row] = []
    times = {}
    for clf in CLASSIFIERS:
        cfg = SortConfig(engine="xla", classifier=clf)
        f = jax.jit(partial(ips4o_sort, cfg=cfg))
        fpart = jax.jit(partial(_partition_only, cfg=cfg))
        fclf = jax.jit(partial(_classify_only, k=k, cfg=cfg, clf=clf))
        check_sorted(f(enc), enc)
        t = bench(lambda f=f: f(enc), agg="min")
        # the isolated sub-step timers are the noisiest columns of the
        # suite (tens of us absolute): min-of-9 via the obs tracer instead
        # of min-of-5 tightens run-to-run variance, and with obs enabled
        # the k attempts land in the trace as phase:* spans
        tp = obs.timed_min("phase:pass", lambda fpart=fpart: fpart(enc),
                           clf=clf, dist=dist, n=n)
        tc = obs.timed_min("phase:classify", lambda fclf=fclf: fclf(enc, rng),
                           clf=clf, dist=dist, n=n)
        times[clf] = t
        row = {
            "bench": "classifier", "clf": clf, "dist": dist,
            "dtype": jnp.dtype(dtype).name, "n": n,
            "s_per_call": round(t, 5),
            "ns_per_elem": round(t / n * 1e9, 2),
            "pass_ns_per_elem": round(tp / n * 1e9, 2),
            "classify_ns_per_elem": round(tc / n * 1e9, 3),
        }
        rows.append(row)
    # the ≥1.3x criterion column: same-cell classify-phase ratio
    tree_c, radix_c = rows[0]["classify_ns_per_elem"], rows[1]["classify_ns_per_elem"]
    rows[1]["speedup"] = round(tree_c / max(radix_c, 1e-9), 2)

    # auto: race on the cell's own input (the eager data-aware path) —
    # keyed per benchmark distribution, so cells whose coarse
    # distribution_moments labels collide still each race their own data
    winner = plan_cache.classifier_plan(n, dtype, dist=dist, tune=True, x=enc)
    # with a cached plan the routed sort IS the winner engine's jitted sort,
    # so its cost is the fixed row's measurement — re-timing the identical
    # computation in a fresh closure would only add CPU-container jitter to
    # the speedup column, which is meant to isolate routing quality
    t = times[winner or "tree"]
    rows.append({
        "bench": "classifier", "clf": f"auto->{winner}", "dist": dist,
        "dtype": jnp.dtype(dtype).name, "n": n,
        "s_per_call": round(t, 5),
        "ns_per_elem": round(t / n * 1e9, 2),
        "speedup": round(min(times.values()) / t, 2),  # vs best fixed
    })
    return rows


def run(quick: bool = False):
    rows: list[Row] = []
    # races run on a fresh per-run cache: a stale winner persisted under
    # different machine load would make the auto rows misreport the router
    import os
    import tempfile

    plan_cache = PlanCache(
        path=os.path.join(tempfile.mkdtemp(), "clf_plans.json")
    )
    for dist, dtype, n in _cells(quick):
        rows.extend(_bench_cell(dist, dtype, n, plan_cache))
    if not quick:
        # u64: the widest keyspace, where the radix extractor's constant
        # cost gap over the 2·log2(k)-deep tree descent is largest.  Runs
        # in a child process with x64 enabled from startup — flipping
        # enable_x64 mid-process destabilizes this jaxlib after a long
        # compile history (see tests/test_classify.py's u64 parity test)
        rows.extend(_u64_rows())
    return rows


def _u64_rows() -> list:
    import json as _json
    import os
    import subprocess
    import sys

    proc = subprocess.run(
        [sys.executable, "-m", "benchmarks.sort_classifier"],
        # CPU child (the parent may hold the chip); its rows say so
        env=dict(os.environ, JAX_ENABLE_X64="1", SORT_CLASSIFIER_U64="1",
                 JAX_PLATFORMS="cpu"),
        capture_output=True,
        text=True,
        timeout=1200,
    )
    if proc.returncode != 0:
        print(f"# u64 cell failed in subprocess:\n{proc.stderr[-2000:]}")
        return []
    return [dict(r, backend="cpu") for r in _json.loads(proc.stdout.splitlines()[-1])]


if __name__ == "__main__":
    import os

    if os.environ.get("SORT_CLASSIFIER_U64"):
        # child mode (x64 on from startup): one u64 cell, rows as JSON
        import json as _json
        import tempfile

        pc = PlanCache(path=os.path.join(tempfile.mkdtemp(), "clf_plans.json"))
        print(_json.dumps(_bench_cell("Uniform", jnp.uint64, SIZES[0], pc)))
    else:
        from benchmarks.common import emit
        emit(run(), ["bench", "clf", "dist", "dtype", "n", "s_per_call",
                     "ns_per_elem", "pass_ns_per_elem", "classify_ns_per_elem",
                     "speedup"])
