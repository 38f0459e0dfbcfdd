"""Layer-by-layer timing of one ``ops.sort`` at the engine's 2^24 limit.

Each layer of the sort is jitted alone and timed on the input it sees
inside a real sort: the upstream layers run once, untimed, to make it.
Keys are uniform f32 (in the keyspace: u32) with an int32 payload; the
config is ``SortConfig()``'s, whose plan at 2^24 is two levels of k = 128.

    PYTHONPATH=src python -m benchmarks.sort_layers [--n N] [--out FILE]

Prints one ``<layer>: <ms> ms`` line per layer (median of 3 warm calls,
host clock around ``block_until_ready``) and writes them as JSON to
``--out`` (default ``chiprun_out/sort_layers.json``).  These are times of
layers run alone, not a profiler trace: a layer inside the whole program
may fuse or overlap differently, and the device's idle share is not seen.
On the CPU backend the Pallas rows run in interpret mode and say nothing
about the chip.  Not registered in ``benchmarks.run``: it is a chip tool,
not part of the CPU perf gate.

What each row covers:

  level 1 xla / pallas     ``level_pass``: sample, classify, stable
                           partition of keys and payload
  level_fused              the fused level kernel and its prefix epilogue
                           (destinations + offsets), no payload move
  classify xla             the branchless tree classifier alone
  stable_partition xla /   the placement (per-tile argsort + gather, or
  pallas                   ``rank_hist`` + scatter) of keys and payload
  level 2                  ``segmented_level_pass``: its own
                           ``segment_ids``, per-segment sample, classify
                           and the (XLA) composite partition
  segment_ids              one ``segment_ids`` call on level 2's offsets,
                           as run again before the base case
  base_case                both window passes over keys and payload
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np

from repro.classify.tree import classify
from repro.core import sampling
from repro.core.ips4o import (
    SortConfig,
    _auto_tile,
    base_case,
    level_pass,
    plan_levels,
    segment_ids,
    segmented_level_pass,
)
from repro.core.partition import stable_partition
from repro.data.distributions import make_input
from repro.kernels.level_fused import level_fused
from repro.ops import keyspace

REPS = 3


def ms(fn, *args):
    """Median ms per call of ``jit(fn)`` after one warm call, and the output."""
    compiled = jax.jit(fn).lower(*args).compile()
    out = jax.block_until_ready(compiled(*args))
    ts = []
    for _ in range(REPS):
        t0 = time.perf_counter()
        jax.block_until_ready(compiled(*args))
        ts.append(time.perf_counter() - t0)
    return round(statistics.median(ts) * 1e3, 3), out


def layers(n: int = 1 << 24, cfg: SortConfig = SortConfig()) -> dict:
    """{layer: ms} for one ``n``-key sort under ``cfg``'s level plan."""
    levels = plan_levels(n, cfg)
    x = jax.device_put(make_input("Uniform", n, np.float32, seed=1))
    v = jax.device_put(np.arange(n, dtype=np.int32))
    rows = {}
    rows["jnp.sort"], _ = ms(jnp.sort, x)
    rows["jnp.argsort stable"], _ = ms(lambda a: jnp.argsort(a, stable=True), x)
    rows["keyspace encode"], enc = ms(keyspace.encode, x)
    perm = jax.device_put(np.random.default_rng(2).permutation(n).astype(np.int32))
    rows["gather jnp.take, random indices"], _ = ms(lambda a, p: jnp.take(a, p), enc, perm)
    rows["scatter .at[].set, random indices"], _ = ms(
        lambda a, p: jnp.zeros_like(a).at[p].set(a, mode="promise_in_bounds"), enc, perm)

    k1 = levels[0]
    nb1 = 2 * k1 + 1
    r1, r2 = jax.random.split(jax.random.PRNGKey(cfg.seed))
    arrays = {"k": enc, "v": v}
    for eng in ("xla", "pallas"):
        c = replace(cfg, engine=eng)
        rows[f"level 1 {eng} k={k1}"], lvl1 = ms(
            lambda a, c=c: level_pass(a, n, k1, c, r1)[:2], arrays)

    m1 = min(max(sampling.oversampling_factor(n) * k1, k1), cfg.max_sample, n)
    sample = jnp.sort(jnp.take(enc, jax.random.randint(r1, (m1,), 0, n)))
    spl = sampling.select_splitters(sample, k1)
    rows[f"level_fused tree k={k1}"], _ = ms(
        lambda a, s: level_fused(a, s, k=k1, n_real=n), enc, spl)
    rows[f"classify xla k={k1}"], b = ms(lambda a, s: classify(a, s, k1), enc, spl)
    for eng in ("xla", "pallas"):
        rows[f"stable_partition {eng} nb={nb1}"], _ = ms(
            lambda bb, a, eng=eng: stable_partition(
                bb, a, nb1, _auto_tile(n, nb1, cfg), engine=eng)[0], b, arrays)

    arrays, off = lvl1
    if len(levels) == 2:
        k2 = levels[1]
        rows[f"level 2 (segmented_level_pass) k={k2}"], lvl2 = ms(
            lambda a, o: segmented_level_pass(a, o, nb1, n, k2, cfg, r2)[:2], arrays, off)
        arrays, off = lvl2
    rows["segment_ids"], fb = ms(lambda o: segment_ids(o, n), off)
    rows[f"base_case W={cfg.base_case}, keys + payload"], _ = ms(
        lambda a, f: base_case(a, f, cfg.base_case), arrays, fb)
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--n", type=int, default=1 << 24)
    ap.add_argument("--out", default=os.path.join("chiprun_out", "sort_layers.json"))
    args = ap.parse_args(argv)
    dev = jax.devices()[0]
    rows = layers(args.n)
    for name, t in rows.items():
        print(f"{name}: {t} ms", flush=True)
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump({"n": args.n, "device": {"platform": dev.platform, "kind": dev.device_kind},
                   "ms": rows}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
