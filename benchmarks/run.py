"""Benchmark driver: one module per paper table/figure.

  sort_sequential    Fig. 6 / 16-19   sequential sizes x algos
  sort_distributions Fig. 8 / 9-11    nine input distributions
  sort_datatypes     Fig. 12-14       Pair / Quartet / 100Bytes payloads
  sort_scaling       Fig. 7 / 15      shard_map scaling (subprocess per d)
  io_volume          §4.5 / App. B    in-place vs out-of-place I/O volume
  moe_dispatch       framework role   sort-based vs one-hot MoE dispatch
  sort_ops           DESIGN.md §5     repro.ops: topk vs full sort, group_by
  sort_batched       DESIGN.md §6     batched (B, n) sort vs loop-over-rows
  sort_external      DESIGN.md §7     external_sort vs single-shot + merge
  sort_distributed   DESIGN.md §8     multi-level mesh sort, volume per level
  sort_classifier    DESIGN.md §9     classifier engines: tree/radix/learned/auto
  sort_records       DESIGN.md §11    workload zoo: string / composite records

``python -m benchmarks.run [--quick] [--only NAME[,NAME...]]`` prints one
CSV block per table plus a Table-1-style summary, and writes every row to
a machine-readable ``BENCH_sort.json`` (``--json PATH`` overrides) so
each PR's perf trajectory is diffable; ``--list`` prints the registered
suites and exits.
"""
from __future__ import annotations

import argparse
import sys
import time

MODULES = [
    "sort_sequential",
    "sort_distributions",
    "sort_datatypes",
    "sort_scaling",
    "io_volume",
    "moe_dispatch",
    "sort_ops",
    "sort_batched",
    "sort_external",
    "sort_distributed",
    "sort_classifier",
    "sort_records",
]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of benchmark modules")
    ap.add_argument("--json", default="BENCH_sort.json",
                    help="machine-readable output path ('' disables)")
    ap.add_argument("--list", action="store_true",
                    help="print the registered benchmark suites and exit")
    args = ap.parse_args(argv)

    if args.list:
        for name in MODULES:
            print(name)
        return 0

    import importlib
    import os
    import re

    import jax

    # the persistent compile cache: $JAX_COMPILATION_CACHE_DIR when set,
    # else one fixed directory in the checkout (a moving path never hits);
    # source locations relative to the checkout, because a Pallas kernel's
    # serialized body keeps them inside the cache key
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", os.path.join(root, ".jax_cache"))
    jax.config.update("jax_hlo_source_file_canonicalization_regex",
                      "^" + re.escape(root + os.sep))

    from benchmarks.common import emit, emit_json

    failures = 0
    all_rows = {}
    only = None
    if args.only:
        only = {s.strip() for s in args.only.split(",") if s.strip()}
        unknown = only - set(MODULES)
        if unknown:  # fail loudly: a typo must not silently drop a bench
            ap.error(f"--only: unknown module(s) {sorted(unknown)}; "
                     f"choose from {MODULES}")
    for name in MODULES:
        if only and name not in only:
            continue
        mod = importlib.import_module(f"benchmarks.{name}")
        t0 = time.perf_counter()
        print(f"\n== {name} ==", flush=True)
        try:
            rows = mod.run(quick=args.quick)
        except Exception as e:  # pragma: no cover
            import traceback
            traceback.print_exc()
            print(f"FAILED {name}: {type(e).__name__}: {e}")
            failures += 1
            continue
        all_rows[name] = rows
        if rows:
            emit(rows, list(rows[0].keys()))
        print(f"-- {name} done in {time.perf_counter() - t0:.1f}s", flush=True)

    if args.json and all_rows:
        emit_json(all_rows, args.json)

    # Table-1-style summary: our speedups vs library sort
    dist = all_rows.get("sort_distributions")
    if dist:
        sp = [r["speedup_vs_jnp"] for r in dist]
        print("\n== summary (Table 1 analogue) ==")
        print(f"is4o vs jnp.sort speedup: min={min(sp):.2f} "
              f"median={sorted(sp)[len(sp)//2]:.2f} max={max(sp):.2f}")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
