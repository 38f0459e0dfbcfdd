"""CI perf-regression gate: fresh quick-bench run vs the committed baseline.

``python -m benchmarks.check_regression --fresh BENCH_fresh.json`` compares
every time-like metric of the fresh run against the committed
``BENCH_sort.json`` baseline and exits non-zero when any tracked metric
slowed down by more than the threshold (default 25%) — so the perf
trajectory the bench history establishes cannot silently regress.

Matching and tracking rules:

  * rows are keyed per bench module by their *identity fields* — every
    field that is neither a tracked (time-like) metric nor a derived one
    (speedup / ratio / Meps / byte counts), e.g. (bench, algo, n, dtype,
    engine);
  * tracked metrics are lower-is-better wall-clock fields:
    ``s_per_call``, ``*_us``, ``us``, ``*ns_per_elem``, ``t`` — except
    reference-implementation columns (``loop_us``, ``single_us``), whose
    variance is a comparison moving, not a product path regressing;
  * rows present in only one file are reported but never fail the gate
    (CI runs ``--quick --only <subset>``; new benches land baseline-first);
  * intentional regressions go in the allowlist
    (``benchmarks/regression_allowlist.json``): a list of entries with a
    ``reason`` and ``match`` dict of identity fields (subset match; an
    optional ``metric`` restricts to one metric) — matched failures
    downgrade to warnings.

Wall clocks are machine-relative; the gate compares runs from the same CI
runner class against a baseline refreshed whenever a PR intentionally
moves a number (regenerate via ``python -m benchmarks.run --quick --only
sort_sequential,sort_batched,sort_external,sort_distributed,sort_classifier``).
"""
from __future__ import annotations

import argparse
import json
import sys
from typing import Any, Dict, Iterable, List, Tuple

_TRACKED_EXACT = {"s_per_call", "us", "t"}
_TRACKED_SUFFIX = ("_us", "ns_per_elem")
# reference-implementation timings (the comparison column of a bench, e.g.
# loop-over-rows, the single-shot sort, or jnp.lexsort): their variance is
# not a product regression — the engine column of the same row is what the
# gate tracks
_REFERENCE_METRICS = {"loop_us", "single_us", "lexsort_us"}
# derived / environment fields: not metrics, not identity (the _bytes /
# _flops families are the static observability columns of compiled_cost)
_IGNORED_EXACT = {
    "speedup", "ratio", "meps", "speedup_vs_1dev", "backend"
} | _REFERENCE_METRICS
_IGNORED_SUFFIX = (
    "_meps", "_bytes", "_bytes_per_dev", "_per_dev", "_ratio", "_flops"
)


def is_tracked_metric(field: str) -> bool:
    if field in _REFERENCE_METRICS:
        return False
    return field in _TRACKED_EXACT or field.endswith(_TRACKED_SUFFIX)


def _is_identity(field: str) -> bool:
    if is_tracked_metric(field) or field in _IGNORED_EXACT:
        return False
    return not field.endswith(_IGNORED_SUFFIX)


def row_identity(bench: str, row: Dict[str, Any]) -> Tuple:
    return (bench,) + tuple(
        sorted((k, str(v)) for k, v in row.items() if _is_identity(k))
    )


def _metrics(row: Dict[str, Any]) -> Dict[str, float]:
    out = {}
    for k, v in row.items():
        if is_tracked_metric(k) and isinstance(v, (int, float)) and v > 0:
            out[k] = float(v)
    return out


def _allowed(entry_list: List[Dict], bench: str, row: Dict, metric: str) -> bool:
    for entry in entry_list:
        match = entry.get("match", {})
        if entry.get("bench") not in (None, bench):
            continue
        if entry.get("metric") not in (None, metric):
            continue
        if all(str(row.get(k)) == str(v) for k, v in match.items()):
            return True
    return False


def compare(
    baseline: Dict[str, List[Dict]],
    fresh: Dict[str, List[Dict]],
    threshold: float,
    allowlist: List[Dict],
) -> Tuple[List[str], List[str]]:
    """Returns (failures, warnings) — human-readable lines."""
    failures: List[str] = []
    warnings: List[str] = []
    base_rows = {
        row_identity(b, r): r for b, rows in baseline.items() for r in rows
    }
    fresh_rows = {
        row_identity(b, r): (b, r) for b, rows in fresh.items() for r in rows
    }
    for ident, (bench, row) in fresh_rows.items():
        base = base_rows.get(ident)
        if base is None:
            warnings.append(f"new row (no baseline): {ident}")
            continue
        base_m = _metrics(base)
        for metric, val in _metrics(row).items():
            ref = base_m.get(metric)
            if ref is None:
                continue
            slowdown = val / ref - 1.0
            if slowdown > threshold:
                line = (
                    f"{bench}: {metric} {ref:g} -> {val:g} "
                    f"(+{slowdown:.0%} > {threshold:.0%}) at "
                    + ", ".join(f"{k}={v}" for k, v in ident[1:])
                )
                if _allowed(allowlist, bench, row, metric):
                    warnings.append("allowlisted: " + line)
                else:
                    failures.append(line)
    for ident in base_rows:
        if ident not in fresh_rows and ident[0] in fresh:
            warnings.append(f"baseline row missing from fresh run: {ident}")
    return failures, warnings


def main(argv: Iterable[str] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--baseline", default="BENCH_sort.json")
    ap.add_argument("--fresh", default="BENCH_fresh.json")
    ap.add_argument("--threshold", type=float, default=0.25,
                    help="max tolerated slowdown fraction (0.25 = +25%%)")
    ap.add_argument("--allowlist", default="benchmarks/regression_allowlist.json")
    args = ap.parse_args(argv)

    try:
        with open(args.baseline) as fh:
            baseline = json.load(fh)
    except FileNotFoundError:
        print(f"no baseline at {args.baseline}: nothing to gate")
        return 0
    with open(args.fresh) as fh:
        fresh = json.load(fh)
    allowlist: List[Dict] = []
    try:
        with open(args.allowlist) as fh:
            allowlist = json.load(fh)
    except FileNotFoundError:
        pass

    failures, warnings = compare(
        baseline.get("benches", {}), fresh.get("benches", {}),
        args.threshold, allowlist,
    )
    for w in warnings:
        print("WARN", w)
    for f in failures:
        print("FAIL", f)
    if failures:
        print(f"\nperf gate: {len(failures)} regression(s) beyond "
              f"{args.threshold:.0%} — add an allowlist entry with a reason "
              f"if intentional ({args.allowlist})")
        return 1
    print(f"perf gate: OK ({len(warnings)} warnings)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
