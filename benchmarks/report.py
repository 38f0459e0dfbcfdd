"""Render BENCH json into a markdown perf dashboard.

``python -m benchmarks.report`` turns the committed ``BENCH_sort.json``
baseline (and, when given ``--fresh``, a just-produced run) into one
markdown document: a table per bench module, with tracked wall-clock
metrics annotated by their committed-vs-fresh delta.  CI renders it next
to the perf gate and uploads it as an artifact, so a PR's perf story is
readable without parsing JSON.

Matching and "tracked metric" rules are imported from
``benchmarks.check_regression`` — the dashboard and the gate can never
disagree about which rows correspond or which columns matter.
"""
from __future__ import annotations

import argparse
import json
import sys
from typing import Any, Dict, List, Optional

from benchmarks.check_regression import is_tracked_metric, row_identity

_FLOAT_FIELDS_SI = ("hlo_flops", "hlo_bytes")


def _fmt(field: str, v: Any) -> str:
    if v is None or v == "":
        return ""
    if field.endswith("_bytes") or field in _FLOAT_FIELDS_SI:
        try:
            x = float(v)
        except (TypeError, ValueError):
            return str(v)
        for unit in ("", "K", "M", "G", "T"):
            if abs(x) < 1024:
                return f"{x:.1f}{unit}" if unit else f"{x:.0f}"
            x /= 1024
        return f"{x:.1f}P"
    if isinstance(v, float):
        return f"{v:g}"
    return str(v)


def _delta(base: Optional[float], fresh: float) -> str:
    if not base:
        return ""
    d = fresh / base - 1.0
    return f" ({d:+.0%})"


def render(
    baseline: Dict[str, Any], fresh: Optional[Dict[str, Any]] = None
) -> str:
    """Markdown for a baseline payload, deltas vs ``fresh`` when given.

    Each bench becomes a table whose columns are the union of its rows'
    fields (baseline order first).  When a fresh run contains a matching
    row (same identity under the gate's ``row_identity``), tracked
    metrics show the fresh value with the relative delta vs the
    committed baseline; fresh-only and baseline-only rows are counted in
    the per-bench caption.
    """
    benches: Dict[str, List[Dict]] = baseline.get("benches") or {}
    fresh_benches: Dict[str, List[Dict]] = (fresh or {}).get("benches") or {}
    fresh_rows = {
        row_identity(b, r): r for b, rows in fresh_benches.items() for r in rows
    }
    lines = ["# Benchmark report", ""]
    meta = [f"baseline backend: `{baseline.get('backend', '?')}`",
            f"generated: {baseline.get('generated_at', '?')}"]
    if fresh:
        meta.append(f"fresh run: {fresh.get('generated_at', '?')} "
                    f"(`{fresh.get('backend', '?')}`)")
    lines += ["; ".join(meta), ""]
    if not benches and not fresh_benches:
        # an empty trajectory (fresh checkout, aborted run, hand-pruned
        # json) is a valid dashboard — say so instead of rendering nothing
        lines += ["*(empty trajectory: no benches recorded — run "
                  "`python -m benchmarks.run` to populate)*", ""]
        return "\n".join(lines) + "\n"
    for bench in sorted(set(benches) | set(fresh_benches)):
        rows = benches.get(bench, [])
        extra = [
            r for b, rs in fresh_benches.items() if b == bench for r in rs
            if row_identity(b, r) not in {row_identity(bench, x) for x in rows}
        ]
        lines.append(f"## {bench}")
        if not rows and not extra:
            lines += ["(no rows)", ""]
            continue
        fields: List[str] = []
        for r in rows + extra:
            for k in r:
                if k not in fields:
                    fields.append(k)
        matched = 0
        body = []
        for r in rows:
            fr = fresh_rows.get(row_identity(bench, r))
            matched += fr is not None
            cells = []
            for f in fields:
                v = r.get(f)
                if fr is not None and is_tracked_metric(f) and f in fr:
                    base_v = v if isinstance(v, (int, float)) else None
                    try:
                        fresh_v = float(fr[f])
                    except (TypeError, ValueError):
                        # non-numeric tracked cell (a crashed run wrote a
                        # marker string): show it verbatim, no delta
                        cells.append(_fmt(f, fr[f]))
                    else:
                        cells.append(_fmt(f, fr[f]) + _delta(base_v, fresh_v))
                else:
                    cells.append(_fmt(f, v))
            body.append("| " + " | ".join(cells) + " |")
        for r in extra:  # fresh-only rows (new bench cells, baseline-first)
            body.append(
                "| " + " | ".join(_fmt(f, r.get(f)) for f in fields) + " | *new*"
            )
        cap = f"{len(rows)} baseline row(s)"
        if fresh:
            cap += f", {matched} matched fresh, {len(extra)} fresh-only"
        lines += [
            cap, "",
            "| " + " | ".join(fields) + " |",
            "|" + "---|" * len(fields),
            *body, "",
        ]
    return "\n".join(lines) + "\n"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--baseline", default="BENCH_sort.json")
    ap.add_argument("--fresh", default=None,
                    help="optional fresh-run json to diff against the baseline")
    ap.add_argument("--out", default="BENCH_report.md")
    args = ap.parse_args(argv)

    with open(args.baseline) as fh:
        baseline = json.load(fh)
    fresh = None
    if args.fresh:
        try:
            with open(args.fresh) as fh:
                fresh = json.load(fh)
        except FileNotFoundError:
            print(f"no fresh run at {args.fresh}; rendering baseline only")
    md = render(baseline, fresh)
    with open(args.out, "w") as fh:
        fh.write(md)
    print(f"wrote {args.out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
