"""Rendering contract for the perf dashboard (benchmarks/report.py).

The dashboard shares row-matching and tracked-metric rules with the perf
gate (tests/test_perf_gate.py covers those); this file pins the rendering
itself — above all that degenerate inputs (an empty trajectory, an empty
bench family, a crashed run's non-numeric metric cell) render an explicit
message instead of crashing or silently emitting nothing.
"""
from benchmarks.report import render

_ROW = {"name": "sort", "n": 1 << 20, "s_per_call": 1.0}


def test_empty_trajectory_renders_explicit_message():
    for payload in ({}, {"benches": {}}, {"benches": None}):
        md = render(payload)
        assert "empty trajectory" in md, payload
        assert md.startswith("# Benchmark report")


def test_empty_bench_family_says_no_rows():
    md = render({"benches": {"sort_ops": []}})
    assert "## sort_ops" in md and "(no rows)" in md


def test_non_numeric_tracked_cell_renders_without_delta():
    base = {"benches": {"b": [dict(_ROW)]}}
    fresh = {"benches": {"b": [{**_ROW, "s_per_call": "crashed"}]}}
    md = render(base, fresh)  # must not raise on float("crashed")
    assert "crashed" in md
    assert "%" not in md.split("crashed")[1].split("|")[0]  # no delta suffix


def test_matched_row_shows_tracked_delta():
    base = {"benches": {"b": [dict(_ROW)]}}
    fresh = {"benches": {"b": [{**_ROW, "s_per_call": 2.0}]}}
    md = render(base, fresh)
    assert "(+100%)" in md


def test_fresh_only_row_is_marked_new():
    base = {"benches": {"b": [dict(_ROW)]}}
    fresh = {"benches": {"b": [dict(_ROW), {**_ROW, "n": 1 << 10}]}}
    md = render(base, fresh)
    assert "*new*" in md and "1 fresh-only" in md
