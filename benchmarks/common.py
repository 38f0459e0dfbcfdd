"""Shared benchmark plumbing: stable timing on one CPU device + CSV rows.

Wall-clock numbers here are CPU-backend (this container has no TPU); they
are *relative* evidence (algorithm vs algorithm on identical hardware),
matching the paper's methodology of same-machine comparisons.  The TPU
roofline story lives in EXPERIMENTS.md §Roofline, derived from the
compiled dry-run instead of wall clocks.
"""
from __future__ import annotations

import json
import time
from typing import Any, Callable, Dict, Iterable, List

import jax
import numpy as np

__all__ = ["bench", "Row", "emit", "emit_json", "check_sorted", "compiled_cost"]

Row = Dict[str, Any]


def compiled_cost(fn: Callable[..., Any], *args: Any):
    """AOT-compile ``fn(*args)`` and capture its static cost profile.

    Returns ``(nullary, row)``: a nullary callable running the compiled
    executable (feed it to :func:`bench`) and a Row of observability
    columns — the XLA memory watermark (``mem_temp_bytes`` /
    ``mem_arg_bytes`` / ``mem_out_bytes`` / ``mem_peak_bytes``, from
    ``compiled.memory_analysis()``) and the analytic HLO cost
    (``hlo_flops`` / ``hlo_bytes``, via the same
    ``repro.launch.hlo_cost.analyze_hlo`` the roofline dry-run uses).
    Every column is gate-neutral (byte/flop suffixes are neither identity
    nor tracked metrics in check_regression); fields a backend doesn't
    report are simply absent.
    """
    compiled = jax.jit(fn).lower(*args).compile()
    row: Row = {}
    try:
        ma = compiled.memory_analysis()
    except Exception:
        ma = None
    if ma is not None:
        peak = 0
        for attr, col in (
            ("temp_size_in_bytes", "mem_temp_bytes"),
            ("argument_size_in_bytes", "mem_arg_bytes"),
            ("output_size_in_bytes", "mem_out_bytes"),
        ):
            v = getattr(ma, attr, None)
            if isinstance(v, (int, float)):
                row[col] = int(v)
                peak += int(v)
        if row:
            row["mem_peak_bytes"] = peak
    try:
        from repro.launch.hlo_cost import analyze_hlo

        cost = analyze_hlo(compiled.as_text())
        row["hlo_flops"] = float(cost.flops)
        row["hlo_bytes"] = float(cost.bytes)
    except Exception:
        pass
    return (lambda: compiled(*args)), row


def bench(
    fn: Callable[[], Any], *, warmup: int = 2, iters: int = 5, agg: str = "median"
) -> float:
    """Seconds/call of a nullary jitted callable (median by default).

    ``agg="min"`` is the noise-robust choice for dispatch-bound
    microbenchmarks on shared machines: the minimum is the cleanest
    observation of the actual cost, where a median still carries
    scheduler hiccups.
    """
    for _ in range(warmup):
        jax.block_until_ready(fn())
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        ts.append(time.perf_counter() - t0)
    return float(min(ts) if agg == "min" else np.median(ts))


def check_sorted(out_keys, in_keys) -> None:
    out = np.asarray(out_keys)
    assert np.all(out[:-1] <= out[1:]), "output not sorted"
    np.testing.assert_array_equal(np.sort(np.asarray(in_keys)), out)


def emit(rows: Iterable[Row], header: List[str]) -> None:
    print(",".join(header))
    for r in rows:
        print(",".join(str(r.get(h, "")) for h in header))


def emit_json(all_rows: Dict[str, List[Row]], path: str) -> None:
    """Write every bench's rows to one machine-readable JSON file, so the
    perf trajectory is trackable per PR (CI archives the artifact)."""
    payload = {
        "schema": 1,
        "backend": jax.default_backend(),
        "generated_at": time.strftime("%Y-%m-%dT%H:%M:%S"),
        "benches": all_rows,
    }
    with open(path, "w") as fh:
        json.dump(payload, fh, indent=1, sort_keys=True)
    print(f"wrote {path}")
