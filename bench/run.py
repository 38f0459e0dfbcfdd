#!/usr/bin/env python3
"""Run one benchmark cell once on the TPU it finds, and print its result.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout that holds ``BENCHMARK.json``, this
directory and the program (``src/repro``).  The cell names a configuration
(``bench/configs/<name>.json``, the file ``BENCHMARK.json`` gives) and a
traffic mix (``bench/traffic/<name>.json``); the configuration may name its
entry (``"entry"``, ``bench/entries/<name>.py``, by default ``orderby``);
its per-layer metrics are readers in ``bench/metrics/<name>.py``.  All are
found by name, so a cell, configuration, entry, mix or metric is added by
adding files and entries.

A run:

1. set-up: the entry draws its inputs from ``--seed`` on the cell's chips
   and builds its call (``load_entry``); the call is compiled once (JAX's
   persistent cache at ``<checkout>/.bench_cache/jax``), and one warm call
   is taken and its fetch awaited;
2. window: calls the entry back to back in a closed loop, one caller
   waiting on each result, cycling through its inputs in an order drawn
   from the seed, until a call ends ``--seconds`` after the first began;
   as each call ends the entry copies what is compared to the host, with
   positions drawn from the seed;
3. reads device memory, frees the program, and with ``--trace 1`` reduces
   the profiler trace of the window to the cell's per-layer metrics;
4. compares every call's output with the entry's plain reference
   (``reference.py``) and prints each number compared beside its limit.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics with
``--trace 0``, its per-layer metrics with ``--trace 1``), ``device``,
``breakdown`` (traced runs) and ``checks``.  With no TPU, or fewer chips
than the cell asks for, it prints no result and exits 2.
"""
from __future__ import annotations

import time

PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import re  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

import numpy as np  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import reference  # noqa: E402

# every JAX program of a run is cached here, at a path fixed in the
# checkout, so that only a cell's first run in a checkout compiles
CACHE_DIR = os.path.join(".bench_cache", "jax")
# output positions per call whose columns are copied out and compared
SAMPLE = 1 << 16


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


# -- what BENCHMARK.json names ----------------------------------------------


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def resolve(root: str, workload: str) -> dict:
    """The cell, its configuration, its traffic mix, its end-to-end metrics
    and the reader of each per-layer metric it reports, found by name under
    ``root`` (the checkout)."""
    bm = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {c["name"]: c for c in bm["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; expected one of {sorted(cells)}")
    cell = cells[workload]
    config = next(c for c in bm["configs"] if c["name"] == cell["config"])
    bench = os.path.join(root, "bench")
    e2e = [m for m in bm["end_to_end"] if workload in m.get("workloads", [workload])]
    reported = {m["name"] for m in e2e}
    per_layer = [
        m for m in bm["per_layer"]
        if (workload in m["workloads"] if "workloads" in m else m["moves"] in reported)
    ]
    cfg = load_json(os.path.join(root, config["file"]))
    return {
        "cell": cell,
        "config": cfg,
        "entry": load_entry(bench, cfg.get("entry", "orderby")),
        "traffic": load_json(os.path.join(bench, "traffic", cell["traffic"] + ".json")),
        "end_to_end": e2e,
        "per_layer": [(m, load_reader(bench, m["name"])) for m in per_layer],
    }


def _load(bench: str, kind: str, name: str):
    path = os.path.join(bench, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_" + re.sub(r"\W", "_", name), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(bench: str, name: str):
    """``bench/metrics/<name>.py``, which defines ``read(trace, ctx)``."""
    return _load(bench, "metrics", name).read


def load_entry(bench: str, name: str):
    """``bench/entries/<name>.py``, the program's entry a configuration runs.

    It defines ``validate(config)``, which raises ``ValueError`` for a
    configuration whose guarantees its reference cannot judge (called
    before JAX loads), and ``setup(jax, config, traffic, devices, seed)``,
    which draws the inputs on the cell's ``devices`` and returns a dict:

    - ``inputs``: one tuple of arguments per table; the window cycles
      through them;
    - ``call``: the jitted entry, called as ``call(*inputs[t])``;
    - ``fetch(out, pos)``: what of one call's output is compared, a tuple
      of device or host arrays, ``pos`` the output positions drawn for it;
      the window copies each to the host, the warm-up only waits for them;
    - ``rows``: rows a call orders (``rows_per_s``, ``ctx["rows"]``, the
      range of ``pos``);
    - ``in_bytes``: one call's input bytes on each device, in the order of
      ``devices`` (the denominator of ``hbm_x``);
    - ``compare(outputs, calls)``: the reference's numbers over
      ``outputs``, a list of ``(input index, fetched, on the host)`` in call
      order, and
      the calls that broke a limit or never came;
    - ``limits``: each number's limit, compared as ``value <= limit``.
    """
    return _load(bench, "entries", name)


# -- set-up ----------------------------------------------------------------


def configure_jax(root: str):
    """Import JAX with the persistent compile cache in the checkout and
    source paths named relative to it (a Pallas kernel's serialized body
    keeps its source locations, which the cache key would otherwise hold
    as absolute paths)."""
    import jax

    cache = os.path.join(root, CACHE_DIR)
    os.makedirs(cache, exist_ok=True)  # JAX writes no entry into a missing directory
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_compilation_cache_max_size", -1)  # no eviction: a few entries
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_hlo_source_file_canonicalization_regex",
                      "^" + re.escape(root + os.sep))
    return jax


def peaks(device_kind: str) -> dict:
    """The chip's published peaks (``peaks.json``); a kind the table lacks
    is an error, never a default."""
    table = load_json(os.path.join(BENCH, "peaks.json"))["chips"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: {sorted(table)}")
    return table[device_kind]


def chips_or_fail(jax, chips: int):
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found platform {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX found {len(devs)}")
    return devs[:chips]


def memory_stats(devices) -> list:
    """``memory_stats()`` of each device; a backend without them is an
    error, not a zero."""
    out = []
    for d in devices:
        s = d.memory_stats()
        if not s or "peak_bytes_in_use" not in s:
            raise RuntimeError(f"device {d} reports no memory_stats")
        out.append(s)
    return out


def peak_bytes(stats: dict) -> int:
    """A device's high-water mark: buffers in use plus the scratch the TPU
    runtime reserves for running programs (``peak_bytes_reserved``, where
    a program's temporaries live; ``peak_bytes_in_use`` leaves them out)."""
    return stats["peak_bytes_in_use"] + stats.get("peak_bytes_reserved", 0)


# -- one run ---------------------------------------------------------------


def percentile(xs, q: float) -> float:
    """Nearest-rank percentile."""
    s = sorted(xs)
    return s[max(0, min(len(s) - 1, int(np.ceil(q / 100 * len(s))) - 1))]


def run_cell(root: str, workload: str, seed: int, seconds: float, trace: bool, *,
             process_start: float = PROCESS_START, rows: int = 0, require_tpu: bool = True,
             memory=memory_stats, wrap=None) -> dict:
    """One run of one cell; returns the result object.

    ``rows``, ``require_tpu=False``, ``memory`` and ``wrap`` (a function
    that wraps the jitted entry) exist for the harness's own tests, which
    drive a run on the CPU at a small size with the timed path broken."""
    spec = resolve(root, workload)
    config, traffic = dict(spec["config"]), spec["traffic"]
    spec["entry"].validate(config)
    if rows:
        config["rows"] = rows
    chips = spec["cell"]["chips"]

    os.environ.pop("REPRO_OBS", None)  # the program's own spans stay off
    with tempfile.TemporaryDirectory(prefix="bench-") as tmp:
        # a fresh plan cache: no persisted plan steers engine="auto", and
        # no autotune sweep runs
        os.environ["REPRO_OPS_PLAN_CACHE"] = os.path.join(tmp, "plans.json")
        # the TPU runtime logs to /tmp/tpu_logs unless told otherwise
        os.environ.setdefault("TPU_LOG_DIR", os.path.join(tmp, "tpu_logs"))
        jax = configure_jax(root)
        if require_tpu:
            devices = chips_or_fail(jax, chips)
        else:
            devices = jax.devices()[:chips]
        sys.path.insert(0, os.path.join(root, "src"))
        return _run(jax, spec, config, traffic, devices, seed, seconds, trace, tmp,
                    process_start, memory, wrap)


def _run(jax, spec, config, traffic, devices, seed, seconds, trace, tmp,
         process_start, memory, wrap):
    entry = spec["entry"].setup(jax, config, traffic, devices, seed)
    f, fetch, inputs, n = entry.pop("call"), entry["fetch"], entry["inputs"], entry["rows"]
    if wrap is not None:
        f = wrap(f)
    rng = np.random.default_rng(seed)
    jax.block_until_ready(inputs)
    before = memory(devices)
    f = f.lower(*inputs[0]).compile()  # or loads it from the cache
    # warms both; nothing is copied to the host here (on a TPU v5e a host
    # copy in the warm-up slowed the window's own copies)
    jax.block_until_ready(fetch(f(*inputs[0]), rng.integers(0, n, SAMPLE, dtype=np.int32)))

    profile_dir = os.path.join(tmp, "profile")
    if trace:
        jax.profiler.start_trace(profile_dir)
    lat, outputs = [], []
    t_start = time.perf_counter()
    setup_s = t_start - process_start
    with jax.profiler.TraceAnnotation("bench.window"):
        # the inputs in an order drawn from the seed, pass after pass,
        # until a call ends --seconds after the first began
        while not lat or t_done - t_start < seconds:
            for t in rng.permutation(len(inputs)):
                pos = rng.integers(0, n, SAMPLE, dtype=np.int32)
                t0 = time.perf_counter()
                with jax.profiler.TraceAnnotation("bench.call"):
                    out = jax.block_until_ready(f(*inputs[t]))
                t_done = time.perf_counter()
                lat.append(t_done - t0)
                with jax.profiler.TraceAnnotation("bench.fetch"):
                    outputs.append((int(t), tuple(np.asarray(a) for a in fetch(out, pos))))
                del out
                if t_done - t_start >= seconds:
                    break
    window_s = t_done - t_start
    if trace:
        jax.profiler.stop_trace()

    after = memory(devices)
    hlo = f.as_text()
    del f  # the reference runs with the program freed
    calls = len(lat)
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(jax.devices()),
              "memory_peak_bytes": max(peak_bytes(a) for a in after)}

    metrics, extra = {}, {}
    if trace:
        import devtrace

        tr = devtrace.load(profile_dir, [d.id for d in devices])
        device["busy_s"] = tr.busy_s()
        device["window_s"] = tr.window_s()
        ctx = {"calls": calls, "rows": n, "peaks": peaks(dev.device_kind),
               "op_names": devtrace.op_names(hlo)}
        for m, read in spec["per_layer"]:
            v = read(tr, ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        extra["breakdown"] = tr.breakdown(ctx["op_names"])
    else:
        values = {
            "rows_per_s": n * calls / window_s,
            "call_ms_p95": percentile(lat, 95) * 1e3,
            # the fullest device, each against the input bytes it holds
            "hbm_x": max((peak_bytes(a) - b["bytes_in_use"]) / in_bytes
                         for a, b, in_bytes in zip(after, before, entry["in_bytes"])),
            "setup_s": setup_s,
        }
        for m in spec["end_to_end"]:
            metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}

    nums, failed = entry["compare"](outputs, calls)
    limits = entry["limits"]
    return {
        "correct": reference.verdict(nums, limits),
        "attempted": calls,
        "failed": failed,
        "metrics": metrics,
        "device": device,
        **extra,
        "memory": {"before": before, "after": after},
        "window": {"calls": calls, "seconds": window_s,
                   "call_ms_median": statistics.median(lat) * 1e3,
                   "call_ms": [x * 1e3 for x in lat]},
        "checks": {k: {"value": nums[k], "limit": lim} for k, lim in limits.items()},
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        res = run_cell(ROOT, args.workload, args.seed, args.seconds, bool(args.trace))
    except NoChip as e:
        print(e, file=sys.stderr)
        return 2
    for k, c in res["checks"].items():
        print(f"{k} {c['value']} limit {c['limit']}", file=sys.stderr)
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
