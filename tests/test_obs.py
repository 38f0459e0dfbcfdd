"""repro.obs test suite (DESIGN.md §12).

The contract under test, in order of importance:

  * **disabled is free**: with obs off (the default), entry points trace
    to bit-identical jaxprs (zero added ops, no effects), ``trace()``
    returns one shared allocation-free null span, and the compiled hot
    path is untouched — the same executable runs before and after an
    enable/disable round-trip;
  * **enabled is structured**: eager sorts record properly nested
    sample/classify/partition/base-case spans under the op root, in-jit
    functional stats (base-case counts, bucket imbalance) arrive through
    unordered debug callbacks, and the host-side counters (plan cache,
    launch specs, stream spills, scheduler admissions) tick at their
    call sites;
  * **exports are valid**: the JSONL lines are typed records, the Chrome
    trace-event file is schema-correct (Perfetto-loadable), and
    ``summary()`` renders.

jax caveat encoded here: ``jax.make_jaxpr`` (and jit) cache traces by
function identity, so every trace after an ``obs.enabled`` toggle uses a
FRESH lambda — re-tracing the same function object would return the
stale cached jaxpr (see ``obs.enabled``'s docstring).
"""
import json
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import obs, ops
from repro.core.ips4o import SortConfig

# small geometry so a level pass + base case engage at test sizes
_CFG = SortConfig(base_case=1024, tile=512, max_sample=1024)
_N = 4096


@pytest.fixture(autouse=True)
def _obs_clean():
    obs.enabled(False)
    obs.reset()
    yield
    obs.enabled(False)
    obs.reset()


def _keys(n=_N, seed=0):
    return jnp.asarray(
        np.random.default_rng(seed).standard_normal(n), jnp.float32
    )


# -- disabled: zero cost ----------------------------------------------------


def test_disabled_adds_zero_traced_ops():
    """The jaxpr-identity proof: obs off adds nothing to traced code, and
    an enable/disable round-trip returns to the identical jaxpr."""
    x = _keys()
    base = jax.make_jaxpr(lambda a: ops.sort(a, cfg=_CFG))(x)
    assert "debug_callback" not in str(base)
    assert not base.effects
    obs.enabled(True)
    inst = jax.make_jaxpr(lambda a: ops.sort(a, cfg=_CFG))(x)
    assert "debug_callback" in str(inst)
    obs.enabled(False)
    again = jax.make_jaxpr(lambda a: ops.sort(a, cfg=_CFG))(x)
    assert str(again) == str(base)
    assert not again.effects


def test_disabled_layer_adds_no_equation_and_records_no_span():
    """``obs.layer`` with obs off is a named scope and nothing more: the
    jaxpr is the one without it, no span is recorded, and the scope
    reaches the lowered program's op names."""
    x = _keys(8)

    def plain(a):
        return a * 2.0 + 1.0

    def scoped(a):
        with obs.layer("sort.level1", k=4):
            return plain(a)

    assert str(jax.make_jaxpr(scoped)(x)) == str(jax.make_jaxpr(plain)(x))
    assert obs.recorder().spans == []
    assert "sort.level1/mul" in jax.jit(scoped).lower(x).as_text(debug_info=True)
    with pytest.raises(ValueError, match="unknown layer"):
        obs.layer("level_pass")
    obs.enabled(True)
    with obs.layer("sort.base_case", W=8):
        pass
    assert [s["name"] for s in obs.recorder().spans] == ["sort.base_case"]


def test_disabled_null_span_is_shared_and_recorder_untouched():
    s1 = obs.trace("a")
    s2 = obs.trace("b", attr=1)
    assert s1 is s2  # one shared null instance: no per-call allocation
    with obs.trace("c"):
        pass
    assert obs.recorder().spans == []
    assert obs.recorder().counters == {}


def test_disabled_span_overhead_budget():
    t0 = time.perf_counter()
    for _ in range(10_000):
        with obs.trace("x", a=1):
            pass
    dt = time.perf_counter() - t0
    # generous CI budget: < 5us per disabled span (measured ~0.1us)
    assert dt < 0.05, f"disabled trace() too slow: {dt * 100:.1f}us/span"


def test_disabled_toggle_keeps_compiled_fn_fast():
    """An enabled->disabled round-trip must not slow the already-compiled
    hot path: the call reuses the same executable (no retrace), and a
    fresh trace stages the same jaxpr as before the round-trip."""
    x = _keys(1 << 16)
    traces = []

    def sort(a):
        traces.append(1)
        return ops.sort(a, cfg=_CFG)

    def fresh_jaxpr():  # a new function object: never a trace-cache hit
        return str(jax.make_jaxpr(lambda a: ops.sort(a, cfg=_CFG))(x))

    f = jax.jit(sort)
    out0 = jax.block_until_ready(f(x))
    jaxpr0 = fresh_jaxpr()
    obs.enabled(True)
    obs.enabled(False)
    out1 = jax.block_until_ready(f(x))
    assert len(traces) == 1  # the compiled executable was reused
    assert fresh_jaxpr() == jaxpr0
    np.testing.assert_array_equal(np.asarray(out1), np.asarray(out0))


# -- enabled: structure and metrics ----------------------------------------


def test_enabled_eager_sort_spans_nest():
    obs.enabled(True)
    x = _keys()
    out = ops.sort(x, cfg=_CFG)
    jax.effects_barrier()
    np.testing.assert_array_equal(np.asarray(out), np.sort(np.asarray(x)))
    spans = obs.recorder().spans
    names = {s["name"] for s in spans}
    assert {"sort", "ips4o_sort", "sort.level1", "sample", "classify",
            "partition", "move", "sort.segment_ids", "sort.base_case"} <= names
    by_id = {s["id"]: s for s in spans}
    root = next(s for s in spans if s["name"] == "sort")
    assert root["parent"] is None and root["depth"] == 0
    for child, parent in [("ips4o_sort", "sort"),
                          ("sort.level1", "ips4o_sort"),
                          ("sample", "sort.level1"),
                          ("classify", "sort.level1"),
                          ("partition", "sort.level1"),
                          ("move", "partition"),
                          ("sort.segment_ids", "ips4o_sort"),
                          ("sort.base_case", "ips4o_sort")]:
        s = next(s for s in spans if s["name"] == child)
        assert by_id[s["parent"]]["name"] == parent, (child, parent)
        assert s["dur_ns"] >= 0


def test_enabled_jit_runtime_metrics():
    """In-jit functional stats travel through unordered debug callbacks:
    base-case count and bucket-imbalance histogram survive jit."""
    obs.enabled(True)
    jax.clear_caches()  # jits traced while disabled carry no obs hooks
    try:
        x = _keys()
        out = jax.jit(lambda a: ops.sort(a, cfg=_CFG))(x)
        jax.block_until_ready(out)
        jax.effects_barrier()
        np.testing.assert_array_equal(np.asarray(out), np.sort(np.asarray(x)))
        assert obs.counter_value("sort.base_case") >= 1
        imb = obs.hist_values("sort.bucket_imbalance")
        assert imb, "bucket imbalance histogram empty"
        assert all(v >= 1.0 for v in imb)  # max/mean is >= 1 by construction
    finally:
        jax.clear_caches()


def test_plan_cache_and_launch_spec_counters(tmp_path):
    from repro.launch.roofline import launch_spec
    from repro.ops.plan import PlanCache

    obs.enabled(True)
    cache = PlanCache(path=str(tmp_path / "plans.json"))
    f = cache.get_sorter(_N, jnp.float32)
    g = cache.get_sorter(_N, jnp.float32)
    assert f is g
    assert obs.counter_value("plan_cache.miss", family="sort") >= 1
    assert obs.counter_value("plan_cache.compiled_miss") == 1
    assert obs.counter_value("plan_cache.compiled_hit") == 1
    spec = launch_spec("classify", 4, 128)
    assert spec.rows > 0
    assert obs.counter_value("launch.spec", kind="classify") == 1
    # rows=0 (XLA fallback) is recorded too, distinguishably
    launch_spec("classify", 4, 128, n=1000)
    assert obs.counter_value("launch.spec", kind="classify", rows="0") == 1


def test_stream_metrics():
    from repro.stream import external_sort

    obs.enabled(True)
    data = np.random.default_rng(1).integers(0, 1 << 20, 4096).astype(np.int32)
    out = external_sort(data, chunk_size=1024)
    np.testing.assert_array_equal(out, np.sort(data))
    # 4 runs -> 2 tournament rounds; each merged pair spills to host
    assert obs.counter_value("stream.tournament_rounds") == 2
    assert obs.counter_value("stream.spill_bytes") > 0
    rounds = [s for s in obs.recorder().spans if s["name"] == "stream.merge_round"]
    assert len(rounds) == 2
    root = next(s for s in obs.recorder().spans
                if s["name"] == "stream.external_sort")
    by_id = {s["id"]: s for s in obs.recorder().spans}
    assert all(by_id[r["parent"]]["name"] == "stream.external_sort"
               for r in rounds)
    assert root["attrs"]["chunks"] == 4


def test_scheduler_metrics():
    from repro.serve.scheduler import Request, Scheduler

    obs.enabled(True)
    s = Scheduler(batch_size=2)
    for i in range(4):
        s.submit(Request(uid=i, prompt_len=1, max_new=10 - i))
    batch = s.next_batch()
    assert [r.uid for r in batch] == [3, 2]  # shortest remaining first
    assert obs.counter_value("serve.admitted") == 2
    assert any(sp["name"] == "serve.next_batch"
               for sp in obs.recorder().spans)


def test_timed_min_records_even_while_disabled():
    rec = obs.Recorder()
    calls = []
    t = obs.timed_min("phase:x", lambda: calls.append(1),
                      iters=3, warmup=1, recorder=rec, n=_N)
    assert t >= 0.0
    spans = [s for s in rec.spans if s["name"] == "phase:x"]
    assert len(spans) == 3
    assert len(calls) == 4  # 1 warmup + 3 timed
    assert {s["attrs"]["iter"] for s in spans} == {0, 1, 2}
    assert obs.recorder().spans == []  # the global recorder stays clean


# -- exporters --------------------------------------------------------------


def test_exporters_and_summary(tmp_path):
    obs.enabled(True)
    x = _keys()
    ops.sort(x, cfg=_CFG)  # eager: callbacks fire synchronously
    jax.effects_barrier()

    jl = tmp_path / "t.jsonl"
    obs.export_jsonl(str(jl))
    lines = [json.loads(ln) for ln in jl.read_text().splitlines() if ln]
    kinds = {ln["type"] for ln in lines}
    assert {"span", "counter", "histogram"} <= kinds
    for ln in lines:
        if ln["type"] == "span":
            assert isinstance(ln["ts_us"], float)
            assert isinstance(ln["dur_us"], float) and ln["dur_us"] >= 0
            assert isinstance(ln["attrs"], dict)

    ct = tmp_path / "t.trace.json"
    obs.export_chrome_trace(str(ct))
    trace = json.loads(ct.read_text())
    assert trace["displayTimeUnit"] == "ms"
    evs = trace["traceEvents"]
    assert any(e["ph"] == "X" for e in evs)
    for e in evs:
        assert e["ph"] in ("M", "X", "i", "C")
        assert "name" in e and "pid" in e
        if e["ph"] == "X":
            assert e["dur"] >= 0 and isinstance(e["ts"], float)
    # span names survive into the chrome trace
    assert {"sort", "sort.level1"} <= {
        e["name"] for e in evs if e["ph"] == "X"
    }

    s = obs.summary()
    assert "sort.level1" in s and "spans" in s


# -- layer scopes in the compiled program ------------------------------------

# two levels (kmax 16 at base_case 256 and n 8,192), fallback kept: both
# branches of the base case's cond are compiled
_LAYER_CFG = SortConfig(kmax=16, base_case=256)
_LAYER_N = 8192


def _bench_module(name):
    import importlib
    import os
    import sys

    bench = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "bench")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    return importlib.import_module(name)


def _layer_program():
    from repro.core.ips4o import plan_levels

    assert len(plan_levels(_LAYER_N, _LAYER_CFG)) == 2 and _LAYER_CFG.fallback
    rng = np.random.default_rng(0)
    k = jnp.asarray(rng.integers(0, 1000, _LAYER_N), jnp.int32)
    v = (jnp.arange(_LAYER_N, dtype=jnp.int32), (jnp.zeros(_LAYER_N, jnp.uint32),))
    return jax.jit(lambda k, v: ops.sort(k, v, engine="xla", cfg=_LAYER_CFG)).lower(k, v)


def test_every_sort_op_carries_a_layer_scope():
    """Every op the sort's trace gives an ``op_name`` names one of
    ``obs.LAYERS``, every layer shows up, and the benchmark's scope
    reader gives each op a part of the sort.  Ops XLA makes itself carry
    no ``jit(...)`` root and are left out."""
    devtrace, scopes = _bench_module("devtrace"), _bench_module("scopes")
    names = devtrace.op_names(_layer_program().compile().as_text())
    traced = [n for n in names.values() if n.startswith("jit(")]
    assert traced
    seen = set()
    for name in traced:
        path = name.split(";")[0].split("/")[:-1]
        assert set(path) & set(obs.LAYERS), name
        seen |= set(path)
        assert scopes.part(name) is not None, name
    assert {"sort.level1", "sort.segment_ids", "sort.level2", "sort.base_case"} <= seen
    assert "sort.payload" in seen  # two payload words a row: deferred
    parts = {scopes.part(n) for n in traced}
    assert {"entry", "level1", "level1_move", "segment_ids", "level2", "level2_move",
            "base_case", "fallback", "fallback_move"} <= parts


def _strip_debug(hlo: str) -> str:
    """A compiled program's text without its debug information: op
    metadata and the source-location tables."""
    import re

    hlo = re.sub(r", metadata=\{[^}]*\}", "", hlo)
    return "\n".join(ln for ln in hlo.splitlines()
                     if not re.match(r'^\d+ (\{file_name_id|")', ln))


def test_layer_scopes_change_only_metadata(monkeypatch):
    """The compiled program with the layer scopes equals the one without
    them once the metadata is stripped: the scopes cost nothing at run
    time."""
    import contextlib

    with_scopes = _layer_program().compile().as_text()
    monkeypatch.setattr(obs, "layer", lambda name, **attrs: contextlib.nullcontext())
    without = _layer_program().compile().as_text()
    assert "sort.level1" in with_scopes and "sort.level1" not in without
    assert _strip_debug(with_scopes) == _strip_debug(without)
