"""repro.obs — structured tracing, metrics, and profiling hooks.

Low-overhead observability for the whole sort engine (DESIGN.md §12).
Layer scopes are **always on**; recording is **off by default**.  Enable
recording with ``REPRO_OBS=1`` in the environment or ``obs.enabled(True)``
at runtime; while disabled every hook is a no-op that adds **zero traced
ops and no host syncs** (verified by the jaxpr-identity test in
``tests/test_obs.py``).

Quickstart::

    from repro import obs, ops

    obs.enabled(True)
    out = ops.sort(x)                      # spans + metrics recorded
    print(obs.summary())                   # human table
    obs.export_jsonl("sort.jsonl")         # machine archive
    obs.export_chrome_trace("sort.trace.json")  # open in Perfetto

Three layers:

* **Tracer** — ``obs.trace(name, **attrs)`` span context managers with
  host-side timing (callers hold ``block_until_ready`` discipline; see
  ``obs.block``/``obs.timed_min``) plus ``jax.profiler.TraceAnnotation``
  and ``jax.named_scope`` pass-through, so spans also land in XLA
  profiles.  ``obs.layer(name, **attrs)`` names one of the 1-D sort's
  layers (``obs.LAYERS``): its ``jax.named_scope`` is entered whether or
  not obs is enabled — metadata in the compiled program, no op, no host
  sync — so a device profile can attribute every op to its layer; the
  host span is recorded only while obs is enabled.
* **Metrics** — counters/gauges/histograms, host-side (``count`` /
  ``gauge`` / ``observe``) and in-jit (``jit_count`` / ``jit_observe`` /
  ``jit_event``, staged as unordered ``jax.debug.callback`` only when
  obs is enabled at trace time).
* **Exporters** — ``export_jsonl`` (JSONL event log),
  ``export_chrome_trace`` (Perfetto-viewable Chrome trace-event file),
  ``summary()`` (human table).

Instrumented call sites: ``ops/sort.py`` and ``core/ips4o.py`` (layer
scopes on the 1-D sort, per-level spans on the batched path,
bucket-imbalance / base-case / fallback stats), ``ops/plan.py``
(plan-cache hit/miss/autotune, classifier races), ``classify/router.py``
(routing decisions), ``dist/exchange.py`` (re-split rounds, collective
volume, overflow events), ``stream/api.py`` (spill bytes, tournament
rounds), ``serve/scheduler.py`` (admission), ``launch/roofline.py``
(chosen ``KernelLaunchSpec`` per launch).
"""
from repro.obs.export import (
    export_chrome_trace,
    export_jsonl,
    span_stats,
    summary,
    timed_min,
)
from repro.obs.metrics import (
    count,
    counter_value,
    gauge,
    hist_values,
    jit_count,
    jit_event,
    jit_observe,
    metrics_snapshot,
    observe,
)
from repro.obs.tracer import (
    LAYERS,
    Recorder,
    block,
    enabled,
    events,
    layer,
    recorder,
    reset,
    trace,
)

__all__ = [
    "LAYERS",
    "Recorder",
    "block",
    "count",
    "counter_value",
    "enabled",
    "events",
    "export_chrome_trace",
    "export_jsonl",
    "gauge",
    "hist_values",
    "jit_count",
    "jit_event",
    "jit_observe",
    "layer",
    "metrics_snapshot",
    "observe",
    "recorder",
    "reset",
    "span_stats",
    "summary",
    "timed_min",
    "trace",
]
