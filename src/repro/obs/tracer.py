"""Span tracer: host-side structured timing with XLA-profile pass-through.

The tracer is the *structural* half of ``repro.obs`` (DESIGN.md §12).
``trace(name, **attrs)`` returns a context manager that records a span —
name, wall-clock duration, parent span, static attributes — into the
process-global :class:`Recorder`.  Two regimes, one API:

* around **eager or already-jitted executions**, a span measures real
  wall time (callers follow ``block_until_ready`` discipline, or use
  :func:`repro.obs.timed_min` which enforces it);
* inside **traced code**, a span measures trace time and contributes
  structure (the nesting of sample/classify/partition under a level
  pass).  Runtime signals from inside jit travel separately, through the
  ``jit_*`` metric hooks in :mod:`repro.obs.metrics`.

Every span also best-effort enters ``jax.profiler.TraceAnnotation`` and
``jax.named_scope``, so the same names land in XLA profiles and HLO
metadata when a device profiler is attached.

Disabled (the default — enable with ``REPRO_OBS=1`` or
``obs.enabled(True)``), ``trace`` returns a shared allocation-free null
span: no lock, no clock read, no jax import side effects, zero added
traced ops.

``layer(name, **attrs)`` splits the span's two jobs.  Its
``jax.named_scope`` is **always on**: the scope is metadata fixed at trace
time (a path component of every op's ``op_name`` in the compiled
program), so it adds no op, no effect and no host sync, and a device
profile names each op's layer whether or not obs is enabled.  Recording
stays opt-in: only while obs is enabled does ``layer`` also record the
host span and enter the ``TraceAnnotation``, exactly as ``trace`` does.
Layer names come from one fixed vocabulary, :data:`LAYERS`:

* ``sort`` — the 1-D sort's entry (``ops.sort``): keyspace encode, pad,
  splitter RNG, decode and the final slice;
* ``sort.level1`` — level 1 (``level_pass``): sample, classify (the fused
  Pallas kernel and its prefix epilogue on that engine), partition;
* ``sort.segment_ids`` — the bucket id of every position, counted from a
  scatter of a level's offsets and a cumsum (``segment_ids``), before
  level 2 and the base case;
* ``sort.level2`` — level 2 (``segmented_level_pass``): sample, classify,
  partition;
* ``sort.base_case`` — the bucket check and the ``lax.cond`` between the
  windowed base case (branch 0) and ``stable_full_sort`` (branch 1);
* ``sort.payload`` — the deferred payload (``ips4o_sort`` with two or
  more payload words a row): every value leaf gathered once by the
  sorted row index, in a ``move`` scope;
* child scopes ``sample``, ``classify``, ``partition`` and ``move`` (the
  payload scatters and gathers) inside those.

The mesh sort (``repro.dist.sort`` / ``argsort``, one ``shard_map`` body
per shard) names three more layers:

* ``dist.pre_exchange`` — the pad of a shard to a multiple of the mesh
  size, the balanced round-robin ``all_to_all`` and the settling of pads
  behind the valid rows;
* ``dist.level`` — one level's exchange (``exchange_level``), with the
  child steps ``sample`` (the sample, its ``all_gather`` and the re-split
  histograms), ``partition`` (classify, the overflow verdict and the
  stable partition into g + 1 buckets), ``exchange`` (``pack``, the
  payload and count ``all_to_all``s) and ``compact`` (``compact_valid``
  of the arrivals);
* ``dist.finish`` — the local sort of each shard's receive buffer and
  the compaction of its valid rows (``_finish_local``); the inner
  ``ips4o_sort`` keeps its ``sort.*`` scopes.

An op's layer is the innermost ``sort.*`` scope in its ``op_name``, and a
``move`` below it marks a payload move of that layer.  The benchmark's
``scope.*`` readers (``bench/scopes.py``) attribute device time that way.
"""
from __future__ import annotations

import os
import threading
import time
from typing import Any, Dict, List, Optional

__all__ = [
    "LAYERS",
    "Recorder",
    "block",
    "enabled",
    "events",
    "layer",
    "recorder",
    "reset",
    "trace",
]

#: the named scopes ``layer`` enters; the ``sort.*`` and ``dist.*`` names
#: are the layers, the last six their child steps (module docstring)
LAYERS = (
    "sort",
    "sort.level1",
    "sort.segment_ids",
    "sort.level2",
    "sort.base_case",
    "sort.payload",
    "dist.pre_exchange",
    "dist.level",
    "dist.finish",
    "sample",
    "classify",
    "partition",
    "move",
    "exchange",
    "compact",
)

_TRUTHY = ("1", "true", "True", "yes", "on")
_STATE = {"enabled": os.environ.get("REPRO_OBS", "") in _TRUTHY}


def enabled(value: Optional[bool] = None) -> bool:
    """Get (no args) or set the global obs enable flag.

    Note the jit-cache caveat: programs compiled while obs was disabled
    stay uninstrumented (and vice versa) until retraced — toggling does
    NOT call ``jax.clear_caches()``.  Tests and the bench exporter clear
    explicitly when they need a re-trace.
    """
    if value is not None:
        _STATE["enabled"] = bool(value)
    return _STATE["enabled"]


class Recorder:
    """Accumulates spans, point events, and metric aggregates.

    One process-global instance backs the module-level API; explicit
    instances can be passed to ``trace(..., recorder=...)`` /
    ``timed_min(..., recorder=...)`` for isolated measurement.

    Metric keys are ``(name, ((label, value), ...))`` with labels sorted,
    so the same name with different labels forms distinct series.
    """

    #: cap on raw values retained per histogram series (count/sum/min/max
    #: keep aggregating past it)
    HIST_CAP = 4096

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._local = threading.local()
        self._next_id = 0
        self.origin_ns = time.perf_counter_ns()
        self.spans: List[Dict[str, Any]] = []
        self.events: List[Dict[str, Any]] = []
        self.counters: Dict[tuple, float] = {}
        self.gauges: Dict[tuple, float] = {}
        self.hists: Dict[tuple, Dict[str, Any]] = {}

    def clear(self) -> None:
        with self._lock:
            self._next_id = 0
            self.origin_ns = time.perf_counter_ns()
            self.spans.clear()
            self.events.clear()
            self.counters.clear()
            self.gauges.clear()
            self.hists.clear()

    # -- span bookkeeping -------------------------------------------------
    def _stack(self) -> list:
        st = getattr(self._local, "stack", None)
        if st is None:
            st = self._local.stack = []
        return st

    def _new_id(self) -> int:
        with self._lock:
            i = self._next_id
            self._next_id += 1
        return i

    def add_span(self, span: Dict[str, Any]) -> None:
        with self._lock:
            self.spans.append(span)

    # -- metrics (called from metrics.py and from debug callbacks) --------
    def add_event(self, name: str, attrs: Dict[str, Any]) -> None:
        ev = {
            "name": name,
            "t_ns": time.perf_counter_ns() - self.origin_ns,
            "attrs": attrs,
        }
        with self._lock:
            self.events.append(ev)

    def add_count(self, name: str, value: float, labels: tuple) -> None:
        key = (name, labels)
        with self._lock:
            self.counters[key] = self.counters.get(key, 0.0) + value

    def set_gauge(self, name: str, value: float, labels: tuple) -> None:
        with self._lock:
            self.gauges[(name, labels)] = value

    def add_observation(self, name: str, value: float, labels: tuple) -> None:
        key = (name, labels)
        with self._lock:
            h = self.hists.get(key)
            if h is None:
                h = self.hists[key] = {
                    "count": 0, "sum": 0.0, "min": value, "max": value,
                    "values": [],
                }
            h["count"] += 1
            h["sum"] += value
            h["min"] = min(h["min"], value)
            h["max"] = max(h["max"], value)
            if len(h["values"]) < self.HIST_CAP:
                h["values"].append(value)


_RECORDER = Recorder()


def recorder() -> Recorder:
    """The process-global recorder (stable identity across ``reset``)."""
    return _RECORDER


def reset() -> None:
    """Clear the global recorder in place (identity preserved, so staged
    debug callbacks keep pointing at the live recorder)."""
    _RECORDER.clear()


def events(name: Optional[str] = None) -> List[Dict[str, Any]]:
    """Recorded point events, optionally filtered by name."""
    with _RECORDER._lock:
        evs = list(_RECORDER.events)
    return evs if name is None else [e for e in evs if e["name"] == name]


class _NullSpan:
    """Shared no-op span returned while obs is disabled."""

    __slots__ = ()

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc: Any) -> bool:
        return False

    def set(self, **attrs: Any) -> "_NullSpan":
        return self


_NULL_SPAN = _NullSpan()


class _Span:
    __slots__ = ("rec", "name", "attrs", "id", "parent", "depth", "t0",
                 "_ann", "_scope")

    def __init__(self, rec: Recorder, name: str, attrs: Dict[str, Any]):
        self.rec = rec
        self.name = name
        self.attrs = attrs

    def set(self, **attrs: Any) -> "_Span":
        self.attrs.update(attrs)
        return self

    def __enter__(self) -> "_Span":
        rec = self.rec
        stack = rec._stack()
        self.parent = stack[-1].id if stack else None
        self.depth = len(stack)
        self.id = rec._new_id()
        stack.append(self)
        self._ann = self._scope = None
        try:  # profiler pass-through is best-effort: never fail the workload
            import jax

            self._ann = jax.profiler.TraceAnnotation(self.name)
            self._ann.__enter__()
            self._scope = jax.named_scope(self.name)
            self._scope.__enter__()
        except Exception:
            pass
        self.t0 = time.perf_counter_ns()
        return self

    def __exit__(self, exc_type: Any, exc: Any, tb: Any) -> bool:
        dur = time.perf_counter_ns() - self.t0
        for cm in (self._scope, self._ann):
            if cm is not None:
                try:
                    cm.__exit__(exc_type, exc, tb)
                except Exception:
                    pass
        rec = self.rec
        stack = rec._stack()
        if stack and stack[-1] is self:
            stack.pop()
        rec.add_span({
            "id": self.id,
            "parent": self.parent,
            "name": self.name,
            "t0_ns": self.t0 - rec.origin_ns,
            "dur_ns": dur,
            "depth": self.depth,
            "tid": threading.get_ident(),
            "attrs": dict(self.attrs),
        })
        return False


def trace(name: str, *, recorder: Optional[Recorder] = None, **attrs: Any):
    """Span context manager: ``with obs.trace("level_pass", level=1): ...``.

    With obs disabled and no explicit ``recorder``, returns a shared
    no-op span (allocation-free fast path).  An explicit ``recorder``
    records regardless of the global flag — that is how
    :func:`repro.obs.timed_min` measures with obs off.
    """
    rec = recorder
    if rec is None:
        if not _STATE["enabled"]:
            return _NULL_SPAN
        rec = _RECORDER
    return _Span(rec, name, attrs)


def layer(name: str, **attrs: Any):
    """Layer scope: ``with obs.layer("sort.level1", k=128): ...``.

    Always enters ``jax.named_scope(name)`` (trace-time metadata only:
    no op, no effect, no host sync).  With obs enabled it is a full
    :func:`trace` span instead, which enters the same scope and also
    records the host span and the ``TraceAnnotation``.  ``name`` must be
    one of :data:`LAYERS`.
    """
    if name not in LAYERS:
        raise ValueError(f"unknown layer {name!r}; expected one of {LAYERS}")
    if _STATE["enabled"]:
        return _Span(_RECORDER, name, attrs)
    import jax

    return jax.named_scope(name)


def block(x: Any) -> Any:
    """``jax.block_until_ready(x)`` when obs is enabled and ``x`` is
    concrete; identity otherwise.

    Used at op boundaries so an enclosing span measures real execution
    time on the eager path without adding a host sync when obs is off,
    and without breaking tracing (Tracers pass through untouched).
    """
    if not _STATE["enabled"]:
        return x
    try:
        import jax

        return jax.block_until_ready(x)
    except Exception:
        return x
