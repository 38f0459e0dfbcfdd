"""repro.dist — sharded sort-derived ops on the multi-level engine.

Public entry points mirror ``repro.ops`` (DESIGN.md §5) lifted onto a
device mesh (DESIGN.md §8): keys biject through ``ops.keyspace`` at the
boundary (NaN-safe, -0.0 < +0.0, identical total order to ``ops.sort``),
the partition engine threads through the same ``engine="xla"|"pallas"|
"auto"`` seam, and "auto" resolves against the ``dist:`` plan family of
the plan cache (capacity factor × oversampling × engine learned per
(n_local, d, dtype)).

  sort / argsort   multi-level AMS-style sort over one or more mesh axes
                   (e.g. ``("pod", "data")``): per-axis splitter sets and
                   per-axis collective fan-in, re-split retry on overflow
  topk / bottomk   distributed rank-k: splitter-based local partial sort
                   (the filter), gather of the per-shard candidates, and a
                   single-shard finish — replicated (k,) results
  group_by         multi-level sort + per-shard run boundaries

Sharded results follow the original distributed-sort contract: each shard
holds its sorted range padded to capacity with sentinels, plus a valid
count per shard and an overflow flag (raised only after every re-split
retry failed — the last resort, not the first response).
"""
from __future__ import annotations

import functools
from dataclasses import replace
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro import obs
from repro.classify import resolve_classifier
from repro.core.ips4o import SortConfig, ips4o_sort, resolve_engine
from repro.dist.exchange import compact_valid, exchange_level, tile_for
from repro.dist.levels import (
    AxisNames, normalize_axes, order_axes, plan_schedule,
)
from repro.ops import keyspace
from repro.ops.topk import smallest_encoded

__all__ = ["sort", "argsort", "topk", "bottomk", "group_by"]


def _mesh_arity(mesh: Mesh, names: Tuple[str, ...]) -> int:
    d = 1
    for a in names:
        d *= mesh.shape[a]
    return d


def _axis_arg(names: Tuple[str, ...]):
    return names if len(names) > 1 else names[0]


def _resolve_dist_engine(
    engine: Optional[str], cfg: SortConfig, plan_engine: Optional[str],
    n_local: int, dtype,
) -> str:
    """Same seam as ``ops.sort.with_engine``: explicit argument > config >
    persisted ``dist:`` plan > backend heuristic — resolved at the API
    boundary against the caller's (n_local, dtype)."""
    eng = engine or cfg.engine
    if eng != "auto":
        return resolve_engine(replace(cfg, engine=eng), n_local, dtype)
    if plan_engine in ("xla", "pallas"):
        return plan_engine
    return resolve_engine(replace(cfg, engine="auto"), n_local, dtype)


def _plan_params(
    n_local: int, d: int, dtype, slack: Optional[float],
    oversample: Optional[int], tune: bool,
):
    from repro.ops.plan import default_cache  # lazy: keep dist importable alone

    plan = default_cache.dist_plan(n_local, d, dtype, tune=tune)
    return (
        plan.slack if slack is None else float(slack),
        plan.oversample if oversample is None else int(oversample),
        plan.engine,
        plan.axis_order,
    )


def _resolve_order(
    order: Optional[str], names: Tuple[str, ...], mesh: Mesh, n_local: int,
    d: int, dtype, planned: Tuple[str, ...], slack: float, oversample: int,
) -> Tuple[str, ...]:
    """``order="auto"``: topology-aware axis ordering (DESIGN.md §13.4).

    A persisted ``axis_order`` from the ``dist:`` plan wins when it names
    exactly this call's axes; otherwise the static cost model picks the
    order and records it as a plan dimension for the next call.  The
    default (None / "given") keeps the caller's order — bit-compatible
    with every pre-existing call site.
    """
    if order not in (None, "given", "auto"):
        raise ValueError(f"order must be None, 'given' or 'auto', got {order!r}")
    if order in (None, "given") or len(names) < 2:
        return names
    if tuple(sorted(planned)) == tuple(sorted(names)):
        return tuple(planned)
    chosen = order_axes(
        dict(mesh.shape), names, n_local, slack=slack, oversample=oversample
    )
    from repro.ops.plan import default_cache

    default_cache.record_dist_axis_order(n_local, d, dtype, chosen)
    return chosen


def _finish_local(arrays, m, cfg: SortConfig, engine: str):
    """Final per-shard IS4o sort.  Pads share the sentinel key with real
    dtype-max / NaN-class keys, so when payload identity matters a validity
    bit rides the sort and one stable 2-bucket partition pushes pads behind
    every real element without disturbing key order."""
    n = arrays["k"].shape[0]
    vals = {k: v for k, v in arrays.items() if k != "k"}
    if not vals:
        return {"k": ips4o_sort(arrays["k"], cfg=cfg)}
    validity = (jnp.arange(n, dtype=jnp.int32) < m).astype(jnp.int32)
    k_sorted, out_v = ips4o_sort(
        arrays["k"], {**vals, "_valid": validity}, cfg=cfg
    )
    valid_sorted = out_v.pop("_valid")
    return compact_valid(
        {"k": k_sorted, **out_v}, valid_sorted > 0, tile_for(n, cfg.tile), engine
    )


def _pre_exchange(arrays, n_local: int, ax, d: int):
    """Balanced pre-exchange over the FULL mesh domain: one round-robin
    all_to_all gives every shard a representative slice of every stripe,
    bounding per-pair counts for ANY input placement (the distributed
    cousin of the paper's beta overpartitioning).  Runs under shard_map."""
    chunk = n_local // d

    def pre(a):
        t = jax.lax.all_to_all(
            a.reshape((d, chunk) + a.shape[1:]),
            ax, split_axis=0, concat_axis=0, tiled=True,
        )
        return t.reshape((n_local,) + a.shape[1:])

    return jax.tree.map(pre, arrays)


def _sort_body(
    arrays, n_local: int, names: Tuple[str, ...], schedule, cfg: SortConfig,
    engine: str, retries: int, d: int, classifier: str = "tree",
    overlap: bool = False,
):
    """Per-shard body: balanced pre-exchange, the explicit level loop, and
    the local finish.  Runs under ``shard_map``."""
    ax = _axis_arg(names)
    if d > 1:
        arrays = _pre_exchange(arrays, n_local, ax, d)

    m = jnp.asarray(n_local, jnp.int32)
    overflow = jnp.asarray(False)
    for i, level in enumerate(schedule):
        # radix destinations only at level 0: deeper domains hold
        # splitter-delimited ranges once any round re-split
        arrays, m, ovf = exchange_level(
            arrays, m, level,
            engine=engine, tile=cfg.tile, seed=cfg.seed,
            level_idx=i, retries=retries,
            classifier=classifier if i == 0 else "tree",
            overlap=overlap,
        )
        overflow = jnp.logical_or(overflow, ovf)
    out = _finish_local(arrays, m, cfg, engine)
    return out, m[None], overflow[None]


def _prepare(
    keys: jax.Array, mesh: Mesh, axes: AxisNames, pre_exchange: bool = True
):
    names = normalize_axes(axes)
    d = _mesh_arity(mesh, names)
    n = keys.shape[0]
    if keys.ndim != 1:
        raise ValueError("keys must be 1-D (sharded over the mesh axes)")
    n_local = n // d
    if n_local * d != n:
        raise ValueError(f"n={n} not divisible by axis size {d}")
    # the balanced pre-exchange reshapes each shard into d chunks; rank-k
    # queries never run it and accept any shard size
    if pre_exchange and d > 1 and n_local % d:
        raise ValueError(
            f"shard size {n_local} must be divisible by d={d} (pre-exchange)"
        )
    return names, d, n_local


def sort(
    keys: jax.Array,
    mesh: Mesh,
    axes: AxisNames = "data",
    *,
    values: Any = None,
    slack: Optional[float] = None,
    oversample: Optional[int] = None,
    retries: int = 2,
    cfg: SortConfig = SortConfig(),
    engine: Optional[str] = None,
    classifier: Optional[str] = None,
    tune: bool = False,
    overlap: bool = False,
    order: Optional[str] = None,
):
    """Multi-level distributed sort of a globally sharded key array.

    Args:
      keys: (n,) array sharded over ``axes`` of ``mesh`` (n divisible by
        the total axis size d; shard size divisible by d for d > 1).
      axes: one mesh axis or an outermost-first tuple (e.g.
        ``("pod", "data")``) — one exchange level per axis.
      values: optional payload pytree (leaves with leading dim n), same
        sharding; rows ride every partition and exchange.
      slack / oversample: capacity factor and per-shard sample size; None
        reads the ``dist:`` plan for (n_local, d, dtype) (``tune=True``
        runs the capacity simulation and persists the winner).
      retries: bounded re-split rounds per level before the overflow flag.
      engine: "xla" | "pallas" | "auto" partition engine override.
      classifier: "tree" | "radix" | "learned" | "auto" classifier-engine
        override (DESIGN.md §9), resolved here against (n_local, dtype).
        "radix" additionally takes bit-range destinations at round 0 of
        level 0, skipping that round's sampling collective; exchange
        levels past the first (and every re-split round) stay
        splitter-based.
      overlap: stagger each level's exchange against local partition work
        via the half-shard protocol (DESIGN.md §13) — bit-identical
        results, collectives issued early enough to hide behind compute.
      order: None/"given" keeps the caller's axis order; "auto" reorders
        the level schedule by the topology cost model (DESIGN.md §13.4),
        consulting/recording the ``dist:`` plan's ``axis_order``.  The
        output contract follows the *chosen* order: shard ranges
        concatenate in the reordered spec's block order.

    Returns (sorted, counts, overflow) — with values,
    (sorted, sorted_values, counts, overflow): shard i of ``sorted`` holds
    its globally-ordered range with sentinel padding at the tail,
    ``counts`` (d,) the valid prefix per shard, ``overflow`` (d,) True only
    if some exchange truncated after exhausting its re-split retries.

    >>> import jax, jax.numpy as jnp
    >>> mesh = jax.make_mesh((1,), ("data",))
    >>> out, counts, ovf = sort(jnp.asarray([3.0, 1.0, 2.0, 0.0]), mesh)
    >>> out[: int(counts[0])].tolist()
    [0.0, 1.0, 2.0, 3.0]
    >>> bool(ovf.any())
    False
    """
    names, d, n_local = _prepare(keys, mesh, axes)
    slack, oversample, plan_engine, planned_order = _plan_params(
        n_local, d, keys.dtype, slack, oversample, tune
    )
    names = _resolve_order(
        order, names, mesh, n_local, d, keys.dtype, planned_order,
        slack, oversample,
    )
    eng = _resolve_dist_engine(engine, cfg, plan_engine, n_local, keys.dtype)
    clf = resolve_classifier(classifier or cfg.classifier, n_local, keys.dtype)
    cfg_run = replace(cfg, engine=eng, classifier=clf)
    schedule = plan_schedule(
        dict(mesh.shape), names, n_local, slack=slack, oversample=oversample
    )
    body = functools.partial(
        _sort_body, n_local=n_local, names=names, schedule=schedule,
        cfg=cfg_run, engine=eng, retries=retries, d=d, classifier=clf,
        overlap=overlap,
    )
    ax = _axis_arg(names)
    spec = P(ax)
    enc = keyspace.encode(keys)
    span = obs.trace(
        "dist.sort", axes=",".join(names), levels=len(schedule), d=d,
        overlap="on" if overlap else "off", engine=eng,
    )

    if values is None:
        def run(k):
            out, m, o = body({"k": k})
            return out["k"], m, o

        f = jax.jit(jax.shard_map(run, mesh=mesh, in_specs=(spec,),
                                  out_specs=(spec, spec, spec), check_vma=False))
        with span:
            out_k, counts, ovf = f(enc)
        return keyspace.decode(out_k, keys.dtype), counts, ovf

    vspecs = jax.tree.map(lambda a: P(ax, *([None] * (a.ndim - 1))), values)

    def run(k, v):
        out, m, o = body({"k": k, "v": v})
        return out["k"], out["v"], m, o

    # check_vma=False throughout: the replication checker cannot see
    # through the engine's scan-shaped internals (jax's own recommendation
    # for this false positive); no output here claims replication anyway.
    # jit throughout too: called eagerly on Explicit mesh axes, a shard_map
    # body with a lax.cond fails XLA's sharding check
    f = jax.jit(jax.shard_map(run, mesh=mesh, in_specs=(spec, vspecs),
                              out_specs=(spec, vspecs, spec, spec), check_vma=False))
    with span:
        out_k, out_v, counts, ovf = f(enc, values)
    return keyspace.decode(out_k, keys.dtype), out_v, counts, ovf


def argsort(
    keys: jax.Array,
    mesh: Mesh,
    axes: AxisNames = "data",
    *,
    slack: Optional[float] = None,
    oversample: Optional[int] = None,
    retries: int = 2,
    cfg: SortConfig = SortConfig(),
    engine: Optional[str] = None,
    classifier: Optional[str] = None,
    tune: bool = False,
    overlap: bool = False,
    order: Optional[str] = None,
):
    """Distributed argsort: global input positions ride as the payload.

    ``overlap`` / ``order`` behave exactly as in :func:`sort` (the global
    indices ride the same half-shard frames).

    Returns (order, counts, overflow): shard i's valid prefix of ``order``
    holds the global indices of its sorted range — concatenating the valid
    prefixes yields a permutation sorting the global array.

    >>> import jax, jax.numpy as jnp
    >>> mesh = jax.make_mesh((1,), ("data",))
    >>> idx, counts, ovf = argsort(jnp.asarray([30, 10, 20, 0]), mesh)
    >>> idx[: int(counts[0])].tolist()
    [3, 1, 2, 0]
    """
    names, d, n_local = _prepare(keys, mesh, axes)
    slack, oversample, plan_engine, planned_order = _plan_params(
        n_local, d, keys.dtype, slack, oversample, tune
    )
    names = _resolve_order(
        order, names, mesh, n_local, d, keys.dtype, planned_order,
        slack, oversample,
    )
    eng = _resolve_dist_engine(engine, cfg, plan_engine, n_local, keys.dtype)
    clf = resolve_classifier(classifier or cfg.classifier, n_local, keys.dtype)
    cfg_run = replace(cfg, engine=eng, classifier=clf)
    schedule = plan_schedule(
        dict(mesh.shape), names, n_local, slack=slack, oversample=oversample
    )
    body = functools.partial(
        _sort_body, n_local=n_local, names=names, schedule=schedule,
        cfg=cfg_run, engine=eng, retries=retries, d=d, classifier=clf,
        overlap=overlap,
    )
    ax = _axis_arg(names)
    spec = P(ax)

    def run(k):
        my = jax.lax.axis_index(ax).astype(jnp.int32)
        gidx = my * n_local + jnp.arange(n_local, dtype=jnp.int32)
        out, m, o = body({"k": k, "v": gidx})
        return out["v"], m, o

    f = jax.jit(jax.shard_map(run, mesh=mesh, in_specs=(spec,),
                              out_specs=(spec, spec, spec), check_vma=False))
    return f(keyspace.encode(keys))


def bottomk(
    keys: jax.Array,
    k: int,
    mesh: Mesh,
    axes: AxisNames = "data",
    *,
    cfg: SortConfig = SortConfig(),
    engine: Optional[str] = None,
    classifier: Optional[str] = None,
) -> Tuple[jax.Array, jax.Array]:
    """The k globally smallest keys (ascending) with their global indices.

    Splitter-filter then single-shard finish: every shard runs the
    splitter-based *partial* sort (``ops`` §5.2 — only the rank-covering
    bucket prefix is base-case-sorted) as its local filter, the per-shard
    candidates are gathered, and one shard-local partial sort finishes.
    Results are replicated (same on every shard), NaN-safe like
    ``ops.bottomk``.

    >>> import jax, jax.numpy as jnp
    >>> mesh = jax.make_mesh((1,), ("data",))
    >>> v, i = bottomk(jnp.asarray([4.0, 1.0, 3.0, 2.0]), 2, mesh)
    >>> (v.tolist(), i.tolist())
    ([1.0, 2.0], [1, 3])
    """
    return _rank_k(
        keys, k, mesh, axes, cfg=cfg, engine=engine, classifier=classifier,
        largest=False,
    )


def topk(
    keys: jax.Array,
    k: int,
    mesh: Mesh,
    axes: AxisNames = "data",
    *,
    cfg: SortConfig = SortConfig(),
    engine: Optional[str] = None,
    classifier: Optional[str] = None,
) -> Tuple[jax.Array, jax.Array]:
    """The k globally largest keys (descending) with their global indices;
    ``bottomk`` of the complemented keyspace codes (``~u`` reverses the
    total order), like ``ops.topk``.

    >>> import jax, jax.numpy as jnp
    >>> mesh = jax.make_mesh((1,), ("data",))
    >>> v, i = topk(jnp.asarray([4.0, 1.0, 3.0, 2.0]), 2, mesh)
    >>> (v.tolist(), i.tolist())
    ([4.0, 3.0], [0, 2])
    """
    return _rank_k(
        keys, k, mesh, axes, cfg=cfg, engine=engine, classifier=classifier,
        largest=True,
    )


def _rank_k(
    keys: jax.Array, k: int, mesh: Mesh, axes: AxisNames,
    *, cfg: SortConfig, engine: Optional[str], largest: bool,
    classifier: Optional[str] = None,
) -> Tuple[jax.Array, jax.Array]:
    names, d, n_local = _prepare(keys, mesh, axes, pre_exchange=False)
    n = keys.shape[0]
    kk = max(0, min(int(k), n))
    if kk == 0:
        return keys[:0], jnp.zeros((0,), jnp.int32)
    if d == 1:
        from repro.ops.topk import bottomk as _bk, topk as _tk

        return (_tk if largest else _bk)(
            keys, kk, cfg=cfg, engine=engine, classifier=classifier
        )

    eng = _resolve_dist_engine(engine, cfg, None, n_local, keys.dtype)
    clf = resolve_classifier(classifier or cfg.classifier, n_local, keys.dtype)
    cfg_run = replace(cfg, engine=eng, classifier=clf)
    ax = _axis_arg(names)
    k_local = min(kk, n_local)
    enc = keyspace.encode(keys)
    if largest:
        enc = ~enc

    def run(e):
        vals, idx = smallest_encoded(e, k_local, cfg_run)   # the local filter
        my = jax.lax.axis_index(ax).astype(jnp.int32)
        gidx = my * n_local + idx
        cand_v = jax.lax.all_gather(vals, ax, tiled=True)   # (d * k_local,)
        cand_i = jax.lax.all_gather(gidx, ax, tiled=True)
        fin_v, fin_i = smallest_encoded(cand_v, kk, cfg_run)  # single-shard finish
        return fin_v, jnp.take(cand_i, fin_i, axis=0)

    # outputs are replicated: every shard computes the same finish over the
    # same gathered candidates (check_vma can't see through the partial
    # sort's internals, so it is disabled rather than trusted to infer)
    f = jax.jit(jax.shard_map(run, mesh=mesh, in_specs=(P(ax),),
                              out_specs=(P(), P()), check_vma=False))
    out_v, out_i = f(enc)
    if largest:
        out_v = ~out_v
    return keyspace.decode(out_v, keys.dtype), out_i


def group_by(
    keys: jax.Array,
    mesh: Mesh,
    axes: AxisNames = "data",
    *,
    values: Any = None,
    slack: Optional[float] = None,
    retries: int = 2,
    cfg: SortConfig = SortConfig(),
    engine: Optional[str] = None,
    classifier: Optional[str] = None,
    overlap: bool = False,
):
    """Sharded grouping: multi-level sort by key, then per-shard run starts.

    Returns (sorted_keys, [sorted_values,] starts, counts, overflow) where
    ``starts`` marks the first element of each key run *within its shard*
    (a run crossing a shard boundary re-starts on the next shard — merging
    boundary runs is one host-side concat of adjacent shard edges; the
    global sort guarantees a key spans only adjacent shards).

    >>> import jax, jax.numpy as jnp
    >>> mesh = jax.make_mesh((1,), ("data",))
    >>> ks, starts, counts, ovf = group_by(jnp.asarray([2, 1, 2, 1]), mesh)
    >>> m = int(counts[0])
    >>> (ks[:m].tolist(), starts[:m].tolist())
    ([1, 1, 2, 2], [True, False, True, False])
    """
    res = sort(
        keys, mesh, axes, values=values, slack=slack, retries=retries,
        cfg=cfg, engine=engine, classifier=classifier, overlap=overlap,
    )
    if values is None:
        out_k, counts, ovf = res
        out_v = None
    else:
        out_k, out_v, counts, ovf = res
    names, d, _ = _prepare(keys, mesh, axes)
    cap = out_k.shape[0] // d
    ax = _axis_arg(names)

    def run(kk, m):
        ek = keyspace.encode(kk)  # NaN-safe equality: one NaN class, -0 != +0
        pos = jnp.arange(cap, dtype=jnp.int32)
        valid = pos < m[0]
        prev = jnp.concatenate([ek[:1], ek[:-1]])
        starts = valid & ((pos == 0) | (ek != prev))
        return starts

    f = jax.jit(jax.shard_map(run, mesh=mesh, in_specs=(P(ax), P(ax)), out_specs=P(ax)))
    starts = f(out_k, counts)
    if values is None:
        return out_k, starts, counts, ovf
    return out_k, out_v, starts, counts, ovf
