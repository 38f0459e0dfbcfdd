"""Batch-axis-native sort ops: (B, n) rows sorted in one trace (DESIGN.md §6).

Every hot caller with real traffic has a batch dimension — MoE routing ids
per layer, the serve scheduler's admission queues, per-shard document
lengths — and looping the 1-D sort over rows leaves the accelerator idle
across exactly that dimension.  These entry points run the one sort
pipeline (``core.ips4o._sort_padded``) with every per-row stage under
``jax.vmap``: sample -> classify -> stable partition -> base case over
all B rows at once, the Pallas kernels on a (B, tiles) grid by Pallas's
batching rule, and one batch-wide fallback ``cond`` outside the lift.
Each row's result is bit-identical to the unbatched op on that row
(``tests/test_batched.py``).

Like ``ops.sort``, keys are bijected through ``ops.keyspace`` first, so
NaN / -0.0 handling matches the unbatched ops exactly.
"""
from __future__ import annotations

from dataclasses import replace
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.classify import resolve_classifier
from repro.core.ips4o import SortConfig, ips4o_sort_batched, resolve_engine
from repro.ops import keyspace
from repro.ops.topk import _smallest

__all__ = [
    "batched_sort",
    "batched_argsort",
    "batched_topk",
    "batched_bottomk",
    "with_engine_batched",
]


def with_engine_batched(
    cfg: SortConfig,
    engine: Optional[str],
    keys: Optional[jax.Array] = None,
    classifier: Optional[str] = None,
) -> SortConfig:
    """Override the partition engine and/or classifier for a batched call.

    The batched analogue of ``ops.sort.with_engine``: "auto" (for either
    knob) resolves here, against the caller's original (B, n, dtype) — the
    plan cache keys batched plans under exactly that triple, so resolving
    deeper (against the encoded dtype / padded n) would never match a
    persisted plan.

    >>> from repro.ops import SortConfig
    >>> import jax.numpy as jnp
    >>> cfg = with_engine_batched(SortConfig(), "pallas")
    >>> cfg.engine
    'pallas'
    >>> with_engine_batched(cfg, None).engine  # None keeps cfg.engine
    'pallas'
    >>> with_engine_batched(SortConfig(), None, classifier="radix").classifier
    'radix'
    """
    cfg = cfg if engine is None else replace(cfg, engine=engine)
    if classifier is not None:
        cfg = replace(cfg, classifier=classifier)
    if keys is not None:
        B, n = keys.shape
        if cfg.engine == "auto":
            cfg = replace(
                cfg, engine=resolve_engine(cfg, n, keys.dtype, batch=B)
            )
        if cfg.classifier == "auto":
            cfg = replace(
                cfg,
                classifier=resolve_classifier("auto", n, keys.dtype, batch=B),
            )
    return cfg


def batched_sort(
    keys: jax.Array,
    values: Any = None,
    *,
    cfg: SortConfig = SortConfig(),
    engine: Optional[str] = None,
    classifier: Optional[str] = None,
):
    """Sort each row of ``keys`` (B, n) ascending, NaN-safe, in one trace.

    Per row this is exactly ``ops.sort`` (NaNs last, -0.0 before +0.0);
    across rows it is one compiled program instead of B dispatches.  An
    optional ``values`` pytree (leaves with leading dims (B, n)) is
    permuted alongside, row by row.  ``engine`` ("xla" | "pallas" |
    "auto") overrides ``cfg.engine`` for this call; ``classifier``
    ("tree" | "radix" | "learned" | "auto") overrides ``cfg.classifier``
    (DESIGN.md §9).

    >>> import jax.numpy as jnp
    >>> x = jnp.asarray([[3.0, 1.0, 2.0], [0.0, 5.0, -1.0]])
    >>> batched_sort(x).tolist()
    [[1.0, 2.0, 3.0], [-1.0, 0.0, 5.0]]
    >>> k, v = batched_sort(x, jnp.asarray([[10, 11, 12], [20, 21, 22]]))
    >>> v.tolist()  # payload rows follow their keys
    [[11, 12, 10], [22, 20, 21]]
    """
    if keys.ndim != 2:
        raise ValueError("keys must be 2-D (B, n)")
    cfg = with_engine_batched(cfg, engine, keys, classifier)
    enc = keyspace.encode(keys)
    if values is None:
        out = ips4o_sort_batched(enc, cfg=cfg)
        return keyspace.decode(out, keys.dtype)
    out, vs = ips4o_sort_batched(enc, values, cfg=cfg)
    return keyspace.decode(out, keys.dtype), vs


def batched_argsort(
    keys: jax.Array,
    *,
    cfg: SortConfig = SortConfig(),
    engine: Optional[str] = None,
    classifier: Optional[str] = None,
) -> jax.Array:
    """Per-row indices that sort ``keys`` (B, n) ascending.

    ``jnp.take_along_axis(keys, batched_argsort(keys), axis=1)`` is sorted
    per row; ties are in arbitrary (but deterministic) order.

    >>> import jax.numpy as jnp
    >>> batched_argsort(jnp.asarray([[30, 10, 20]])).tolist()
    [[1, 2, 0]]
    """
    if keys.ndim != 2:
        raise ValueError("keys must be 2-D (B, n)")
    B, n = keys.shape
    idx = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32)[None, :], (B, n))
    if n <= 1:
        return idx
    _, order = ips4o_sort_batched(
        keyspace.encode(keys), idx,
        cfg=with_engine_batched(cfg, engine, keys, classifier),
    )
    return order


def _batched_smallest(
    enc: jax.Array, kk: int, cfg: SortConfig
) -> Tuple[jax.Array, jax.Array]:
    """Per-row (sorted kk smallest encoded keys, their original indices):
    ``ops.topk``'s partial sort with the stages under ``jax.vmap``.  The
    static W-aligned prefix P covers the rank-(kk-1) bucket of *every*
    row, so the base case runs over [0, P) of each row only.
    """
    B, n = enc.shape
    idx = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32)[None, :], (B, n))
    return _smallest({"k": enc, "v": idx}, kk, cfg, lift=jax.vmap)


def batched_bottomk(
    keys: jax.Array,
    k: int,
    *,
    cfg: SortConfig = SortConfig(),
    engine: Optional[str] = None,
    classifier: Optional[str] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Per row: the ``k`` smallest keys ascending, with their indices.

    Returns (values, indices), each (B, min(k, n)) — the batched form of
    ``ops.bottomk`` with one partial sort covering every row (the base
    case touches only the shared rank-covering prefix of each row).

    >>> import jax.numpy as jnp
    >>> v, i = batched_bottomk(jnp.asarray([[4.0, 1.0, 3.0], [9.0, 8.0, 7.0]]), 2)
    >>> v.tolist()
    [[1.0, 3.0], [7.0, 8.0]]
    >>> i.tolist()
    [[1, 2], [2, 1]]
    """
    if keys.ndim != 2:
        raise ValueError("keys must be 2-D (B, n)")
    n = keys.shape[1]
    kk = max(0, min(int(k), n))
    if kk == 0:
        return keys[:, :0], jnp.zeros((keys.shape[0], 0), jnp.int32)
    out, idx = _batched_smallest(
        keyspace.encode(keys), kk, with_engine_batched(cfg, engine, keys, classifier)
    )
    return keyspace.decode(out, keys.dtype), idx


def batched_topk(
    keys: jax.Array,
    k: int,
    *,
    cfg: SortConfig = SortConfig(),
    engine: Optional[str] = None,
    classifier: Optional[str] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Per row: the ``k`` largest keys descending, with their indices.

    The batched ``ops.topk`` — same ``jax.lax.top_k`` contract per row
    (modulo tie order), implemented as batched bottom-k of the
    complemented encoded keys.

    >>> import jax.numpy as jnp
    >>> v, i = batched_topk(jnp.asarray([[1.0, 9.0, 3.0], [7.0, 2.0, 5.0]]), 2)
    >>> v.tolist()
    [[9.0, 3.0], [7.0, 5.0]]
    >>> i.tolist()
    [[1, 2], [0, 2]]
    """
    if keys.ndim != 2:
        raise ValueError("keys must be 2-D (B, n)")
    n = keys.shape[1]
    kk = max(0, min(int(k), n))
    if kk == 0:
        return keys[:, :0], jnp.zeros((keys.shape[0], 0), jnp.int32)
    out, idx = _batched_smallest(
        ~keyspace.encode(keys), kk, with_engine_batched(cfg, engine, keys, classifier)
    )
    return keyspace.decode(~out, keys.dtype), idx
