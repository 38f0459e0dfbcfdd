"""NaN-safe full sort and argsort on top of the IPS4o engine.

These are thin compositions: biject keys into the ordered uint space
(``ops.keyspace``), run ``ips4o_sort`` there (where ``>`` / ``==`` are a
total order, so the documented NaN limitation disappears), and decode.

``sort_records`` / ``argsort_records`` extend the same composition to
multi-word keys (strings and composite records decomposed by
``keyspace.encode_words``, DESIGN.md §11): word 0 is sorted outright and
the runs that tie are re-sorted word by word through the MSD tie-break
schedule (``core.ips4o.tiebreak_passes``), with the engine and classifier
seams threaded through every pass — the radix classifier is the natural
winner on prefix words (the high bits of a pass's composite run structure
are exactly what it buckets on), and ``classifier="auto"`` routes through
the racing plan-cache router like every other op.
"""
from __future__ import annotations

from dataclasses import replace
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import obs
from repro.classify import resolve_classifier
from repro.core.ips4o import SortConfig, ips4o_sort, resolve_engine, tiebreak_passes
from repro.ops import keyspace

__all__ = ["sort", "argsort", "sort_records", "argsort_records", "with_engine"]


def with_engine(
    cfg: SortConfig,
    engine: Optional[str],
    keys: Optional[jax.Array] = None,
    classifier: Optional[str] = None,
) -> SortConfig:
    """Override the partition engine and/or classifier on a config (None
    keeps the cfg's value).

    When ``keys`` is given, "auto" (for either knob) is resolved HERE —
    against the caller's original (n, dtype), which is what the plan cache
    keys tuned plans under.  Deeper layers see the keyspace-encoded dtype
    and the padded n, so resolving any later would never match a persisted
    plan.

    >>> with_engine(SortConfig(), "pallas").engine
    'pallas'
    >>> with_engine(SortConfig(engine="pallas"), None).engine
    'pallas'
    >>> with_engine(SortConfig(), None, classifier="radix").classifier
    'radix'
    """
    cfg = cfg if engine is None else replace(cfg, engine=engine)
    if classifier is not None:
        cfg = replace(cfg, classifier=classifier)
    if keys is not None:
        if cfg.engine == "auto":
            cfg = replace(
                cfg, engine=resolve_engine(cfg, keys.shape[0], keys.dtype)
            )
        if cfg.classifier == "auto":
            cfg = replace(
                cfg,
                classifier=resolve_classifier(
                    "auto", keys.shape[0], keys.dtype
                ),
            )
    return cfg


def sort(
    keys: jax.Array,
    values: Any = None,
    *,
    cfg: SortConfig = SortConfig(),
    engine: Optional[str] = None,
    classifier: Optional[str] = None,
):
    """Sort ``keys`` ascending (NaNs last, -0.0 before +0.0), optionally
    permuting a ``values`` pytree alongside.  Jit-compatible.  ``engine``
    ("xla" | "pallas" | "auto") overrides ``cfg.engine`` for this call;
    ``classifier`` ("tree" | "radix" | "learned" | "auto") overrides
    ``cfg.classifier`` the same way (DESIGN.md §9).

    >>> import jax.numpy as jnp
    >>> sort(jnp.asarray([3.0, 1.0, 2.0])).tolist()
    [1.0, 2.0, 3.0]
    >>> k, v = sort(jnp.asarray([2, 1]), {"tag": jnp.asarray([20, 10])})
    >>> (k.tolist(), v["tag"].tolist())  # payload rows follow their keys
    ([1, 2], [10, 20])
    """
    cfg = with_engine(cfg, engine, keys, classifier)
    with obs.layer(
        "sort", n=keys.shape[0], dtype=str(keys.dtype), engine=cfg.engine
    ):
        enc = keyspace.encode(keys)
        if values is None:
            out = keyspace.decode(ips4o_sort(enc, cfg=cfg), keys.dtype)
        else:
            k, vs = ips4o_sort(enc, values, cfg=cfg)
            out = (keyspace.decode(k, keys.dtype), vs)
        obs.block(out)  # eager path: the span measures real execution
    return out


def argsort(
    keys: jax.Array,
    *,
    cfg: SortConfig = SortConfig(),
    engine: Optional[str] = None,
    classifier: Optional[str] = None,
) -> jax.Array:
    """Indices that sort ``keys`` ascending: ``keys[argsort(keys)]`` is
    sorted.  The index payload rides the existing values-pytree threading;
    ties are in arbitrary (but deterministic) order.

    >>> import jax.numpy as jnp
    >>> argsort(jnp.asarray([30.0, 10.0, 20.0])).tolist()
    [1, 2, 0]
    """
    n = keys.shape[0]
    idx = jnp.arange(n, dtype=jnp.int32)
    if n <= 1:
        return idx
    cfg = with_engine(cfg, engine, keys, classifier)
    with obs.trace("ops.argsort", n=n, dtype=str(keys.dtype), engine=cfg.engine):
        _, order = ips4o_sort(keyspace.encode(keys), idx, cfg=cfg)
        obs.block(order)
    return order


def _check_words(words: jax.Array) -> jax.Array:
    words = jnp.asarray(words)
    if words.ndim != 2:
        raise ValueError("words must be 2-D (n, W)")
    if words.shape[1] == 0:
        raise ValueError("words must have at least one word column")
    return words


def _record_cols(words: jax.Array) -> Tuple[jax.Array, ...]:
    return tuple(keyspace.encode(words[:, j]) for j in range(words.shape[1]))


def sort_records(
    words: jax.Array,
    values: Any = None,
    *,
    cfg: SortConfig = SortConfig(),
    engine: Optional[str] = None,
    classifier: Optional[str] = None,
):
    """Sort multi-word records (n, W) into row-lexicographic order.

    ``words`` is the fixed-width word decomposition of each record —
    usually ``keyspace.encode_words`` output (uint32, word 0 most
    significant), but any supported dtype works (each word column is
    keyspace-encoded, so float/signed words order naturally, NaNs last).
    The sort is **stable**: equal records keep their input order, and the
    implied permutation is bit-identical to ``np.lexsort`` over the
    columns.  A ``values`` pytree (leaves with leading dim n) is permuted
    alongside.  Jit-compatible; ``engine`` / ``classifier`` thread through
    every tie-break pass (DESIGN.md §11).

    >>> import jax.numpy as jnp
    >>> w = jnp.asarray([[1, 9], [0, 5], [1, 2]], jnp.uint32)
    >>> sort_records(w).tolist()  # row-lexicographic
    [[0, 5], [1, 2], [1, 9]]
    """
    words = _check_words(words)
    n = words.shape[0]
    if n <= 1:
        return words if values is None else (words, values)
    cfg = with_engine(cfg, engine, words[:, 0], classifier)
    cols, vals = tiebreak_passes(_record_cols(words), values, cfg=cfg)
    out = jnp.stack(
        [keyspace.decode(c, words.dtype) for c in cols], axis=1
    )
    return out if values is None else (out, vals)


def argsort_records(
    words: jax.Array,
    *,
    cfg: SortConfig = SortConfig(),
    engine: Optional[str] = None,
    classifier: Optional[str] = None,
) -> jax.Array:
    """Stable lexicographic argsort of multi-word records (n, W):
    ``words[argsort_records(words)]`` is row-sorted, and the permutation
    is bit-identical to ``np.lexsort`` over the word columns (ties keep
    input order).

    >>> import jax.numpy as jnp
    >>> w = jnp.asarray([[1, 9], [0, 5], [1, 2]], jnp.uint32)
    >>> argsort_records(w).tolist()
    [1, 2, 0]
    """
    words = _check_words(words)
    n = words.shape[0]
    idx = jnp.arange(n, dtype=jnp.int32)
    if n <= 1:
        return idx
    cfg = with_engine(cfg, engine, words[:, 0], classifier)
    _, order = tiebreak_passes(_record_cols(words), idx, cfg=cfg)
    return order
