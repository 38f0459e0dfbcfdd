"""Sort-derived grouping ops: unique / run_length / group_by.

All three are "sort plus boundary extraction" (DESIGN.md §5.3).  The §4.4
equality buckets make the sort side cheap on duplicate-heavy inputs — a
run of identical keys lands in one equality bucket and is never base-case
sorted — which is exactly the regime grouping ops live in.

Static shapes: JAX cannot return data-dependent lengths, so the per-group
outputs (``unique`` values, counts, run lengths) come back padded to n
with a scalar count of the valid prefix, mirroring the static-shape
conventions used elsewhere in the repo (e.g. ``repro.dist``).

``group_by`` has three interchangeable engines:
  * ``"partition"`` — keys are small ints in [0, num_groups): one stable
    block partition (``core.partition``), no sampling, exact buckets.
    This is the MoE-dispatch path (``models.moe.sort_dispatch``).
  * ``"pallas"``    — same contract, ranks computed by the fused
    ``kernels.dispatch_rank`` kernel (one pass, SMEM running counters).
  * ``"sort"``      — arbitrary keys: full IPS4o sort + boundary scan.
"""
from __future__ import annotations

from typing import Any, NamedTuple, Optional, Tuple, Union

import jax
import jax.numpy as jnp

from repro.core.ips4o import SortConfig, ips4o_sort, replicated
from repro.core.partition import partition_permutation
from repro.ops import keyspace

__all__ = ["Groups", "group_by", "unique", "run_length"]


class Groups(NamedTuple):
    """Result of :func:`group_by`; positions are grouped key-ascending."""

    keys: jax.Array            # (n,) grouped keys
    values: Any                # grouped payload pytree (None if not given)
    group_ids: jax.Array       # (n,) group index of each grouped position
    counts: jax.Array          # (num_groups,) exact, or (n,) padded for "sort"
    num_groups: Union[int, jax.Array]  # static int, or traced scalar for "sort"
    perm: jax.Array            # (n,) source index of each grouped position


def _boundaries(enc_sorted: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """(group id per position, num groups) from sorted encoded keys."""
    n = enc_sorted.shape[0]
    mask = jnp.concatenate(
        [jnp.ones((1,), jnp.bool_), enc_sorted[1:] != enc_sorted[:-1]]
    )
    gid = jnp.cumsum(mask).astype(jnp.int32) - 1
    return gid, gid[-1] + 1


def _int_group_perm(
    keys: jax.Array, num_groups: int, method: str, tile: int
) -> Tuple[jax.Array, jax.Array]:
    """(perm, offsets) grouping small-int keys; both engines are stable."""
    n = keys.shape[0]
    b = keys.astype(jnp.int32)
    if method == "pallas":
        from repro.kernels.dispatch_rank import LANES, dispatch_ranks

        unit = 8 * LANES
        n_pad = -(-n // unit) * unit
        # pad ids into an extra trash group so the kernel sees a full grid
        ids = jnp.full((n_pad,), num_groups, jnp.int32).at[:n].set(b)
        counts = jnp.bincount(b, length=num_groups)
        offsets = jnp.concatenate(
            [jnp.zeros((1,), jnp.int32), jnp.cumsum(counts).astype(jnp.int32)]
        )
        start = jnp.concatenate([offsets[:-1], jnp.full((1,), n, jnp.int32)])
        dest = dispatch_ranks(ids, start, num_experts=num_groups + 1)
        perm = (
            jnp.zeros((n_pad,), jnp.int32)
            .at[dest]
            .set(jnp.arange(n_pad, dtype=jnp.int32), mode="promise_in_bounds")
        )
        return perm[:n], offsets
    t = min(tile, n)
    if n % t:
        t = n  # single tile fallback for odd sizes (as in models.moe)
    return partition_permutation(b, num_groups, t)


def group_by(
    keys: jax.Array,
    values: Any = None,
    *,
    num_groups: Optional[int] = None,
    method: str = "auto",
    tile: int = 2048,
    cfg: SortConfig = SortConfig(),
) -> Groups:
    """Group elements by key, key-ascending, stably within a group.

    With ``num_groups`` (keys are ints in [0, num_groups)) the grouping is
    a single stable block partition — or the fused Pallas ranking kernel
    with ``method="pallas"`` — and ``counts``/``num_groups`` are exact and
    static.  Without it, keys are arbitrary (``method="sort"``): a full
    NaN-safe sort groups equal keys, ``counts`` comes back (n,)-padded and
    ``num_groups`` is a traced scalar.

    >>> import jax.numpy as jnp
    >>> g = group_by(jnp.asarray([2, 0, 2, 1]), num_groups=3)
    >>> g.keys.tolist()
    [0, 1, 2, 2]
    >>> g.counts.tolist()
    [1, 1, 2]
    >>> g.perm.tolist()  # stable within a group
    [1, 3, 0, 2]
    """
    n = keys.shape[0]
    if keys.ndim != 1:
        raise ValueError("keys must be 1-D")
    if method == "auto":
        method = "partition" if num_groups is not None else "sort"
    if method in ("partition", "pallas"):
        if num_groups is None:
            raise ValueError(f"method={method!r} requires num_groups")
        if n == 0:
            return Groups(
                keys, values, jnp.zeros((0,), jnp.int32),
                jnp.zeros((num_groups,), jnp.int32), num_groups,
                jnp.zeros((0,), jnp.int32),
            )
        keys, values = replicated((keys, values))
        perm, offsets = _int_group_perm(keys, num_groups, method, tile)
        gk = jnp.take(keys, perm, axis=0)
        gv = (
            None
            if values is None
            else jax.tree.map(lambda a: jnp.take(a, perm, axis=0), values)
        )
        return Groups(
            keys=gk,
            values=gv,
            group_ids=gk.astype(jnp.int32),
            counts=jnp.diff(offsets),
            num_groups=num_groups,
            perm=perm,
        )
    if method != "sort":
        raise ValueError(f"unknown group_by method {method!r}")
    if n == 0:
        return Groups(
            keys, values, jnp.zeros((0,), jnp.int32),
            jnp.zeros((0,), jnp.int32), jnp.zeros((), jnp.int32),
            jnp.zeros((0,), jnp.int32),
        )
    enc = keyspace.encode(keys)
    payload = {"i": jnp.arange(n, dtype=jnp.int32)}
    if values is not None:
        payload["v"] = values
    enc_sorted, out = ips4o_sort(enc, payload, cfg=cfg)
    perm = out["i"]
    gid, num = _boundaries(enc_sorted)
    counts = jnp.zeros((n,), jnp.int32).at[gid].add(1, mode="promise_in_bounds")
    return Groups(
        keys=keyspace.decode(enc_sorted, keys.dtype),
        values=out.get("v"),
        group_ids=gid,
        counts=counts,
        num_groups=num,
        perm=perm,
    )


def unique(
    keys: jax.Array, *, cfg: SortConfig = SortConfig()
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Distinct keys, ascending.  Returns (values, counts, num_unique):
    ``values``/``counts`` are (n,)-padded, valid for the first
    ``num_unique`` entries (entries beyond that are unspecified).

    >>> import jax.numpy as jnp
    >>> vals, counts, num = unique(jnp.asarray([3, 1, 3, 1, 1]))
    >>> int(num)
    2
    >>> (vals[:2].tolist(), counts[:2].tolist())
    ([1, 3], [3, 2])
    """
    n = keys.shape[0]
    if n == 0:
        return keys, jnp.zeros((0,), jnp.int32), jnp.zeros((), jnp.int32)
    enc = keyspace.encode(keys)
    enc_sorted = ips4o_sort(enc, cfg=cfg)
    gid, num = _boundaries(enc_sorted)
    vals = (
        jnp.zeros((n,), enc_sorted.dtype)
        .at[gid]
        .set(enc_sorted, mode="promise_in_bounds")
    )
    counts = jnp.zeros((n,), jnp.int32).at[gid].add(1, mode="promise_in_bounds")
    return keyspace.decode(vals, keys.dtype), counts, num


def run_length(
    keys: jax.Array, *, cfg: SortConfig = SortConfig()
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Run-length encoding of *consecutive* equal keys (no sorting).

    Returns (values, lengths, num_runs), (n,)-padded like :func:`unique`
    (entries beyond num_runs are unspecified).
    Equality is keyspace equality, so NaN runs and -0.0/+0.0 behave
    deterministically (NaN == NaN, -0.0 != +0.0).

    >>> import jax.numpy as jnp
    >>> vals, lens, num = run_length(jnp.asarray([5, 5, 2, 2, 2, 5]))
    >>> int(num)
    3
    >>> (vals[:3].tolist(), lens[:3].tolist())
    ([5, 2, 5], [2, 3, 1])
    """
    n = keys.shape[0]
    if n == 0:
        return keys, jnp.zeros((0,), jnp.int32), jnp.zeros((), jnp.int32)
    enc = keyspace.encode(keys)
    rid, num = _boundaries(enc)  # runs are "groups" of the unsorted stream
    vals = jnp.zeros((n,), enc.dtype).at[rid].set(enc, mode="promise_in_bounds")
    lengths = jnp.zeros((n,), jnp.int32).at[rid].add(1, mode="promise_in_bounds")
    return keyspace.decode(vals, keys.dtype), lengths, num
