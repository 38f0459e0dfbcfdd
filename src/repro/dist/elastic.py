"""Elastic distributed sort: level boundaries as restore points.

``repro.dist.sort`` runs its whole pipeline — pre-exchange, every level's
exchange, the local finish — inside one jitted ``shard_map``: fast, but a
shard loss anywhere loses everything.  This module re-expresses the same
computation as a *host-driven state machine* whose per-shard state
materialises at every level boundary and is checkpointed through
``repro.checkpoint.CheckpointManager`` (DESIGN.md §13.3):

    INIT ──save(0)──> LEVEL 0 ──save(1)──> LEVEL 1 ── ... ──save(L)──> FINISH

  * **state** at boundary s: the per-shard key (and payload) arrays, the
    per-shard validity counts, the accumulated overflow flags, the
    observed per-shard fill histogram (valid counts at every boundary so
    far), the consumed-level index, and a parameter fingerprint;
  * **restore**: ``latest_step()`` finds the last completed boundary,
    ``read_leaf`` recovers the consumed-level index (state shapes depend
    on it), and ``restore`` re-lays the arrays out on the CURRENT mesh —
    the manager's elastic path, so resumption tolerates a re-formed mesh
    of the same shape and axis names;
  * **determinism**: every level's splitter RNG folds (seed, level_idx,
    round, shard-index) — history-independent — so a resumed sort draws
    exactly the samples the uninterrupted sort would have drawn, and the
    final output is bit-identical, re-split retries and truncation
    included.

Each step is one jitted ``shard_map`` over the exact per-shard bodies of
``dist.api`` (``_pre_exchange`` / ``exchange_level`` / ``_finish_local``),
so the elastic path cannot drift from the monolithic one.  The price of
restorability is one host round-trip and checkpoint write per level;
``save(..., blocking=False)`` overlaps the write with the next level's
compute, the same compute/IO overlap the checkpoint manager gives
training loops.

A directory identifies ONE sort job: calling :func:`sort_elastic` with a
directory holding a finished job's checkpoints just replays its finish.
Point different sorts at different directories (or clean up between).
"""
from __future__ import annotations

import hashlib
import json
from dataclasses import replace
from typing import Any, Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import obs
from repro.checkpoint.manager import CheckpointManager
from repro.classify import resolve_classifier
from repro.core.ips4o import SortConfig
from repro.dist.api import (
    _axis_arg, _finish_local, _plan_params, _pre_exchange, _prepare,
    _resolve_dist_engine,
)
from repro.dist.exchange import exchange_level
from repro.dist.levels import AxisNames, plan_schedule
from repro.ops import keyspace

__all__ = ["sort_elastic"]


def _fingerprint(meta: dict) -> np.ndarray:
    """sha256 of the sort parameters as a (32,) uint8 leaf — a checkpoint
    from a *different* sort configuration must never silently resume."""
    digest = hashlib.sha256(
        json.dumps(meta, sort_keys=True).encode()
    ).digest()
    return np.frombuffer(digest, dtype=np.uint8).copy()


def _leaf_specs(arrays, ax):
    return jax.tree.map(lambda a: P(ax, *([None] * (a.ndim - 1))), arrays)


def _state_shardings(like, mesh, ax):
    """NamedShardings for the checkpoint state on the CURRENT mesh: array
    leaves and per-shard scalars shard over ``ax``; host metadata (fills
    history, level index, fingerprint) replicates."""
    shard = jax.tree.map(
        lambda a: NamedSharding(mesh, P(ax, *([None] * (len(a.shape) - 1)))),
        like["arrays"],
    )
    row = NamedSharding(mesh, P(ax))
    rep = NamedSharding(mesh, P())
    return {
        "arrays": shard, "m": row, "ovf": row,
        "fills": rep, "level": rep, "fingerprint": rep,
    }


def sort_elastic(
    keys: jax.Array,
    mesh: Mesh,
    axes: AxisNames = "data",
    *,
    manager: CheckpointManager,
    values: Any = None,
    slack: Optional[float] = None,
    oversample: Optional[int] = None,
    retries: int = 2,
    cfg: SortConfig = SortConfig(),
    engine: Optional[str] = None,
    classifier: Optional[str] = None,
    overlap: bool = False,
    blocking_saves: bool = True,
    _fail_at_step: Optional[int] = None,
):
    """Restorable multi-level distributed sort (module docstring).

    Same contract as :func:`repro.dist.sort` — returns (sorted, counts,
    overflow), or (sorted, sorted_values, counts, overflow) with
    ``values`` — and bit-identical output, but the sort checkpoints its
    per-shard state into ``manager`` at every level boundary and, when
    the manager's directory already holds a matching checkpoint, resumes
    from the last completed level instead of restarting.  On resume the
    *data* comes from the checkpoint; ``keys`` / ``values`` supply only
    shapes, dtypes and sharding.  A checkpoint whose parameter
    fingerprint disagrees (different seed, schedule, dtype, ...) raises
    ``ValueError`` rather than resuming into a different sort.

    ``blocking_saves=False`` uses the manager's async path: the write of
    boundary s overlaps level s's compute.  ``_fail_at_step`` is the
    fault-injection hook for the elastic-restore test suite: it raises
    ``RuntimeError`` (simulating shard loss) right after the named
    boundary's checkpoint commits.

    >>> import tempfile
    >>> import jax, jax.numpy as jnp
    >>> from repro.checkpoint import CheckpointManager
    >>> mesh = jax.make_mesh((1,), ("data",))
    >>> ck = CheckpointManager(tempfile.mkdtemp())
    >>> out, counts, ovf = sort_elastic(
    ...     jnp.asarray([3.0, 1.0, 2.0, 0.0]), mesh, manager=ck)
    >>> out[: int(counts[0])].tolist()
    [0.0, 1.0, 2.0, 3.0]
    >>> ck.latest_step()  # boundaries 0 (pre-exchange) and 1 (one level)
    1
    """
    names, d, n_local = _prepare(keys, mesh, axes)
    slack, oversample, plan_engine, _ = _plan_params(
        n_local, d, keys.dtype, slack, oversample, False
    )
    eng = _resolve_dist_engine(engine, cfg, plan_engine, n_local, keys.dtype)
    clf = resolve_classifier(classifier or cfg.classifier, n_local, keys.dtype)
    cfg_run = replace(cfg, engine=eng, classifier=clf)
    schedule = plan_schedule(
        dict(mesh.shape), names, n_local, slack=slack, oversample=oversample
    )
    levels = len(schedule)
    ax = _axis_arg(names)
    enc = keyspace.encode(keys)
    arrays = {"k": enc} if values is None else {"k": enc, "v": values}
    val_meta = [
        (str(path), str(leaf.dtype), list(leaf.shape[1:]))
        for path, leaf in jax.tree_util.tree_flatten_with_path(
            {} if values is None else values
        )[0]
    ]
    fp = _fingerprint({
        "axes": list(names), "d": d, "n_local": n_local,
        "slack": float(slack), "oversample": int(oversample),
        "retries": int(retries), "seed": int(cfg.seed),
        "dtype": str(keys.dtype), "engine": eng, "classifier": clf,
        "overlap": bool(overlap), "values": val_meta,
    })

    def _arrays_like(n_shard: int):
        def sds(a):
            return jax.ShapeDtypeStruct((d * n_shard,) + a.shape[1:], a.dtype)

        return jax.tree.map(sds, arrays)

    # ---------------------------------------------------------- resume
    start = 0
    fills = np.zeros((levels + 1, d), np.int32)
    last = manager.latest_step()
    resumed = last is not None
    if resumed:
        saved_fp = manager.read_leaf(last, "fingerprint")
        if not np.array_equal(saved_fp, fp):
            raise ValueError(
                "checkpoint directory holds a different sort "
                "(parameter fingerprint mismatch); use a fresh directory"
            )
        start = int(manager.read_leaf(last, "level"))
        n_shard = n_local if start == 0 else schedule[start - 1].n_out
        like = {
            "arrays": _arrays_like(n_shard),
            "m": jax.ShapeDtypeStruct((d,), jnp.int32),
            "ovf": jax.ShapeDtypeStruct((d,), jnp.bool_),
            "fills": jax.ShapeDtypeStruct((levels + 1, d), jnp.int32),
            "level": jax.ShapeDtypeStruct((), jnp.int32),
            "fingerprint": jax.ShapeDtypeStruct((32,), jnp.uint8),
        }
        st = manager.restore(last, like, _state_shardings(like, mesh, ax))
        arrays, m, ovf = st["arrays"], st["m"], st["ovf"]
        fills = np.array(st["fills"])  # np.asarray of a jax array is read-only

    def _save(step: int):
        state = {
            "arrays": arrays, "m": m, "ovf": ovf,
            "fills": fills.copy(), "level": np.int32(step),
            "fingerprint": fp,
        }
        manager.save(step, state, blocking=blocking_saves)
        if _fail_at_step is not None and step == _fail_at_step:
            manager.wait()
            raise RuntimeError(
                f"injected shard loss after level boundary {step}"
            )

    with obs.trace(
        "dist.sort_elastic", axes=",".join(names), levels=levels, d=d,
        resumed="yes" if resumed else "no", start_level=start,
        overlap="on" if overlap else "off",
    ):
        if not resumed:
            aspec = _leaf_specs(arrays, ax)
            init = jax.shard_map(
                lambda t: _pre_exchange(t, n_local, ax, d) if d > 1 else t,
                mesh=mesh, in_specs=(aspec,), out_specs=aspec,
                check_vma=False,
            )
            arrays = jax.jit(init)(arrays)
            m = jnp.full((d,), n_local, jnp.int32)
            ovf = jnp.zeros((d,), jnp.bool_)
            fills[0] = n_local
            _save(0)

        for i in range(start, levels):
            level = schedule[i]

            def step(tree, mm, _i=i, _lv=level):
                out, m1, o1 = exchange_level(
                    tree, mm[0], _lv,
                    engine=eng, tile=cfg.tile, seed=cfg.seed,
                    level_idx=_i, retries=retries,
                    classifier=clf if _i == 0 else "tree",
                    overlap=overlap,
                )
                return out, m1[None], o1[None]

            in_a = _leaf_specs(arrays, ax)
            out_like = _arrays_like(level.n_out)
            f = jax.shard_map(
                step, mesh=mesh, in_specs=(in_a, P(ax)),
                out_specs=(_leaf_specs(out_like, ax), P(ax), P(ax)),
                check_vma=False,
            )
            arrays, m, ovf_i = jax.jit(f)(arrays, m)
            ovf = jnp.logical_or(ovf, ovf_i)
            fills[i + 1] = np.asarray(m)
            _save(i + 1)

        aspec = _leaf_specs(arrays, ax)
        fin = jax.shard_map(
            lambda t, mm: _finish_local(t, mm[0], cfg_run, eng),
            mesh=mesh, in_specs=(aspec, P(ax)), out_specs=aspec,
            check_vma=False,
        )
        out = jax.jit(fin)(arrays, m)
    manager.wait()

    decoded = keyspace.decode(out["k"], keys.dtype)
    if values is None:
        return decoded, m, ovf
    return decoded, out["v"], m, ovf
