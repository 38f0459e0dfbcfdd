"""Batched-engine parity suite (DESIGN.md §6).

The contract: ``batched_sort(X)[i]`` is bit-identical to ``sort(X[i])``
for every row, across all nine paper input distributions x {f32, i32} x
both partition engines; B=1 equals unbatched; the batch-grid kernels
match their unbatched counterparts row-for-row; ragged batch shapes
round-trip through the plan cache under distinct (op, B, n, dtype) keys;
and pre-batch plan schemas load (migrated) instead of being discarded.
"""
import json
from dataclasses import replace

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro import ops
from repro.core.ips4o import SortConfig, plan_levels
from repro.data.distributions import DISTRIBUTIONS, make_input

# one-level path with per-row pads (n=5000 -> n_pad=6144, k=32)
_cfg = SortConfig(base_case=1024, kmax=32, tile=256, max_sample=256, slack=4)
_N = 5000
_B = 4


def _rows(dist, n, dtype, nrows=_B):
    return np.stack([make_input(dist, n, dtype, seed=s) for s in range(nrows)])


# ---------------------------------------------------------------- tentpole
@pytest.mark.parametrize("dist", sorted(DISTRIBUTIONS))
@pytest.mark.parametrize("dtype", [np.float32, np.int32])
@pytest.mark.parametrize("engine", ["xla", "pallas"])
def test_batched_sort_parity_distributions(dist, dtype, engine):
    """batched_sort(x)[i] == sort(x[i]) bit-identical, every distribution."""
    x = _rows(dist, _N, dtype)
    out = np.asarray(ops.batched_sort(jnp.asarray(x), cfg=_cfg, engine=engine))
    for i in range(_B):
        ref = np.asarray(ops.sort(jnp.asarray(x[i]), cfg=_cfg, engine=engine))
        np.testing.assert_array_equal(out[i], ref)
        np.testing.assert_array_equal(out[i], np.sort(x[i]))


@pytest.mark.parametrize("engine", ["xla", "pallas"])
def test_batched_two_level_parity(engine):
    """Rows long enough for the per-row segmented second level."""
    n = 20000
    assert len(plan_levels(20480, _cfg)) == 2
    x = _rows("TwoDup", n, np.int32, nrows=3)
    out = np.asarray(ops.batched_sort(jnp.asarray(x), cfg=_cfg, engine=engine))
    np.testing.assert_array_equal(out, np.sort(x, axis=1))
    for i in range(3):
        ref = np.asarray(ops.sort(jnp.asarray(x[i]), cfg=_cfg, engine=engine))
        np.testing.assert_array_equal(out[i], ref)


def test_batched_b1_equals_unbatched():
    """The degenerate batch is exactly the unbatched op."""
    x = make_input("Exponential", _N, np.float32, seed=2)
    for engine in ("xla", "pallas"):
        b1 = np.asarray(
            ops.batched_sort(jnp.asarray(x[None, :]), cfg=_cfg, engine=engine)
        )
        ref = np.asarray(ops.sort(jnp.asarray(x), cfg=_cfg, engine=engine))
        np.testing.assert_array_equal(b1[0], ref)


def _conds(jaxpr):
    """Every ``cond`` equation of a jaxpr, nested jaxprs included."""
    from jax.extend.core import ClosedJaxpr, Jaxpr

    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "cond":
            yield eqn
        for p in eqn.params.values():
            for sub in p if isinstance(p, (tuple, list)) else (p,):
                if isinstance(sub, ClosedJaxpr):
                    yield from _conds(sub.jaxpr)
                elif isinstance(sub, Jaxpr):
                    yield from _conds(sub)


@pytest.mark.parametrize("engine", ["xla", "pallas"])
def test_batched_one_fallback_cond_outside_the_lift(engine):
    """The batch lift keeps ONE fallback ``cond`` on a scalar predicate
    (a ``vmap`` of the cond would be a ``select`` running both branches),
    and a batch whose first row alone breaks the base case's precondition
    still sorts every row bit for bit like the 1-D sort of that row.

    One level of k = 16 from 8 sampled keys: at most 9 ranges hold the
    4,088 unsampled distinct keys of row 0, so one even bucket has more
    than W/2 = 128 of them; a constant row lands wholly in an equality
    bucket."""
    from repro.core.ips4o import (
        bucket_violations, ips4o_sort, ips4o_sort_batched, partition_passes,
    )

    cfg = SortConfig(base_case=256, kmax=128, tile=256, max_sample=8, slack=1,
                     engine=engine)
    n = 4096
    levels = plan_levels(n, cfg)
    assert levels == [16]
    x = np.stack([np.random.default_rng(0).permutation(n),
                  np.full(n, 7), np.full(n, -3)]).astype(np.int32)
    v = np.tile(np.arange(n, dtype=np.int32), (3, 1))

    flags = []
    for row in x:
        _, off, nb, pad = partition_passes({"k": jnp.asarray(row)}, n, cfg, levels)
        flags.append(bool(bucket_violations(off, nb, cfg.base_case, pad)))
    assert flags == [True, False, False]

    f = lambda k, v: ips4o_sort_batched(k, v, cfg=cfg)
    conds = list(_conds(jax.make_jaxpr(f)(x, v).jaxpr))
    assert len(conds) == 1
    assert conds[0].invars[0].aval.shape == ()

    out_k, out_v = jax.jit(f)(jnp.asarray(x), jnp.asarray(v))
    for i in range(3):
        ref_k, ref_v = ips4o_sort(jnp.asarray(x[i]), jnp.asarray(v[i]), cfg=cfg)
        np.testing.assert_array_equal(np.asarray(out_k[i]), np.asarray(ref_k))
        np.testing.assert_array_equal(np.asarray(out_v[i]), np.asarray(ref_v))
    np.testing.assert_array_equal(np.asarray(out_v[0]), np.argsort(x[0], kind="stable"))


def test_batched_payload_and_argsort():
    x = _rows("TwoDup", _N, np.float32)
    v = jnp.broadcast_to(jnp.arange(_N, dtype=jnp.int32)[None, :], (_B, _N))
    for engine in ("xla", "pallas"):
        k2, v2 = ops.batched_sort(jnp.asarray(x), v, cfg=_cfg, engine=engine)
        np.testing.assert_array_equal(
            np.take_along_axis(x, np.asarray(v2), axis=1), np.asarray(k2)
        )
        order = np.asarray(ops.batched_argsort(jnp.asarray(x), cfg=_cfg, engine=engine))
        np.testing.assert_array_equal(
            np.take_along_axis(x, order, axis=1), np.sort(x, axis=1)
        )


@pytest.mark.parametrize("engine", ["xla", "pallas"])
def test_batched_topk_bottomk(engine):
    x = _rows("Uniform", _N, np.float32)
    v, i = ops.batched_bottomk(jnp.asarray(x), 37, cfg=_cfg, engine=engine)
    np.testing.assert_array_equal(np.asarray(v), np.sort(x, axis=1)[:, :37])
    np.testing.assert_array_equal(
        np.take_along_axis(x, np.asarray(i), axis=1), np.asarray(v)
    )
    v, i = ops.batched_topk(jnp.asarray(x), 12, cfg=_cfg, engine=engine)
    np.testing.assert_array_equal(np.asarray(v), -np.sort(-x, axis=1)[:, :12])
    np.testing.assert_array_equal(
        np.take_along_axis(x, np.asarray(i), axis=1), np.asarray(v)
    )
    # per-row parity with the unbatched partial sort
    vu, iu = ops.bottomk(jnp.asarray(x[0]), 37, cfg=_cfg, engine=engine)
    vb, _ = ops.batched_bottomk(jnp.asarray(x), 37, cfg=_cfg, engine=engine)
    np.testing.assert_array_equal(np.asarray(vb[0]), np.asarray(vu))


def test_batched_nan_and_negzero():
    """Keyspace semantics hold per row: NaNs last, -0.0 before +0.0."""
    x = np.asarray(
        [[np.nan, 1.0, -0.0, 0.0, -1.0], [2.0, np.nan, np.nan, -2.0, 0.0]],
        np.float32,
    )
    out = np.asarray(ops.batched_sort(jnp.asarray(x)))
    np.testing.assert_array_equal(out[0], np.asarray([-1.0, -0.0, 0.0, 1.0, np.nan], np.float32))
    assert np.signbit(out[0][1]) and not np.signbit(out[0][2])
    np.testing.assert_array_equal(out[1][:3], np.asarray([-2.0, 0.0, 2.0], np.float32))
    assert np.all(np.isnan(out[1][3:]))


# ---------------------------------------------------------- batched kernels
def test_batched_classify_kernel_matches_unbatched():
    from repro.kernels.classify import classify_histogram, classify_histogram_batched

    rng = np.random.default_rng(0)
    B, n, k = 3, 2048, 16
    keys = jnp.asarray(rng.standard_normal((B, n)), jnp.float32)
    spl = jnp.sort(jnp.asarray(rng.standard_normal((B, k - 1)), jnp.float32), axis=1)
    b, hist = classify_histogram_batched(keys, spl, k=k, rows=8)
    for i in range(B):
        bi, hi = classify_histogram(keys[i], spl[i], k=k, rows=8)
        np.testing.assert_array_equal(np.asarray(b[i]), np.asarray(bi))
        np.testing.assert_array_equal(np.asarray(hist[i]), np.asarray(hi))


def test_batched_rank_kernel_matches_unbatched():
    from repro.kernels.dispatch_rank import partition_ranks, partition_ranks_batched

    rng = np.random.default_rng(1)
    B, n, nb = 4, 3000, 21  # n not tile-aligned: exercises the pad path
    bkt = jnp.asarray(rng.integers(0, nb, (B, n)), jnp.int32)
    totals = jax.vmap(lambda r: jnp.bincount(r, length=nb))(bkt)
    start = (jnp.cumsum(totals, axis=1) - totals).astype(jnp.int32)
    dest = partition_ranks_batched(bkt, start, nb=nb)
    for i in range(B):
        ref = partition_ranks(bkt[i], start[i], nb=nb)
        np.testing.assert_array_equal(np.asarray(dest[i]), np.asarray(ref))
        assert len(set(np.asarray(dest[i]).tolist())) == n  # per-row permutation


# ------------------------------------------------------------- plan cache
def test_plan_cache_ragged_batch_roundtrip(tmp_path):
    """Ragged batch shapes get distinct plans; persisted plans reload."""
    path = str(tmp_path / "plans.json")
    pc = ops.PlanCache(path=path)
    rng = np.random.default_rng(0)
    x3 = jnp.asarray(rng.standard_normal((3, 4096)), jnp.float32)
    for b in (2, 3):
        f = pc.get_sorter(4096, jnp.float32, "sort", batch=b)
        out = np.asarray(f(x3[:b]))
        np.testing.assert_array_equal(out, np.sort(np.asarray(x3[:b]), axis=1))
    assert pc._key("sort", 4096, jnp.float32, None, 2) != pc._key(
        "sort", 4096, jnp.float32, None, 3
    )
    # tuned batched plan persists under the B= key and reloads
    pc.get_sorter(2048, jnp.float32, "sort", batch=4, tune=True)
    key = pc._key("sort", 2048, jnp.float32, None, 4)
    assert key in pc._plans and key.startswith("sort:B=4:")
    pc2 = ops.PlanCache(path=path)
    assert pc2.config_for("sort", 2048, jnp.float32, batch=4) == SortConfig(
        **pc._plans[key]["config"]
    )
    # batched "auto" falls back to the unbatched row-shape plan's engine
    pc2._plans[pc2._key("sort", 512, jnp.float32, None)] = {
        "engine": "pallas", "config": {}
    }
    assert pc2.engine_hint(512, jnp.float32, batch=7) == "pallas"
    # and an unbatched lookup never sees a batched plan
    assert pc2.engine_hint(2048, jnp.float32) is None


def test_plan_cache_pre_batch_schema_migrates(tmp_path):
    """Plan entries written by a pre-batch schema (unknown config fields)
    load with their tuned geometry — migrated, not discarded — and the
    migrated form is what the next save persists."""
    path = str(tmp_path / "plans.json")
    stale = {
        "sort:n=4096:dtype=float32": {
            "config": {"base_case": 2048, "kmax": 64, "tile": 1024,
                       "max_sample": 4096, "slack": 4, "seed": 1,
                       "fallback": True, "engine": "xla",
                       "batch": 1, "rows_per_block": 8},  # pre-batch extras
            "engine": "xla",
            "us": 2.0,
        },
        "sort:n=2048:dtype=float32": {
            "config": {"window": 9999},  # fully foreign -> defaults still
            "us": 3.0,
        },
        "sort:n=1024:dtype=float32": "xla",  # not even a dict -> defaults
        "sort:n=512:dtype=float32": {
            "config": {"tile": "big", "base_case": 2048},  # wrong value kind
            "us": 1.0,
        },
    }
    with open(path, "w") as fh:
        json.dump(stale, fh)
    pc = ops.PlanCache(path=path)
    cfg = pc.config_for("sort", 4096, jnp.float32)
    assert cfg.base_case == 2048 and cfg.kmax == 64  # tuned geometry kept
    assert "batch" not in pc._plans["sort:n=4096:dtype=float32"]["config"]
    assert pc.config_for("sort", 2048, jnp.float32) == SortConfig()
    assert pc.config_for("sort", 1024, jnp.float32) == SortConfig()
    assert pc.engine_hint(1024, jnp.float32) is None
    assert pc.engine_hint(1024, jnp.float32, batch=2) is None
    # mis-typed field dropped, well-typed sibling still loads
    assert pc.config_for("sort", 512, jnp.float32) == SortConfig(base_case=2048)
    pc._save()
    with open(path) as fh:
        saved = json.load(fh)
    assert "rows_per_block" not in saved["sort:n=4096:dtype=float32"]["config"]


# -------------------------------------------------------------- rewired callers
def test_scheduler_admit_many_matches_unbatched():
    import copy

    from repro.serve.scheduler import Request, Scheduler, admit_many

    rng = np.random.default_rng(3)
    scheds = []
    for s in range(5):
        sc = Scheduler(batch_size=int(rng.integers(1, 5)))
        for u in range(int(rng.integers(0, 20))):
            sc.submit(Request(uid=s * 1000 + u, prompt_len=4,
                              max_new=int(rng.integers(1, 40))))
        scheds.append(sc)
    ref = [copy.deepcopy(s) for s in scheds]
    got = admit_many(scheds)
    for i, s in enumerate(ref):
        exp = s.next_batch()
        assert [r.uid for r in got[i]] == [r.uid for r in exp]
        assert [r.uid for r in scheds[i].queue] == [r.uid for r in s.queue]
    assert admit_many([Scheduler(batch_size=2)]) == [[]]


def test_pack_by_length_batched_matches_per_shard():
    from repro.data.pipeline import pack_by_length

    rng = np.random.default_rng(4)
    lengths = rng.integers(1, 64, (3, 257)).astype(np.int32)
    batched = pack_by_length(lengths, 128)
    assert len(batched) == 3
    for s in range(3):
        r1, o1, nr1 = pack_by_length(lengths[s], 128)
        r2, o2, nr2 = batched[s]
        np.testing.assert_array_equal(r1, r2)
        np.testing.assert_array_equal(o1, o2)
        assert nr1 == nr2


def test_moe_sort_dispatch_batched_matches_per_layer():
    from repro.models.moe import expert_capacity, sort_dispatch

    rng = np.random.default_rng(5)
    E, k, n, L = 8, 2, 1024, 4
    cap = expert_capacity(n, E, k, 1.25)
    fe = jnp.asarray(rng.integers(0, E, (L, n * k)).astype(np.int32))
    slot, kept, counts = sort_dispatch(fe, E, cap)
    assert slot.shape == (L, n * k) and counts.shape == (L, E)
    for l in range(L):
        s1, k1, c1 = sort_dispatch(fe[l], E, cap)
        np.testing.assert_array_equal(np.asarray(slot[l]), np.asarray(s1))
        np.testing.assert_array_equal(np.asarray(kept[l]), np.asarray(k1))
        np.testing.assert_array_equal(np.asarray(counts[l]), np.asarray(c1))


# ------------------------------------------------------------------ shape guards
def test_batched_rejects_1d():
    x = jnp.zeros((8,), jnp.float32)
    for fn in (ops.batched_sort, ops.batched_argsort):
        with pytest.raises(ValueError, match="2-D"):
            fn(x)
    for fn in (ops.batched_topk, ops.batched_bottomk):
        with pytest.raises(ValueError, match="2-D"):
            fn(x, 2)


def test_batched_trivial_shapes():
    x = jnp.asarray([[5.0], [3.0]])
    np.testing.assert_array_equal(np.asarray(ops.batched_sort(x)), np.asarray(x))
    v, i = ops.batched_topk(x, 0)
    assert v.shape == (2, 0) and i.shape == (2, 0)
    # engine threading: explicit cfg engine + per-call override agree
    y = _rows("Ones", 2048, np.float32, nrows=2)
    a = np.asarray(ops.batched_sort(jnp.asarray(y), cfg=replace(_cfg, engine="pallas")))
    b = np.asarray(ops.batched_sort(jnp.asarray(y), cfg=_cfg, engine="pallas"))
    np.testing.assert_array_equal(a, b)
