"""Tests for the repro.ops subsystem (DESIGN.md §5).

Covers the keyspace bijection (NaN / -0.0 / extreme ints), NaN-safe
sort/argsort, the splitter-based partial sorts (incl. k >= n, k = 0,
all-equal keys, multi-level inputs), segmented sort, unique / run_length /
group_by (all three engines), and the plan cache.
"""
import os

import numpy as np
import jax.numpy as jnp
import pytest

from repro import ops
from repro.core.ips4o import SortConfig
from repro.ops import keyspace

# small config exercises the 1- and 2-level paths at test-friendly sizes
_small_cfg = SortConfig(base_case=1024, kmax=32, tile=256, max_sample=256, slack=4)


def _rand(n, seed=0):
    return np.random.default_rng(seed).standard_normal(n).astype(np.float32)


# ---------------------------------------------------------------- keyspace
@pytest.mark.parametrize(
    "dtype", [np.float32, np.int32, np.uint32, np.int16, np.uint8, jnp.bfloat16]
)
def test_keyspace_roundtrip_and_order(dtype):
    rng = np.random.default_rng(1)
    if dtype is jnp.bfloat16:
        x = jnp.asarray(rng.standard_normal(4096).astype(np.float32)).astype(dtype)
    elif np.issubdtype(dtype, np.floating):
        x = jnp.asarray(rng.standard_normal(4096).astype(dtype))
    else:
        info = np.iinfo(dtype)
        x = jnp.asarray(
            rng.integers(info.min, info.max, 4096, endpoint=True).astype(dtype)
        )
    u = keyspace.encode(x)
    assert u.dtype == keyspace.ordered_uint_dtype(x.dtype)
    back = keyspace.decode(u, x.dtype)
    np.testing.assert_array_equal(
        np.asarray(back.astype(jnp.float32) if dtype is jnp.bfloat16 else back),
        np.asarray(x.astype(jnp.float32) if dtype is jnp.bfloat16 else x),
    )
    # order preserved: sorting codes == sorting values
    xs = np.asarray(x.astype(jnp.float32) if dtype is jnp.bfloat16 else x)
    order = np.argsort(np.asarray(u), kind="stable")
    np.testing.assert_array_equal(xs[order], np.sort(xs))


def test_keyspace_nan_and_signed_zero():
    x = jnp.asarray([np.nan, -0.0, 0.0, -np.inf, np.inf, 1.5, -1.5, -np.nan],
                    jnp.float32)
    u = np.asarray(keyspace.encode(x))
    # total order: -inf < -1.5 < -0.0 < +0.0 < 1.5 < +inf < NaN == NaN
    assert u[3] < u[6] < u[1] < u[2] < u[5] < u[4] < u[0]
    assert u[0] == u[7], "all NaNs canonicalize to one code"
    back = np.asarray(keyspace.decode(keyspace.encode(x), x.dtype))
    assert np.isnan(back[0]) and np.isnan(back[7])
    assert np.signbit(back[1]) and not np.signbit(back[2])  # -0.0 / +0.0 exact


def test_keyspace_extreme_ints():
    x = jnp.asarray([np.iinfo(np.int32).min, -1, 0, 1, np.iinfo(np.int32).max],
                    jnp.int32)
    u = np.asarray(keyspace.encode(x))
    assert np.all(np.diff(u.astype(np.uint64)) > 0)
    np.testing.assert_array_equal(np.asarray(keyspace.decode(keyspace.encode(x), x.dtype)),
                                  np.asarray(x))


# ---------------------------------------------------------------- sort/argsort
def test_sort_nan_safe():
    x = _rand(20_000, 3)
    x[::101] = np.nan
    x[::97] = -0.0
    out = np.asarray(ops.sort(jnp.asarray(x), cfg=_small_cfg))
    np.testing.assert_array_equal(out, np.sort(x))  # numpy also sorts NaNs last
    assert np.isnan(out[-1])


def test_sort_with_payload():
    x = _rand(9_000, 4)
    v = np.arange(9_000, dtype=np.int32)
    ks, vs = ops.sort(jnp.asarray(x), jnp.asarray(v), cfg=_small_cfg)
    ks, vs = np.asarray(ks), np.asarray(vs)
    np.testing.assert_array_equal(ks, np.sort(x))
    np.testing.assert_array_equal(x[vs], ks)


@pytest.mark.parametrize("dtype", [np.int8, np.int16, np.uint8, np.uint16])
def test_sort_narrow_int_dtypes(dtype):
    # narrow dtypes ride the same distributions as wide ones; Exponential
    # used to clamp int8/int16 to a constant info.max array (scale bug in
    # data.distributions._exponential) — pin non-degeneracy AND parity
    from repro.data.distributions import make_input

    x = make_input("Exponential", 5000, dtype, seed=9)
    assert len(np.unique(x)) > 3, "Exponential degenerated to ~constant"
    assert x.max() <= np.iinfo(dtype).max
    out = np.asarray(ops.sort(jnp.asarray(x), cfg=_small_cfg))
    np.testing.assert_array_equal(out, np.sort(x))
    for dist in ("Uniform", "TwoDup", "Ones"):
        y = make_input(dist, 4096, dtype, seed=9)
        np.testing.assert_array_equal(
            np.asarray(ops.sort(jnp.asarray(y), cfg=_small_cfg)), np.sort(y)
        )


@pytest.mark.parametrize("n", [0, 1, 2, 255, 4096])
def test_argsort_sizes(n):
    x = _rand(n, n)
    order = np.asarray(ops.argsort(jnp.asarray(x), cfg=_small_cfg))
    assert order.shape == (n,)
    if n:
        assert len(np.unique(order)) == n
        np.testing.assert_array_equal(x[order], np.sort(x))


# ---------------------------------------------------------------- topk/bottomk
@pytest.mark.parametrize("n,k", [(100_000, 7), (100_000, 512), (6_000, 100)])
def test_bottomk_topk(n, k):
    x = _rand(n, k)
    v, i = ops.bottomk(jnp.asarray(x), k, cfg=_small_cfg)
    v, i = np.asarray(v), np.asarray(i)
    np.testing.assert_array_equal(v, np.sort(x)[:k])
    np.testing.assert_array_equal(x[i], v)
    v2, i2 = ops.topk(jnp.asarray(x), k, cfg=_small_cfg)
    v2, i2 = np.asarray(v2), np.asarray(i2)
    np.testing.assert_array_equal(v2, np.sort(x)[::-1][:k])
    np.testing.assert_array_equal(x[i2], v2)


def test_topk_k_geq_n():
    x = _rand(300, 9)
    v, i = ops.topk(jnp.asarray(x), 1000, cfg=_small_cfg)
    assert v.shape == (300,)
    np.testing.assert_array_equal(np.asarray(v), np.sort(x)[::-1])
    assert len(np.unique(np.asarray(i))) == 300


def test_topk_k_zero_and_empty():
    x = _rand(64, 2)
    v, i = ops.topk(jnp.asarray(x), 0)
    assert v.shape == (0,) and i.shape == (0,)
    v, i = ops.bottomk(jnp.asarray(x[:0]), 5)
    assert v.shape == (0,) and i.shape == (0,)


def test_topk_all_equal_keys():
    x = np.full(50_000, 3.25, np.float32)
    v, i = ops.bottomk(jnp.asarray(x), 17, cfg=_small_cfg)
    np.testing.assert_array_equal(np.asarray(v), x[:17])
    assert len(np.unique(np.asarray(i))) == 17


def test_topk_small_n_base_case_path():
    # n <= base_case: degenerates to the plain stable base case
    x = _rand(100, 5)
    v, i = ops.bottomk(jnp.asarray(x), 3, cfg=_small_cfg)
    np.testing.assert_array_equal(np.asarray(v), np.sort(x)[:3])


def test_topk_with_nans():
    # NaN is the maximum of the keyspace total order (like lax.top_k):
    # topk surfaces NaNs first, bottomk ranks them last.
    x = _rand(30_000, 11)
    x[:50] = np.nan
    v, _ = ops.topk(jnp.asarray(x), 60, cfg=_small_cfg)
    v = np.asarray(v)
    assert np.all(np.isnan(v[:50]))
    np.testing.assert_array_equal(v[50:], np.sort(x[50:])[::-1][:10])
    bv, _ = ops.bottomk(jnp.asarray(x), 10, cfg=_small_cfg)
    assert not np.any(np.isnan(np.asarray(bv)))


def test_topk_int_extremes():
    # int32 max encodes to the pad-sentinel code; must still be selected
    x = np.asarray(np.random.default_rng(0).integers(-100, 100, 20_000), np.int32)
    x[:5] = np.iinfo(np.int32).max
    v, _ = ops.topk(jnp.asarray(x), 8, cfg=_small_cfg)
    np.testing.assert_array_equal(np.asarray(v), np.sort(x)[::-1][:8])


@pytest.mark.parametrize(
    "entry", ["sort", "argsort", "bottomk", "topk", "group_by", "unique"]
)
def test_explicit_axis_sharded_input(entry):
    # jax.make_mesh builds Explicit axes: an input placed on such a mesh
    # carries its sharding in its type, and the engine's whole-array pads,
    # scatters and gathers must still resolve (one device: no data moves)
    import jax
    from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

    mesh = jax.make_mesh((1,), ("data",))
    assert mesh.axis_types == (AxisType.Explicit,)
    x = _rand(6_000, 4)
    ids = (np.arange(6_000) * 7919 % 37).astype(np.int32)
    xs, vs, gs = (
        jax.device_put(jnp.asarray(a), NamedSharding(mesh, P("data")))
        for a in (x, np.arange(6_000, dtype=np.int32), ids)
    )
    if entry == "sort":
        k, v = ops.sort(xs, vs, cfg=_small_cfg)
        np.testing.assert_array_equal(np.asarray(k), np.sort(x))
        np.testing.assert_array_equal(np.asarray(v), np.argsort(x, kind="stable"))
    elif entry == "argsort":
        o = ops.argsort(xs, cfg=_small_cfg)
        np.testing.assert_array_equal(np.asarray(o), np.argsort(x, kind="stable"))
    elif entry in ("bottomk", "topk"):
        v, i = getattr(ops, entry)(xs, 7, cfg=_small_cfg)
        want = np.sort(x)[:7] if entry == "bottomk" else np.sort(x)[::-1][:7]
        np.testing.assert_array_equal(np.asarray(v), want)
        np.testing.assert_array_equal(x[np.asarray(i)], want)
    elif entry == "group_by":
        g = ops.group_by(gs, vs, num_groups=37)
        order = np.argsort(ids, kind="stable")
        np.testing.assert_array_equal(np.asarray(g.keys), ids[order])
        np.testing.assert_array_equal(np.asarray(g.values), order)
    else:
        vals, counts, num = ops.unique(gs, cfg=_small_cfg)
        want, want_counts = np.unique(ids, return_counts=True)
        assert int(num) == len(want)
        np.testing.assert_array_equal(np.asarray(vals)[: len(want)], want)
        np.testing.assert_array_equal(np.asarray(counts)[: len(want)], want_counts)


# ---------------------------------------------------------------- segmented
@pytest.mark.parametrize("n,nseg", [(3_000, 4), (40_000, 9), (2_000, 1)])
def test_segmented_sort(n, nseg):
    rng = np.random.default_rng(nseg)
    cuts = np.sort(rng.integers(0, n, nseg - 1)) if nseg > 1 else np.empty(0, np.int64)
    offs = np.concatenate([[0], cuts, [n]]).astype(np.int32)
    x = rng.standard_normal(n).astype(np.float32)
    out = np.asarray(
        ops.segmented_sort(jnp.asarray(x), jnp.asarray(offs), nseg, cfg=_small_cfg)
    )
    for a, b in zip(offs[:-1], offs[1:]):
        np.testing.assert_array_equal(out[a:b], np.sort(x[a:b]))


def test_segmented_sort_payload_and_empty_segments():
    n, nseg = 10_000, 6
    offs = np.asarray([0, 0, 2_500, 2_500, 9_000, 9_000, n], np.int32)  # empties
    rng = np.random.default_rng(7)
    x = rng.standard_normal(n).astype(np.float32)
    v = np.arange(n, dtype=np.int32)
    ks, vs = ops.segmented_sort(
        jnp.asarray(x), jnp.asarray(offs), nseg, jnp.asarray(v), cfg=_small_cfg
    )
    ks, vs = np.asarray(ks), np.asarray(vs)
    np.testing.assert_array_equal(x[vs], ks)
    for a, b in zip(offs[:-1], offs[1:]):
        np.testing.assert_array_equal(ks[a:b], np.sort(x[a:b]))
        assert set(vs[a:b]) == set(range(a, b))  # payload stays in-segment


def test_segmented_sort_skewed_segment_fallback():
    # one huge all-distinct segment forces buckets past W/2 at tiny k ->
    # the (segment, key) stable fallback must kick in and stay per-segment
    n = 8_192
    offs = np.asarray([0, 100, n], np.int32)
    x = np.random.default_rng(13).permutation(n).astype(np.float32)
    out = np.asarray(
        ops.segmented_sort(
            jnp.asarray(x), jnp.asarray(offs), 2, k=2,
            cfg=SortConfig(base_case=512, kmax=4, tile=256, max_sample=64),
        )
    )
    for a, b in zip(offs[:-1], offs[1:]):
        np.testing.assert_array_equal(out[a:b], np.sort(x[a:b]))


# ---------------------------------------------------------------- grouping
def test_unique_against_numpy():
    x = np.random.default_rng(5).integers(0, 37, 25_000).astype(np.int32)
    uv, uc, un = ops.unique(jnp.asarray(x), cfg=_small_cfg)
    un = int(un)
    ref_v, ref_c = np.unique(x, return_counts=True)
    assert un == len(ref_v)
    np.testing.assert_array_equal(np.asarray(uv)[:un], ref_v)
    np.testing.assert_array_equal(np.asarray(uc)[:un], ref_c)


def test_unique_all_equal_and_empty():
    x = np.full(5_000, 2.5, np.float32)
    uv, uc, un = ops.unique(jnp.asarray(x), cfg=_small_cfg)
    assert int(un) == 1 and float(np.asarray(uv)[0]) == 2.5
    assert int(np.asarray(uc)[0]) == 5_000
    _, _, un0 = ops.unique(jnp.asarray(x[:0]))
    assert int(un0) == 0


def test_run_length():
    x = np.asarray([5, 5, 1, 1, 1, 9, 5, 5], np.float32)
    rv, rc, rn = ops.run_length(jnp.asarray(x))
    rn = int(rn)
    np.testing.assert_array_equal(np.asarray(rv)[:rn], [5, 1, 9, 5])
    np.testing.assert_array_equal(np.asarray(rc)[:rn], [2, 3, 1, 2])


def test_run_length_nan_runs():
    x = np.asarray([np.nan, np.nan, 1.0, np.nan], np.float32)
    rv, rc, rn = ops.run_length(jnp.asarray(x))
    assert int(rn) == 3  # NaN == NaN under keyspace equality
    np.testing.assert_array_equal(np.asarray(rc)[:3], [2, 1, 1])


@pytest.mark.parametrize("method", ["partition", "pallas"])
def test_group_by_int_engines(method):
    E, n = 13, 26 * 1000
    ids = np.random.default_rng(11).integers(0, E, n).astype(np.int32)
    vals = np.arange(n, dtype=np.int32)
    g = ops.group_by(jnp.asarray(ids), jnp.asarray(vals), num_groups=E, method=method)
    np.testing.assert_array_equal(np.asarray(g.counts), np.bincount(ids, minlength=E))
    gk, gv = np.asarray(g.keys), np.asarray(g.values)
    assert np.all(np.diff(gk) >= 0)
    np.testing.assert_array_equal(ids[gv], gk)  # payload association
    # stability: within a group, source order preserved
    for e in range(E):
        grp = gv[gk == e]
        assert np.all(np.diff(grp) > 0)


def test_group_by_sort_engine_generic_keys():
    x = np.random.default_rng(17).choice(
        np.asarray([0.5, -3.0, np.nan, 7.25], np.float32), 20_000
    )
    g = ops.group_by(jnp.asarray(x), cfg=_small_cfg)
    num = int(g.num_groups)
    assert num == 4
    gk = np.asarray(g.keys)
    np.testing.assert_array_equal(gk, np.sort(x))
    gids = np.asarray(g.group_ids)
    assert gids[0] == 0 and gids[-1] == num - 1
    counts = np.asarray(g.counts)[:num]
    assert counts.sum() == 20_000
    # perm recovers the original positions
    np.testing.assert_array_equal(x[np.asarray(g.perm)], gk)


# ---------------------------------------------------------------- plan cache
def test_plan_cache_roundtrip(tmp_path):
    path = str(tmp_path / "plans.json")
    pc = ops.PlanCache(path=path)
    f = pc.get_sorter(2_048, jnp.float32, "sort", tune=True)
    x = jnp.asarray(_rand(2_048, 1))
    np.testing.assert_array_equal(np.asarray(f(x)), np.sort(np.asarray(x)))
    assert os.path.exists(path)
    # a fresh cache instance loads the persisted plan without re-tuning
    pc2 = ops.PlanCache(path=path)
    key = list(pc2._plans)[0]
    assert "config" in pc2._plans[key] and "us" in pc2._plans[key]
    cfg = pc2.config_for("sort", 2_048, jnp.float32)
    assert isinstance(cfg, SortConfig)
    # compiled callables are memoized per (op, n, dtype, k)
    assert pc.get_sorter(2_048, jnp.float32, "sort") is f


def test_plan_cache_topk_requires_k(tmp_path):
    pc = ops.PlanCache(path=str(tmp_path / "p.json"))
    with pytest.raises(ValueError, match="requires k"):
        pc.get_sorter(1_000, jnp.float32, "topk")
    f = pc.get_sorter(4_096, jnp.float32, "bottomk", k=5)
    x = jnp.asarray(_rand(4_096, 2))
    v, i = f(x)
    np.testing.assert_array_equal(np.asarray(v), np.sort(np.asarray(x))[:5])


def test_get_sorter_module_level():
    f = ops.get_sorter(1_024, jnp.int32, op="argsort")
    x = jnp.asarray(np.random.default_rng(3).integers(0, 50, 1_024), jnp.int32)
    order = np.asarray(f(x))
    np.testing.assert_array_equal(np.asarray(x)[order], np.sort(np.asarray(x)))
