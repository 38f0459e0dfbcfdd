"""Deferred payload (DESIGN.md §4.9): ``ips4o_sort`` with a payload of two
or more 32-bit words a row sorts (key, row index) and gathers every value
leaf once at the end; a one-word payload keeps the path that moves it
through every round.

The contract under test:

  * **parity**: a multi-leaf ``ops.sort`` equals, bit for bit, the
    one-leaf calls (which keep the undeferred path) and a NumPy stable
    argsort — at every depth (no level, one level, two levels), with
    sentinel-valued real keys, on both branches of the base case, and
    under ``jit``;
  * **switch rule**: the path engages by width alone — ``argsort`` and a
    one-word payload neither count ``sort.payload_deferred`` nor carry a
    ``sort.payload`` scope; wider payloads count their words.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import obs, ops
from repro.core.ips4o import SortConfig, plan_levels


@pytest.fixture(autouse=True)
def _obs_clean():
    obs.enabled(False)
    obs.reset()
    yield
    obs.enabled(False)
    obs.reset()


# the adversarial geometry of tests/test_sort_core.py: a few huge buckets
# of distinct keys overflow W/2, so the robustness fallback runs
_FALLBACK_CFG = SortConfig(base_case=2048, kmax=8, slack=1, max_sample=64)

# (n, key kind, cfg, levels, branch of the base case)
CASES = [
    (5_000, "u32", SortConfig(), 0, None),
    (50_000, "u32", SortConfig(), 1, "base_case"),
    (140_000, "f32", SortConfig(), 2, "base_case"),
    (50_000, "f32", _FALLBACK_CFG, 2, "fallback"),
    (60_000, "u32", _FALLBACK_CFG, 2, "fallback"),
]


def _keys(kind, n, cfg, seed):
    rng = np.random.default_rng(seed)
    if cfg is _FALLBACK_CFG:
        # 97 % of the keys spread over a narrow band of distinct values
        heavy = rng.random(n) < 0.97
        x = np.where(heavy, 1000 + rng.integers(0, 4 * n, n), rng.integers(0, 2**31, n))
    else:
        x = rng.integers(0, max(n // 30, 2), n)  # ~30 rows a value: ties
    if kind == "u32":
        x = x.astype(np.uint32)
        x[rng.choice(n, 40, replace=False)] = np.iinfo(np.uint32).max
    else:
        x = x.astype(np.float32)
        x[rng.choice(n, 40, replace=False)] = np.nan
    return x


def _payload(n, seed):
    rng = np.random.default_rng(seed + 1)
    return (
        rng.integers(-(2**31), 2**31 - 1, n, dtype=np.int64).astype(np.int32),
        rng.standard_normal(n).astype(np.float32),
        rng.integers(0, 256, n).astype(np.uint8),
        rng.random(n) < 0.5,
        rng.integers(-1000, 1000, (n, 3)).astype(np.int32),
    )


def _branch(k, cfg):
    """Which branch of the base case the sort of ``k`` takes."""
    obs.enabled(True)
    obs.reset()
    jax.block_until_ready(ops.sort(jnp.asarray(k), engine="xla", cfg=cfg))
    fell = obs.counter_value("sort.fallback_engaged")
    obs.enabled(False)
    obs.reset()
    return "fallback" if fell else "base_case"


@pytest.mark.parametrize("jit", [False, True], ids=["eager", "jit"])
@pytest.mark.parametrize(
    "n,kind,cfg,levels,branch", CASES,
    ids=[f"{n}-{kind}-{b or 'nolevel'}" for n, kind, _, _, b in CASES],
)
def test_multi_leaf_sort_matches_one_leaf_calls_and_numpy(n, kind, cfg, levels, branch, jit):
    unit = max(cfg.base_case, cfg.tile)
    assert len(plan_levels(-(-n // unit) * unit, cfg)) == levels
    seed = n + levels
    k = _keys(kind, n, cfg, seed)
    leaves = _payload(n, seed)
    if branch is not None and not jit:
        assert _branch(k, cfg) == branch
    sort = lambda k, v: ops.sort(k, v, engine="xla", cfg=cfg)  # noqa: E731
    if jit:
        sort = jax.jit(sort)

    ks, vs = sort(jnp.asarray(k), tuple(jnp.asarray(a) for a in leaves))
    order = np.argsort(k, kind="stable")
    np.testing.assert_array_equal(np.asarray(ks), k[order])
    for got, a in zip(vs, leaves):
        assert got.dtype == a.dtype and got.shape == a.shape
        np.testing.assert_array_equal(np.asarray(got), a[order])
    # one word a row: the undeferred path, which must agree bit for bit
    for got, a in zip(vs[:4], leaves[:4]):
        k1, v1 = sort(jnp.asarray(k), jnp.asarray(a))
        np.testing.assert_array_equal(np.asarray(k1).view(np.uint32), np.asarray(ks).view(np.uint32))
        np.testing.assert_array_equal(np.asarray(v1), np.asarray(got))
    col = sort(jnp.asarray(k), jnp.asarray(leaves[4][:, 1]))[1]
    np.testing.assert_array_equal(np.asarray(col), np.asarray(vs[4])[:, 1])


@pytest.mark.parametrize(
    "values,words",
    [
        (lambda n: jnp.arange(n, dtype=jnp.int32), 1),
        (lambda n: {"a": jnp.zeros(n, jnp.uint8)}, 1),
        (lambda n: (jnp.zeros(n, jnp.int32), jnp.zeros(n, jnp.bool_)), 2),
        (lambda n: {"a": jnp.zeros((n, 3), jnp.float32), "b": jnp.zeros(n, jnp.uint32)}, 4),
        (lambda n: [jnp.zeros((n, 2, 2), jnp.int8)], 4),
    ],
    ids=["index", "one-byte", "two-leaves", "wide-leaf", "matrix-leaf"],
)
def test_deferral_counts_payload_words(values, words):
    n = 3000
    k = jnp.asarray(np.random.default_rng(0).integers(0, 50, n), jnp.int32)
    obs.enabled(True)
    obs.reset()
    ops.sort(k, values(n), engine="xla")
    assert obs.counter_value("sort.payload_deferred") == (words if words >= 2 else 0)


def test_argsort_is_not_deferred():
    k = jnp.asarray(np.random.default_rng(1).standard_normal(3000), jnp.float32)
    obs.enabled(True)
    obs.reset()
    order = ops.argsort(k, engine="xla")
    assert obs.counter_value("sort.payload_deferred") == 0
    np.testing.assert_array_equal(np.asarray(order), np.argsort(np.asarray(k), kind="stable"))


def _compiled(f, *args):
    return jax.jit(f).lower(*args).compile().as_text()


@pytest.mark.parametrize(
    "entry,deferred",
    [
        (lambda k, v: ops.argsort(k, engine="xla"), False),
        (lambda k, v: ops.sort(k, v[0], engine="xla"), False),
        (lambda k, v: ops.sort(k, v, engine="xla"), True),
    ],
    ids=["argsort", "one-word", "two-words"],
)
def test_payload_scope_only_where_deferred(entry, deferred):
    n = 20_000  # one level and the base case
    k = jnp.asarray(np.random.default_rng(2).integers(0, 900, n), jnp.int32)
    v = (jnp.arange(n, dtype=jnp.int32), jnp.zeros(n, jnp.float32))
    hlo = _compiled(entry, k, v)
    assert ("sort.payload" in hlo) == deferred
    assert "sort.level1" in hlo


@pytest.mark.parametrize("width", [2, 3])
def test_sort_records_with_a_multi_leaf_payload(width):
    """Records of several words carry ``rest`` and the caller's payload
    through the tie-break passes, deferred; the result is ``np.lexsort``'s."""
    from oracle import lex_argsort_words

    n = 6000
    rng = np.random.default_rng(width)
    words = rng.integers(0, 4, (n, width)).astype(np.uint32)
    vals = {"id": np.arange(n, dtype=np.int32), "f": rng.standard_normal((n, 2)).astype(np.float32)}
    out, got = ops.sort_records(
        jnp.asarray(words), jax.tree.map(jnp.asarray, vals), engine="xla"
    )
    order = lex_argsort_words(words)
    np.testing.assert_array_equal(np.asarray(out), words[order])
    np.testing.assert_array_equal(np.asarray(got["id"]), order)
    np.testing.assert_array_equal(np.asarray(got["f"]), vals["f"][order])
