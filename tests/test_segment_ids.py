"""``segment_ids`` against the binary search it replaced, and its callers.

``segment_ids(offsets, n)`` counts the offsets <= p for every position p
(a scatter of the offsets and a cumsum); the ids must equal
``searchsorted(offsets, arange(n), side="right") - 1`` bit for bit on
every sorted offset vector, so the sorts built on it stay byte-identical.
"""
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import ops
from repro.core.ips4o import SortConfig, segment_ids

# the module of the one sort pipeline, whose base case takes the ids of
# every caller's level offsets (``ops.bottomk``, ``ops.segmented_sort``)
core_ips4o = importlib.import_module("repro.core.ips4o")

_small_cfg = SortConfig(base_case=1024, kmax=32, tile=256, max_sample=256, slack=4)


def _searched(offsets, n):
    return np.searchsorted(offsets, np.arange(n), side="right").astype(np.int32) - 1


def _random_offsets(rng, n, nb):
    return np.sort(rng.integers(0, n + 1, nb + 1)).astype(np.int32)


_CASES = {
    "one_bucket": (1024, np.asarray([0, 1024])),
    "empty_middle": (1024, np.asarray([0, 10, 10, 10, 500, 500, 1024])),
    "trailing_at_n": (1024, np.asarray([0, 100, 700, 1024, 1024, 1024])),
    "first_above_0": (1024, np.asarray([37, 37, 100, 1024])),
    "below_0": (1024, np.asarray([-5, -5, 0, 3, 1024])),
    "nb_geq_n": (64, _random_offsets(np.random.default_rng(1), 64, 200)),
    "n_not_mult_128": (1000, np.asarray([0, 1, 127, 128, 129, 999, 1000])),
    "random": (6_000, _random_offsets(np.random.default_rng(2), 6_000, 256)),
}


@pytest.mark.parametrize("case", list(_CASES))
def test_segment_ids_match_search(case):
    n, offsets = _CASES[case]
    offsets = offsets.astype(np.int32)
    got = jax.jit(segment_ids, static_argnums=1)(jnp.asarray(offsets), n)
    assert got.dtype == jnp.int32 and got.shape == (n,)
    np.testing.assert_array_equal(np.asarray(got), _searched(offsets, n))


def test_segment_ids_match_search_random_draws():
    rng = np.random.default_rng(3)
    f = jax.jit(segment_ids, static_argnums=1)
    for _ in range(200):
        offsets = _random_offsets(rng, 1_000, 64)
        np.testing.assert_array_equal(
            np.asarray(f(jnp.asarray(offsets), 1_000)), _searched(offsets, 1_000)
        )


def test_batched_segment_ids_rows_differ():
    n = 1000
    rows = np.stack(
        [
            [0] + [n] * 8,  # one bucket, a trailing run at n
            [0, 0, 0, 250, 250, 600, 600, 999, n],  # empty buckets
            [40, 41, 42, 43, 500, 900, 950, n, n],  # first offset above 0
            _random_offsets(np.random.default_rng(4), n, 8),
        ]
    ).astype(np.int32)
    # (B, nb+1) offsets go through the batch lift of the sort pipeline
    got = np.asarray(jax.vmap(lambda off: segment_ids(off, n))(jnp.asarray(rows)))
    np.testing.assert_array_equal(got, np.stack([_searched(r, n) for r in rows]))


def test_segment_ids_compiles_without_a_loop():
    # the binary search it replaced was a while loop of log2(nb) gathers
    text = (
        jax.jit(segment_ids, static_argnums=1)
        .lower(jnp.zeros((32_897,), jnp.int32), 1 << 20)
        .compile()
        .as_text()
    )
    assert "while" not in text


def _spy_offsets(monkeypatch, module):
    seen = []

    def spy(offsets, n):
        seen.append((np.asarray(offsets), n))
        return segment_ids(offsets, n)

    monkeypatch.setattr(module, "segment_ids", spy)
    return seen


def _empty_inside_and_run_at_n(offsets, n):
    inner = offsets[offsets < n]
    return bool(np.any(np.diff(inner) == 0)) and offsets[-2] == offsets[-1] == n


def _bottomk_case(monkeypatch):
    # n a multiple of the window: no pads, so the top buckets are empty
    n, k = 8_192, 3_000
    x = np.random.default_rng(5).integers(0, 40, n).astype(np.int32)
    seen = _spy_offsets(monkeypatch, core_ips4o)
    v, i = ops.bottomk(jnp.asarray(x), k, cfg=_small_cfg)
    order = np.argsort(x, kind="stable")[:k]
    return seen, [(np.asarray(v), x[order]), (np.asarray(i), order)]


def _segmented_case(monkeypatch):
    # an empty segment inside, an empty one at the end, no pads: the
    # level's offsets end in a run of the padded length
    n = 8_192
    offs = np.asarray([0, 1_000, 1_000, 5_000, n, n], np.int32)
    x = np.random.default_rng(6).integers(0, 25, n).astype(np.int32)
    seen = _spy_offsets(monkeypatch, core_ips4o)
    ks, vs = ops.segmented_sort(
        jnp.asarray(x),
        jnp.asarray(offs),
        len(offs) - 1,
        jnp.arange(n, dtype=jnp.int32),
        cfg=_small_cfg,
    )
    order = np.concatenate(
        [a + np.argsort(x[a:b], kind="stable") for a, b in zip(offs[:-1], offs[1:])]
    )
    return seen, [(np.asarray(ks), x[order]), (np.asarray(vs), order)]


@pytest.mark.parametrize(
    "caller", [_bottomk_case, _segmented_case], ids=["bottomk", "segmented_sort"]
)
def test_callers_match_stable_argsort(monkeypatch, caller):
    seen, pairs = caller(monkeypatch)
    assert any(_empty_inside_and_run_at_n(o, n) for o, n in seen), seen
    for got, want in pairs:
        np.testing.assert_array_equal(got, want)
