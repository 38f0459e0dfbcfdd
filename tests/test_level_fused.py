"""Bit-parity suite for the fused single-pass level kernel (DESIGN.md §10).

The fused kernel (``kernels.level_fused``) replaces the classify ->
histogram-glue -> counting-rank three-pass chain with ONE grid sweep.
The contract is *bit-identity*: destinations and bucket offsets must
equal the stable counting placement the "xla" engine computes, for every
classifier mode and every wrapper layer.  Covered here:

  * direct kernel parity vs a numpy stable-rank oracle (tree + radix
    classifiers, in-kernel pad routing, batched grid, ``rank_hist`` on
    precomputed ids with self-padding);
  * stack parity over all nine paper distributions x {f32, i32} x
    {single-level, two-level, batched, batched-two-level/segmented} —
    engine "pallas" vs engine "xla" through ``partition_passes`` /
    ``batched_partition_passes``, keys AND offsets bit-equal;
  * u64 keys in a subprocess (x64 must be enabled from interpreter
    startup — see tests/test_classify.py for why);
  * unit tests for the unified :class:`KernelLaunchSpec` every sort
    kernel now launches through.
"""
import os
import subprocess
import sys
from dataclasses import replace

import numpy as np
import jax.numpy as jnp
import pytest

from oracle import stable_dest
from repro import ops
from repro.classify import classify, radix_bucket_ids
from repro.core import sampling
from repro.core.ips4o import (
    SortConfig,
    _classify_rows,
    batched_pad_with_sentinel,
    batched_partition_passes,
    pad_with_sentinel,
    partition_passes,
    plan_levels,
)
from repro.data.distributions import DISTRIBUTIONS, make_input
from repro.kernels.level_fused import (
    fused_rows,
    level_fused,
    level_fused_batched,
    rank_hist,
    rank_hist_batched,
)
from repro.launch.roofline import (
    _CLASSIFY_VMEM_FRACTION,
    HW,
    _bytes_per_row,
    launch_spec,
    peaks,
    spec_candidates,
)

_cfg = SortConfig(base_case=1024, kmax=32, tile=256, max_sample=256, slack=4)


# ---------------------------------------------------------------------------
# oracles
# ---------------------------------------------------------------------------


# global stable counting placement (the scatter inverse of a stable
# argsort) — shared across suites in tests/oracle.py
_stable_dest = stable_dest


def _oracle_ids(keys, spl, k, n_real, clf, consumed=0):
    if clf == "radix":
        b = np.asarray(radix_bucket_ids(keys, k, consumed))
    else:
        b = np.asarray(classify(keys, spl, k))
    b = b.copy()
    b[n_real:] = 2 * k  # pad bucket
    return b


def _keys_for(dist, n, dtype, seed=7):
    """Sentinel-free encoded keyspace keys, 128-aligned length."""
    return ops.keyspace.encode(jnp.asarray(make_input(dist, n, dtype, seed=seed)))


def _splitters(keys, k, n_real, seed=0):
    samp = jnp.sort(keys[:n_real][: min(256, n_real)])
    return sampling.select_splitters(samp, k)


# ---------------------------------------------------------------------------
# direct kernel parity
# ---------------------------------------------------------------------------


class TestFusedKernelDirect:
    N, N_REAL, K = 6144, 6000, 32

    @pytest.mark.parametrize("dist", sorted(DISTRIBUTIONS))
    def test_tree_parity(self, dist):
        keys = _keys_for(dist, self.N, np.float32)
        spl = _splitters(keys, self.K, self.N_REAL)
        dest, off = level_fused(
            keys, spl, k=self.K, n_real=self.N_REAL, interpret=True
        )
        ids = _oracle_ids(keys, spl, self.K, self.N_REAL, "tree")
        want_dest, want_off = _stable_dest(ids, 2 * self.K + 1)
        np.testing.assert_array_equal(np.asarray(dest), want_dest)
        np.testing.assert_array_equal(np.asarray(off), want_off)

    @pytest.mark.parametrize("consumed", [0, 5])
    def test_radix_parity(self, consumed):
        keys = _keys_for("Uniform", self.N, np.int32)
        dest, off = level_fused(
            keys, None, k=self.K, n_real=self.N_REAL, classifier="radix",
            consumed_bits=consumed, interpret=True,
        )
        ids = _oracle_ids(keys, None, self.K, self.N_REAL, "radix", consumed)
        want_dest, want_off = _stable_dest(ids, 2 * self.K + 1)
        np.testing.assert_array_equal(np.asarray(dest), want_dest)
        np.testing.assert_array_equal(np.asarray(off), want_off)

    def test_no_pads(self):
        keys = _keys_for("TwoDup", self.N, np.int32)
        spl = _splitters(keys, self.K, self.N)
        dest, off = level_fused(keys, spl, k=self.K, interpret=True)
        ids = _oracle_ids(keys, spl, self.K, self.N, "tree")
        want_dest, want_off = _stable_dest(ids, 2 * self.K + 1)
        np.testing.assert_array_equal(np.asarray(dest), want_dest)
        np.testing.assert_array_equal(np.asarray(off), want_off)
        assert int(off[-2]) == self.N  # empty pad bucket

    def test_batched_parity(self):
        B, k = 3, 16
        rows_keys, spls = [], []
        for b in range(B):
            kb = _keys_for("Exponential", self.N, np.float32, seed=b)
            rows_keys.append(kb)
            spls.append(_splitters(kb, k, self.N_REAL, seed=b))
        keys = jnp.stack(rows_keys)
        spl = jnp.stack(spls)
        dest, off = level_fused_batched(
            keys, spl, k=k, n_real=self.N_REAL, interpret=True
        )
        for b in range(B):
            ids = _oracle_ids(rows_keys[b], spls[b], k, self.N_REAL, "tree")
            want_dest, want_off = _stable_dest(ids, 2 * k + 1)
            np.testing.assert_array_equal(np.asarray(dest[b]), want_dest)
            np.testing.assert_array_equal(np.asarray(off[b]), want_off)

    def test_rank_hist_self_pads(self):
        """Precomputed-ids variant: n not tile-aligned; the kernel pads
        with the all-zero one-hot trash id and trims the result."""
        nb = 65
        n = 5000  # not a multiple of any rows*128 tile
        ids = np.random.default_rng(0).integers(0, nb, n).astype(np.int32)
        dest, off = rank_hist(jnp.asarray(ids), nb=nb, interpret=True)
        want_dest, want_off = _stable_dest(ids, nb)
        np.testing.assert_array_equal(np.asarray(dest), want_dest)
        np.testing.assert_array_equal(np.asarray(off), want_off)

    def test_rank_hist_batched(self):
        nb, B, n = 33, 4, 2500
        ids = np.random.default_rng(1).integers(0, nb, (B, n)).astype(np.int32)
        dest, off = rank_hist_batched(jnp.asarray(ids), nb=nb, interpret=True)
        for b in range(B):
            want_dest, want_off = _stable_dest(ids[b], nb)
            np.testing.assert_array_equal(np.asarray(dest[b]), want_dest)
            np.testing.assert_array_equal(np.asarray(off[b]), want_off)


# ---------------------------------------------------------------------------
# stack parity: engine "pallas" (fused) vs engine "xla", all wrapper layers
# ---------------------------------------------------------------------------


def _passes_1d(x, cfg):
    arrays = pad_with_sentinel(
        {"k": ops.keyspace.encode(jnp.asarray(x))}, max(cfg.base_case, cfg.tile)
    )
    levels = plan_levels(arrays["k"].shape[0], cfg)
    out, off, nb, _ = partition_passes(arrays, len(x), cfg, levels)
    return np.asarray(out["k"]), np.asarray(off), levels, arrays["k"].shape[0]


def _passes_batched(x, cfg):
    arrays = batched_pad_with_sentinel(
        {"k": ops.keyspace.encode(jnp.asarray(x))}, max(cfg.base_case, cfg.tile)
    )
    levels = plan_levels(arrays["k"].shape[1], cfg)
    out, off, nb, _ = batched_partition_passes(arrays, x.shape[-1], cfg, levels)
    return np.asarray(out["k"]), np.asarray(off), levels, arrays["k"].shape[1]


_MODES = {
    # mode -> (n per row, batch B or None, expected number of levels)
    "single": (5000, None, 1),
    "two_level": (20000, None, 2),
    "batched": (3000, 3, 1),
    "segmented_batched": (12000, 2, 2),
}


@pytest.mark.parametrize("dist", sorted(DISTRIBUTIONS))
@pytest.mark.parametrize("dtype", [np.float32, np.int32])
@pytest.mark.parametrize("mode", sorted(_MODES))
def test_stack_parity(dist, dtype, mode):
    n, B, want_levels = _MODES[mode]
    if B is None:
        x = make_input(dist, n, dtype, seed=7)
        run = _passes_1d
    else:
        x = np.stack(
            [make_input(dist, n, dtype, seed=7 + b) for b in range(B)]
        )
        run = _passes_batched
    keys_x, off_x, levels, n_pad = run(x, replace(_cfg, engine="xla"))
    keys_p, off_p, _, _ = run(x, replace(_cfg, engine="pallas"))
    assert len(levels) == want_levels
    # the pallas run must actually take the fused path at level 1
    assert _classify_rows(n_pad, _cfg, np.dtype(dtype), levels[0]) > 0
    np.testing.assert_array_equal(off_x, off_p)
    np.testing.assert_array_equal(keys_x, keys_p)


_U64_CHILD = """
import numpy as np
import jax.numpy as jnp
from repro import ops
from repro.core import sampling
from repro.data.distributions import DISTRIBUTIONS, make_input
from repro.kernels.level_fused import level_fused

N, N_REAL, K = 6144, 6000, 32
for dist in sorted(DISTRIBUTIONS):
    keys = ops.keyspace.encode(jnp.asarray(make_input(dist, N, np.uint64, seed=7)))
    assert keys.dtype == jnp.uint64
    samp = jnp.sort(keys[:256])
    spl = sampling.select_splitters(samp, K)
    for clf in ("tree", "radix"):
        dest, off = level_fused(
            keys, None if clf == "radix" else spl, k=K, n_real=N_REAL,
            classifier=clf, interpret=True,
        )
        if clf == "radix":
            from repro.classify import radix_bucket_ids
            ids = np.array(radix_bucket_ids(keys, K, 0))
        else:
            from repro.classify import classify
            ids = np.array(classify(keys, spl, K))
        ids[N_REAL:] = 2 * K
        order = np.argsort(ids, kind="stable")
        want = np.empty(N, np.int32); want[order] = np.arange(N)
        np.testing.assert_array_equal(np.asarray(dest), want, err_msg=dist + clf)
        woff = np.concatenate([[0], np.cumsum(np.bincount(ids, minlength=2*K+1))])
        np.testing.assert_array_equal(np.asarray(off), woff)
print("u64 fused parity OK")
"""


def test_fused_parity_u64_subprocess():
    """u64 keys exercise the widest keyspace; x64 must be on from startup
    (see tests/test_classify.py), so the sweep runs in a child process."""
    env = dict(os.environ, JAX_ENABLE_X64="1", JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = (
        os.path.join(os.path.dirname(__file__), "..", "src")
        + os.pathsep
        + env.get("PYTHONPATH", "")
    )
    proc = subprocess.run(
        [sys.executable, "-c", _U64_CHILD],
        env=env,
        capture_output=True,
        text=True,
        timeout=1200,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "u64 fused parity OK" in proc.stdout


# ---------------------------------------------------------------------------
# the unified KernelLaunchSpec
# ---------------------------------------------------------------------------


class TestKernelLaunchSpec:
    def test_candidates_descending_powers_of_two(self):
        for kind, k in (("classify", 64), ("rank", 129), ("level_fused", 64),
                        ("merge", None), ("permute", None)):
            cands = spec_candidates(kind, 4, k)
            assert cands[-1] == 1
            assert all(a == 2 * b for a, b in zip(cands, cands[1:]))

    def test_leading_candidate_fits_vmem_budget(self):
        budget = HW["vmem_bytes"] // _CLASSIFY_VMEM_FRACTION
        for kind, k in (("classify", 128), ("level_fused", 128), ("rank", 257)):
            lead = spec_candidates(kind, 4, k)[0]
            assert lead * _bytes_per_row(kind, 4, k) <= budget

    def test_wider_keys_never_grow_the_tile(self):
        assert (spec_candidates("level_fused", 8, 128)[0]
                <= spec_candidates("level_fused", 4, 128)[0])
        assert (spec_candidates("classify", 4, 256)[0]
                <= spec_candidates("classify", 4, 32)[0])

    def test_n_filter(self):
        assert launch_spec("level_fused", 4, 32, n=1000).rows == 0
        spec = launch_spec("level_fused", 4, 32, n=6144)
        assert spec.rows > 0 and 6144 % spec.tile == 0

    def test_rows_pin(self):
        assert launch_spec("rank", 4, 65, rows=8).rows == 8
        # a pinned tile that does not divide n is rejected, not truncated
        assert launch_spec("rank", 4, 65, rows=8, n=1000).rows == 0

    def test_fused_rows_is_the_spec_projection(self):
        assert fused_rows(6144, 4, 32) == launch_spec(
            "level_fused", 4, 32, n=6144
        ).rows

    def test_merge_and_permute_kinds(self):
        assert launch_spec("merge", 4).tile == 1024
        assert spec_candidates("permute", 4)[0] <= 64


def test_peak_table_keyed_by_device_kind():
    """The roofline peaks belong to one chip kind; an unknown kind is an
    error, never a silent default."""
    assert peaks("TPU v5 lite") is HW
    assert HW["hbm_bw"] == 819e9 and HW["peak_flops"] == 197e12
    with pytest.raises(KeyError, match="cpu"):
        peaks("cpu")
