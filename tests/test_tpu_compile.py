"""Native TPU compiles of the sort path's Pallas kernels, with no chip.

Interpret mode (what every other suite runs here) hides the Mosaic
refusals: block shapes off the (8, 128) tiling, primitives the TPU lowering
lacks, layouts it cannot relayout.  These tests compile each kernel of the
sort path — and the whole ``ops.sort(engine="pallas")`` program at the
engine's 2^24 limit — for a *described* v5e chip, natively, at real sizes.
Nothing runs; a compile that passes is not a chip run.

The topology is described only inside a module-scoped fixture: only one
process may load the TPU library, and it keeps it until it exits, so
nothing here may touch it at import time.  ``kernels.resolve_interpret``
sees the CPU backend and would pick interpret mode, so the ``native``
fixture steers every module that calls it to native lowering.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import repro.core.ips4o
import repro.kernels
import repro.kernels.dispatch_rank
import repro.kernels.level_fused
import repro.kernels.merge_path
from repro import ops
from repro.core.ips4o import SortConfig
from repro.kernels.dispatch_rank import dispatch_ranks, partition_ranks
from repro.kernels.level_fused import level_fused, rank_hist
from repro.kernels.bitonic import bitonic_sort_windows
from repro.kernels.block_permute import permute_blocks_by_dest
from repro.kernels.classify import (
    classify_histogram,
    classify_histogram_batched,
    radix_histogram,
    radix_histogram_batched,
)
from repro.kernels.flash_decode import flash_decode
from repro.kernels.merge_path import merge_path_perm

HBM_BYTES = 16 * 2**30  # one v5e chip


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a described chip's compiles cannot be read back from the persistent
    # cache: keep it off for these compiles
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture
def native(monkeypatch):
    def resolve(interpret=None):
        return False if interpret is None else interpret

    for mod in (repro.kernels, repro.core.ips4o, repro.kernels.level_fused,
                repro.kernels.dispatch_rank, repro.kernels.merge_path):
        monkeypatch.setattr(mod, "resolve_interpret", resolve)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


N = 1 << 24  # the two-level limit of the engine (core/ips4o.py plan_levels)

# name -> (kernel call, [(shape, dtype) of each operand])
KERNELS = {
    "level_fused_tree": (
        functools.partial(level_fused, k=128, n_real=N - 1000, interpret=False),
        [((N,), jnp.uint32), ((127,), jnp.uint32)],
    ),
    "level_fused_radix": (
        functools.partial(level_fused, k=128, classifier="radix", interpret=False),
        [((N,), jnp.uint32)],
    ),
    # the (B, tiles) grid is Pallas's batching rule on the 1-D kernel
    "level_fused_batched": (
        jax.vmap(functools.partial(level_fused, k=16, interpret=False)),
        [((64, 1 << 16), jnp.uint32), ((64, 15), jnp.uint32)],
    ),
    "rank_hist": (
        functools.partial(rank_hist, nb=257, interpret=False),
        [((N - 1000,), jnp.int32)],
    ),
    "rank_hist_batched": (
        jax.vmap(functools.partial(rank_hist, nb=1024, interpret=False)),
        [((8, 1 << 18), jnp.int32)],
    ),
    "dispatch_ranks": (
        functools.partial(dispatch_ranks, num_experts=65, interpret=False),
        [((1 << 20,), jnp.int32), ((65,), jnp.int32)],
    ),
    "partition_ranks": (
        functools.partial(partition_ranks, nb=257, interpret=False),
        [((N - 1000,), jnp.int32), ((257,), jnp.int32)],
    ),
    "merge_path_perm": (
        functools.partial(merge_path_perm, interpret=False),
        [((1 << 22,), jnp.uint32), ((1 << 22,), jnp.uint32)],
    ),
    # off the sort path: the window sort, the three-pass classify chain's
    # first pass, the in-place block mover and the decode attention
    "bitonic_sort_windows": (
        functools.partial(bitonic_sort_windows, interpret=False),
        [((N // 8192, 8192), jnp.int32), ((N // 8192, 8192), jnp.uint32),
         ((N // 8192, 8192), jnp.int32)],
    ),
    "classify_histogram": (
        functools.partial(classify_histogram, k=128, interpret=False),
        [((N,), jnp.uint32), ((127,), jnp.uint32)],
    ),
    "classify_histogram_batched": (
        functools.partial(classify_histogram_batched, k=16, interpret=False),
        [((64, 1 << 16), jnp.uint32), ((64, 15), jnp.uint32)],
    ),
    "radix_histogram": (
        functools.partial(radix_histogram, k=128, interpret=False),
        [((N,), jnp.uint32)],
    ),
    "radix_histogram_batched": (
        functools.partial(radix_histogram_batched, k=16, interpret=False),
        [((64, 1 << 16), jnp.uint32)],
    ),
    "permute_blocks_by_dest": (
        functools.partial(permute_blocks_by_dest, block_elems=1024, interpret=False),
        [((N,), jnp.uint32), ((N // 1024,), jnp.int32)],
    ),
    "flash_decode": (
        functools.partial(flash_decode, interpret=False),
        [((8, 16, 1, 128), jnp.bfloat16), ((8, 16, 4096, 128), jnp.bfloat16),
         ((8, 16, 4096, 128), jnp.bfloat16), ((8,), jnp.int32)],
    ),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_natively(one_chip, name):
    fn, operands = KERNELS[name]
    _compile(fn, *(jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in operands))


@pytest.mark.parametrize("classifier", ["tree", "radix"])
def test_ops_sort_pallas_compiles_at_limit(one_chip, native, classifier):
    """The whole sort program, payload included: the level kernel is in it
    and the program fits one chip's HBM."""
    cfg = SortConfig(engine="pallas", classifier=classifier)
    keys = jax.ShapeDtypeStruct((N,), jnp.float32, sharding=one_chip)
    vals = jax.ShapeDtypeStruct((N,), jnp.int32, sharding=one_chip)
    compiled = _compile(lambda k, v: ops.sort(k, v, cfg=cfg), keys, vals)
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert total < HBM_BYTES, f"{total / 2**30:.2f} GiB does not fit one chip"
