"""Pallas TPU kernel: the paper's in-place block permutation (§4.2), faithful.

This kernel realizes IPS4o's central mechanism *literally* on one TPU core:

  * the array is a sequence of N homogeneous blocks of b elements (the
    output of local classification);
  * per-bucket write/read pointers w_i, r_i live in SMEM (the paper keeps
    them in a 128-bit atomic word; on TPU the grid executes sequentially on
    a core, so one core == one paper-thread and no atomics are needed —
    cross-core parallelism happens one level up via shard_map stripes);
  * two VMEM swap buffers (the paper's "each thread maintains two local
    swap buffers") alternate roles via a parity flag;
  * each grid step performs exactly one block *write* (either swapping the
    swap buffer with the unprocessed block at w_dest, or dropping it into an
    empty slot), preceded — when the swap buffer is empty — by a cyclic
    primary-bucket scan and a block *read* that decrements r_p;
  * the data array is input/output aliased: the permutation is genuinely
    in-place in HBM; block moves are explicit HBM<->VMEM DMAs
    (``pltpu.make_async_copy``) — the TPU spelling of the paper's
    cache-block transfers.

Invariant per bucket (Fig. 3): [d_i, w_i) correct | [w_i, r_i) unprocessed |
[r_i, d_{i+1}) empty(read).  Each step preserves it; N writes complete the
permutation; grid = N+1 (the last step detects termination).

Not stable (the paper's permutation isn't either); the oracle checks
per-bucket block multisets.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

__all__ = ["permute_blocks_inplace"]

LANES = 128

# scalar state slots
S_FILLED, S_PRIMARY, S_DONE, S_SBB, S_CUR, S_MOVES = range(6)


def _kernel(d_ref, bb_ref, a_in, a_out, w_ref, r_ref, st_ref, swap0, swap1, sem,
            *, k: int, nblocks: int, brows: int):
    pid = pl.program_id(0)

    @pl.when(pid == 0)
    def _init():
        for i in range(k):
            w_ref[i] = d_ref[i]
            r_ref[i] = d_ref[i + 1]
        for s in range(6):
            st_ref[s] = 0

    def copy(src, dst):
        cp = pltpu.make_async_copy(src, dst, sem)
        cp.start()
        cp.wait()

    def block(ref, idx):
        return ref.at[pl.dslice(idx * brows, brows), :]

    def swap_ref(sel):
        # returns a pair (read_fn writing into, ...) — we emit both branches
        # under pl.when since refs can't be selected dynamically.
        return swap0 if sel == 0 else swap1

    @pl.when(st_ref[S_DONE] == 0)
    def _step():
        # ---- refill swap buffer if empty (cyclic primary-bucket scan) ----
        @pl.when(st_ref[S_FILLED] == 0)
        def _fill():
            # hoist the pointer reads out of the while_loop: SMEM scalars to
            # values first (k is small/static), so the loop carries no ref
            # effects — interpret-mode state discharge has no rule for a
            # ref-reading `while`.
            ws = jnp.stack([w_ref[i] for i in range(k)])
            rs = jnp.stack([r_ref[i] for i in range(k)])

            def cond(s):
                p, cnt = s
                return (cnt < k) & (ws[p] >= rs[p])

            def body(s):
                p, cnt = s
                return ((p + 1) % k, cnt + 1)

            p, cnt = jax.lax.while_loop(
                cond, body, (st_ref[S_PRIMARY], jnp.int32(0))
            )
            st_ref[S_PRIMARY] = p
            found = w_ref[p] < r_ref[p]

            @pl.when(found)
            def _read():
                src = r_ref[p] - 1
                r_ref[p] = src
                for sel in (0, 1):
                    @pl.when(st_ref[S_CUR] == sel)
                    def _(sel=sel):
                        copy(block(a_in, src), swap_ref(sel))
                st_ref[S_SBB] = bb_ref[src]
                st_ref[S_FILLED] = 1

            @pl.when(jnp.logical_not(found))
            def _done():
                st_ref[S_DONE] = 1

        # ---- one block write --------------------------------------------
        @pl.when(st_ref[S_FILLED] == 1)
        def _write():
            dest = st_ref[S_SBB]
            wd = w_ref[dest]
            exchange = wd < r_ref[dest]

            # Read the displaced block into the *other* swap buffer first.
            @pl.when(exchange)
            def _displace():
                for sel in (0, 1):
                    @pl.when(st_ref[S_CUR] == sel)
                    def _(sel=sel):
                        copy(block(a_in, wd), swap_ref(1 - sel))

            next_sbb = jnp.where(exchange, bb_ref[wd], 0)

            for sel in (0, 1):
                @pl.when(st_ref[S_CUR] == sel)
                def _(sel=sel):
                    copy(swap_ref(sel), block(a_out, wd))

            w_ref[dest] = wd + 1
            st_ref[S_MOVES] = st_ref[S_MOVES] + 1

            @pl.when(exchange)
            def _rotate():
                st_ref[S_CUR] = 1 - st_ref[S_CUR]
                st_ref[S_SBB] = next_sbb

            @pl.when(jnp.logical_not(exchange))
            def _emptied():
                st_ref[S_FILLED] = 0


@functools.partial(jax.jit, static_argnames=("k", "block_elems", "interpret"))
def permute_blocks_inplace(
    a: jax.Array,
    block_bucket: jax.Array,
    d: jax.Array,
    *,
    k: int,
    block_elems: int = 1024,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """In-place block permutation.

    Args:
      a: (N * block_elems,) data; block i is homogeneous (single bucket).
      block_bucket: (N,) int32 bucket of each block, in [0, k).
      d: (k+1,) int32 block-index bucket boundaries (from the histogram
         prefix sum); d[k] == N.
      k: number of buckets (static).
      block_elems: elements per block; must be a multiple of 128.

    Returns the permuted array (same buffer: input is aliased/donated).
    """
    interpret = resolve_interpret(interpret)
    if block_elems % LANES:
        raise ValueError("block_elems must be a multiple of 128")
    brows = block_elems // LANES
    n = a.shape[0]
    nblocks = n // block_elems
    if n != nblocks * block_elems:
        raise ValueError("array size must be a multiple of block_elems")
    a2 = a.reshape(nblocks * brows, LANES)

    out = pl.pallas_call(
        functools.partial(_kernel, k=k, nblocks=nblocks, brows=brows),
        grid=(nblocks + 1,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),  # d
            pl.BlockSpec(memory_space=pltpu.SMEM),  # block_bucket
            pl.BlockSpec(memory_space=pl.ANY),  # a (HBM)
        ],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        out_shape=jax.ShapeDtypeStruct(a2.shape, a2.dtype),
        scratch_shapes=[
            pltpu.SMEM((k,), jnp.int32),  # w
            pltpu.SMEM((k,), jnp.int32),  # r
            pltpu.SMEM((8,), jnp.int32),  # scalar state
            pltpu.VMEM((brows, LANES), a2.dtype),  # swap buffer 0
            pltpu.VMEM((brows, LANES), a2.dtype),  # swap buffer 1
            pltpu.SemaphoreType.DMA,
        ],
        input_output_aliases={2: 0},
        interpret=interpret,
    )(d, block_bucket, a2)
    return out.reshape(n)
