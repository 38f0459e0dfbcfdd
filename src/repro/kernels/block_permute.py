"""Pallas TPU kernel: stable in-place block permutation by explicit dests.

The paper's §4.2 block permutation, upgraded from the bucket-pointer form
(``kernels.permute_inplace``) to *explicit per-block destinations*: the
caller hands every full block its final slot (``dst``, a permutation of
[0, N)), and the kernel chases the permutation cycles HBM-in-place:

  * the data array is input/output aliased (``input_output_aliases``) —
    no second n-sized buffer exists; block moves are explicit HBM<->VMEM
    DMAs through two swap buffers alternating via a parity flag (the
    paper's "two local swap buffers per thread");
  * a VMEM visited bitmap (one int32 lane per block) tracks which slots'
    original content has been consumed; the next cycle head is the first
    unvisited slot (one vectorized ``argmin`` — no sequential scan loop);
  * each grid step performs exactly one block *write* — swapping the held
    block into its destination after DMA-ing the displaced block into the
    other buffer, or dropping it into an already-consumed slot (cycle
    close) — preceded, when no block is held, by the cycle-head scan and
    read.  N writes complete the permutation; grid = N + 1.

Because ``dst`` is explicit, the placement is whatever the caller
computed — ``core.partition.partition_blocks`` passes the *stable* block
order (``argsort(block_bucket, stable=True)`` inverted), so unlike the
bucket-pointer kernel this one realizes the stable grouping, and the
kernel and fallback paths of ``partition_blocks`` now agree exactly.

Cleanup phase (paper §4.3, the overflow block): a trailing *partial*
block (n % block_elems = r > 0) cannot ride the block DMAs.  It is the
analogue of the paper's overflow block: the caller guarantees it already
sits at its final position (its bucket is >= every full block's bucket —
true by construction for the sentinel-pad tail bucket), and the cleanup
re-attaches the r tail elements outside the grid.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

__all__ = ["permute_blocks_by_dest", "stable_block_dest"]

LANES = 128

# scalar state slots
S_FILLED, S_DONE, S_CUR, S_DST = range(4)


def stable_block_dest(block_bucket: jax.Array) -> jax.Array:
    """Destination slot of every block under the *stable* bucket grouping:
    dst[i] = #blocks with a smaller bucket + #earlier blocks of the same
    bucket.  The scatter form of ``argsort(block_bucket, stable=True)``."""
    nblocks = block_bucket.shape[0]
    order = jnp.argsort(block_bucket, stable=True).astype(jnp.int32)
    return (
        jnp.zeros((nblocks,), jnp.int32)
        .at[order]
        .set(jnp.arange(nblocks, dtype=jnp.int32), mode="promise_in_bounds")
    )


def _kernel(dst_ref, a_in, a_out, visited, st_ref, swap0, swap1, sem,
            *, nblocks: int, brows: int):
    pid = pl.program_id(0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, nblocks), 1)

    @pl.when(pid == 0)
    def _init():
        visited[...] = jnp.zeros((1, nblocks), jnp.int32)
        for s in range(4):
            st_ref[s] = 0

    def copy(src, dst):
        cp = pltpu.make_async_copy(src, dst, sem)
        cp.start()
        cp.wait()

    def block(ref, idx):
        return ref.at[pl.dslice(idx * brows, brows), :]

    def swap_ref(sel):
        return swap0 if sel == 0 else swap1

    @pl.when(st_ref[S_DONE] == 0)
    def _step():
        # ---- cycle-head scan + read when no block is held ----------------
        @pl.when(st_ref[S_FILLED] == 0)
        def _scan():
            vi = visited[...]  # (1, nblocks)
            # first unvisited slot, vectorized: the least lane whose bit is
            # 0 (an int min-reduction; Mosaic's argmin takes only float32)
            head = jnp.min(jnp.where(vi == 0, lane, nblocks))
            found = head < nblocks

            @pl.when(found)
            def _read():
                for sel in (0, 1):
                    @pl.when(st_ref[S_CUR] == sel)
                    def _(sel=sel):
                        copy(block(a_in, head), swap_ref(sel))
                visited[...] = jnp.maximum(vi, (lane == head).astype(jnp.int32))
                st_ref[S_DST] = dst_ref[head]
                st_ref[S_FILLED] = 1

            @pl.when(jnp.logical_not(found))
            def _done():
                st_ref[S_DONE] = 1

        # ---- one block write --------------------------------------------
        @pl.when(st_ref[S_FILLED] == 1)
        def _write():
            d = st_ref[S_DST]
            vi = visited[...]
            # slot d still holds unconsumed content iff its visited lane is 0
            occupied = jnp.sum(jnp.where(lane == d, vi, 0)) == 0

            @pl.when(occupied)
            def _displace():
                for sel in (0, 1):
                    @pl.when(st_ref[S_CUR] == sel)
                    def _(sel=sel):
                        copy(block(a_in, d), swap_ref(1 - sel))

            next_dst = dst_ref[d]

            for sel in (0, 1):
                @pl.when(st_ref[S_CUR] == sel)
                def _(sel=sel):
                    copy(swap_ref(sel), block(a_out, d))

            visited[...] = jnp.maximum(vi, (lane == d).astype(jnp.int32))

            @pl.when(occupied)
            def _rotate():
                st_ref[S_CUR] = 1 - st_ref[S_CUR]
                st_ref[S_DST] = next_dst

            @pl.when(jnp.logical_not(occupied))
            def _emptied():
                st_ref[S_FILLED] = 0


@functools.partial(jax.jit, static_argnames=("block_elems", "interpret"))
def permute_blocks_by_dest(
    a: jax.Array,
    dst: jax.Array,
    *,
    block_elems: int = 1024,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Move block i of ``a`` to slot dst[i], HBM-in-place.

    Args:
      a: (n,) data, n >= N * block_elems with N = n // block_elems full
         blocks; a trailing partial block of r = n % block_elems elements
         is the *overflow block* — the caller guarantees it already sits
         at its final (tail) position and the cleanup phase re-attaches it
         untouched.
      dst: (N,) int32, a permutation of [0, N): block i's destination
         slot.  For stable bucket grouping use :func:`stable_block_dest`.
      block_elems: elements per block; must be a multiple of 128.

    Returns the permuted array (same HBM buffer for the aligned prefix:
    input is aliased/donated).
    """
    interpret = resolve_interpret(interpret)
    if block_elems % LANES:
        raise ValueError("block_elems must be a multiple of 128")
    brows = block_elems // LANES
    n = a.shape[0]
    nblocks = n // block_elems
    r = n - nblocks * block_elems
    if nblocks <= 1:
        return a
    body, tail = (a[: n - r], a[n - r :]) if r else (a, None)
    a2 = body.reshape(nblocks * brows, LANES)

    out = pl.pallas_call(
        functools.partial(_kernel, nblocks=nblocks, brows=brows),
        grid=(nblocks + 1,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),  # dst
            pl.BlockSpec(memory_space=pl.ANY),  # a (HBM)
        ],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        out_shape=jax.ShapeDtypeStruct(a2.shape, a2.dtype),
        scratch_shapes=[
            pltpu.VMEM((1, nblocks), jnp.int32),  # visited bitmap
            pltpu.SMEM((4,), jnp.int32),  # scalar state
            pltpu.VMEM((brows, LANES), a2.dtype),  # swap buffer 0
            pltpu.VMEM((brows, LANES), a2.dtype),  # swap buffer 1
            pltpu.SemaphoreType.DMA,
        ],
        input_output_aliases={1: 0},
        interpret=interpret,
    )(dst.astype(jnp.int32), a2)
    flat = out.reshape(n - r)
    # cleanup phase: re-attach the overflow (partial boundary) block
    return jnp.concatenate([flat, tail]) if r else flat
