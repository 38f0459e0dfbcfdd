"""Pallas TPU kernel: fused flash-DECODE attention (q_len = 1 vs a cache).

§Roofline identified decode cells running 4–15× above the ideal
params+cache read; after the CPU-artifact (2×) and scan-restack (≈2×)
shares, the remainder is the score/softmax/weighted-sum passes each
re-reading cache-sized tensors through HBM.  This kernel performs the
whole per-head reduction in one VMEM pass over the KV cache: HBM traffic
= K + V read once + (1, hd) out — the floor.

Grid: (B*H, T/bt) with a SEQUENTIAL reduction over the T axis carried in
VMEM scratch (m, l, acc persist across grid steps of the same (b,h) row;
TPU grid iteration is sequential so the carry is race-free — the same
property the in-place block permutation kernel relies on).  The `length`
operand masks the valid cache prefix, so one compiled kernel serves all
ring positions.

Per-step VMEM: k,v blocks (bt × hd) + q (1 × hd) + scratch ≈
2·bt·hd·4 B — bt = 1024, hd = 128: ~1 MiB.
"""
from __future__ import annotations

import functools
from typing import Optional
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

__all__ = ["flash_decode"]

NEG_INF = -1e30


def _kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
            *, bt: int, hd: int):
    length = len_ref[pl.program_id(0)]
    t_idx = pl.program_id(1)
    nt = pl.num_programs(1)

    @pl.when(t_idx == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32) * (1.0 / math.sqrt(hd))  # (1, hd)
    kb = k_ref[0].astype(jnp.float32)                         # (bt, hd)
    vb = v_ref[0].astype(jnp.float32)
    s = jnp.sum(q * kb, axis=-1)[None, :]                     # (1, bt)
    pos = t_idx * bt + jax.lax.broadcasted_iota(jnp.int32, (1, bt), 1)
    valid = pos < length
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_ref[...]                                       # (1, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    p = jnp.where(valid, p, 0.0)
    corr = jnp.exp(m_prev - m_new)
    m_ref[...] = m_new
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + p @ vb               # (1, hd)

    @pl.when(t_idx == nt - 1)
    def _fini():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bt", "interpret"))
def flash_decode(
    q: jax.Array,        # (B, H, 1, hd)
    k: jax.Array,        # (B, H, T, hd)  (GQA pre-expanded)
    v: jax.Array,        # (B, H, T, hd)
    length: jax.Array,   # (B,) int32: valid cache prefix per request
    *,
    bt: int = 1024,
    interpret: Optional[bool] = None,
) -> jax.Array:
    interpret = resolve_interpret(interpret)
    b, h, _, hd = q.shape
    t = k.shape[2]
    bt = min(bt, t)
    if t % bt:
        raise ValueError(f"cache len {t} must be a multiple of bt={bt}")
    bh = b * h
    qf = q.reshape(bh, 1, hd)
    kf = k.reshape(bh, t, hd)
    vf = v.reshape(bh, t, hd)
    lens = jnp.repeat(length.astype(jnp.int32), h)  # (bh,)

    out = pl.pallas_call(
        functools.partial(_kernel, bt=bt, hd=hd),
        grid=(bh, t // bt),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),             # lengths
            pl.BlockSpec((1, 1, hd), lambda i, j: (i, 0, 0)),  # q
            pl.BlockSpec((1, bt, hd), lambda i, j: (i, j, 0)),  # k block
            pl.BlockSpec((1, bt, hd), lambda i, j: (i, j, 0)),  # v block
        ],
        out_specs=pl.BlockSpec((1, 1, hd), lambda i, j: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, 1, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((1, 1), jnp.float32),    # m
            pltpu.VMEM((1, 1), jnp.float32),    # l
            pltpu.VMEM((1, hd), jnp.float32),   # acc
        ],
        interpret=interpret,
    )(lens, qf, kf, vf)
    return out.reshape(b, h, 1, hd)
