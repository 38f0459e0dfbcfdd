"""Pallas TPU kernel: fused flash attention (causal / windowed, GQA).

§Perf motivation (EXPERIMENTS.md): after the JAX-level KV-chunked attention
removed the head-contraction all-reduce, the dominant roofline term of the
32k prefill cells became HBM traffic of the *chunk score matrices* — XLA
materializes the (B, H, S, block) logits between the two chunk einsums, so
every layer still moves ~88 GB/device through HBM.  The fix is the classic
fused kernel: scores live and die in VMEM.

Grid: (B*H, S/bq) — one grid step owns a (bq, hd) query block and loops the
KV blocks with ``jax.lax.fori_loop``, carrying the online-softmax state
(m, l, acc) in VMEM.  Per-step VMEM: q (bq x hd) + k,v (bk x hd each) +
scores (bq x bk) f32 + acc (bq x hd) f32 — for bq = bk = 512, hd = 128:
~2.8 MiB, comfortably inside ~16 MiB VMEM.  MXU alignment: bq, bk, hd all
multiples of 128 (hd 64 also allowed — (8,128) tiling pads).

Causality is exploited at BLOCK granularity: KV blocks strictly above the
diagonal are skipped by clamping the fori_loop bound — this is what the
pure-JAX scan path cannot express with one unchunked q, and it halves the
attention FLOPs of a causal prefill.

HBM traffic per (layer, device): q + k + v + out  (+ nothing else) —
the 16x reduction claimed in §Perf iteration 3.

``ref.py`` holds the jnp oracle; tests sweep shapes/dtypes/windows in
``interpret=True`` (this container is CPU-only; on TPU the same call lowers
to Mosaic natively).
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret

__all__ = ["flash_attention"]

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, *, bq: int, bk: int, seq: int,
            window: int, causal: bool):
    qi = pl.program_id(1)  # query-block index
    q = q_ref[0].astype(jnp.float32)          # (bq, hd)
    hd = q.shape[-1]
    scale = 1.0 / math.sqrt(hd)
    q = q * scale

    q_lo = qi * bq                             # first absolute query row
    nkv = seq // bk
    if causal:
        # skip KV blocks strictly above the diagonal
        hi = jax.lax.div(q_lo + bq - 1, bk) + 1
        hi = jnp.minimum(hi, nkv)
    else:
        hi = nkv
    if causal and window:
        lo = jnp.maximum(jax.lax.div(q_lo - window + 1, bk), 0)
    else:
        lo = 0

    def body(j, carry):
        m, l, acc = carry
        kb = k_ref[0, pl.ds(j * bk, bk), :].astype(jnp.float32)  # (bk, hd)
        vb = v_ref[0, pl.ds(j * bk, bk), :].astype(jnp.float32)
        s = jax.lax.dot_general(q, kb, (((1,), (1,)), ((), ())))  # (bq, bk)
        rows = q_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        cols = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        valid = jnp.full((bq, bk), True)
        if causal:
            valid = cols <= rows
        if window:
            valid = valid & (cols > rows - window)
        s = jnp.where(valid, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        p = jnp.where(valid, p, 0.0)
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=1)
        acc_new = acc * corr[:, None] + jax.lax.dot_general(
            p, vb, (((1,), (0,)), ((), ())))
        return m_new, l_new, acc_new

    m0 = jnp.full((bq,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq,), jnp.float32)
    a0 = jnp.zeros((bq, hd), jnp.float32)
    m, l, acc = jax.lax.fori_loop(lo, hi, body, (m0, l0, a0))
    out = acc / jnp.maximum(l, 1e-30)[:, None]
    o_ref[0] = out.astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "bq", "bk", "interpret"),
)
def flash_attention(
    q: jax.Array,          # (B, H, S, hd)
    k: jax.Array,          # (B, H, S, hd)  (GQA pre-expanded: H == q heads)
    v: jax.Array,          # (B, H, S, hd)
    *,
    causal: bool = True,
    window: int = 0,
    bq: int = 512,
    bk: int = 512,
    interpret: Optional[bool] = None,
) -> jax.Array:
    interpret = resolve_interpret(interpret)
    b, h, s, hd = q.shape
    bq = min(bq, s)
    bk = min(bk, s)
    if s % bq or s % bk:
        raise ValueError(f"seq {s} must be a multiple of bq={bq}, bk={bk}")

    kern = functools.partial(
        _kernel, bq=bq, bk=bk, seq=s, window=window, causal=causal,
    )
    bh = b * h
    qf = q.reshape(bh, s, hd)
    kf = k.reshape(bh, s, hd)
    vf = v.reshape(bh, s, hd)
    out = pl.pallas_call(
        kern,
        grid=(bh, s // bq),
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda i, j: (i, j, 0)),   # q block
            pl.BlockSpec((1, s, hd), lambda i, j: (i, 0, 0)),    # full K row
            pl.BlockSpec((1, s, hd), lambda i, j: (i, 0, 0)),    # full V row
        ],
        out_specs=pl.BlockSpec((1, bq, hd), lambda i, j: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s, hd), q.dtype),
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(b, h, s, hd)
