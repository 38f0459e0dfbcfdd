"""Shared neural building blocks (pure-functional JAX, no framework deps)."""
from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

__all__ = [
    "rms_norm",
    "dense",
    "swiglu",
    "gelu_mlp",
    "rope",
    "init_dense",
    "init_norm",
    "cross_entropy",
    "shard_hint",
]


def shard_hint(x: jax.Array, *axes) -> jax.Array:
    """Best-effort ``with_sharding_constraint`` against the ambient mesh.

    ``axes`` give per-dimension mesh axis names (str, tuple of str, or
    None); names absent from the ambient mesh are silently dropped, and
    with no ambient mesh (plain CPU tests) this is the identity — so model
    code can carry its sharding contract without depending on the launcher.
    Critical use: the logits constraint keeps the (B, S, vocab) tensor
    vocab-sharded instead of letting GSPMD replicate it (49 GB/dev -> fits).
    """
    mesh_axes = set(jax.sharding.get_abstract_mesh().axis_names)
    if not mesh_axes:
        return x

    def filt(a):
        if a is None:
            return None
        if isinstance(a, str):
            return a if a in mesh_axes else None
        t = tuple(n for n in a if n in mesh_axes)
        return t if t else None

    from jax.sharding import PartitionSpec

    spec = PartitionSpec(*[filt(a) for a in axes])
    return jax.lax.with_sharding_constraint(x, spec)


DP = ("pod", "data")  # data-parallel axes (filtered by shard_hint)

Params = Dict[str, Any]


def init_norm(d: int, dtype=jnp.float32) -> Params:
    return {"scale": jnp.ones((d,), dtype)}


def rms_norm(p: Params, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * p["scale"].astype(jnp.float32)).astype(x.dtype)


def init_dense(key, d_in: int, d_out: int, bias: bool = False, dtype=jnp.bfloat16) -> Params:
    w = jax.random.normal(key, (d_in, d_out), jnp.float32) * (1.0 / math.sqrt(d_in))
    p = {"w": w.astype(dtype)}
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype)
    return p


def dense(p: Params, x: jax.Array) -> jax.Array:
    y = x @ p["w"]
    if "b" in p:
        y = y + p["b"]
    return y


def swiglu(p: Params, x: jax.Array) -> jax.Array:
    g = dense(p["gate"], x)
    u = dense(p["up"], x)
    return dense(p["down"], jax.nn.silu(g) * u)


def gelu_mlp(p: Params, x: jax.Array) -> jax.Array:
    return dense(p["down"], jax.nn.gelu(dense(p["up"], x)))


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotary embedding.  x: (..., seq, heads, hd); positions: (..., seq)."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = jnp.exp(
        -math.log(theta) * jnp.arange(0, half, dtype=jnp.float32) / half
    )
    ang = positions[..., :, None, None].astype(jnp.float32) * freqs  # (..., s, 1, half)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    y1 = x1 * cos - x2 * sin
    y2 = x2 * cos + x1 * sin
    return jnp.concatenate([y1, y2], axis=-1).astype(x.dtype)


def cross_entropy(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Mean token cross-entropy, fp32 accumulation.  logits (..., V)."""
    logits = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - ll)
