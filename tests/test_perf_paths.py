"""The §Perf optimization paths must match their baselines exactly.

  * JAX KV-chunked flash attention  == eager SDPA           (models/attention)
  * Pallas fused flash kernel       == jnp oracle           (kernels/flash_attention)
  * shard_map explicit-EP MoE       == GSPMD-lowered MoE    (models/moe), fwd + grad
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType

from repro.kernels.flash_attention import flash_attention
from repro.kernels.ref import flash_attention_ref
from repro.models.attention import _causal_mask, _sdpa, _sdpa_flash
from repro.models.policy import compute_policy, current_policy


@pytest.mark.parametrize("b,s,h,kvh,hd,window,block", [
    (2, 128, 8, 4, 32, 0, 32),
    (1, 96, 6, 2, 16, 40, 32),
    (2, 64, 4, 4, 32, 0, 64),
    (1, 256, 4, 1, 64, 0, 128),
])
def test_flash_jax_matches_eager(b, s, h, kvh, hd, window, block):
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((b, s, h, hd)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, s, kvh, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, s, kvh, hd)), jnp.float32)
    ref = _sdpa(q, k, v, _causal_mask(s, s, 0, window))
    out = _sdpa_flash(q, k, v, 0, window, block)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5), (jnp.bfloat16, 2e-2)])
@pytest.mark.parametrize("b,h,s,hd,window,bq,bk", [
    (2, 4, 512, 64, 0, 128, 128),
    (1, 2, 1024, 128, 0, 256, 256),
    (1, 2, 512, 64, 200, 128, 128),
])
def test_flash_pallas_matches_ref(b, h, s, hd, window, bq, bk, dtype, tol):
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.standard_normal((b, h, s, hd)), dtype)
    k = jnp.asarray(rng.standard_normal((b, h, s, hd)), dtype)
    v = jnp.asarray(rng.standard_normal((b, h, s, hd)), dtype)
    out = flash_attention(q, k, v, causal=True, window=window,
                          bq=bq, bk=bk, interpret=True)
    ref = flash_attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        atol=tol, rtol=tol)


def test_flash_pallas_noncausal():
    rng = np.random.default_rng(2)
    q = jnp.asarray(rng.standard_normal((1, 2, 256, 64)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 2, 256, 64)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, 2, 256, 64)), jnp.float32)
    out = flash_attention(q, k, v, causal=False, bq=128, bk=128)
    ref = flash_attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_policy_stack():
    assert current_policy().flash_block == 0
    with compute_policy(flash_block=1024):
        assert current_policy().flash_block == 1024
        with compute_policy(explicit_ep=True):
            assert current_policy().flash_block == 1024
            assert current_policy().explicit_ep
        assert not current_policy().explicit_ep
    assert current_policy().flash_block == 0


def test_explicit_ep_matches_baseline():
    """Single-device mesh: shard_map column == GSPMD path (fwd + grad)."""
    from functools import partial

    from repro.models.moe import init_moe, moe_ffn

    E, k, d, dff = 8, 2, 32, 16
    p = init_moe(jax.random.PRNGKey(0), d, num_experts=E, d_ff_expert=dff,
                 top_k=k, dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, d), jnp.float32)
    mesh = jax.make_mesh((1, 1), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
    f = partial(moe_ffn, num_experts=E, top_k=k, capacity_factor=float(E))

    def run(ep):
        def g(p, x):
            if ep:
                with compute_policy(explicit_ep=True):
                    y, aux = f(p, x)
            else:
                y, aux = f(p, x)
            return y, aux
        with jax.set_mesh(mesh):
            y, aux = jax.jit(g)(p, x)
            grads = jax.jit(jax.grad(lambda p: jnp.sum(g(p, x)[0] ** 2)))(p)
        return y, aux, grads

    y0, a0, g0 = run(False)
    y1, a1, g1 = run(True)
    np.testing.assert_allclose(np.asarray(y0), np.asarray(y1),
                               atol=2e-5, rtol=2e-5)
    assert int(a0["dropped"]) == int(a1["dropped"]) == 0
    for l0, l1 in zip(jax.tree.leaves(g0), jax.tree.leaves(g1)):
        np.testing.assert_allclose(np.asarray(l0), np.asarray(l1),
                                   atol=5e-4, rtol=5e-4)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5), (jnp.bfloat16, 2e-2)])
@pytest.mark.parametrize("b,h,t,hd,bt", [
    (2, 4, 2048, 64, 512),
    (1, 2, 1024, 128, 256),
    (3, 2, 512, 64, 512),   # single T block
])
def test_flash_decode_matches_ref(b, h, t, hd, bt, dtype, tol):
    from repro.kernels.flash_decode import flash_decode
    from repro.kernels.ref import flash_decode_ref

    rng = np.random.default_rng(4)
    q = jnp.asarray(rng.standard_normal((b, h, 1, hd)), dtype)
    k = jnp.asarray(rng.standard_normal((b, h, t, hd)), dtype)
    v = jnp.asarray(rng.standard_normal((b, h, t, hd)), dtype)
    length = jnp.asarray(rng.integers(1, t + 1, (b,)), jnp.int32)
    out = flash_decode(q, k, v, length, bt=bt, interpret=True)
    ref = flash_decode_ref(q, k, v, length)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=tol, rtol=tol)


def test_flash_decode_policy_in_attention():
    """attention() with ComputePolicy.flash_decode must match the eager
    decode path (linear cache)."""
    from repro.models.attention import attention, init_attention, init_cache

    b, hd, h, kvh, T = 2, 32, 4, 2, 128
    d = 64
    p = init_attention(jax.random.PRNGKey(0), d, h, kvh, hd, dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (b, 1, d), jnp.float32)
    cache = init_cache(b, T, kvh, hd, dtype=jnp.float32)
    # pretend 17 tokens were prefilled
    cache = {**cache, "pos": jnp.asarray(17, jnp.int32),
             "k": cache["k"].at[:, :17].set(
                 jax.random.normal(jax.random.PRNGKey(2), (b, 17, kvh, hd))),
             "v": cache["v"].at[:, :17].set(
                 jax.random.normal(jax.random.PRNGKey(3), (b, 17, kvh, hd)))}
    pos = jnp.full((b, 1), 17, jnp.int32)
    kw = dict(num_heads=h, num_kv_heads=kvh, head_dim=hd, rope_theta=1e4,
              cache=cache, update_cache=True)
    out0, c0 = attention(p, x, pos, **kw)
    with compute_policy(flash_decode=True):
        out1, c1 = attention(p, x, pos, **kw)
    np.testing.assert_allclose(np.asarray(out0), np.asarray(out1),
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_array_equal(np.asarray(c0["k"]), np.asarray(c1["k"]))
