"""Serving-engine correctness: the donated KV cache must not leak state
across generate() calls, and sampling must be seed-deterministic."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType

from repro.configs.registry import get_reduced
from repro.models.transformer import init_model
from repro.serve.engine import Engine, ServeConfig


@pytest.fixture(scope="module")
def setup():
    cfg = get_reduced("yi-9b", num_layers=1)
    mesh = jax.make_mesh((1, 1), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
    params = init_model(jax.random.PRNGKey(0), cfg)
    return cfg, mesh, params


def _prompts(cfg, b, plen, seed):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.integers(0, cfg.vocab_size, (b, plen)), jnp.int32)


def test_double_generate_matches_fresh_engines(setup):
    """Two back-to-back generate() calls == two fresh engines.

    The second prompt is SHORTER than the first: before the fix the reused
    donated cache still held the first call's KV beyond the new prompt
    length, and decoding attended over it.
    """
    cfg, mesh, params = setup
    scfg = ServeConfig(max_seq=32, batch_size=2)
    p_long = _prompts(cfg, 2, 12, seed=1)
    p_short = _prompts(cfg, 2, 4, seed=2)

    engine = Engine(cfg, scfg, mesh, params)
    with jax.set_mesh(mesh):
        out1 = engine.generate(p_long, 6)
        out2 = engine.generate(p_short, 6)

    fresh1 = Engine(cfg, scfg, mesh, params)
    fresh2 = Engine(cfg, scfg, mesh, params)
    with jax.set_mesh(mesh):
        ref1 = fresh1.generate(p_long, 6)
        ref2 = fresh2.generate(p_short, 6)

    np.testing.assert_array_equal(np.asarray(out1), np.asarray(ref1))
    np.testing.assert_array_equal(np.asarray(out2), np.asarray(ref2))


def test_sampled_generate_deterministic_per_seed(setup):
    """Temperature sampling: same seed -> same stream (and the first token
    uses a split key, not the parent), different seed -> different stream."""
    cfg, mesh, params = setup
    scfg = ServeConfig(max_seq=32, batch_size=2, temperature=1.0)
    p = _prompts(cfg, 2, 8, seed=3)
    engine = Engine(cfg, scfg, mesh, params)
    with jax.set_mesh(mesh):
        a = engine.generate(p, 8, seed=0)
        b = engine.generate(p, 8, seed=0)
        c = engine.generate(p, 8, seed=1)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert not np.array_equal(np.asarray(a), np.asarray(c))
