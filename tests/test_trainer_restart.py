"""Fault tolerance: checkpoint/restart must reproduce the uninterrupted run.

Trains a reduced config 6 steps straight, then the same thing as
3 steps -> "crash" -> restore -> 3 more steps, and compares final params
bitwise (the data pipeline is deterministic in (seed, step), restore
fast-forwards the stream, and the step is deterministic on CPU).
"""
import numpy as np
import jax
import pytest
from jax.sharding import AxisType

from repro.configs.registry import get_reduced
from repro.data.pipeline import SyntheticLM
from repro.optim.adamw import AdamWConfig
from repro.train.trainer import TrainConfig, Trainer


@pytest.mark.slow
def test_restart_bitwise_identical(tmp_path):
    cfg = get_reduced("yi-9b")
    tcfg = TrainConfig(microbatch=2, warmup_steps=2, total_steps=6,
                       adamw=AdamWConfig(lr=1e-3))
    mesh = jax.make_mesh((1, 1), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
    data = lambda: iter(SyntheticLM(vocab_size=cfg.vocab_size, seq_len=32,
                                    global_batch=4, seed=7))

    def leaves(state):
        return [np.asarray(x) for x in jax.tree.leaves(state["params"])]

    # uninterrupted
    t0 = Trainer(cfg, tcfg, mesh, ckpt_dir=None, seed=0)
    t0.init_state()
    t0.run(data(), 6, ckpt_every=100, log_every=100, log=lambda *_: None)
    ref = leaves(t0.state)

    # interrupted at step 3
    ck = str(tmp_path / "ck")
    t1 = Trainer(cfg, tcfg, mesh, ckpt_dir=ck, seed=0)
    t1.init_state()
    t1.run(data(), 3, ckpt_every=3, log_every=100, log=lambda *_: None)
    del t1  # "crash"

    t2 = Trainer(cfg, tcfg, mesh, ckpt_dir=ck, seed=0)
    t2.init_state()
    assert t2.maybe_restore(), "no checkpoint found"
    assert t2.step_num == 3
    it = data()
    for _ in range(t2.step_num):  # deterministic fast-forward
        next(it)
    t2.run(it, 3, ckpt_every=100, log_every=100, log=lambda *_: None)

    for a, b in zip(ref, leaves(t2.state)):
        np.testing.assert_array_equal(a, b)
