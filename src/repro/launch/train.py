"""End-to-end training launcher.

  PYTHONPATH=src python -m repro.launch.train --arch yi-9b --reduced \
      --steps 200 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt

On this CPU container use ``--reduced`` (the ~100M-class smoke config); the
same launcher drives the full configs on a real mesh (the multi-pod path is
exercised by launch/dryrun.py).  Demonstrates: data pipeline, sharded init,
jitted step with accumulation, checkpoint/restart (kill it mid-run and
re-launch: it resumes from the newest complete checkpoint), straggler
ledger logging.
"""
import argparse
import sys

import jax
from jax.sharding import AxisType


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--microbatch", type=int, default=0)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--compress-grads", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from repro.configs.registry import get_config, get_reduced
    from repro.data.pipeline import SyntheticLM
    from repro.optim.adamw import AdamWConfig
    from repro.train.trainer import TrainConfig, Trainer

    cfg = get_reduced(args.arch) if args.reduced else get_config(args.arch)
    tcfg = TrainConfig(
        microbatch=args.microbatch,
        warmup_steps=max(args.steps // 10, 1),
        total_steps=args.steps,
        compress_grads=args.compress_grads,
        adamw=AdamWConfig(lr=args.lr),
    )
    ndev = len(jax.devices())
    mesh = jax.make_mesh(
        (ndev, 1), ("data", "model"), axis_types=(AxisType.Auto,) * 2
    )
    data = SyntheticLM(
        vocab_size=cfg.vocab_size, seq_len=args.seq, global_batch=args.batch,
        seed=args.seed, embed_dim=cfg.d_model if cfg.takes_embeds else 0,
    )

    trainer = Trainer(cfg, tcfg, mesh, ckpt_dir=args.ckpt_dir, seed=args.seed)
    trainer.init_state()
    if trainer.maybe_restore():
        print(f"resumed from step {trainer.step_num}")
    it = iter(data)
    # fast-forward the data stream for bitwise-identical resume
    for _ in range(trainer.step_num):
        next(it)
    metrics = trainer.run(it, args.steps - trainer.step_num,
                          ckpt_every=args.ckpt_every)
    print("final:", metrics)
    return 0


if __name__ == "__main__":
    sys.exit(main())
