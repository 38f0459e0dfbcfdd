import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)
# ^ MUST precede any jax import (jax locks the device count on first init).

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this:
  1. builds the production mesh ((16,16) or (2,16,16));
  2. builds ShapeDtypeStruct inputs (no allocation) via configs.registry;
  3. jits the right step (train_step / prefill / decode) with the
     production in/out shardings and ``.lower().compile()``s it;
  4. prints ``memory_analysis()`` (proves the cell fits 16 GiB/chip) and
     ``cost_analysis()`` (FLOPs/bytes for EXPERIMENTS.md §Roofline);
  5. parses the optimized HLO for collective bytes and emits the roofline
     JSON row.

Usage:
  python -m repro.launch.dryrun --arch yi-9b --shape train_4k [--multi-pod]
  python -m repro.launch.dryrun --all --out results/dryrun  (40 cells)
"""
import argparse
import json
import sys
import time
import traceback
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.configs.registry import (
    SHAPES, Shape, cells, get_config, input_specs, shape_applicable,
)
from repro.launch.mesh import dp_axes, make_production_mesh
from repro.launch.roofline import model_flops, roofline_terms
from repro.launch.shardings import (
    ShardingStrategy, batch_specs, cache_specs, named, param_specs,
)


def default_microbatch(cfg: ModelConfig, shape: Shape, mesh) -> int:
    """Accumulation so that per-dp-shard microbatch keeps live activations
    inside 16 GiB (1 row/shard for the giant archs, 4 otherwise)."""
    dp = 1
    for a in dp_axes(mesh):
        dp *= mesh.shape[a]
    per_shard = 1 if cfg.d_model >= 8192 or cfg.num_layers >= 90 else 4
    mb = min(shape.global_batch, dp * per_shard)
    while shape.global_batch % mb:
        mb -= 1
    return max(1, mb)


def lower_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
               strat: ShardingStrategy = ShardingStrategy(),
               tcfg=None, verbose: bool = True,
               hlo_out: Optional[str] = None,
               flash_block: int = 0,
               explicit_ep: bool = False) -> Dict[str, Any]:
    from repro.models.transformer import (
        forward, init_decode_cache, init_model,
    )
    from repro.train.trainer import TrainConfig, make_train_step
    from repro.optim.adamw import adamw_init

    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    if not shape_applicable(cfg, shape):
        return {"arch": arch, "shape": shape_name, "status": "skipped",
                "reason": "full-attention arch: long_500k needs sub-quadratic"}

    mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_name = "x".join(str(mesh.shape[a]) for a in mesh.axis_names)
    chips = 1
    for a in mesh.axis_names:
        chips *= mesh.shape[a]

    specs = input_specs(cfg, shape)
    params_like = jax.eval_shape(lambda: init_model(jax.random.PRNGKey(0), cfg))
    psh = named(mesh, param_specs(params_like, cfg, mesh, strat))

    from repro.models.policy import compute_policy

    t0 = time.perf_counter()
    # ambient mesh: resolves shard_hint P-constraints at trace
    with jax.set_mesh(mesh):
        with compute_policy(flash_block=flash_block, explicit_ep=explicit_ep):
            lowered = _lower(shape, cfg, mesh, specs, params_like, psh,
                             strat, tcfg)
    t_lower = time.perf_counter() - t0
    t0 = time.perf_counter()
    with jax.set_mesh(mesh):
        compiled = lowered.compile()
    t_compile = time.perf_counter() - t0
    if hlo_out:
        with open(hlo_out, "w") as f:
            f.write(compiled.as_text())
    return _finish(arch, shape_name, cfg, shape, mesh_name, chips, compiled,
                   t_lower, t_compile, verbose)


def _lower(shape, cfg, mesh, specs, params_like, psh, strat, tcfg):
    import jax
    import jax.numpy as jnp
    from repro.models.transformer import forward, init_decode_cache
    from repro.train.trainer import TrainConfig, make_train_step
    from repro.optim.adamw import adamw_init
    from repro.launch.shardings import batch_specs, cache_specs, named

    if shape.kind == "train":
        if tcfg is None:
            tcfg = TrainConfig(microbatch=default_microbatch(cfg, shape, mesh))
        stepf, state_sh, batch_sh_fn = make_train_step(
            cfg, tcfg, mesh, strat, params_like, batch_like=specs
        )
        state_like = {
            "params": params_like,
            "opt": jax.eval_shape(lambda p: adamw_init(p, tcfg.adamw), params_like),
        }
        if tcfg.compress_grads:
            state_like["eff"] = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, jnp.float32), params_like
            )
        batch_like = specs
        lowered = stepf.lower(state_like, batch_like)
    elif shape.kind == "prefill":
        cache_like = jax.eval_shape(
            lambda: init_decode_cache(cfg, shape.global_batch, shape.seq_len)
        )
        csh = named(mesh, cache_specs(cfg, mesh, cache_like, strat))
        bsh = named(mesh, batch_specs(cfg, mesh, specs))

        def prefill(params, inputs, cache):
            logits, new_cache, _ = forward(params, cfg, inputs, cache=cache,
                                           update_cache=True)
            return logits[:, -1], new_cache

        fn = jax.jit(prefill, in_shardings=(psh, bsh["inputs"], csh),
                     donate_argnums=(2,))
        lowered = fn.lower(params_like, specs["inputs"], cache_like)
    else:  # decode
        cache_like = specs["cache"]
        csh = named(mesh, cache_specs(cfg, mesh, cache_like, strat))
        tok_like = specs["inputs"]
        bsh = named(mesh, batch_specs(cfg, mesh, {"inputs": tok_like}))
        pos_like = jax.ShapeDtypeStruct((shape.global_batch, 1), jnp.int32)

        def decode(params, tok, pos, cache):
            logits, new_cache, _ = forward(params, cfg, tok, positions=pos,
                                           cache=cache, update_cache=True)
            return logits[:, 0], new_cache

        fn = jax.jit(decode, in_shardings=(psh, bsh["inputs"], None, csh),
                     donate_argnums=(3,))
        lowered = fn.lower(params_like, tok_like, pos_like, cache_like)

    return lowered


def _finish(arch, shape_name, cfg, shape, mesh_name, chips, compiled,
            t_lower, t_compile, verbose) -> Dict[str, Any]:
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    hlo = compiled.as_text()
    flops = float(cost.get("flops", 0.0))
    bytes_acc = float(cost.get("bytes accessed", 0.0))
    peak = None
    mem_repr = {}
    if mem is not None:
        for k in ("temp_size_in_bytes", "argument_size_in_bytes",
                  "output_size_in_bytes", "generated_code_size_in_bytes",
                  "peak_memory_in_bytes", "alias_size_in_bytes"):
            v = getattr(mem, k, None)
            if v is not None:
                mem_repr[k] = int(v)
        peak = mem_repr.get("peak_memory_in_bytes") or (
            mem_repr.get("temp_size_in_bytes", 0)
            + mem_repr.get("argument_size_in_bytes", 0)
        )

    rep = roofline_terms(
        arch=arch, shape=shape_name, mesh_name=mesh_name, chips=chips,
        flops_per_dev=flops, bytes_per_dev=bytes_acc, hlo_text=hlo,
        model_fl=model_flops(cfg, shape), peak_mem=peak,
    )
    row = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name, "chips": chips,
        "status": "ok", "t_lower_s": round(t_lower, 1),
        "t_compile_s": round(t_compile, 1), "memory": mem_repr,
        "roofline": json.loads(rep.to_json()),
    }
    if verbose:
        print(f"[{arch} x {shape_name} x {mesh_name}] compiled "
              f"in {t_compile:.1f}s; mem={mem_repr}", flush=True)
        print(f"  flops/dev={flops:.3e} bytes/dev={bytes_acc:.3e} "
              f"coll/dev={rep.coll_bytes_per_dev:.3e} "
              f"bottleneck={rep.bottleneck}", flush=True)
        print(f"  t_comp={rep.t_compute*1e3:.2f}ms t_mem={rep.t_memory*1e3:.2f}ms "
              f"(min {rep.t_memory_min*1e3:.2f}ms) "
              f"t_coll={rep.t_collective*1e3:.2f}ms useful={rep.useful_ratio:.2f} "
              f"bott_min={rep.bottleneck_min}",
              flush=True)
    return row


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=None, help="write one JSON per cell here")
    ap.add_argument("--seq-shard-cache", action="store_true", default=None)
    ap.add_argument("--save-hlo", default=None,
                    help="write the optimized HLO text of each cell here")
    ap.add_argument("--flash", type=int, default=0,
                    help="flash-attention KV block size (0 = eager baseline)")
    ap.add_argument("--explicit-ep", action="store_true",
                    help="shard_map expert parallelism for MoE archs")
    ap.add_argument("--tag", default=None,
                    help="suffix for --out/--save-hlo filenames")
    ap.add_argument("--microbatch", type=int, default=0,
                    help="override gradient-accumulation microbatch size")
    ap.add_argument("--compress-grads", action="store_true",
                    help="int8 error-feedback gradient compression")
    args = ap.parse_args(argv)

    strat = ShardingStrategy(seq_shard_cache=args.seq_shard_cache)
    todo = (
        cells(include_inapplicable=True) if args.all
        else [(args.arch, args.shape)]
    )
    failures = 0
    for arch, shape in todo:
        try:
            hlo_out = None
            pod = "2pod" if args.multi_pod else "1pod"
            if args.tag:
                pod = f"{pod}__{args.tag}"
            if args.save_hlo:
                os.makedirs(args.save_hlo, exist_ok=True)
                hlo_out = os.path.join(args.save_hlo,
                                       f"{arch}__{shape}__{pod}.hlo")
            tcfg = None
            if args.microbatch or args.compress_grads:
                from repro.train.trainer import TrainConfig
                tcfg = TrainConfig(microbatch=args.microbatch,
                                   compress_grads=args.compress_grads)
            row = lower_cell(arch, shape, multi_pod=args.multi_pod,
                             strat=strat, hlo_out=hlo_out, tcfg=tcfg,
                             flash_block=args.flash,
                             explicit_ep=args.explicit_ep)
        except Exception as e:  # a failure here is a bug in our sharding
            traceback.print_exc()
            row = {"arch": arch, "shape": shape, "status": "error",
                   "error": f"{type(e).__name__}: {e}",
                   "mesh": "2x16x16" if args.multi_pod else "16x16"}
            failures += 1
        if args.out:
            os.makedirs(args.out, exist_ok=True)
            fn = os.path.join(args.out, f"{arch}__{shape}__{pod}.json")
            with open(fn, "w") as f:
                json.dump(row, f, indent=1)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
