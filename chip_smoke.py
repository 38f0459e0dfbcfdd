#!/usr/bin/env python3
"""Chip smoke test: drive the sort's main path once, at full size, on a TPU.

Run from the root of a checkout, with nothing else holding the chip:

    python3 chip_smoke.py             # one chip: phases A-D
    python3 chip_smoke.py --chips 4   # four chips: the mesh sort only

Phases (one chip):
  A  ``ops.sort`` (int32 payload) and ``ops.argsort`` on 2^24 f32 keys —
     the two-level limit of the engine — uniform and duplicate-heavy
     (RootDup) inputs, engine "xla" and engine "pallas";
  B  ``ops.sort(engine="pallas")`` at n = 2^24 - 1000 (not tile-aligned:
     pads route to the pad bucket inside the level kernel);
  C  ``stream.merge(engine="pallas")`` of two sorted 2^22 runs, and
     ``stream.external_sort`` of 2^24 host keys in 2^22 chunks;
  D  ``ops.group_by(num_groups=64, method="pallas")`` on 2^20 skewed
     int32 expert ids.
With ``--chips 4``: ``dist.sort`` of 2^24 f32 keys (2^22 per chip) with an
int32 payload on a (4,) and a (2, 2) mesh, overlap off and on, and one
``dist.sort_elastic`` killed at a level boundary and resumed.

Every phase is checked against a plain numpy reference in the keyspace
order (NaNs last, -0.0 before +0.0), engines pinned explicitly; the pallas
programs must contain a ``tpu_custom_call`` (the kernel is in the program
that was timed).  Each check prints its result, compile seconds, steady ms
per call (``block_until_ready``, median of 3) and the ``jnp.sort`` time on
the same input.  The last stdout line is one JSON object,
``{"ok": true, "device": {...}}``, printed only when every phase passed on
a TPU; otherwise the exit code is non-zero.  Results also go to
``chiprun_out/chip_smoke_<chips>.json``.

The compile cache is ``$JAX_COMPILATION_CACHE_DIR`` when set, else
``<checkout>/.jax_cache``; source locations are named relative to the
checkout, so its entries hit from a checkout at any path; the plan cache is a fresh file in
``chiprun_out/`` so no plan tuned elsewhere steers "auto".
"""
import argparse
import json
import os
import re
import shutil
import statistics
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(ROOT, "chiprun_out")
REPS = 3  # timed calls per check, after one untimed warm call


def log(msg):
    print(msg, flush=True)


# -- numpy references (independent of the code under test) ------------------


def np_encode_f32(x):
    """f32 -> u32 with the bit-pattern order equal to the keyspace order."""
    b = x.view(np.uint32)
    return np.where(b >> 31, ~b, b | np.uint32(1 << 31))


def np_stable_perm(x):
    enc = np_encode_f32(x) if x.dtype == np.float32 else x
    return np.argsort(enc, kind="stable")


def bits(a):
    a = np.asarray(a)
    return a.view(np.uint32) if a.dtype == np.float32 else a


def same(a, b):
    return a.shape == b.shape and np.array_equal(bits(a), bits(b))


# -- compile / time helpers -------------------------------------------------


def build(fn, *args):
    """(compiled, compile seconds, count of tpu_custom_call in its HLO)."""
    t0 = time.perf_counter()
    compiled = jax.jit(fn).lower(*args).compile()
    secs = time.perf_counter() - t0
    return compiled, secs, compiled.as_text().count("tpu_custom_call")


def steady_ms(compiled, *args):
    """Median ms per call of an already-warm executable."""
    ts = []
    for _ in range(REPS):
        t0 = time.perf_counter()
        jax.block_until_ready(compiled(*args))
        ts.append(time.perf_counter() - t0)
    return statistics.median(ts) * 1e3


_JNP_SORT = {}


def jnp_sort_ms(x):
    """``jnp.sort`` ms per call on ``x``, measured once per input array."""
    if id(x) not in _JNP_SORT:
        compiled = build(jnp.sort, x)[0]
        jax.block_until_ready(compiled(x))
        _JNP_SORT[id(x)] = (x, round(steady_ms(compiled, x), 3))
    return _JNP_SORT[id(x)][1]


class Phase:
    """Collects one phase's checks; a failed check fails the phase."""

    def __init__(self, name, results):
        self.name, self.ok, self.rows = name, True, []
        results[name] = self

    def check(self, what, ok, **nums):
        ok = bool(ok)
        self.ok &= ok
        fields = " ".join(f"{k}={v}" for k, v in nums.items())
        log(f"phase {self.name}: {what} {'ok' if ok else 'FAILED'} {fields}".rstrip())
        self.rows.append({"check": what, "ok": ok, **nums})


def timed(phase, what, fn, args, ref_ok, *, kernel, jnp_input):
    """Compile ``fn``, check its output with ``ref_ok``, time it."""
    compiled, secs, ncall = build(fn, *args)
    out = jax.block_until_ready(compiled(*args))
    ok = ref_ok(out) and (ncall > 0 if kernel else True)
    phase.check(
        what, ok, compile_s=round(secs, 3), steady_ms=round(steady_ms(compiled, *args), 3),
        jnp_sort_ms=jnp_sort_ms(jnp_input), tpu_custom_call=ncall,
    )
    return out


# -- one-chip phases --------------------------------------------------------


def phase_a(results, n=1 << 24):
    p = Phase("A", results)
    payload = np.arange(n, dtype=np.int32)
    vd = jax.device_put(payload)
    outs = {}
    for name in ("Uniform", "RootDup"):
        x = make_input(name, n, np.float32, seed=1)
        perm = np_stable_perm(x)
        xd = jax.device_put(x)
        for eng in ("xla", "pallas"):
            cfg = SortConfig(engine=eng)
            k, v = timed(
                p, f"{name} ops.sort engine={eng}",
                lambda a, b, cfg=cfg: ops.sort(a, b, cfg=cfg), (xd, vd),
                lambda o: same(o[0], x[perm]) and same(o[1], payload[perm]),
                kernel=eng == "pallas", jnp_input=xd,
            )
            idx = timed(
                p, f"{name} ops.argsort engine={eng}",
                lambda a, cfg=cfg: ops.argsort(a, cfg=cfg), (xd,),
                lambda o: same(o, perm.astype(np.int32)),
                kernel=eng == "pallas", jnp_input=xd,
            )
            outs[eng] = [np.asarray(a) for a in (k, v, idx)]
        p.check(f"{name} xla == pallas bitwise",
                all(same(a, b) for a, b in zip(outs["xla"], outs["pallas"])))


def phase_b(results, n=(1 << 24) - 1000):
    p = Phase("B", results)
    x = make_input("Exponential", n, np.float32, seed=2)
    payload = np.arange(n, dtype=np.int32)
    perm = np_stable_perm(x)
    cfg = SortConfig(engine="pallas")
    timed(
        p, f"n={n} ops.sort engine=pallas",
        lambda a, b: ops.sort(a, b, cfg=cfg),
        (jax.device_put(x), jax.device_put(payload)),
        lambda o: same(o[0], x[perm]) and same(o[1], payload[perm]),
        kernel=True, jnp_input=jax.device_put(x),
    )


def phase_c(results, run=1 << 22, total=1 << 24):
    p = Phase("C", results)
    rng = np.random.default_rng(3)
    # integral values: many ties inside and across the runs pin the
    # stable tie rule (run 0 first, then run order within each run)
    a = np.sort(rng.integers(0, 1 << 16, run).astype(np.float32))
    b = np.sort(rng.integers(0, 1 << 16, run).astype(np.float32))
    va, vb = np.arange(run, dtype=np.int32), np.arange(run, 2 * run, dtype=np.int32)
    cat = np.concatenate([a, b])
    perm = np_stable_perm(cat)
    args = tuple(jax.device_put(t) for t in (a, b, va, vb))
    catd = jax.device_put(cat)
    outs = {}
    for eng in ("xla", "pallas"):
        outs[eng] = timed(
            p, f"stream.merge 2x{run} engine={eng}",
            lambda x, y, u, w, eng=eng: stream.merge([x, y], values=[u, w], engine=eng),
            args,
            lambda o: same(o[0], cat[perm]) and same(o[1], perm.astype(np.int32)),
            kernel=eng == "pallas", jnp_input=catd,
        )
    p.check("stream.merge xla == pallas bitwise",
            all(same(np.asarray(s), np.asarray(t)) for s, t in zip(*outs.values())))

    data = make_input("Uniform", total, np.float32, seed=4)
    want = data[np_stable_perm(data)]
    walls = []
    for _ in range(2):  # cold (compiles), then warm
        t0 = time.perf_counter()
        got = stream.external_sort(data, chunk_size=run, engine="pallas")
        walls.append(time.perf_counter() - t0)
    p.check(
        f"stream.external_sort {total} in {run} chunks engine=pallas", same(got, want),
        cold_wall_s=round(walls[0], 3), warm_wall_s=round(walls[1], 3),
        jnp_sort_ms=jnp_sort_ms(jax.device_put(data)),
    )


def phase_d(results, n=1 << 20, groups=64):
    p = Phase("D", results)
    rng = np.random.default_rng(5)
    pop = 1.0 / np.arange(1, groups + 1) ** 1.1  # Zipf-skewed expert popularity
    ids = rng.choice(groups, n, p=pop / pop.sum()).astype(np.int32)
    perm = np.argsort(ids, kind="stable")
    counts = np.bincount(ids, minlength=groups)
    idd = jax.device_put(ids)
    outs = {}
    for method in ("partition", "pallas"):
        def f(k, method=method):
            g = ops.group_by(k, num_groups=groups, method=method)
            return g.keys, g.perm, g.counts

        outs[method] = timed(
            p, f"ops.group_by num_groups={groups} method={method}", f, (idd,),
            lambda o: same(o[0], ids[perm]) and same(o[1], perm.astype(np.int32))
            and same(o[2], counts.astype(np.int32)),
            kernel=method == "pallas", jnp_input=idd,
        )
    p.check("group_by partition == pallas bitwise",
            all(same(np.asarray(s), np.asarray(t)) for s, t in zip(*outs.values())))


# -- four-chip phase --------------------------------------------------------


def phase_mesh(results, n=1 << 24):
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.checkpoint import CheckpointManager

    p = Phase("mesh", results)
    x = make_input("Exponential", n, np.float32, seed=6)
    payload = np.arange(n, dtype=np.int32)
    want = x[np_stable_perm(x)]
    xd = jax.device_put(x)  # one chip: the jnp.sort baseline
    cfg = SortConfig(engine="pallas")
    for shape, axes in (((4,), ("data",)), ((2, 2), ("pod", "data"))):
        mesh = jax.make_mesh(shape, axes)
        ax = axes if len(axes) > 1 else axes[0]
        sh = NamedSharding(mesh, P(ax))
        xs, vs = jax.device_put(x, sh), jax.device_put(payload, sh)

        def check(out):
            k, v, counts, ovf = (np.asarray(o) for o in out)
            per = k.shape[0] // counts.shape[0]  # capacity per shard
            keep = np.concatenate(
                [np.arange(i * per, i * per + c) for i, c in enumerate(counts)]
            )
            return (not ovf.any() and same(k[keep], want)
                    and np.array_equal(np.sort(v[keep]), payload)
                    and same(x[v[keep]], want))

        got = {}
        for overlap in (False, True):
            out = timed(
                p, f"dist.sort mesh={shape} overlap={overlap}",
                lambda k, v, mesh=mesh, ax=ax, overlap=overlap: dist.sort(
                    k, mesh, ax, values=v, cfg=cfg, overlap=overlap),
                (xs, vs), check, kernel=True, jnp_input=xd,
            )
            devices = {s.device for s in out[0].addressable_shards}
            p.check(f"dist.sort mesh={shape} overlap={overlap} output on 4 devices",
                    len(devices) == 4, devices=len(devices))
            got[overlap] = [np.asarray(o) for o in out]
        p.check(f"dist.sort mesh={shape} overlap on == off bitwise",
                all(same(a, b) for a, b in zip(got[False], got[True])))
    ref = got[False]

    # kill after the first level boundary of the (2, 2) mesh, then resume
    ck = os.path.join(ROOT, ".chip_smoke_ck")
    shutil.rmtree(ck, ignore_errors=True)
    try:
        killed = False
        try:
            dist.sort_elastic(xs, mesh, ax, values=vs, cfg=cfg,
                              manager=CheckpointManager(ck, keep=8), _fail_at_step=1)
        except RuntimeError as e:
            killed = "injected shard loss" in str(e)
        survivor = CheckpointManager(ck, keep=8)
        t0 = time.perf_counter()
        out = dist.sort_elastic(xs, mesh, ax, values=vs, cfg=cfg, manager=survivor)
        got = [np.asarray(o) for o in jax.block_until_ready(out)]
        p.check("dist.sort_elastic kill at boundary 1, resume == dist.sort bitwise",
                killed and survivor.latest_step() is not None
                and all(same(a, b) for a, b in zip(ref, got)),
                resume_wall_s=round(time.perf_counter() - t0, 3))
    finally:
        shutil.rmtree(ck, ignore_errors=True)


# -- main -------------------------------------------------------------------


def main():
    global jax, jnp, np, ops, stream, dist, SortConfig, make_input
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: phases A-D; 4: the mesh sort and its reference only")
    args = ap.parse_args()

    os.makedirs(OUT, exist_ok=True)
    plans = os.path.join(OUT, "chip_smoke_plans.json")
    if os.path.exists(plans):
        os.remove(plans)
    os.environ["REPRO_OPS_PLAN_CACHE"] = plans

    import jax
    import jax.numpy as jnp
    import numpy as np

    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache:
        cache = os.path.join(ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", cache)
    # a Pallas kernel's serialized body keeps its source locations, which
    # the cache key does not strip: name files relative to the checkout so
    # a checkout at another path hits the same entries
    jax.config.update("jax_hlo_source_file_canonicalization_regex",
                      "^" + re.escape(ROOT + os.sep))
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        log(f"no TPU: JAX found platform {dev.platform!r}")
        return 2
    if len(jax.devices()) < args.chips:
        log(f"--chips {args.chips} needs {args.chips} devices, found {len(jax.devices())}")
        return 2

    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro import dist, ops, stream
    from repro.core.ips4o import SortConfig
    from repro.data.distributions import make_input

    log(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}; "
        f"compile cache: {cache}")
    phases = [phase_mesh] if args.chips == 4 else [phase_a, phase_b, phase_c, phase_d]
    results = {}
    for ph in phases:
        t0 = time.perf_counter()
        try:
            ph(results)
        except Exception:
            traceback.print_exc()
            Phase(ph.__name__, results).check("raised", False)
        log(f"{ph.__name__} wall_s={time.perf_counter() - t0:.3f}")
    ok = all(p.ok for p in results.values())
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(jax.devices())}
    with open(os.path.join(OUT, f"chip_smoke_{args.chips}.json"), "w") as f:
        json.dump({"ok": ok, "device": device,
                   "phases": {k: {"ok": p.ok, "checks": p.rows} for k, p in results.items()}},
                  f, indent=1)
    if not ok:
        log("FAILED: " + ", ".join(k for k, p in results.items() if not p.ok))
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
