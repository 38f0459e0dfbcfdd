"""DESIGN.md §8: multi-level distributed sort on a simulated host mesh.

For d in {2, 4, 8} virtual CPU devices (subprocess each, like
``sort_scaling``), runs the ``repro.dist`` engine on a single-axis mesh
(one exchange level) and — where d factors — a two-axis mesh (2, d/2)
(two levels), reporting wall clock and the **collective volume per
level**: bytes entering each level's ``all_to_all`` per device, the
quantity the multi-level schedule is designed to keep per-axis-sized
(splitter sets of ``groups - 1``, fan-in ``groups`` instead of d).

Each row also times the overlap-scheduled exchange (DESIGN.md §13) next
to the synchronous one — ``s_per_call`` vs ``overlap_us`` are the
off/on wall clocks, ``overlap_ratio`` their quotient — after asserting
the two outputs are bit-identical, and reports ``order_cost_ratio``:
the static topology cost (``dist.schedule_cost``) of the declared axis
order over the cost-model optimum (1.0 = already optimal).

NOTE: virtual devices share one physical core, so wall clock validates
overhead only (overlap cannot *win* here — there is no second core to
overlap onto; ``overlap_ratio`` ~ 1 is the expected healthy reading);
the volume-per-level accounting (static, from the level schedule) is
the scaling evidence, matching the Fugaku observation that per-axis
collective fan-in is what survives at scale.

``python -m benchmarks.sort_distributed --overlap-trace PATH`` runs one
d=8 two-axis overlapped sort with ``repro.obs`` enabled and exports the
JSONL trace — the per-level ``dist.overlap_efficiency`` /
``dist.collective_bytes`` evidence the CI mesh job uploads.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

from benchmarks.common import Row

N = 1 << 18
DEVICE_COUNTS = [2, 4, 8]

_CHILD = r"""
import os, sys, json
d = int(sys.argv[1]); n = int(sys.argv[2]); axes_kind = sys.argv[3]
trace = sys.argv[4] if len(sys.argv) > 4 else ""
os.environ["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={d}"
import jax, time
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro import dist, obs
from repro.dist.levels import axis_bandwidths, order_axes, plan_schedule, schedule_cost

if trace:
    obs.enabled(True)  # before any jit traces, so the hooks are staged

if axes_kind == "two" and d >= 4:
    mesh = jax.make_mesh((2, d // 2), ("pod", "data"))
    axes = ("pod", "data")
else:
    mesh = jax.make_mesh((d,), ("data",))
    axes = "data"

rng = np.random.default_rng(0)
x = jnp.asarray(rng.random(n, dtype=np.float32))
x = jax.device_put(x, NamedSharding(mesh, P(axes if isinstance(axes, str) else tuple(axes))))
f = jax.jit(lambda a: dist.sort(a, mesh, axes))
f_ovl = jax.jit(lambda a: dist.sort(a, mesh, axes, overlap=True))
out, counts, overflow = jax.block_until_ready(f(x))
assert not bool(np.any(np.asarray(overflow))), "capacity overflow"
counts = np.asarray(counts)
vals = np.asarray(out)
cap = vals.shape[0] // counts.shape[0]
glob = np.concatenate([vals[i*cap:i*cap+counts[i]] for i in range(counts.shape[0])])
np.testing.assert_array_equal(np.sort(np.asarray(x)), glob)
# the overlap schedule must be bit-identical before its clock means anything
# (uint32 view: float sentinel tails decode to NaN)
out_o, counts_o, ovf_o = jax.block_until_ready(f_ovl(x))
assert not bool(np.any(np.asarray(ovf_o)))
np.testing.assert_array_equal(np.asarray(counts_o), counts)
np.testing.assert_array_equal(np.asarray(out_o).view(np.uint32), vals.view(np.uint32))
def med(fn):
    ts = []
    for _ in range(3):
        t0 = time.perf_counter(); jax.block_until_ready(fn(x))
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))
t_sync, t_ovl = med(f), med(f_ovl)

if trace:
    jax.effects_barrier()
    obs.export_jsonl(trace)

# static collective-volume accounting from the level schedule: each level
# moves groups * capacity key slots (+ the count vector) per device
sched = plan_schedule(dict(mesh.shape), axes, n // d, slack=2.0)
itemsize = 4
vol_per_level = [lvl.groups * lvl.capacity * itemsize for lvl in sched]
# static topology cost of the declared order vs the cost-model optimum
bw = axis_bandwidths(dict(mesh.shape))
best = order_axes(dict(mesh.shape), axes, n // d)
best_cost = schedule_cost(plan_schedule(dict(mesh.shape), best, n // d, slack=2.0), bw)
print(json.dumps({
    "d": d, "t": t_sync, "t_overlap": t_ovl, "levels": len(sched),
    "splitters_per_level": [lvl.groups - 1 for lvl in sched],
    "vol_per_level": vol_per_level,
    "exchange_bytes_per_dev": int(sum(vol_per_level)),
    "order_cost_ratio": schedule_cost(sched, bw) / best_cost,
}))
"""


def run(quick: bool = False):
    n = (1 << 16) if quick else N
    counts = DEVICE_COUNTS[:2] if quick else DEVICE_COUNTS
    rows: list[Row] = []
    for d in counts:
        kinds = ["one"] + (["two"] if d >= 4 else [])
        for kind in kinds:
            res = _child(d, n, kind)
            rows.append({
                "bench": "dist_multilevel",
                "backend": "cpu",
                "devices": d,
                "mesh": "1-axis" if kind == "one" else "2-axis",
                "n": n,
                "levels": res["levels"],
                "splitters_per_level": "/".join(
                    str(s) for s in res["splitters_per_level"]
                ),
                "s_per_call": round(res["t"], 5),
                "overlap_us": round(res["t_overlap"] * 1e6, 1),
                "overlap_ratio": round(res["t_overlap"] / res["t"], 3),
                "order_cost_ratio": round(res["order_cost_ratio"], 3),
                "exchange_bytes_per_dev": res["exchange_bytes_per_dev"],
                "vol_per_level_bytes": "/".join(
                    str(v) for v in res["vol_per_level"]
                ),
            })
    return rows


def _child(d: int, n: int, kind: str, trace: str = "") -> dict:
    # a virtual-device CPU simulation: the child never touches the chip,
    # which the parent process may hold
    env = {**os.environ, "PYTHONPATH": os.pathsep.join(sys.path),
           "JAX_PLATFORMS": "cpu"}
    r = subprocess.run(
        [sys.executable, "-c", _CHILD, str(d), str(n), kind, trace],
        capture_output=True, text=True, env=env, timeout=1200,
    )
    if r.returncode != 0:
        raise RuntimeError(
            f"dist child d={d} {kind} failed:\n{r.stderr[-2000:]}"
        )
    return json.loads(r.stdout.strip().splitlines()[-1])


HEADER = [
    "bench", "devices", "mesh", "n", "levels", "splitters_per_level",
    "s_per_call", "overlap_us", "overlap_ratio", "order_cost_ratio",
    "exchange_bytes_per_dev", "vol_per_level_bytes",
]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--quick", action="store_true")
    ap.add_argument(
        "--overlap-trace", default=None, metavar="PATH",
        help="run one d=8 two-axis overlapped sort with obs enabled and "
             "export the per-level overlap-efficiency JSONL trace to PATH",
    )
    args = ap.parse_args(argv)
    if args.overlap_trace:
        path = os.path.abspath(args.overlap_trace)
        res = _child(8, 1 << 16, "two", trace=path)
        spans = sum(1 for line in open(path) if line.strip())
        print(f"wrote {path} ({spans} records; overlap sort "
              f"{res['t_overlap'] * 1e3:.1f} ms vs sync {res['t'] * 1e3:.1f} ms)")
        return 0
    for row in run(quick=args.quick):
        print(json.dumps(row))
    return 0


if __name__ == "__main__":
    sys.exit(main())
