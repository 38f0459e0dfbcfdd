"""Quickstart: the IPS4o sorting library in seven snippets.

  PYTHONPATH=src python examples/quickstart.py
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.ips4o import ips4o_sort, make_sorter

# 1. Sort keys -------------------------------------------------------------
x = jnp.asarray(np.random.default_rng(0).random(1 << 17, dtype=np.float32))
y = ips4o_sort(x)
assert bool(jnp.all(y[:-1] <= y[1:]))
print(f"1. sorted {x.shape[0]} f32 keys: head={np.asarray(y[:4])}")

# 2. Key + payload (any pytree with matching leading dim) -------------------
payload = {"idx": jnp.arange(x.shape[0]), "vec": jnp.zeros((x.shape[0], 3))}
yk, yv = ips4o_sort(x, payload)
assert bool(jnp.all(jnp.take(x, yv["idx"]) == yk))
print("2. payload rows follow their keys (checked)")

# 3. In-place: donate the input buffer (the paper's headline property) ------
sorter = make_sorter(x.shape[0], x.dtype, donate=True)
y = sorter(jnp.array(x))  # donated copy: XLA reuses its HBM allocation
print("3. donated sorter compiled; input buffer reused by XLA")

# 4. Duplicate-heavy input -> equality buckets (§4.4) ----------------------
dup = jnp.asarray((np.arange(1 << 17) % 317).astype(np.float32))
yd = ips4o_sort(dup)
assert bool(jnp.all(yd[:-1] <= yd[1:]))
print("4. RootDup-style input sorted via equality buckets")

# 5. Distributed sort under shard_map (single device here; the same code
#    runs on the (data,) axis of the production mesh) ----------------------
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import dist

mesh = jax.make_mesh((len(jax.devices()),), ("data",))
ds = jax.jit(functools.partial(dist.sort, mesh=mesh))
xs = jax.device_put(x, NamedSharding(mesh, P("data")))
out, counts, overflow = ds(xs)
assert not bool(jnp.any(overflow))
print(f"5. distributed sort: {int(counts.sum())} elements globally ordered "
      f"across {mesh.shape['data']} shard(s)")

# 6. Batched: (B, n) rows sorted in ONE trace (no python loop) --------------
from repro.ops import batched_sort, batched_topk

xb = jnp.asarray(np.random.default_rng(1).random((8, 1 << 14), np.float32))
yb = batched_sort(xb)                          # every row, one compiled call
assert bool(jnp.all(yb[:, :-1] <= yb[:, 1:]))
vals, idx = batched_topk(xb, 4)                # per-row top-k, same call shape
assert bool(jnp.all(vals[:, 0] == xb.max(axis=1)))
print(f"6. batched: {xb.shape[0]} rows x {xb.shape[1]} keys sorted in one "
      "trace; per-row top-4 via batched_topk")

# 7. Streaming / out-of-core (DESIGN.md §7): datasets larger than one device
#    allocation — IPS4o run formation + a stable merge-path k-way merge ------
from repro.stream import external_sort, merge, streaming_topk

host = np.random.default_rng(2).standard_normal(1 << 16).astype(np.float32)
ys = external_sort(host, chunk_size=1 << 14)   # 4 chunks, never all on device
assert (ys[:-1] <= ys[1:]).all()
runs = [jnp.sort(jnp.asarray(host[: 1 << 13])),  # device-resident k-way merge
        jnp.sort(jnp.asarray(host[1 << 13 : 1 << 14]))]
m = merge(runs)                                # stable; engine="pallas" for the
assert bool(jnp.all(m[:-1] <= m[1:]))          # merge-path kernel
tv, ti = streaming_topk(host, 8, chunk_size=1 << 14)  # bounded candidate buffer
assert tv[0] == host.max()
print(f"7. streaming: {host.shape[0]} host-resident keys external-sorted in "
      "chunks; k-way merge + streaming top-8 (indices into the stream)")
print("quickstart OK")
