"""A test entry: a TPC-H table sharded over the cell's chips and ordered by
``repro.dist.sort`` on a ``(chips,)`` mesh, every other column riding as
its payload.

The harness's tests copy it to ``bench/entries/`` of a copy of the
checkout, as a configuration that brings its own entry would add it.  Each
of a table's shards is its own dbgen draw of ``rows / chips`` rows on its
own chip (``tpch.table`` of the shard's index), so no chip holds the whole
table; a row's id is its position in the table the shards make together.
``dist.sort`` keeps no tie order and returns padded shards, per-shard
counts and overflow flags, so the configuration states no stable sort and
the comparison is ``reference.compare_shards``.
"""
import numpy as np

import reference
import tpch


def validate(config: dict):
    if config["guarantees"]["stable"]:
        raise ValueError("dist.sort keeps no tie order; this configuration states a stable sort")


def setup(jax, config: dict, traffic: dict, devices, seed: int) -> dict:
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro import dist

    d, n = len(devices), config["rows"]
    m = n // d
    mesh = Mesh(np.asarray(devices), ("data",))
    by_rows = NamedSharding(mesh, P("data"))

    def glue(shards):
        return jax.make_array_from_single_device_arrays((n,), by_rows, list(shards))

    def table(t):
        shards = [tpch.table(dict(config, rows=m), traffic["key"], seed, t * d + i, dev)
                  for i, dev in enumerate(devices)]
        cols = tuple(glue(c[w] for _, c in shards) for w in range(len(shards[0][1])))
        return glue(k for k, _ in shards), cols

    tables = [table(t) for t in range(traffic["tables"])]
    rowids = glue(jax.device_put(np.arange(i * m, (i + 1) * m, dtype=np.int32), dev)
                  for i, dev in enumerate(devices))

    def entry(k, ids, cols):
        return dist.sort(k, mesh, "data", values=(ids, cols), engine=traffic["engine"])

    @jax.jit
    def pick(cols, pos):
        return jnp.stack([jnp.take(c, pos) for c in cols])

    def fetch(out, pos):
        keys, (ids, cols), counts, overflow = out
        counts = np.asarray(counts)
        cap = keys.shape[0] // d
        # each sampled position of the joined output, in the padded shards
        valid = np.clip(counts, 0, cap)
        ends = np.cumsum(valid)
        shard = np.minimum(np.searchsorted(ends, pos, side="right"), d - 1)
        at = shard * cap + np.clip(pos - (ends[shard] - valid[shard]), 0, cap - 1)
        return keys, ids, counts, overflow, pos, pick(cols, at.astype(np.int32))

    def compare(outputs, calls):
        host_keys = [np.asarray(k) for k, _ in tables]

        def columns_at(t, rows):
            return np.asarray(pick(tables[t][1], rows))

        shards = [(t, k.reshape(d, -1), r.reshape(d, -1), *o) for t, (k, r, *o) in outputs]
        return reference.compare_shards(shards, host_keys, columns_at, calls)

    words = tpch.payload_words(config, traffic["key"])
    return {
        "inputs": [(k, rowids, cols) for k, cols in tables],
        "call": jax.jit(entry),
        "fetch": fetch,
        "rows": n,
        "in_bytes": [m * 4 * (2 + words)] * d,
        "compare": compare,
        "limits": reference.SHARD_LIMITS,
    }
