"""The ``orderby`` entry: a whole TPC-H table ordered by one key on one chip.

The call is ``repro.ops.sort(key, (rowids, columns), engine=...)``: the
library's stable sort, carrying the row id and every other column of the
table as its payload, as an engine orders a whole table.  The mix's
``tables`` tables are drawn from the seed on the cell's first device
(``tpch.py``); each call's keys and row ids are fetched whole, its other
columns at the sampled positions, and compared with the stable reference
(``reference.compare``).  The entry of every configuration that names none.
"""
import numpy as np

import reference
import tpch


def validate(config: dict):
    """Refuse what the stable reference cannot judge; called before JAX loads."""
    if not config["guarantees"]["stable"]:
        raise ValueError("the reference compares a stable sort; this configuration states none")


def setup(jax, config: dict, traffic: dict, devices, seed: int) -> dict:
    import jax.numpy as jnp
    from repro import ops

    engine = traffic["engine"]
    dev = devices[0]
    n = config["rows"]

    def entry(k, rowids, cols):
        return ops.sort(k, (rowids, cols), engine=engine)

    def pick(cols, pos):
        return jnp.stack([jnp.take(c, pos) for c in cols])

    pick = jax.jit(pick)
    tables = [tpch.table(config, traffic["key"], seed, t, dev) for t in range(traffic["tables"])]
    rowids = jax.device_put(np.arange(n, dtype=np.int32), dev)

    def fetch(out, pos):
        keys, (ids, cols) = out
        return keys, ids, pos, pick(cols, jax.device_put(pos, dev))

    def compare(outputs, calls):
        # the reference reads the tables as they were drawn: the key column
        # whole, the other columns at the rows it puts at each sampled position
        host_keys = [np.asarray(k) for k, _ in tables]

        def columns_at(t, rows):
            return np.asarray(pick(tables[t][1], jax.device_put(rows, dev)))

        return reference.compare([(t, *o) for t, o in outputs], host_keys, columns_at, calls)

    words = tpch.payload_words(config, traffic["key"])
    return {
        "inputs": [(k, rowids, cols) for k, cols in tables],
        "call": jax.jit(entry),
        "fetch": fetch,
        "rows": n,
        "in_bytes": [n * 4 * (2 + words)],  # key, row id and every other column
        "compare": compare,
        "limits": reference.LIMITS,
    }
