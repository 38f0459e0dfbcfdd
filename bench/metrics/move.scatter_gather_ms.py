"""Device ms per sort call in scatter and gather ops that move keys and
payload: the partitions' moves (``.at[dest].set`` on the Pallas engine,
``jnp.take`` on XLA, ``core/partition.py``) and the base case's window
gathers (``take_along_axis``).  An op counts where its opcode, or the
``op_name`` the compiled program gives it (a fused gather or scatter is a
``fusion``), ends in gather or scatter; the gathers of ``jnp.searchsorted``
are not moves and are left to ``segment_ids.searchsorted_ms``.  Averaged
over the cell's devices."""

WORDS = ("gather", "scatter", "scatter-add")


def is_move(op, opcode, op_names):
    name = op_names.get(op, "")
    if "searchsorted" in name:
        return False
    return opcode in WORDS or name.rsplit("/", 1)[-1] in WORDS


def read(trace, ctx):
    names = ctx["op_names"]
    secs = [trace.op_seconds(d, lambda op, opcode: is_move(op, opcode, names))
            for d in trace.devices]
    v = sum(secs) / len(secs) / ctx["calls"] * 1e3
    return v if v > 0 else None
