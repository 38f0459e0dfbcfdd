"""Device ms per sort call in ``jnp.searchsorted``: the bucket id of every
position, searched in the level offsets (``core/ips4o.py::segment_ids``,
before level 2 and before the base case), with the small searches of the
segmented sample and, on a mesh, of the exchange's classify.  An op counts
where the compiled program's ``op_name`` holds ``jit(searchsorted)``; the
loop around the search and the gathers in it count once.  Averaged over
the cell's devices."""


def read(trace, ctx):
    names = ctx["op_names"]
    secs = [trace.op_seconds(d, lambda op, opcode: "jit(searchsorted)" in names.get(op, ""))
            for d in trace.devices]
    v = sum(secs) / len(secs) / ctx["calls"] * 1e3
    return v if v > 0 else None
