"""Device ms per sort call in XLA ``sort`` ops: the windowed stable
argsorts of the base case (``core/ips4o.py::base_case``), and with them the
small sample sorts of the level passes and the fallback full sort, which
an op kind cannot tell apart.  Averaged over the cell's devices."""


def read(trace, ctx):
    secs = [trace.op_seconds(d, lambda op, opcode: opcode == "sort") for d in trace.devices]
    v = sum(secs) / len(secs) / ctx["calls"] * 1e3
    return v if v > 0 else None
