"""Device ms per sort call in the Pallas level kernels
(``kernels/level_fused.py``): the fused classify + rank + histogram kernel
``level_fused`` and the rank + histogram kernel ``rank_hist``, which the
XLA engine replaces.  A Pallas call's op in the compiled program, and its
event in the trace, is named after the jitted function around it.
Averaged over the cell's devices; nothing is returned where neither ran."""

KERNELS = ("level_fused", "rank_hist")


def read(trace, ctx):
    secs = [trace.op_seconds(d, lambda op, opcode: op.split(".")[0] in KERNELS)
            for d in trace.devices]
    v = sum(secs) / len(secs) / ctx["calls"] * 1e3
    return v if v > 0 else None
