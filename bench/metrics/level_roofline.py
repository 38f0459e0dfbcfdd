"""Share of its HBM roofline that the level pass's classify step reaches.

Work, counted from the configuration whatever implements it: classifying
the table's n keys reads each 4-byte key once and writes one 4-byte
destination, 8 bytes a key (the rows a program pads the table to are its
own cost, not the step's work).  The least time is those bytes over the
chip's HBM bandwidth.  The time taken is that of the Pallas level kernels
(``kernels/level_fused.py``: ``level_fused`` and ``rank_hist``, the ops
``level.kernel_ms`` reads) per call, averaged over the cell's devices.
Nothing is returned where no level kernel ran."""
BYTES_PER_KEY = 8
KERNELS = ("level_fused", "rank_hist")


def read(trace, ctx):
    secs = [trace.op_seconds(d, lambda op, opcode: op.split(".")[0] in KERNELS)
            for d in trace.devices]
    t = sum(secs) / len(secs) / ctx["calls"]
    if t <= 0:
        return None
    return 100.0 * ctx["rows"] * BYTES_PER_KEY / ctx["peaks"]["hbm_bytes_per_s"] / t
