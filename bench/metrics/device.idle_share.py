"""Share of the traced window in which no operation ran on the device,
averaged over the cell's devices: 1 - (union of op intervals) / window."""


def read(trace, ctx):
    return 100.0 * (1.0 - trace.busy_s() / trace.window_s())
