"""Device ms per sort call of self time in level 1 (scope ``sort.level1``,
``core/ips4o.py::level_pass``): its sample, classify (on the Pallas engine
the fused ``level_fused`` kernel and its prefix epilogue) and partition,
without its payload move.  Self time and parts as ``bench/scopes.py``
defines them; averaged over the cell's devices.  Nothing is returned where
the program names no such scope or the part never ran."""
import scopes


def read(trace, ctx):
    return scopes.ms(trace, ctx, "level1")
