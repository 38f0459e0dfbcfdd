"""Device ms per sort call of self time in the entry, ``ops.sort`` (scope
``sort``): keyspace encode, pad, the splitter RNG, decode and the final
slice, outside every level.  Self time and parts as ``bench/scopes.py``
defines them; averaged over the cell's devices.  Nothing is returned where
the program names no such scope or the part never ran."""
import scopes


def read(trace, ctx):
    return scopes.ms(trace, ctx, "entry")
