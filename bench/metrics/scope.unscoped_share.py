"""Share of the device's busy time, in %, whose innermost op names no layer
of the sort (no ``sort`` scope in its ``op_name``): XLA-made copies and
ops with no ``op_name``, or every op of a program that names no scopes.
Where a scope is dropped this share grows, where a reader of one scope
would fall silent.  Self time as ``bench/scopes.py`` defines it; over the
cell's devices."""
import scopes


def read(trace, ctx):
    return scopes.unscoped_share(trace, ctx)
