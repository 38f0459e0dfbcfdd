"""Device ms per sort call of self time in the deferred payload's gather
(``move`` under ``sort.payload``, ``core/ips4o.py::ips4o_sort``): every
value leaf gathered once by the sorted row index.  Self time and parts as
``bench/scopes.py`` defines them; averaged over the cell's devices.
Nothing is returned where the program names no such scope or the part
never ran (a payload of fewer than two words a row is not deferred)."""
import scopes


def read(trace, ctx):
    return scopes.ms(trace, ctx, "payload_move")
