"""Device ms per sort call of self time in the fallback's payload move
(``move`` in ``cond/branch_1_fun``): ``stable_full_sort``'s gather of
every array by its order.  Self time and parts as ``bench/scopes.py``
defines them; averaged over the cell's devices.  Nothing is returned where
the program names no such scope or the part never ran."""
import scopes


def read(trace, ctx):
    return scopes.ms(trace, ctx, "fallback_move")
