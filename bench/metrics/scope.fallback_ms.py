"""Device ms per sort call of self time in the fallback (the ops of
``cond/branch_1_fun`` under ``sort.base_case``): ``stable_full_sort``'s
argsort of the whole array.  Self time and parts as ``bench/scopes.py``
defines them; averaged over the cell's devices.  Nothing is returned where
the program names no such scope or the part never ran."""
import scopes


def read(trace, ctx):
    return scopes.ms(trace, ctx, "fallback")
