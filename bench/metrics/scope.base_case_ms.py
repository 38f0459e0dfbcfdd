"""Device ms per sort call of self time in the base case (scope
``sort.base_case``): the bucket check, the ``lax.cond`` itself and its
first branch, the windowed sorts and their gathers.  Self time and parts
as ``bench/scopes.py`` defines them; averaged over the cell's devices.
Nothing is returned where the program names no such scope or the part
never ran."""
import scopes


def read(trace, ctx):
    return scopes.ms(trace, ctx, "base_case")
