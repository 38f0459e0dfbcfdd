"""Device ms per sort call of self time in level 1's payload move (``move``
under ``sort.level1``): the scatter of every array to its destination.
Self time and parts as ``bench/scopes.py`` defines them; averaged over the
cell's devices.  Nothing is returned where the program names no such scope
or the part never ran."""
import scopes


def read(trace, ctx):
    return scopes.ms(trace, ctx, "level1_move")
