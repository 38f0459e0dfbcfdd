"""Device ms per sort call of self time in level 2 (scope ``sort.level2``,
``segmented_level_pass``): its per-segment sample, classify and partition
(on the XLA engine the per-tile argsort permutation), without its payload
move.  Self time and parts as ``bench/scopes.py`` defines them; averaged
over the cell's devices.  Nothing is returned where the program names no
such scope or the part never ran."""
import scopes


def read(trace, ctx):
    return scopes.ms(trace, ctx, "level2")
