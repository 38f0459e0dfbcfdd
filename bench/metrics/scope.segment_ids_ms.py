"""Device ms per sort call of self time in ``segment_ids`` (scope
``sort.segment_ids``): the bucket id of every position, searched in the
offsets of level 1 (before level 2) and of the last level (before the base
case).  Self time and parts as ``bench/scopes.py`` defines them; averaged
over the cell's devices.  Nothing is returned where the program names no
such scope or the part never ran."""
import scopes


def read(trace, ctx):
    return scopes.ms(trace, ctx, "segment_ids")
