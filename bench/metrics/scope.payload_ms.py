"""Device ms per sort call of self time in ``sort.payload`` outside its
``move`` scope.  The program runs every op of that scope inside ``move``
(``scope.payload_move_ms``), so this reads nothing on it; it keeps every
part of ``bench/scopes.py`` with a reader, so that the parts add up to the
busy time whatever a program puts there.  Averaged over the cell's
devices; nothing is returned where the part never ran."""
import scopes


def read(trace, ctx):
    return scopes.ms(trace, ctx, "payload")
