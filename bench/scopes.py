"""Device self time of each layer of the 1-D sort, read from the scopes
the program names in every op's ``op_name`` (``repro.obs.LAYERS``).

At each instant of the window a device's time goes to the innermost op
running then: of the events that cover the instant (a ``while`` spans the
ops of its body, a ``conditional`` those of its branch), the one that
started last.  That op's part of the sort is read from its ``op_name``
(``jit(entry)/sort/sort.level2/partition/move/jit(_take)/gather``): the
innermost ``sort.*`` scope names the layer, a ``move`` scope below it
makes the time that layer's payload move, and under ``sort.base_case`` the
ops of the cond's second branch (``cond/branch_1_fun``) are the fallback,
``stable_full_sort``.  ``sort.payload`` is the deferred payload's final
gather, all of it in its ``move`` scope.  An op whose ``op_name`` holds no ``sort`` scope (an
XLA-made copy, or a program that names no scopes) is unscoped.  Every
instant of busy time goes to exactly one part, so the parts add up to the
busy time.
"""
from __future__ import annotations

from collections import defaultdict

# the program's layer scopes, and the part each names
LAYERS = {
    "sort": "entry",
    "sort.level1": "level1",
    "sort.segment_ids": "segment_ids",
    "sort.level2": "level2",
    "sort.base_case": "base_case",
    "sort.payload": "payload",
}
# the parts whose payload moves are told apart; a move elsewhere stays
# with its layer
MOVES = {"level1": "level1_move", "level2": "level2_move", "fallback": "fallback_move",
         "payload": "payload_move"}


def part(op_name: str):
    """The part of the sort an op belongs to, or None where its ``op_name``
    names no layer.  XLA joins the names of ops it merged with ``;``: the
    first is read."""
    path = op_name.split(";")[0].split("/")[:-1]  # the last component is the op
    got, move = None, False
    for i, p in enumerate(path):
        if p in LAYERS:
            got, move = LAYERS[p], False
        elif got == "base_case" and p == "branch_1_fun" and i and path[i - 1] == "cond":
            got, move = "fallback", False
        elif p == "move" and got:
            move = True
    return MOVES.get(got, got) if move else got


def self_ns(events, label) -> dict:
    """Nanoseconds of self time per label over ``events`` (op, start, end,
    opcode): each instant goes to the latest-starting event that covers it."""
    out = defaultdict(int)
    stack, t = [], 0  # open events as (end, label), in start order

    def advance(to):
        nonlocal t
        while stack and t < to:
            end, lab = stack[-1]
            if end <= t:
                stack.pop()
                continue
            step = min(end, to)
            out[lab] += step - t
            t = step
        t = to

    # at equal starts the longer event encloses the shorter one
    for op, a, b, _ in sorted(events, key=lambda e: (e[1], -e[2])):
        advance(a)
        stack.append((b, label(op)))
    advance(float("inf"))
    return out


def times(trace, op_names: dict) -> dict:
    """Self nanoseconds per part (None: unscoped), summed over the trace's
    devices."""
    tot = defaultdict(int)
    for d in trace.devices:
        for k, v in self_ns(trace.events(d), lambda op: part(op_names.get(op, ""))).items():
            tot[k] += v
    return tot


def ms(trace, ctx, name: str):
    """Device ms per sort call of one part, averaged over the devices;
    None where that part never ran."""
    ns = times(trace, ctx["op_names"]).get(name, 0)
    if ns <= 0:
        return None
    return ns / len(trace.devices) / ctx["calls"] / 1e6


def unscoped_share(trace, ctx):
    """% of busy time whose innermost op names no layer; None where the
    devices ran nothing in the window."""
    t = times(trace, ctx["op_names"])
    busy = sum(t.values())
    if busy <= 0:
        return None
    return 100.0 * t.get(None, 0) / busy
