"""Share of sort calls whose base case fell back to the plain stable sort.

After the level passes, ``core/ips4o.py::_sort_padded`` checks the bucket
sizes and, where a bucket is too large for a base-case window, runs
``stable_full_sort`` (one XLA argsort of the whole array and its gathers)
in place of ``base_case`` through a ``lax.cond``: the level passes' work is
then spent for nothing.  The fallback is the cond's second branch, whose
``op_name`` runs ``cond/branch_1_fun/jit(argsort)``.  The share is the
calls (the harness's ``bench.call`` spans) in which a device ran that
sort, averaged over the cell's devices.  Nothing is returned where
neither branch ran."""


def fallback(op, opcode, op_names):
    return opcode == "sort" and "cond/branch_1_fun/jit(argsort)" in op_names.get(op, "")


def window_sort(op, opcode, op_names):
    return opcode == "sort" and "cond/branch_0_fun/jit(argsort)" in op_names.get(op, "")


def read(trace, ctx):
    names = ctx["op_names"]
    calls = [(s, s + d) for name, s, d in trace.host if name == "bench.call"]
    shares, seen = [], False
    for d in trace.devices:
        evs = list(trace.events(d))
        seen |= any(fallback(op, opc, names) or window_sort(op, opc, names)
                    for op, _, _, opc in evs)
        starts = [a for op, a, _, opc in evs if fallback(op, opc, names)]
        fell = sum(any(c0 <= a < c1 for a in starts) for c0, c1 in calls)
        shares.append(fell / len(calls) if calls else 0.0)
    if not seen:
        return None
    return 100.0 * sum(shares) / len(shares)
