"""Drive whole runs of a cell on the CPU with the timed path broken.

    python bench/tests/faults.py <checkout> <cell> <rows> <fault> [<fault> ...]
    python bench/tests/faults.py <checkout> <cell> 0 <fault> ... --seeds <n> ... --seconds <s>

With ``rows`` > 0, skips the harness's look for a chip (and reads a fixed
memory figure, which the CPU backend does not report) and runs the cell at
``rows`` rows with a quarter-second window under each fault.  With
``rows`` 0, runs the cell as it is on the chip, at its own size, once for
each seed.  Prints one JSON object ``{fault: {"correct": ..., "checks":
{...}}}`` (with ``rows`` 0, a list of them, one per seed).  Faults:

- ``none``: the program as it is;
- ``unchanged``: each call returns its input, as a step that leaves its
  state unchanged;
- ``half``: each call sorts the first half of the rows and leaves the rest;
- ``altered``: each call's first and last output keys and row ids are
  swapped;
- ``columns_altered``: each call's other columns come out one word off in
  every row, where the payload is produced;
- ``control_unstable``: the reference in the program's place, with ties
  in no set order (``lax.sort(is_stable=False)``): it breaks the stability
  that the configuration states;
- ``control_bf16``: the reference in the program's place, ordering by the
  key rounded to bfloat16, the next precision below a 4-byte key: it
  breaks the exact order that the configuration states.
"""
import argparse
import json
import os
import sys

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir))
import run  # noqa: E402


# each fault takes the jitted entry ``f(keys, rowids, cols) -> (keys, (rowids, cols))``
def unchanged(f):
    return lambda k, v, cs: (k, (v, cs))


def half(f):
    def g(k, v, cs):
        h = k.shape[0] // 2
        ks, (vs, cs2) = f(k[:h], v[:h], tuple(c[:h] for c in cs))
        return (jnp.concatenate([ks, k[h:]]),
                (jnp.concatenate([vs, v[h:]]),
                 tuple(jnp.concatenate([a, c[h:]]) for a, c in zip(cs2, cs))))
    return g


def altered(f):
    def g(k, v, cs):
        ks, (vs, cs2) = f(k, v, cs)
        ks = ks.at[0].set(ks[-1]).at[-1].set(ks[0])
        vs = vs.at[0].set(vs[-1]).at[-1].set(vs[0])
        return ks, (vs, cs2)
    return g


def columns_altered(f):
    def g(k, v, cs):
        ks, (vs, cs2) = f(k, v, cs)
        return ks, (vs, (cs2[0] + 1,) + tuple(cs2[1:]))
    return g


def control_unstable(f):
    def g(k, v, cs):
        out = jax.lax.sort((k, v) + tuple(cs), num_keys=1, is_stable=False)
        return out[0], (out[1], tuple(out[2:]))
    return g


def control_bf16(f):
    def g(k, v, cs):
        out = jax.lax.sort((k.astype(jnp.bfloat16), k, v) + tuple(cs), num_keys=1, is_stable=True)
        return out[1], (out[2], tuple(out[3:]))
    return g


FAULTS = {"none": None, "unchanged": unchanged, "half": half, "altered": altered,
          "columns_altered": columns_altered, "control_unstable": control_unstable,
          "control_bf16": control_bf16}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("root")
    ap.add_argument("cell")
    ap.add_argument("rows", type=int)
    ap.add_argument("faults", nargs="+", choices=sorted(FAULTS))
    ap.add_argument("--seeds", type=int, nargs="+", default=[2**31 + 7])
    ap.add_argument("--seconds", type=float, default=0.25)
    args = ap.parse_args()
    if args.rows:
        kw = {"rows": args.rows, "require_tpu": False,
              "memory": lambda devs: [{"peak_bytes_in_use": 2 << 20, "bytes_in_use": 1 << 20}] * len(devs)}
    else:
        kw = {}
    out = {}
    for name in args.faults:
        fault = FAULTS[name]
        wrap = None if fault is None else (lambda f, fault=fault: jax.jit(fault(f)))
        out[name] = []
        for seed in args.seeds:
            res = run.run_cell(args.root, args.cell, seed, args.seconds, False, wrap=wrap, **kw)
            out[name].append({"seed": seed, "correct": res["correct"], "attempted": res["attempted"],
                              "failed": res["failed"], "checks": res["checks"],
                              "metrics": sorted(res["metrics"])})
            print(name, json.dumps(out[name][-1]), file=sys.stderr, flush=True)
    if args.rows:
        out = {name: runs[0] for name, runs in out.items()}
    print(json.dumps(out))


if __name__ == "__main__":
    main()
