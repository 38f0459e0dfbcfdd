"""Run a cell once on the CPU at a small size and report where its entry
placed the inputs.

    python bench/tests/placement.py <checkout> <cell> <rows>

Skips the harness's look for a chip (and reads a fixed memory figure,
which the CPU backend does not report), runs the cell at ``rows`` rows
with a quarter-second window, and prints one JSON object: the run's
``correct``, ``attempted``, ``failed`` and ``checks``, and ``placement``,
for each array of the first input, the (device id, shape) of each of its
shards.  Give the process as many devices as the cell asks for
(``XLA_FLAGS=--xla_force_host_platform_device_count=4``).
"""
import argparse
import json
import os
import sys

import jax

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir))
import run  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("root")
    ap.add_argument("cell")
    ap.add_argument("rows", type=int)
    args = ap.parse_args()
    placement = []
    load_entry = run.load_entry

    def spied(bench, name):
        mod = load_entry(bench, name)
        setup = mod.setup

        def spy(*a):
            entry = setup(*a)
            placement.extend([[s.device.id, list(s.data.shape)] for s in leaf.addressable_shards]
                             for leaf in jax.tree.leaves(entry["inputs"][0]))
            return entry

        mod.setup = spy
        return mod

    run.load_entry = spied
    res = run.run_cell(args.root, args.cell, 2**31 + 11, 0.25, False, rows=args.rows,
                       require_tpu=False,
                       memory=lambda devs: [{"peak_bytes_in_use": 2 << 20,
                                             "bytes_in_use": 1 << 20}] * len(devs))
    print(json.dumps({k: res[k] for k in ("correct", "attempted", "failed", "checks")}
                     | {"placement": placement}))


if __name__ == "__main__":
    main()
