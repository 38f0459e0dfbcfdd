"""The profiler trace of a run's window, reduced to device events.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote and keeps:

- per device, every event of its ``XLA Ops`` line as
  ``[op, start_ns, duration_ns, opcode]``: on a TPU the event is named by
  its HLO instruction's text (``%fusion.166 = s32[...] fusion(...), ...``),
  of which the instruction's name (``fusion.166``) and opcode (``fusion``)
  are kept;
- on the host, the harness's own ``bench.*`` annotations;
- the window: the ``bench.window`` annotation's start and end.

Ops of one program nest (a ``while`` spans the ops of its body), so a
reduction over several ops takes the union of their intervals.  Which
code an op comes from is in the compiled program's metadata: ``op_names``
maps each instruction to its ``op_name`` (``jit(<entry>)/jit(searchsorted)/
.../gather``).  A ``Trace`` also round-trips through JSON (``to_json`` /
``from_json``), which is how the harness's tests hold a recorded trace.
"""
from __future__ import annotations

import glob
import gzip
import json
import os
import re
from collections import defaultdict

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
INSTR = re.compile(r"^%([^\s=]+) = .*?\s([a-z][a-z0-9\-]*)\(")
OP_NAME = re.compile(r"^\s*(?:ROOT )?%([^\s=]+) = .*metadata=\{op_name=\"([^\"]*)\"", re.M)


def parse_op(text: str):
    """(instruction name, opcode) of a device event's name."""
    m = INSTR.match(text)
    if m:
        return m.group(1), m.group(2)
    return text, text.split(".")[0]


def op_names(hlo: str) -> dict:
    """Instruction name -> ``op_name`` metadata of a compiled program's text."""
    return {m.group(1): m.group(2) for m in OP_NAME.finditer(hlo)}


class Trace:
    def __init__(self, devices: dict, host: list, window: list, meta: dict = None):
        self.devices = devices  # device id (str) -> [[op, start_ns, dur_ns, opcode], ...]
        self.host = host        # [[name, start_ns, dur_ns], ...]
        self.window = window    # [start_ns, end_ns]
        self.meta = meta or {}  # what a recorded trace keeps of its run

    # -- persistence --------------------------------------------------------

    def to_json(self, path: str) -> None:
        with gzip.open(path, "wt") as f:
            json.dump({"devices": self.devices, "host": self.host, "window": self.window,
                       "meta": self.meta}, f)

    @classmethod
    def from_json(cls, path: str) -> "Trace":
        with gzip.open(path, "rt") as f:
            d = json.load(f)
        return cls(d["devices"], d["host"], d["window"], d.get("meta"))

    # -- reductions ---------------------------------------------------------

    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def events(self, dev: str):
        """The device's events clipped to the window, as
        (op, start, end, opcode)."""
        w0, w1 = self.window
        for name, s, d, cat in self.devices[dev]:
            a, b = max(s, w0), min(s + d, w1)
            if b > a:
                yield name, a, b, cat

    def busy_intervals(self, dev: str) -> list:
        """Union of the device's op intervals inside the window, sorted."""
        out = []
        for _, a, b, _ in sorted(self.events(dev), key=lambda e: e[1]):
            if out and a <= out[-1][1]:
                out[-1][1] = max(out[-1][1], b)
            else:
                out.append([a, b])
        return out

    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the devices."""
        tot = [sum(b - a for a, b in self.busy_intervals(d)) for d in self.devices]
        return sum(tot) / len(tot) / 1e9

    def op_seconds(self, dev: str, match) -> float:
        """Seconds of the device's events for which ``match(op, opcode)``
        holds; an event nested in another matching one counts once."""
        ivs = []
        for name, a, b, cat in sorted(self.events(dev), key=lambda e: e[1]):
            if match(name, cat):
                if ivs and a < ivs[-1][1]:
                    ivs[-1][1] = max(ivs[-1][1], b)
                else:
                    ivs.append([a, b])
        return sum(b - a for a, b in ivs) / 1e9

    def host_at(self, t: float) -> str:
        """The innermost ``bench.*`` span on the host at time ``t``, or
        "harness" where none is open."""
        best = None
        for name, s, d in self.host:
            if s <= t < s + d and (best is None or d < best[1]):
                best = (name, d)
        return best[0] if best else "harness"

    def breakdown(self, op_names: dict, top: int = 10) -> dict:
        """The device ops that took most time (summed over devices, by op,
        each named with its ``op_name``; an op inside a loop or branch is
        counted in its own row and in the loop's) and the longest idle gaps
        of the first device, named by what the host was doing in the
        middle of each."""
        ops = defaultdict(int)
        for dev in self.devices:
            for name, a, b, _ in self.events(dev):
                ops[f"{name} {op_names.get(name, '')}".strip()] += b - a
        first = sorted(self.devices)[0]
        busy = self.busy_intervals(first)
        edges = [self.window[0]] + [x for iv in busy for x in iv] + [self.window[1]]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2) if edges[i + 1] > edges[i]]
        gaps.sort(key=lambda g: g[0] - g[1])
        return {
            "device_ops": [[n, v / 1e9] for n, v in sorted(ops.items(), key=lambda x: -x[1])[:top]],
            "idle_gaps": [[self.host_at((a + b) / 2), (b - a) / 1e9] for a, b in gaps[:top]],
        }


def load(profile_dir: str, device_ids) -> Trace:
    """Read the one ``.xplane.pb`` under ``profile_dir``; keep the devices
    whose ids are in ``device_ids``."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"), recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {profile_dir}, found {paths}")
    pd = ProfileData.from_file(paths[0])
    want = {str(i) for i in device_ids}
    devices, host, window = {}, [], None
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m and m.group(1) in want:
            evs = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    evs += [[*parse_op(e.name), int(e.start_ns), int(e.duration_ns)]
                            for e in line.events]
            devices[m.group(1)] = [[n, s, d, op] for n, op, s, d in evs]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        host.append([e.name, int(e.start_ns), int(e.duration_ns)])
                        if e.name == "bench.window":
                            window = [int(e.start_ns), int(e.start_ns + e.duration_ns)]
    if window is None or set(devices) != want:
        raise RuntimeError(f"trace lacks the window or a device: devices {sorted(devices)}")
    return Trace(devices, host, window)
