"""The ``scope.*`` readers: device self time per layer of the sort, read
from the scopes the program names in each op's ``op_name``.

Checked on a hand-built trace with nested ops (a ``while`` and its body, a
``conditional`` and its second branch, an op with no ``op_name``), where
every answer is known, and on the recorded chip trace of a program that
named no scopes, where every layer reader must stay silent.
"""
import glob
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)

import devtrace  # noqa: E402
import run  # noqa: E402
import scopes  # noqa: E402

FIXTURE = os.path.join(HERE, "fixtures", "sf1_partkey_trace.json.gz")
E = "jit(entry)/sort"

OP_NAMES = {
    "fusion.1": f"{E}/convert_element_type",
    "level_fused.2": f"{E}/sort.level1/classify/jit(level_fused)/level_fused/pallas_call",
    "scatter.3": f"{E}/sort.level1/partition/move/scatter",
    "while.4": f"{E}/sort.segment_ids/jit(searchsorted)/vmap()/while",
    "fusion.5": f"{E}/sort.segment_ids/jit(searchsorted)/vmap()/while/body/closed_call/gather",
    "sort.7": f"{E}/sort.level2/partition/jit(argsort)/sort",
    "fusion.8": f"{E}/sort.level2/partition/move/jit(_take)/gather",
    "fusion.9": f"{E}/sort.base_case/reduce_or",
    "conditional.10": f"{E}/sort.base_case/cond",
    "sort.11": f"{E}/sort.base_case/cond/branch_1_fun/jit(argsort)/sort",
    "fusion.12": f"{E}/sort.base_case/cond/branch_1_fun/move/jit(_take)/gather",
    # copy.13 has no op_name
    "fusion.14": f"{E}/jit(_take)/gather;{E}/sort.level1/partition/move/scatter",
    "fusion.15": f"{E}/sort.level2/sort.segment_ids/jit(searchsorted)/while/body/gather",
    "fusion.16": f"{E}/sort.base_case/cond/branch_0_fun/move/vmap()/gather",
    "fusion.17": f"{E}/sort.payload/move/jit(_take)/gather",
    "fusion.18": f"{E}/sort.payload/slice",
}


def _trace():
    """Two devices, a window of 1,000 ns from t = 0, two calls."""
    dev0 = [
        ["fusion.1", -5, 15, "fusion"],           # clipped to 0..10: entry 10
        ["level_fused.2", 10, 20, "custom-call"],  # level1 20
        ["scatter.3", 30, 15, "scatter"],         # level1_move 15
        ["while.4", 45, 55, "while"],             # segment_ids: 45..50 and 90..100
        ["fusion.5", 50, 40, "fusion"],           #   its body 50..90: segment_ids 40
        ["fusion.15", 100, 10, "fusion"],         # level 2's search: segment_ids 10
        ["sort.7", 110, 30, "sort"],              # level2 30
        ["fusion.8", 140, 30, "fusion"],          # level2_move 30
        ["fusion.9", 170, 5, "fusion"],           # base_case 5
        ["conditional.10", 175, 125, "conditional"],  # base_case: 175..180, 290..300
        ["sort.11", 180, 50, "sort"],             #   fallback 50
        ["fusion.12", 230, 60, "fusion"],         #   fallback_move 60
        ["copy.13", 300, 20, "copy"],             # unscoped 20; idle 320..400
        ["fusion.14", 400, 10, "fusion"],         # two merged names, the first: entry 10
        ["fusion.16", 410, 10, "fusion"],         # branch 0's move: base_case 10
        ["fusion.17", 420, 10, "fusion"],         # the final gather: payload_move 10
        ["fusion.18", 430, 5, "fusion"],          # outside its move: payload 5
    ]
    dev1 = [["level_fused.2", 0, 40, "custom-call"],   # level1 40
            ["copy.13", 40, 20, "copy"]]              # unscoped 20
    return devtrace.Trace({"0": dev0, "1": dev1}, [["bench.window", 0, 1000]], [0, 1000])


def _readers():
    paths = sorted(glob.glob(os.path.join(BENCH, "metrics", "scope.*.py")))
    names = [os.path.basename(p)[:-3] for p in paths]
    return {name: run.load_reader(BENCH, name) for name in names}


# per part: ns on device 0 + ns on device 1, over 2 devices and 2 calls
WANT_NS = {
    "entry": 10 + 10, "level1": 20 + 40, "level1_move": 15, "segment_ids": 5 + 40 + 10 + 10,
    "level2": 30, "level2_move": 30, "base_case": 5 + 5 + 10 + 10, "fallback": 50,
    "fallback_move": 60, "payload": 5, "payload_move": 10,
}


def test_every_layer_has_a_reader():
    parts = set(scopes.LAYERS.values()) | set(scopes.MOVES.values()) | {"fallback"}
    assert set(_readers()) == {f"scope.{p}_ms" for p in parts} | {"scope.unscoped_share"}


def test_parts_of_op_names():
    part = scopes.part
    assert part(OP_NAMES["fusion.5"]) == "segment_ids"
    assert part(OP_NAMES["fusion.15"]) == "segment_ids"  # the innermost layer wins
    assert part(OP_NAMES["sort.11"]) == "fallback"
    assert part(OP_NAMES["fusion.12"]) == "fallback_move"
    assert part(OP_NAMES["fusion.16"]) == "base_case"     # a move of branch 0 stays there
    assert part(OP_NAMES["conditional.10"]) == "base_case"
    assert part(f"{E}/sort.level1/move/sort") == "level1_move"
    # the deferred payload's gather is its own part, no longer the entry's
    assert part(OP_NAMES["fusion.17"]) == "payload_move"
    assert part(OP_NAMES["fusion.18"]) == "payload"
    assert part("jit(entry)/jit(argsort)/sort") is None   # no scope named
    assert part("jit(entry)/sort") is None                 # an op named sort, not the scope
    assert part("gather") is None and part("") is None


def test_readers_on_a_hand_built_trace():
    tr = _trace()
    ctx = {"calls": 2, "rows": 160, "op_names": OP_NAMES, "peaks": {"hbm_bytes_per_s": 819e9}}
    got = {name: read(tr, ctx) for name, read in _readers().items()}
    for p, ns in WANT_NS.items():
        assert got[f"scope.{p}_ms"] == pytest.approx(ns / 2 / 2 * 1e-6), p
    busy_ns = tr.busy_s() * 1e9 * 2  # summed over the devices
    assert busy_ns == pytest.approx(320 + 15 + 20 + 60)
    assert got["scope.unscoped_share"] == pytest.approx(100 * (20 + 20) / busy_ns)
    # the parts and the unscoped time add up to the busy time
    layers_ms = sum(v for k, v in got.items() if k.endswith("_ms"))
    unscoped_ms = got["scope.unscoped_share"] / 100 * tr.busy_s() * 1e3 / ctx["calls"]
    assert layers_ms + unscoped_ms == pytest.approx(tr.busy_s() * 1e3 / ctx["calls"])


def test_a_part_that_never_ran_reads_nothing():
    tr = devtrace.Trace({"0": [["fusion.1", 0, 10, "fusion"]]}, [], [0, 100])
    ctx = {"calls": 1, "op_names": OP_NAMES}
    got = {name: read(tr, ctx) for name, read in _readers().items()}
    assert got.pop("scope.entry_ms") == pytest.approx(10e-6)
    assert got.pop("scope.unscoped_share") == 0.0
    assert all(v is None for v in got.values()), got
    idle = devtrace.Trace({"0": []}, [], [0, 100])
    assert all(read(idle, ctx) is None for read in _readers().values())


def test_self_time_of_overlapping_events_goes_to_the_later_start():
    evs = [("a", 0, 10, "x"), ("b", 5, 15, "x"), ("c", 5, 8, "x")]
    got = scopes.self_ns(evs, lambda op: op)
    # 0..5 a; 5..8 c (started with b, but shorter: inside it); 8..15 b
    assert dict(got) == {"a": 5, "c": 3, "b": 7}


def test_readers_on_a_recorded_chip_trace_without_scopes():
    """The recorded trace was taken before the program named any scope:
    no layer reads, and all of its busy time is unscoped."""
    tr = devtrace.Trace.from_json(FIXTURE)
    ctx = {"calls": tr.meta["calls"], "rows": tr.meta["rows"], "op_names": tr.meta["op_names"]}
    got = {name: read(tr, ctx) for name, read in _readers().items()}
    assert got.pop("scope.unscoped_share") == pytest.approx(100.0)
    assert all(v is None for v in got.values()), got
