"""The on-chip benchmark's harness, checked on the CPU.

Nothing here loads a TPU library or times anything: the table generator,
the comparisons that decide ``correct`` (stable, and over padded shards
with no tie order), the trace reduction (on a trace recorded on a TPU v5e
and kept as a fixture), the lookup of cells, mixes, entries and metrics by
name (a configuration that brings its own entry runs on four virtual CPU
devices, ``placement.py``), the refusal to run without a chip, and whole
runs driven on the CPU at a small size with the timed path broken
(``faults.py``), each of which must come out not correct.
"""
import hashlib
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

import devtrace  # noqa: E402
import reference  # noqa: E402
import tpch  # noqa: E402

SF1 = os.path.join(BENCH, "configs", "tpch-lineitem-sf1.json")
FIXTURE = os.path.join(HERE, "fixtures", "sf1_partkey_trace.json.gz")


def small_config(rows=10_007):
    with open(SF1) as f:
        cfg = json.load(f)
    cfg["rows"] = rows
    return cfg


def checkout(dst) -> str:
    """A copy of the benchmark's committed files, BENCHMARK.json and bench/."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), dst)
    shutil.copytree(BENCH, os.path.join(dst, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    return str(dst)


def cpu_env():
    return dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=os.path.join(ROOT, "src"))


def host_table(cfg, key, seed, t=0):
    """(key column, (words, rows) payload) of a table, on the host."""
    k, cols = tpch.table(cfg, key, seed, t)
    return np.asarray(k), np.stack([np.asarray(c) for c in cols])


def column(cfg, payload, key, name):
    """The words of column ``name`` in a (words, rows) payload."""
    at = 0
    for col, spec in cfg["columns"].items():
        if col == key:
            continue
        w = tpch.words(spec["bytes"])
        if col == name:
            return payload[at:at + w]
        at += w
    raise KeyError(name)


def text(words_):
    """Each row's bytes of a (words, rows) text column."""
    b = np.ascontiguousarray(words_.T).astype("<u4").view(np.uint8)
    return [bytes(r) for r in b]


# -- tables ----------------------------------------------------------------


@pytest.mark.parametrize("key", ["l_partkey", "l_shipdate"])
def test_table_is_deterministic_per_seed(key):
    cfg = small_config()
    k, p = host_table(cfg, key, 2**33 + 5)
    assert k.dtype == np.int32 and k.shape == (cfg["rows"],)
    assert p.dtype == np.uint32 and p.shape == (tpch.payload_words(cfg, key), cfg["rows"])
    k2, p2 = host_table(cfg, key, 2**33 + 5)
    np.testing.assert_array_equal(k, k2)
    np.testing.assert_array_equal(p, p2)
    assert not np.array_equal(k, host_table(cfg, key, 2**33 + 6)[0])
    assert not np.array_equal(k, host_table(cfg, key, 2**33 + 5, 1)[0])
    # seeds 2**32 apart are different seeds
    assert not np.array_equal(k, host_table(cfg, key, 5)[0])


@pytest.mark.parametrize("key", ["l_comment", "l_quantity", "no_such_column"])
def test_a_key_that_is_not_a_4_byte_column_is_refused(key):
    with pytest.raises(ValueError, match="not a 4-byte column"):
        tpch.table(small_config(), key, 1, 0)


@pytest.mark.parametrize("seed", [0, 1, 2**31 - 1, 2**31 + 12_345, 3 * 2**31])
def test_every_seed_gives_the_same_row_count(seed):
    cfg = small_config()
    k, p = host_table(cfg, "l_shipdate", seed)
    assert k.shape == (cfg["rows"],) and p.shape[1] == cfg["rows"]
    line = column(cfg, p, "l_shipdate", "l_linenumber")[0].astype(np.int64)
    assert line[0] == 1 and line.min() >= 1 and line.max() <= 7
    # a line number steps by one within an order and restarts at 1
    assert np.all((np.diff(line) == 1) | (line[1:] == 1))


def test_columns_follow_dbgen_ranges_at_sf1():
    with open(SF1) as f:
        cfg = json.load(f)
    key = "l_partkey"
    part, p = host_table(cfg, key, 2**31 + 3)
    ship = column(cfg, p, key, "l_shipdate")[0].astype(np.int64)
    commit = column(cfg, p, key, "l_commitdate")[0].astype(np.int64)
    receipt = column(cfg, p, key, "l_receiptdate")[0].astype(np.int64)
    # 1992-01-02 .. 1998-12-01: every one of the 2,526 ship dates occurs
    assert ship.min() == 8036 and ship.max() == 10561
    assert np.unique(ship).shape[0] == 2526
    assert np.all((receipt - ship >= 1) & (receipt - ship <= 30))
    assert np.all((commit - ship >= 30 - 121) & (commit - ship <= 90 - 1))
    assert part.min() >= 1 and part.max() <= 200_000
    supp = column(cfg, p, key, "l_suppkey")[0].astype(np.int64)
    assert supp.min() >= 1 and supp.max() <= 10_000
    qty = column(cfg, p, key, "l_quantity")
    assert np.all(qty[1] == 0) and set(np.unique(qty[0] // 100)) == set(range(1, 51))
    price = column(cfg, p, key, "l_extendedprice")[0].astype(np.int64)
    retail = 90000 + (part // 10) % 20001 + 100 * (part % 1000)
    np.testing.assert_array_equal(price, qty[0] // 100 * retail)
    assert column(cfg, p, key, "l_discount")[0].max() == 10
    assert column(cfg, p, key, "l_tax")[0].max() == 8
    flag = column(cfg, p, key, "l_returnflag")[0]
    status = column(cfg, p, key, "l_linestatus")[0]
    np.testing.assert_array_equal(flag == ord("N"), receipt > tpch.CURRENTDATE)
    assert set(np.unique(flag)) == {ord("A"), ord("N"), ord("R")}
    np.testing.assert_array_equal(status == ord("O"), ship > tpch.CURRENTDATE)
    instruct = {s[:25].decode() for s in text(column(cfg, p, key, "l_shipinstruct"))}
    assert instruct == {s.ljust(25) for s in tpch.SHIPINSTRUCT}
    modes = {s[:10].decode() for s in text(column(cfg, p, key, "l_shipmode"))}
    assert modes == {s.ljust(10) for s in tpch.SHIPMODE}
    lengths = [len(s.rstrip(b"\0")) for s in text(column(cfg, p, key, "l_comment"))]
    assert min(lengths) == 10 and max(lengths) == 43
    # 16 columns, 141 bytes at dbgen's widths; the payload is all but the key
    assert sum(c["bytes"] for c in cfg["columns"].values()) == 141
    assert p.shape[0] == 37


# -- the comparison ----------------------------------------------------------


def _sound(cfg, key, seed):
    """A table and one call's sound output, as the harness keeps it."""
    keys, payload = host_table(cfg, key, seed)
    wk, wr = reference.expected(keys)
    pos = np.random.default_rng(seed).integers(0, keys.shape[0], 256)
    out = [0, wk.copy(), wr.copy(), pos, payload[:, wr[pos]]]

    def columns_at(t, rows):
        return payload[:, rows]

    return keys, out, columns_at


def test_comparison_flags_two_rows_swapped():
    keys, out, at = _sound(small_config(), "l_shipdate", 11)
    k, r = out[1], out[2]
    i, j = 0, k.shape[0] - 1  # keys differ: the first and the last row
    k[[i, j]], r[[i, j]] = k[[j, i]], r[[j, i]]
    nums, failed = reference.compare([tuple(out)], [keys], at, 1)
    assert nums["wrong_keys"] == 2 and nums["wrong_rowids"] == 2 and failed == 1
    assert not reference.verdict(nums)


def test_comparison_of_ties_follows_the_stated_guarantee():
    """The configuration states a stable sort: two tied rows swapped are
    wrong, though every key is right."""
    keys, out, at = _sound(small_config(), "l_shipdate", 12)
    k, r, pos = out[1], out[2], out[3]
    i = int(np.flatnonzero(k[1:] == k[:-1])[0])  # two rows with one key
    r[[i, i + 1]] = r[[i + 1, i]]
    out[3] = np.array([i, i + 1])
    out[4] = at(0, r[out[3]])  # the columns follow the swapped rows
    nums, _ = reference.compare([tuple(out)], [keys], at, 1)
    assert nums["wrong_keys"] == 0
    assert nums["wrong_rowids"] == 2 and nums["wrong_column_words"] > 0
    assert not reference.verdict(nums)


def test_comparison_flags_a_column_word_off():
    keys, out, at = _sound(small_config(), "l_partkey", 15)
    out[4] = out[4].copy()
    out[4][-1, 7] ^= 1
    nums, failed = reference.compare([tuple(out)], [keys], at, 1)
    assert nums["wrong_column_words"] == 1 and failed == 1
    assert nums["wrong_keys"] == nums["wrong_rowids"] == 0
    assert not reference.verdict(nums)


def test_comparison_counts_repeated_ids_missing_rows_and_lost_calls():
    keys, out, at = _sound(small_config(), "l_partkey", 13)
    k, r, pos, cols = out[1:]
    r2 = r.copy()
    r2[1] = r2[0]  # one id twice, one never
    nums, failed = reference.compare(
        [(0, k, r2, pos, cols), (0, k[:-3], r[:-3], pos, cols)], [keys], at, 3)
    assert nums["wrong_rowids"] == 1 + keys.shape[0]
    assert nums["missing_rows"] == 3
    assert nums["unchecked_calls"] == 1
    assert failed == 3


def test_sound_output_passes():
    keys, out, at = _sound(small_config(), "l_partkey", 14)
    nums, failed = reference.compare([tuple(out)], [keys], at, 1)
    assert reference.verdict(nums) and failed == 0


def _sound_shards(cfg, key, seed, d=4):
    """A table and one call's sound output as an entry that keeps no tie
    order and returns padded shards hands it over: ties in descending row
    id (not the stable order), shards of unequal counts padded to one
    capacity."""
    keys, payload = host_table(cfg, key, seed)
    n = keys.shape[0]
    order = np.lexsort((-np.arange(n), keys)).astype(np.int32)
    counts = np.full(d, n // d)
    counts[0] += n - counts.sum() + 5
    counts[1] -= 5
    cap = int(counts.max()) + 7
    k = np.full((d, cap), np.iinfo(np.int32).max, np.int32)
    r = np.zeros((d, cap), np.int32)
    for i, (a, c) in enumerate(zip(np.cumsum(counts) - counts, counts)):
        k[i, :c], r[i, :c] = keys[order[a:a + c]], order[a:a + c]
    pos = np.random.default_rng(seed).integers(0, n, 256)
    out = [0, k, r, counts, np.zeros(d, bool), pos, payload[:, order[pos]]]

    def columns_at(t, rows):
        return payload[:, rows]

    return keys, out, columns_at


def test_shard_comparison_accepts_another_tie_order():
    keys, out, at = _sound_shards(small_config(), "l_shipdate", 16)
    ids = reference.join(out[2], out[3])
    assert not np.array_equal(ids, reference.expected(keys)[1])  # not the stable order
    nums, failed = reference.compare_shards([tuple(out)], [keys], at, 1)
    assert reference.verdict(nums, reference.SHARD_LIMITS) and failed == 0, nums


def _swap_rows(out):
    # the first row and the last valid one, whose keys differ
    k, r, counts = out[1], out[2], out[3]
    last = (len(counts) - 1, counts[-1] - 1)
    k[0, 0], k[last] = k[last], k[0, 0]
    r[0, 0], r[last] = r[last], r[0, 0]


def _repeat_id(out):
    out[2][0, 1] = out[2][0, 0]


def _column_word_off(out):
    out[6] = out[6].copy()
    out[6][-1, 7] ^= 1


def _cut_count(out):
    out[3] = out[3].copy()
    out[3][1] -= 1


def _overflow(out):
    out[4] = out[4].copy()
    out[4][2] = True


@pytest.mark.parametrize("plant, flagged", [
    (_swap_rows, {"wrong_keys": 2}),
    (_repeat_id, {"ids_not_once": 2, "keys_off_their_row": 1}),
    (_column_word_off, {"wrong_column_words": 1}),
    (_cut_count, {"missing_rows": 1, "ids_not_once": 1}),
    (_overflow, {"overflow_shards": 1}),
], ids=["two_rows_swapped", "repeated_row_id", "column_word_off", "count_cut_by_one",
        "overflow_flag"])
def test_shard_comparison_flags_a_planted_fault(plant, flagged):
    keys, out, at = _sound_shards(small_config(), "l_shipdate", 17)
    plant(out)
    nums, failed = reference.compare_shards([tuple(out)], [keys], at, 1)
    for name, v in flagged.items():
        assert nums[name] == v, (name, nums)
    assert failed == 1 and not reference.verdict(nums, reference.SHARD_LIMITS)


# -- the trace reduction ------------------------------------------------------


OP_NAMES = {"fusion.1": "jit(f)/jit(searchsorted)/while/body/gather",
            "fusion.2": "jit(f)/jit(take_along_axis)/gather",
            "level_fused.1": "jit(f)/jit(level_fused)/pallas_call"}


def _synthetic():
    """Two devices, a 100 ns window from t = 1000, known answers."""
    devices = {
        "0": [["fusion.1", 990, 30, "fusion"],                # clipped to 1000..1020
              ["level_fused.1", 1030, 10, "custom-call"],
              ["sort.3", 1050, 20, "sort"],
              ["sort.4", 1060, 20, "sort"],                   # overlaps sort.3: union 1050..1080
              ["scatter.1", 1085, 5, "scatter"]],
        "1": [["all-to-all.2", 1000, 40, "all-to-all"],
              ["fusion.2", 1040, 10, "fusion"],
              ["rank_hist.3", 1090, 20, "custom-call"]],      # clipped to 1090..1100
    }
    host = [["bench.window", 1000, 100], ["bench.call", 1000, 40], ["bench.fetch", 1040, 60]]
    return devtrace.Trace(devices, host, [1000, 1100])


def test_busy_idle_and_breakdown_on_a_synthetic_trace():
    tr = _synthetic()
    assert tr.window_s() == pytest.approx(100e-9)
    # device 0 busy 20 + 10 + 30 + 5 = 65; device 1 busy 40 + 10 + 10 = 60
    assert tr.busy_s() == pytest.approx(62.5e-9)
    assert tr.op_seconds("0", lambda n, c: c == "sort") == pytest.approx(30e-9)
    bd = tr.breakdown(OP_NAMES)
    assert bd["device_ops"][0] == ["all-to-all.2", pytest.approx(40e-9)]
    assert ["fusion.1 jit(f)/jit(searchsorted)/while/body/gather",
            pytest.approx(20e-9)] in bd["device_ops"]
    # device 0's gaps: 1020..1030, 1040..1050, 1090..1100 (10 ns) and 1080..1085;
    # the first lies inside bench.call
    assert bd["idle_gaps"][0] == ["bench.call", pytest.approx(10e-9)]
    assert [g[0] for g in bd["idle_gaps"]] == ["bench.call", "bench.fetch", "bench.fetch",
                                               "bench.fetch"]
    assert len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10


def _readers():
    import run

    bm = run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    names = [m["name"] for m in bm["per_layer"]]
    return {name: run.load_reader(BENCH, name) for name in names}, run


def test_per_layer_readers_on_a_synthetic_trace():
    readers, _ = _readers()
    ctx = {"calls": 1, "rows": 160, "op_names": OP_NAMES, "peaks": {"hbm_bytes_per_s": 819e9}}
    tr = _synthetic()
    got = {name: read(tr, ctx) for name, read in readers.items()}
    assert got["device.idle_share"] == pytest.approx(37.5)
    assert got["level.kernel_ms"] == pytest.approx((10 + 10) / 2 * 1e-6)
    assert got["base_case.xla_sort_ms"] == pytest.approx(30 / 2 * 1e-6)
    assert got["move.scatter_gather_ms"] == pytest.approx((5 + 10) / 2 * 1e-6)
    assert got["segment_ids.searchsorted_ms"] == pytest.approx(20 / 2 * 1e-6)
    assert got["base_case.fallback_share"] is None  # no base-case cond ran
    # 160 keys x 8 bytes at 819 GB/s over the level kernels' time: 10 ns of
    # level_fused on device 0 and 10 of rank_hist on device 1, 10 ns a device
    assert got["level_roofline"] == pytest.approx(100 * 160 * 8 / 819e9 / 10e-9)


def test_a_reader_with_nothing_to_read_returns_nothing():
    readers, _ = _readers()
    tr = devtrace.Trace({"0": [["fusion.9", 0, 10, "fusion"]]}, [], [0, 100])
    ctx = {"calls": 1, "rows": 160, "op_names": OP_NAMES, "peaks": {"hbm_bytes_per_s": 819e9}}
    for name in ("level.kernel_ms", "level_roofline", "base_case.xla_sort_ms",
                 "move.scatter_gather_ms", "segment_ids.searchsorted_ms",
                 "base_case.fallback_share"):
        assert readers[name](tr, ctx) is None, name


def test_per_layer_readers_on_a_recorded_chip_trace():
    """A TPU v5e trace of five ``ops.sort`` calls of a 6,001,215-row
    ``l_partkey`` table with an int32 row id as the payload, kept with the
    ``op_name`` of every op it ran; the run printed the metrics kept beside
    them."""
    tr = devtrace.Trace.from_json(FIXTURE)
    readers, run = _readers()
    meta = tr.meta
    assert meta["calls"] == 5 and meta["rows"] == 6_001_215
    ctx = {"calls": meta["calls"], "rows": meta["rows"], "op_names": meta["op_names"],
           "peaks": run.peaks(meta["device_kind"])}
    want = {k: v["value"] for k, v in meta["metrics"].items()}
    # the trace was recorded before the fallback reader existed, and when
    # the roofline counted the padded keys of level_fused alone; readers
    # added since (the scope.* readers) are checked elsewhere
    want.pop("level_fused_roofline")
    names = set(want) | {"base_case.fallback_share", "level_roofline"}
    assert names <= set(readers)
    got = {name: readers[name](tr, ctx) for name in names}
    for name, v in want.items():
        assert got[name] == pytest.approx(v, rel=1e-9), name
    assert got["level_roofline"] == pytest.approx(
        100 * 6_001_215 * 8 / 819e9 / (want["level.kernel_ms"] * 1e-3), rel=1e-9)
    # every call of this cell fell back to the full sort after the level passes
    assert got["base_case.fallback_share"] == 100.0
    assert 0 < got["device.idle_share"] < 5
    assert 0 < got["level_roofline"] <= 100
    assert got["segment_ids.searchsorted_ms"] > got["move.scatter_gather_ms"] > 0
    assert 0 < tr.busy_s() <= tr.window_s()


# -- finding cells, mixes and metrics by name ---------------------------------


def _digest(root):
    out = {}
    for dirpath, _, files in os.walk(os.path.join(root, "bench")):
        for f in files:
            p = os.path.join(dirpath, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = hashlib.sha256(fh.read()).hexdigest()
    return out


def test_new_config_traffic_and_metric_are_found_by_name(tmp_path):
    root = checkout(tmp_path)
    before = _digest(root)
    bench = os.path.join(root, "bench")
    cfg = small_config(rows=4096)
    cfg["name"] = "tiny-lineitem"
    with open(os.path.join(bench, "configs", "tiny-lineitem.json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(bench, "traffic", "orderby-both.json"), "w") as f:
        json.dump({"loop": "closed", "clients": 1, "key": "l_shipdate", "engine": "xla",
                   "tables": 2, "why": "a test mix"}, f)
    with open(os.path.join(bench, "metrics", "test.answer.py"), "w") as f:
        f.write("def read(trace, ctx):\n    return 42.0\n")
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bm = json.load(f)
    bm["configs"].append({"name": "tiny-lineitem", "source": "a test",
                          "file": "bench/configs/tiny-lineitem.json", "reduced": ["rows"],
                          "why": "a test"})
    bm["workloads"].append({"name": "tiny.both", "config": "tiny-lineitem",
                            "traffic": "orderby-both", "chips": 1, "why": "a test"})
    bm["per_layer"].append({"name": "test.answer", "unit": "x", "better": "higher",
                            "source": "device_trace", "layer": "test", "moves": "rows_per_s",
                            "workloads": ["tiny.both"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bm, f)

    _, run = _readers()
    spec = run.resolve(root, "tiny.both")
    assert spec["config"]["rows"] == 4096
    assert spec["traffic"]["key"] == "l_shipdate" and spec["traffic"]["tables"] == 2
    names = [m["name"] for m, _ in spec["per_layer"]]
    assert "test.answer" in names
    assert dict((m["name"], r) for m, r in spec["per_layer"])["test.answer"](None, {}) == 42.0
    assert [m["name"] for m in spec["end_to_end"]] == [
        "rows_per_s", "call_ms_p95", "hbm_x", "setup_s"]
    after = _digest(root)
    assert {k: v for k, v in after.items() if k in before} == before  # nothing edited
    # the committed cell does not report the new cell's metric
    names = [m["name"] for m, _ in run.resolve(root, "lineitem-sf1.partkey")["per_layer"]]
    assert "test.answer" not in names


def test_new_entry_reference_and_four_chip_cell_are_added_by_new_files(tmp_path):
    """A configuration that brings its own entry (``dist.sort`` of a table
    sharded over a ``(4,)`` mesh, fixtures/dist_orderby.py) and states no
    tie order, its mix and a four-chip cell, added to a copy of the
    checkout without editing a file under bench/, run on four virtual CPU
    devices: correct by the shard comparison, every input spread over the
    four devices."""
    root = checkout(tmp_path)
    before = _digest(root)
    bench = os.path.join(root, "bench")
    shutil.copy(os.path.join(HERE, "fixtures", "dist_orderby.py"), os.path.join(bench, "entries"))
    cfg = small_config()
    cfg.update(name="tiny-lineitem-mesh", entry="dist_orderby", chips=4, mesh={"data": 4})
    cfg["guarantees"] = dict(cfg["guarantees"], stable=False, stable_means="no tie order")
    with open(os.path.join(bench, "configs", "tiny-lineitem-mesh.json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(bench, "traffic", "dist-shipdate.json"), "w") as f:
        json.dump({"loop": "closed", "clients": 1, "key": "l_shipdate", "engine": "xla",
                   "tables": 2, "why": "ties in every call, no tie order kept"}, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bm = json.load(f)
    bm["configs"].append({"name": "tiny-lineitem-mesh", "source": "a test",
                          "file": "bench/configs/tiny-lineitem-mesh.json", "reduced": ["rows"],
                          "why": "a test"})
    bm["workloads"].append({"name": "tiny-mesh.shipdate", "config": "tiny-lineitem-mesh",
                            "traffic": "dist-shipdate", "chips": 4, "why": "a test"})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bm, f)
    after = _digest(root)
    assert {k: v for k, v in after.items() if k in before} == before  # nothing edited

    rows = 8192
    p = subprocess.run(
        [sys.executable, os.path.join(HERE, "placement.py"), root, "tiny-mesh.shipdate",
         str(rows)],
        cwd=root, env=dict(cpu_env(), XLA_FLAGS="--xla_force_host_platform_device_count=4"),
        capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    res = json.loads(p.stdout.strip().splitlines()[-1])
    assert res["correct"] and res["failed"] == 0 and res["attempted"] >= 1, res
    assert set(res["checks"]) == set(reference.SHARD_LIMITS)
    # key, row id and 37 column words, each a quarter of the table on each device
    assert len(res["placement"]) == 39
    for shards in res["placement"]:
        assert sorted(dev for dev, _ in shards) == [0, 1, 2, 3]
        assert all(shape == [rows // 4] for _, shape in shards)


# -- refusing to run ------------------------------------------------------------


def test_a_configuration_without_a_stable_sort_is_refused(tmp_path):
    root = checkout(tmp_path)
    path = os.path.join(root, "bench", "configs", "tpch-lineitem-sf1.json")
    with open(path) as f:
        cfg = json.load(f)
    cfg["guarantees"]["stable"] = False
    with open(path, "w") as f:
        json.dump(cfg, f)
    _, run = _readers()
    with pytest.raises(ValueError, match="stable"):
        run.run_cell(root, "lineitem-sf1.partkey", 1, 0.1, False, rows=64, require_tpu=False)


def test_run_without_a_tpu_exits_nonzero_and_prints_no_result():
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "lineitem-sf1.partkey",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=cpu_env(), capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_run_with_only_the_benchmark_files_exits_nonzero(tmp_path):
    root = checkout(tmp_path)
    env = cpu_env()
    env.pop("PYTHONPATH")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "lineitem-sf1.partkey",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=root, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


# -- whole runs with the timed path broken ----------------------------------------


def _fault_runs(tmp_path_factory, cell, rows, faults):
    """Runs of ``cell`` in a copy of the checkout to which a cell that
    orders by ``l_shipdate`` (2,526 dates, so ties in every call) is added."""
    root = checkout(tmp_path_factory.mktemp("checkout"))
    with open(os.path.join(root, "bench", "traffic", "orderby-shipdate.json"), "w") as f:
        json.dump({"loop": "closed", "clients": 1, "key": "l_shipdate", "engine": "auto",
                   "tables": 2, "why": "ties in every call"}, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bm = json.load(f)
    bm["workloads"].append({"name": "lineitem-sf1.shipdate", "config": "tpch-lineitem-sf1",
                            "traffic": "orderby-shipdate", "chips": 1, "why": "ties"})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bm, f)
    p = subprocess.run(
        [sys.executable, os.path.join(HERE, "faults.py"), root, cell, str(rows), *faults],
        cwd=root, env=cpu_env(), capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


FAULTS = ["unchanged", "half", "altered", "columns_altered", "control_unstable", "control_bf16"]


@pytest.fixture(scope="module")
def fault_runs(tmp_path_factory):
    return _fault_runs(tmp_path_factory, "lineitem-sf1.shipdate", 20_000, ["none"] + FAULTS)


def test_sound_one_chip_run_is_correct(fault_runs):
    res = fault_runs["none"]
    assert res["correct"] and res["failed"] == 0 and res["attempted"] >= 1
    assert res["metrics"] == ["call_ms_p95", "hbm_x", "rows_per_s", "setup_s"]
    assert all(c["value"] == 0 for c in res["checks"].values())


@pytest.mark.parametrize("fault", FAULTS)
def test_one_chip_fault_is_not_correct(fault_runs, fault):
    res = fault_runs[fault]
    assert not res["correct"]
    assert res["failed"] == res["attempted"]
