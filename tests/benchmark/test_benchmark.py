"""The benchmark's harness, rehearsed on the CPU at a tiny size.

Nothing here is a measurement: `--rehearse-cpu` runs the whole of a
run (three OS processes, REST upload, HTTP queries, the reference)
against the CPU backend and proves the plumbing and `correct`. What
only a chip can show is `python benchmarks/run.py` through the chip
tool.
"""
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from conftest import BENCH_DIR, REPO

RUN = os.path.join(BENCH_DIR, "run.py")
BENCH = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
CELLS = [w["name"] for w in BENCH["workloads"]]
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def run_cell(run_py, *flags, cwd=None, timeout=420):
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_RUN="ignored")
    return subprocess.run([sys.executable, run_py, *flags], env=env,
                          cwd=cwd or REPO, capture_output=True, text=True,
                          timeout=timeout)


def last_line(proc) -> dict:
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def cells_metrics(bench: dict, kind: str, cell: str) -> set:
    return {m["name"] for m in bench[kind]
            if "workloads" not in m or cell in m["workloads"]}


def check_contract_line(proc, out, bench, config, cell, trace):
    """What a finished rehearsal run must have printed. Everything that
    differs from cell to cell is read from the cell's own files: its
    metrics from `bench`, its paths from the configuration."""
    assert RESULT_KEYS <= set(out) and out["rehearsal"] is True
    assert out["workload"] == cell
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0 and out["answers_compared"] > 0
    assert out["device"]["platform"] == "cpu"
    assert list(out)[-1] == "compared"
    for c in out["compared"].values():
        assert c["value"] <= c["limit"]
    # stderr ends with each number compared beside its limit
    tail = proc.stderr.strip().splitlines()[-len(out["compared"]):]
    assert all(line.startswith("compared ") for line in tail)
    per_layer = cells_metrics(bench, "per_layer", cell)
    if trace:
        # every per-layer metric that needs no chip; the device's own
        # (trace-based) metrics find nothing to read on the CPU backend
        # and are left out: never a CPU number under a device's name
        want = per_layer - {m["name"] for m in bench["per_layer"]
                            if m["source"] == "device_trace"}
        assert set(out["metrics"]) == want
        assert {"busy_s", "window_s", "memory_peak_bytes"} <= \
            set(out["device"])
        if "result_cache_hit_pct" in per_layer:
            assert out["metrics"]["result_cache_hit_pct"]["value"] == 0.0
        assert "breakdown" in out
        # the traced run's own tail, by phase, and the trace's size
        assert {"trace_write_s", "children_stop_s", "trace_extract_s"} <= \
            set(out["phases"])
        assert out["trace_file_bytes"] > 0
    else:
        assert set(out["metrics"]) == cells_metrics(bench, "end_to_end",
                                                    cell)
        assert all(m["value"] > 0 for m in out["metrics"].values())
    # the same reading as run.py's `path_violations`
    assert set(out["paths"]) == set(config["paths"])
    for p, rule in config["paths"].items():
        assert rule in ("some", "none")
        assert (out["paths"][p] == 0) == (rule == "none"), (p, rule)


def check_rehearsal(proc, root, bench_dir, cell, trace, seed):
    """`check_contract_line` with the cell's files found as `run.py`
    finds them, in the checkout `root`; the run's work files are gone.
    -> (the result line, the configuration)."""
    from harness import cells
    bench, _entry, config, _traffic = cells.load_cell(root, bench_dir, cell)
    out = last_line(proc)
    check_contract_line(proc, out, bench, config, cell, trace)
    assert not os.path.exists(os.path.join(bench_dir, ".work",
                                           f"{cell}.{seed}"))
    return out, config


@pytest.mark.parametrize("cell,trace", [(c, t) for c in CELLS
                                        for t in (1, 0)])
def test_rehearsal_run_ends_in_the_contracts_line(cell, trace):
    seed = 2**31 + 7
    proc = run_cell(RUN, "--workload", cell, "--seed", str(seed),
                    "--seconds", "3", "--trace", str(trace),
                    "--rehearse-cpu")
    check_rehearsal(proc, REPO, BENCH_DIR, cell, trace, seed)


def test_run_without_a_chip_fails_and_prints_no_result():
    proc = run_cell(RUN, "--workload", CELLS[0], "--seed", "3",
                    "--seconds", "1", "--trace", "0")
    assert proc.returncode != 0
    assert "metrics" not in proc.stdout and "correct" not in proc.stdout
    assert "not 'tpu'" in proc.stderr


def test_run_without_the_program_fails_and_prints_no_result(tmp_path):
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH_DIR, tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns(".work", "__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", CELLS[0],
         "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=100)
    assert proc.returncode != 0 and not proc.stdout.strip()


# -- traffic ---------------------------------------------------------------

def make_traffic(seed):
    from generators import ssb_dbgen
    from harness import shapes, traffic
    spec = traffic.load(BENCH_DIR, "mix13_c4")
    family = shapes.load_family(BENCH_DIR, spec["shapes"],
                                ssb_dbgen.pools())
    return traffic.Traffic(spec, family, seed)


def sequence(tr, n=300):
    out = []
    for k in range(tr.clients):
        stream = tr.client_stream(k)
        out.append([next(stream).pql for _ in range(n)])
    return out


def test_same_seed_same_queries_another_seed_others():
    a, b, c = (sequence(make_traffic(s)) for s in (2**31 + 5, 2**31 + 5, 6))
    assert a == b and a != c


def test_no_shape_and_literals_pair_repeats_in_a_run():
    tr = make_traffic(2**31 + 9)
    window = [q for qs in sequence(tr, 600) for q in qs]
    assert len(set(window)) == len(window)
    # the warm-up's bursts are not traced, so a result cache keeps their
    # answers: the window never reaches their literals, however long
    bursts = [r.pql for burst in tr.warm_bursts() for r in burst]
    assert len(set(bursts)) == len(bursts) == 3 * 7
    assert not set(bursts) & set(window)
    q11 = tr.by_name["q1.1"]
    stream, sent = make_traffic(2**31 + 9).client_stream(0), set()
    for request in stream:
        if len(sent) > 13 * 700:
            break
        sent.add(request.pql)
    assert not set(bursts) & sent and q11.domain_size == 630
    # a warm-up round holds every shape once, with literals of its own
    rounds = [tr.warm_round(i) for i in range(5)]
    assert all([r.shape.name for r in rnd] == [s.name for s in tr.shapes]
               for rnd in rounds)
    assert len({r.pql for rnd in rounds for r in rnd}) == 5 * 13


def test_every_deck_holds_every_shape_once():
    tr = make_traffic(11)
    stream = tr.client_stream(2)
    names = [next(stream).shape.name for _ in range(13 * 5)]
    for i in range(0, len(names), 13):
        assert sorted(names[i:i + 13]) == sorted(s.name for s in tr.shapes)


def test_a_dry_domain_drops_out_and_is_counted():
    tr = make_traffic(12)
    small = min(tr.shapes, key=lambda s: s.domain_size)
    stream = tr.client_stream(0)
    assert small.name == "q1.1" and small.domain_size == 630
    seen = [next(stream) for _ in range(13 * 170)]     # 630 / 4 clients
    used = [r for r in seen if r.shape is small]
    assert len({r.pql for r in used}) == len(used) <= small.domain_size
    assert tr.exhausted > 0


def test_domain_sizes_are_as_the_shape_file_records():
    from generators import ssb_dbgen
    from harness import shapes
    family = shapes.load_family(BENCH_DIR, "ssb", ssb_dbgen.pools())
    doc = json.load(open(os.path.join(BENCH_DIR, "shapes", "ssb.json")))
    assert [s.domain_size for s in family] == \
        [s["domain_size"] for s in doc["shapes"]]
    for s in family:
        for i in (0, s.domain_size // 2, s.domain_size - 1):
            assert s.index_of(s.literals(i)) == i


# -- shapes against bench.py's fixed literals ---------------------------------

def make_table(rows, segments, seed):
    from harness import tables
    return tables.make_table(tables.load_generator("ssb_dbgen"), rows,
                             segments, seed)


@pytest.fixture(scope="module")
def small_table():
    return make_table(240_000, 3, seed=4)


def test_pql_and_reference_agree_with_bench_py(small_table):
    import bench
    from harness import shapes
    family = shapes.load_family(BENCH_DIR, "ssb", small_table.pools)
    assert [s.name for s in family] == list(bench.SSB_PQLS)
    # bench.py's reference reads pools and id lanes, revenue among them
    ids = {c: np.concatenate([seg[0][c] for seg in small_table.segments])
           for c in small_table.segments[0][0]}
    revenue, cost = (np.concatenate([seg[1][c] for seg in
                                     small_table.segments])
                     for c in ("lo_revenue", "lo_supplycost"))
    pools = dict(small_table.pools, lo_revenue=np.unique(revenue))
    ids["lo_revenue"] = np.searchsorted(pools["lo_revenue"], revenue)
    cpu = bench.make_cpu_queries(pools, ids, cost.astype(np.float64))
    for shape in family:
        lits = shape.spec["ssb"]
        assert shape.pql(lits) == bench.SSB_PQLS[shape.name]
        assert 0 <= shape.index_of(lits) < shape.domain_size
        mine, theirs = shape.reference(lits, small_table), cpu[shape.name]()
        if not shape.spec["group_by"]:
            mine = mine[0]
        elif shape.name != "q3.4":           # ~0.2 rows match here
            assert mine, shape.name
        assert mine == theirs, shape.name      # integers: exact


def test_rows_are_the_seeds_and_follow_dbgens_rules(small_table):
    from generators import ssb_dbgen as gen
    again = make_table(240_000, 3, seed=4)
    other = make_table(240_000, 3, seed=2**31 + 4)
    for (a, va), (b, vb), (c, vc) in zip(small_table.segments,
                                         again.segments, other.segments):
        assert all(np.array_equal(a[k], b[k]) for k in a)
        assert all(np.array_equal(va[k], vb[k]) for k in va)
        assert not np.array_equal(va["lo_revenue"], vc["lo_revenue"])
    ids, values = small_table.segments[0]
    qty, disc = ids["lo_quantity"] + 1, ids["lo_discount"].astype(np.int64)
    # lo_supplycost = 6 x price / 10 gives the price back to within a
    # cent or two; lo_revenue = quantity x price x (100 - discount) / 100
    price = (values["lo_supplycost"].astype(np.int64) * 10 + 9) // 6
    assert np.all(6 * price // 10 == values["lo_supplycost"])
    low = qty * (price - 1) * (100 - disc) // 100
    assert np.all((low <= values["lo_revenue"]) &
                  (values["lo_revenue"] <= qty * price * (100 - disc) // 100))
    assert 90_000 <= price.min() and price.max() <= 209_900
    assert gen.retail_price(np.array([1, 10, 999, 200_000])).tolist() == \
        [90_100, 90_001 + 1000, 90_099 + 99_900, 90_000 + 20_000]
    assert gen.dimension_sizes(50_000_000) == {
        "part": 800_000, "customer": 250_000, "supplier": 16_666}
    # order dates end on 1998-08-02: 1998 holds 214 of its 365 days
    years = np.bincount(np.concatenate(
        [s[0]["d_year"] for s in small_table.segments]))
    assert 0.5 < years[6] / years[:6].mean() < 0.67
    assert small_table.pools["d_yearmonthnum"][-1] == 199808
    # city -> nation -> region, brand -> category -> manufacturer
    assert np.array_equal(ids["c_nation"], ids["c_city"] // 10)
    assert np.array_equal(ids["p_mfgr"], ids["p_brand1"] // 200)


# -- the trace reduction and the work function --------------------------------

def test_trace_reduction_on_hand_made_events():
    from harness import trace_reduce as tr
    P, L = "/device:TPU:0", tr.OP_LINE
    events = [
        [P, L, "fusion.1", 1_000, 500],        # 1000-1500
        [P, L, "fusion.2", 1_400, 600],        # overlaps: 1400-2000
        [P, L, "copy.3", 5_000, 1_000],        # 5000-6000
        [P, L, "fusion.1", 9_000, 250],        # 9000-9250
        [P, "XLA Modules", "jit_kernel", 900, 9_000],   # another line
        ["/host:CPU", "python", "wait", 0, 50_000],     # not a device
    ]
    assert tr.union([(1, 3), (2, 5), (7, 8)]) == [(1, 5), (7, 8)]
    assert tr.busy_seconds(events) == pytest.approx(2_250e-9)
    assert tr.op_totals(events) == [["copy.3", 1_000e-9],
                                    ["fusion.1", 750e-9],
                                    ["fusion.2", 600e-9]]
    assert tr.idle_gaps(events) == [["unattributed", 3_000e-9],
                                    ["unattributed", 3_000e-9]]
    two = events + [["/device:TPU:1", L, "fusion.1", 0, 4_500]]
    assert tr.busy_seconds(two) == pytest.approx((2_250 + 4_500) / 2 * 1e-9)
    assert tr.busy_seconds([]) == 0.0 and tr.idle_gaps([]) == []


def test_trace_reduction_on_a_slice_recorded_on_the_chip():
    """0.4 s of the device trace of a run of PR 25 on the v5e, as the
    extracted event list. The expected values were computed by hand
    with a sweep over the sorted interval ends, not with `union`."""
    from harness import trace_reduce as tr
    path = os.path.join(os.path.dirname(__file__), "data",
                        "trace_slice_v5e.json")
    events = json.load(open(path))["events"]
    assert os.path.getsize(path) < 1_000_000
    ops = tr.device_ops(events)
    assert list(ops) == ["/device:TPU:0"] and len(ops["/device:TPU:0"]) == 1205
    assert tr.busy_seconds(events) == pytest.approx(23_057_840e-9, rel=1e-12)
    first, last = 3_499_799, 395_667_742
    idle = 1 - tr.busy_seconds(events) / ((last - first) * 1e-9)
    assert idle == pytest.approx(0.941204, abs=1e-6)
    totals = tr.op_totals(events, top=3)
    assert [round(s * 1e9) for _n, s in totals] == [5_062_141, 3_587_547,
                                                    2_539_580]
    assert totals[0][0].startswith("%concatenate.2 = bf16[3052,2048,5]")
    assert [round(s * 1e9) for _n, s in tr.idle_gaps(events, top=3)] == \
        [97_123_816, 49_531_336, 36_335_259]


def test_roofline_counts_only_the_shapes_its_file_names():
    """Two queries wholly inside a 1 s slice, the device busy for 10 ms:
    `scan_roofline` counts both, `q1_scan_roofline` the Q1.x one alone;
    a device that was never busy gives no number, never 0."""
    from harness import shapes, work
    from reducers import trace_roofline
    table = make_table(40_000, 8, seed=5)
    family = {s.name: s for s in shapes.load_family(BENCH_DIR, "ssb",
                                                    table.pools)}
    raw, ranges = ["lo_revenue"], table.value_ranges()
    ctx = {"trace": {"busy_s": 0.01, "slice": (10.0, 11.0)},
           "requests": [{"shape": "q1.1", "t_send": 10.1, "t_recv": 10.2},
                        {"shape": "q2.1", "t_send": 10.3, "t_recv": 10.6},
                        {"shape": "q1.2", "t_send": 10.3, "t_recv": 10.4,
                         "error": "partialResponse"}],
           "config": {"rows": 40_000, "segments": 8,
                      "no_dictionary_columns": raw},
           "shapes": family, "pools": table.pools, "value_ranges": ranges,
           "bench_dir": BENCH_DIR, "device_kind": "TPU v5 lite"}

    def spec(name):
        return json.load(open(os.path.join(BENCH_DIR, "layer_metrics",
                                           f"{name}.json")))

    def share(names):
        return 100 * sum(work.lane_bytes([family[n].spec], table.pools,
                                         ranges, 40_000, 8, raw)
                         for n in names) / 819e9 / 0.01
    assert trace_roofline.reduce(ctx, spec("q1_scan_roofline")) == \
        pytest.approx(share(["q1.1"]))
    assert trace_roofline.reduce(ctx, spec("scan_roofline")) == \
        pytest.approx(share(["q1.1", "q2.1"]))
    # half of a query's interval inside the slice counts half its bytes
    ctx["requests"][0].update(t_send=9.9, t_recv=10.1)
    assert trace_roofline.reduce(ctx, spec("q1_scan_roofline")) == \
        pytest.approx(share(["q1.1"]) / 2)
    ctx["trace"]["busy_s"] = 0.0
    assert trace_roofline.reduce(ctx, spec("q1_scan_roofline")) is None


def test_lane_bytes_counts_lanes_as_the_loader_stores_them(tmp_path):
    """For each of the 13 queries, `work.lane_widths` against the host
    twins of the device lanes of a segment built and loaded by the
    program: id lanes for predicates and group-bys, one-byte slices for
    the summed integers behind a dictionary, the raw lane for the column
    without one."""
    from harness import build, shapes, work
    from pinot_tpu.segment.loader import ImmutableSegmentLoader
    config = dict(json.load(open(os.path.join(
        BENCH_DIR, "configs", "ssb_flat_startree.json"))), rows=40_000,
        segments=1, star_tree_configs=[])
    table = make_table(40_000, 1, seed=3)
    seg = ImmutableSegmentLoader.load(build.build_segment(
        (config, 3, 0, 40_000, str(tmp_path))))
    family = shapes.load_family(BENCH_DIR, "ssb", table.pools)
    ranges = table.value_ranges()
    raw = config["no_dictionary_columns"]
    assert raw == ["lo_revenue"]
    union = {}
    for shape in family:
        widths = work.lane_widths(shape.spec, table.pools, ranges[0], raw)
        assert set(widths) == {
            c + (".raw" if c in raw else ".parts" if c in
                 shape.spec["aggregates"] else "") for c in shape.columns}
        for lane, width in widths.items():
            col, _, kind = lane.partition(".")
            host = seg.data_source(col).host_operand(kind or "ids")
            assert host.shape[-1] == work.padded_rows(40_000) == 40_960
            assert host.nbytes == width * 40_960, lane
        union.update(widths)
    assert union["lo_revenue.raw"] == 4 and \
        union["lo_supplycost.parts"] == 3 and union["p_brand1"] == 2
    # behind a dictionary the revenues would be four slices
    assert work.lane_widths(family[0].spec, table.pools, ranges[0]
                            )["lo_revenue.parts"] == 4
    every = work.lane_bytes([s.spec for s in family], table.pools, ranges,
                            40_000, 1, raw)
    assert every == 25 * 40_960 == sum(union.values()) * 40_960
    eight = make_table(80_001, 8, seed=3)
    assert work.lane_bytes([family[0].spec], eight.pools,
                           eight.value_ranges(), 80_001, 8, raw) == \
        (1 + 1 + 1 + 4) * (7 * 16_384 + 16_384)
    assert work.peaks(BENCH_DIR, "TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        work.peaks(BENCH_DIR, "TPU v9")
