"""A second configuration's cell, taken as files alone.

What a `model_config` PR may add is written into a copy of the
benchmark: the configuration without cubes (`ssb_flat_nocube`: the
star-tree file with `star_tree_configs` [], `paths.cube` "none" and a
traced slice of 5 s), its
`configs` entry, the cell `ssb_nocube.mix13_c4` on the traffic that is
there, and two `per_layer` entries whose `workloads` name only that
cell, each through a reducer of the tree. The copy's rehearsal runs then
go through the same `check_contract_line` as the cells of
`BENCHMARK.json`: a check that holds every cell to what is true of one
configuration only (cubes that answer, a metric listed for another
cell) fails here. In a file of its own, so that `--dist loadfile` gives
its two runs a worker of their own.
"""
import json
import os
import shutil

import pytest

from conftest import BENCH_DIR, REPO
from test_benchmark import check_rehearsal, run_cell

CONFIG, CELL = "ssb_flat_nocube", "ssb_nocube.mix13_c4"
# name -> (its `per_layer` entry, its `layer_metrics` file where the tree
# has none): a device metric whose file is there, listed for no cell,
# and a program counter through a reducer of the tree
NEW_METRICS = {
    "scan_roofline": (
        {"unit": "%", "better": "higher", "source": "device_trace",
         "layer": "kernels"}, None),
    "scan_segments_per_query": (
        {"unit": "count", "better": "lower", "source": "program_counter",
         "layer": "executor and planner"},
        {"layer": "executor and planner", "source": "counter",
         "reducer": "counter_ratio", "unit": "count",
         "moves": "queries_per_s",
         "params": {"num": ["broker.tableStats.paths.scan"],
                    "den": ["broker.tableStats.queries"]}}),
}


def without(entries, *names):
    return [e for e in entries if e["name"] not in names]


def add_second_cell(root, metrics=tuple(NEW_METRICS)) -> None:
    """Write the additions into the checkout `root`, editing no file of
    the harness. Entries of these names that its `BENCHMARK.json` may
    have by then are replaced, so that the test keeps its own."""
    b = os.path.join(root, "benchmarks")
    config = json.load(open(os.path.join(b, "configs",
                                         "ssb_flat_startree.json")))
    config.update(name=CONFIG, star_tree_configs=[], trace_slice_s=5,
                  paths=dict(config["paths"], cube="none"))
    with open(os.path.join(b, "configs", f"{CONFIG}.json"), "w") as fh:
        json.dump(config, fh)
    for name in metrics:
        spec = NEW_METRICS[name][1]
        if spec is not None:
            with open(os.path.join(b, "layer_metrics", f"{name}.json"),
                      "w") as fh:
                json.dump(spec, fh)
    bench = json.load(open(os.path.join(root, "BENCHMARK.json")))
    bench["configs"] = without(bench["configs"], CONFIG) + [
        {"name": CONFIG, "source": "test", "reduced": config["reduced"],
         "file": f"benchmarks/configs/{CONFIG}.json", "why": "test"}]
    bench["workloads"] = without(bench["workloads"], CELL) + [
        {"name": CELL, "config": CONFIG, "traffic": "mix13_c4", "chips": 1,
         "why": "test"}]
    bench["per_layer"] = without(bench["per_layer"], *NEW_METRICS) + [
        dict(NEW_METRICS[name][0], name=name, moves="queries_per_s",
             workloads=[CELL]) for name in metrics]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as fh:
        json.dump(bench, fh, indent=1)


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    root = tmp_path_factory.mktemp("second_cell")
    shutil.copytree(BENCH_DIR, root / "benchmarks",
                    ignore=shutil.ignore_patterns(".work", "__pycache__"))
    os.symlink(os.path.join(REPO, "pinot_tpu"), root / "pinot_tpu")
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    add_second_cell(str(root))
    return root


@pytest.mark.parametrize("trace", [1, 0])
def test_a_cell_without_cubes_added_as_files_passes_the_same_checks(
        copy, trace):
    b, seed = str(copy / "benchmarks"), 2**31 + 11
    proc = run_cell(os.path.join(b, "run.py"), "--workload", CELL,
                    "--seed", str(seed), "--seconds", "3",
                    "--trace", str(trace), "--rehearse-cpu", cwd=str(copy))
    out, config = check_rehearsal(proc, str(copy), b, CELL, trace, seed)
    assert out["paths"]["cube"] == 0 and out["paths"]["scan"] > 0
    if trace:
        # every query scans every segment, and none is answered twice
        assert out["metrics"]["scan_segments_per_query"]["value"] == \
            config["segments"]


def test_a_configuration_may_shorten_the_traced_slice(bench_run):
    """The rows without cubes keep the device busy enough that the
    profiler cannot write 40% of a 51 s window in the time a run has
    (PERF.md section 6, PR 34): the configuration states the slice."""
    bounds = bench_run.trace_slice_bounds
    assert bounds(51, {}) == pytest.approx((15.3, 20.4))
    assert bounds(51, {"trace_slice_s": 5}) == pytest.approx((23.0, 5.0))
    # never longer than the default, which a rehearsal's 3 s keep
    assert bounds(3, {"trace_slice_s": 5}) == pytest.approx((0.9, 1.2))
