"""`correct` shown to fail, and the harness shown to be driven by data.

- the control (the reference in the program's place, summed values in
  bfloat16) reads above the configurations' limits at a test's size;
- one cent too many in one sum, a lost group, a failed request and a
  wrong path each fail the verdict;
- a run whose timed path is broken underneath (an answer altered where
  it is produced) ends with `correct` false;
- a cell, a configuration, a traffic mix and a per-layer metric added
  as files to a copy of the benchmark are found and run with no edit.
"""
import json
import os
import shutil
import subprocess
import sys

import pytest

from conftest import BENCH_DIR, REPO
from test_benchmark import BENCH, CELLS, last_line, run_cell

HERE = os.path.dirname(os.path.abspath(__file__))


def load_config(name):
    entry = next(c for c in BENCH["configs"] if c["name"] == name)
    return json.load(open(os.path.join(REPO, entry["file"])))


@pytest.mark.parametrize("seed", [1, 2, 2**31 + 3])
def test_control_reads_above_every_configurations_limit(seed):
    import control
    from harness import compare, traffic
    spec = traffic.load(BENCH_DIR, "mix13_c4")
    for cfg in BENCH["configs"]:
        config = load_config(cfg["name"])
        numbers = control.control_numbers(config, spec, seed, per_shape=4,
                                          rows=400_000)
        verdict = compare.verdict(numbers, config["correct"])
        assert verdict["correct"] is False
        for name in ("revenue_rel_err", "cost_rel_err"):
            c = verdict["compared"][name]
            assert c["value"] > 1e-3 > 3 * c["limit"], (name, c)
        assert numbers["sums_compared"] == {"revenue_rel_err": 13 * 4,
                                            "cost_rel_err": 3 * 4}
        assert numbers["keys_mismatched"] == 0


def test_exact_answers_pass_and_a_wrong_one_fails():
    from harness import compare, shapes, tables
    table = tables.make_table(tables.load_generator("ssb_dbgen"), 120_000,
                              2, seed=9)
    family = shapes.load_family(BENCH_DIR, "ssb", table.pools)
    limits = load_config("ssb_flat_startree")["correct"]

    def body_of(shape, answer):
        aggs = shape.spec["aggregates"]
        if not shape.spec["group_by"]:
            return {"aggregationResults": [{"value": repr(v)}
                                           for v in answer]}
        return {"aggregationResults": [
            {"groupByResult": [{"group": list(k), "value": repr(v[i])}
                               for k, v in answer.items()]}
            for i in range(len(aggs))]}

    def numbers_for(mutate):
        picked = []
        for i, shape in enumerate(family):
            lits = shape.spec["ssb"]
            answer = shape.reference(lits, table)
            picked.append({"shape": shape.name, "literals": lits,
                           "body": body_of(shape, mutate(i, answer)),
                           "client": 0, "seq": i})
        n = compare.compare_answers(picked, {s.name: s for s in family},
                                    table)
        n.update(failed_requests=0, path_violations=0)
        return compare.verdict(n, limits)

    assert numbers_for(lambda i, a: a)["correct"] is True

    def revenue_a_thousandth_off(i, a):     # q1.1: a float tolerance
        return (a[0] * 1.001,) + a[1:] if i == 0 else a
    v = numbers_for(revenue_a_thousandth_off)
    assert v["correct"] is False
    assert v["compared"]["revenue_rel_err"]["value"] > 9e-4
    assert v["compared"]["cost_rel_err"]["value"] == 0

    def one_cent_too_many(i, a):            # q4.1's supply cost: exact
        if i != 10:
            return a
        a, k = dict(a), next(iter(a))
        a[k] = (a[k][0], a[k][1] + 1.0)
        return a
    v = numbers_for(one_cent_too_many)
    assert v["correct"] is False
    assert 0 < v["compared"]["cost_rel_err"]["value"] < 1e-6
    assert v["compared"]["revenue_rel_err"]["value"] == 0

    def a_group_lost(i, a):
        if isinstance(a, dict) and a and i == 4:
            a = dict(a)
            a.pop(next(iter(a)))
        return a
    v = numbers_for(a_group_lost)
    assert v["correct"] is False
    assert v["compared"]["keys_mismatched"]["value"] == 1


def test_a_failed_request_and_a_wrong_path_fail_the_verdict():
    from harness import compare
    limits = load_config("ssb_flat_startree")["correct"]
    good = {"failed_requests": 0, "keys_mismatched": 0,
            "path_violations": 0, "revenue_rel_err": 0.0,
            "cost_rel_err": 0.0}
    assert compare.verdict(dict(good, revenue_rel_err=1e-7),
                           limits)["correct"] is True
    assert compare.verdict(dict(good, cost_rel_err=1e-7),
                           limits)["correct"] is False
    assert compare.verdict(good, limits)["correct"] is True
    for name in ("failed_requests", "path_violations"):
        assert compare.verdict(dict(good, **{name: 1}),
                               limits)["correct"] is False
    assert client_complete({"exceptions": [{"message": "x"}]})
    assert client_complete({"partialResponse": True,
                            "numServersQueried": 1,
                            "numServersResponded": 1})
    assert client_complete({"numServersQueried": 2,
                            "numServersResponded": 1})
    assert client_complete({"numServersQueried": 1,
                            "numServersResponded": 1}) is None


def client_complete(body):
    from harness import client
    return client.complete(body)


DRIVER = """
import runpy, sys
sys.path[:0] = [{bench!r}, {repo!r}]
import harness.cluster
harness.cluster.SERVER_LAUNCHER = {launcher!r}
sys.argv = ["run.py"] + {argv!r}
runpy.run_path({run!r}, run_name="__main__")
"""


def test_an_answer_altered_where_it_is_produced_makes_correct_false():
    """Skips nothing but the look for a chip (`--rehearse-cpu`): the
    whole run, with the server's device results altered underneath."""
    argv = ["--workload", CELLS[0], "--seed", "77", "--seconds", "3",
            "--trace", "0", "--rehearse-cpu"]
    code = DRIVER.format(bench=BENCH_DIR, repo=REPO, argv=argv,
                         launcher=os.path.join(HERE, "faulty_launcher.py"),
                         run=os.path.join(BENCH_DIR, "run.py"))
    proc = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                          env=dict(os.environ, JAX_PLATFORMS="cpu"),
                          capture_output=True, text=True, timeout=420)
    out = last_line(proc)
    assert out["correct"] is False and out["attempted"] > 0
    # Q1.x scan on the device (float sums of the raw lane); the sums
    # of lo_supplycost come from the cubes, which the fault leaves alone
    c = out["compared"]["revenue_rel_err"]
    assert c["value"] > 5e-4 > c["limit"]
    assert all(out["sums_compared"].values())
    assert out["compared"]["failed_requests"]["value"] == 0
    assert out["compared"]["keys_mismatched"]["value"] == 0


def test_files_added_to_a_copy_are_found_and_run_with_no_edit(tmp_path):
    """A later PR adds a configuration (here the one without cubes, so
    that every query scans), a shape family whose sums feed
    numbers of other names, a traffic mix, a driver, a per-layer metric
    (with a reducer of its own) and one `workloads` entry, and edits no
    file of the harness."""
    shutil.copytree(BENCH_DIR, tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns(".work", "__pycache__"))
    os.symlink(os.path.join(REPO, "pinot_tpu"), tmp_path / "pinot_tpu")
    b = tmp_path / "benchmarks"
    config = load_config("ssb_flat_startree")
    config.update(name="ssb_flat_two_segments", segments=2,
                  star_tree_configs=[],
                  paths=dict(config["paths"], cube="none"))
    limits = config["correct"]
    limits["revenue_gap"] = limits.pop("revenue_rel_err")
    limits["cost_gap"] = limits.pop("cost_rel_err")
    family = json.load(open(b / "shapes" / "ssb.json"))
    family.update(family="ssb_renamed", compared={
        "lo_revenue": "revenue_gap", "lo_supplycost": "cost_gap"})
    (b / "shapes" / "ssb_renamed.json").write_text(json.dumps(family))
    (b / "drivers" / "closed_loop_counted.py").write_text(
        "from drivers import closed_loop\n"
        "def run_window(*a):\n"
        "    out = closed_loop.run_window(*a)\n"
        "    for r in out['requests']:\n"
        "        r['counted'] = True\n"
        "    return out\n")
    (b / "configs" / "ssb_flat_two_segments.json").write_text(
        json.dumps(config))
    mix = json.load(open(b / "traffic" / "mix13_c4.json"))
    mix.update(name="mix13_c2", clients=2, shapes="ssb_renamed",
               driver="closed_loop_counted")
    (b / "traffic" / "mix13_c2.json").write_text(json.dumps(mix))
    (b / "layer_metrics" / "segment_exec_ms.json").write_text(json.dumps(
        {"layer": "executor and planner", "source": "span",
         "reducer": "span_mean", "unit": "ms", "moves": "queries_per_s",
         "params": {"add": ["segmentExecution"]}}))
    (b / "reducers" / "request_count.py").write_text(
        "def reduce(ctx, spec):\n"
        "    return sum(1 for r in ctx['requests'] if r['counted'])\n")
    (b / "layer_metrics" / "requests_sent.json").write_text(json.dumps(
        {"layer": "client", "source": "counter", "reducer": "request_count",
         "unit": "count", "moves": "queries_per_s", "params": {}}))
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append(
        {"name": "ssb_flat_two_segments", "source": "test",
         "file": "benchmarks/configs/ssb_flat_two_segments.json",
         "reduced": [], "why": "test"})
    bench["workloads"].append(
        {"name": "two_segments.mix13_c2", "config": "ssb_flat_two_segments",
         "traffic": "mix13_c2", "chips": 1, "why": "test"})
    for name in ("segment_exec_ms", "requests_sent"):
        bench["per_layer"].append(
            {"name": name, "unit": "ms", "better": "lower",
             "source": "program_span", "layer": "executor and planner",
             "moves": "queries_per_s",
             "workloads": ["two_segments.mix13_c2"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    proc = run_cell(str(b / "run.py"), "--workload", "two_segments.mix13_c2",
                    "--seed", "5", "--seconds", "3", "--trace", "1",
                    "--rehearse-cpu", cwd=str(tmp_path))
    out = last_line(proc)
    assert out["correct"] is True and out["workload"] == \
        "two_segments.mix13_c2"
    assert out["metrics"]["segment_exec_ms"]["value"] > 0
    assert out["metrics"]["requests_sent"]["value"] == out["attempted"]
    assert "device_idle_pct" not in out["metrics"]       # no chip here
    assert {"revenue_gap", "cost_gap"} < set(out["compared"]) and \
        "revenue_rel_err" not in out["compared"]
    assert out["sums_compared"]["revenue_gap"] == out["answers_compared"]
    assert out["paths"]["scan"] > 0 and out["paths"]["scan"] % 2 == 0
