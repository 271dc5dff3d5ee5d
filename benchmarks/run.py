#!/usr/bin/env python3
"""One run of one benchmark cell on the served path.

    python benchmarks/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

The cell, its configuration, traffic mix, shape family, per-layer
metrics and their reducers are found by name from `BENCHMARK.json` and
the files under `benchmarks/`; this file holds no cell's name. See
`benchmarks/README.md`. This process is the PARENT: numpy, HTTP and
child processes only; it never initialises a JAX backend.
"""
from __future__ import annotations

import time

T0 = time.monotonic()       # set-up is counted from here

import argparse                                             # noqa: E402
import importlib                                            # noqa: E402
import json                                                 # noqa: E402
import os                                                   # noqa: E402
import re                                                   # noqa: E402
import shutil                                               # noqa: E402
import subprocess                                           # noqa: E402
import sys                                                  # noqa: E402
import threading                                            # noqa: E402
import traceback                                            # noqa: E402
from concurrent.futures import ThreadPoolExecutor           # noqa: E402

import numpy as np                                          # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(BENCH_DIR)
for p in (CHECKOUT, BENCH_DIR):
    if p not in sys.path:
        sys.path.insert(0, p)

from harness import (build, cells, client, compare,         # noqa: E402
                     shapes, tables, trace_reduce, traffic)
from harness.cells import load_json                         # noqa: E402

REHEARSAL_ROWS = 80_000
# what a traced run may spend after its window, each recorded in
# `phases` under the name of what it waits for
TRACE_WRITE_WAIT_S = 120        # trace.stop touched -> trace.done seen
CHILDREN_STOP_WAIT_S = 60.0     # a signal to each child -> its exit
TRACE_EXTRACT_WAIT_S = 300      # trace_extract.py: .xplane.pb -> events


class RunFailure(Exception):
    pass


def say(msg: str) -> None:
    print(f"bench[{time.monotonic() - T0:7.1f}s] {msg}", file=sys.stderr,
          flush=True)


def compile_seconds(log: str) -> float:
    """Seconds JAX_LOG_COMPILES says were spent tracing, lowering and
    compiling in a stretch of the server's log."""
    return sum(float(m) for m in re.findall(
        r"Finished [^\n]*? in ([0-9.]+) sec", log))


def trace_slice_bounds(seconds: float, config: dict) -> tuple:
    """(seconds into the window at which the traced slice starts, its
    length): 40% of the window, from 30% to 70%, or the `trace_slice_s`
    seconds about its middle that the configuration states where its
    device is too busy for that. The profiler's export took 12 s for
    every traced second of the rows without cubes (85,000 device ops a
    second; PERF.md section 6, PR 34), and a run has to end in 360 s."""
    length = min(float(config.get("trace_slice_s", seconds)), 0.4 * seconds)
    return 0.5 * seconds - length / 2, length


def layer_metric_specs(bench: dict, cell: str) -> list:
    """The per-layer metrics this cell reports, each with its file."""
    out = []
    for m in bench["per_layer"]:
        if "workloads" in m and cell not in m["workloads"]:
            continue
        spec = load_json(BENCH_DIR, "layer_metrics", f"{m['name']}.json")
        out.append(dict(spec, name=m["name"], unit=m["unit"]))
    return out


class Run:
    def __init__(self, args):
        self.args = args
        self.bench, self.cell, self.config, self.traffic_spec = \
            cells.load_cell(CHECKOUT, BENCH_DIR, args.workload)
        self.rehearsal = args.rehearse_cpu
        if self.rehearsal:
            self.config = dict(self.config, rows=REHEARSAL_ROWS)
        elif args.rows:
            say(f"*** {args.rows} rows, not the configuration's "
                f"{self.config['rows']}: a trial of scale, not the cell ***")
            self.config = dict(self.config, rows=args.rows)
        # one directory a cell and seed, so that runs side by side in
        # one checkout (the tests' workers) do not share work files
        self.work_dir = os.path.join(BENCH_DIR, ".work",
                                     f"{self.cell['name']}.{args.seed}")
        self.phases = {}
        self.cluster = None

    # -- set-up ------------------------------------------------------------
    def phase(self, name: str, t_start: float) -> None:
        self.phases[name] = time.monotonic() - t_start
        say(f"phase {name}: {self.phases[name]:.1f}s")

    def start_cluster(self):
        from harness.cluster import BenchCluster
        t = time.monotonic()
        env = {"JAX_PLATFORMS": "cpu"} if self.rehearsal else {}
        # JAX_LOG_COMPILES: the warm-up and `compiles_in_window` count
        # the log's 'Compiling' lines; it costs a line a new program
        server_env = {"BENCH_STATS_FILE":
                      os.path.join(self.work_dir, "device_stats.json"),
                      "JAX_LOG_COMPILES": "1"}
        if self.args.trace:
            server_env["BENCH_TRACE_DIR"] = self.work_dir
        self.cluster = BenchCluster(os.path.join(self.work_dir, "cluster"),
                                    CHECKOUT, server_env, env)
        self.phase("start_s", t)

    def make_table(self, pool):
        t = time.monotonic()
        table = tables.make_table(
            tables.load_generator(self.config["generator"]),
            self.config["rows"], self.config["segments"], self.args.seed,
            pool)
        self.phase("rows_s", t)
        return table

    def build_segments(self):
        t = time.monotonic()
        dirs = build.build_all(self.config, self.args.seed,
                               os.path.join(self.work_dir, "built"),
                               CHECKOUT, max(1, (os.cpu_count() or 2) - 3))
        self.phase("build_s", t)
        return dirs

    def require_device(self, dev: dict) -> None:
        want = "cpu" if self.rehearsal else "tpu"
        if dev["platform"] != want:
            raise RunFailure(f"the server runs on {dev['platform']!r}, not "
                             f"{want!r}: no accelerator, no result")
        if dev["count"] < self.cell["chips"]:
            raise RunFailure(f"{dev['count']} chip(s), the cell asks for "
                             f"{self.cell['chips']}")
        if dev["x64"]:
            raise RunFailure("x64 is on; the deployed mode is x32")

    def upload(self, dirs, pool):
        t = time.monotonic()
        c, name = self.cluster, self.config["table"]
        schema, table_config = build.table_objects(self.config)
        c.add_schema(schema)
        c.add_table(table_config)
        list(pool.map(lambda d: c.upload_segment(f"{name}_OFFLINE", d),
                      dirs))
        shutil.rmtree(os.path.join(self.work_dir, "built"))
        c.await_ready(name, self.config["rows"], timeout_s=900)
        self.phase("load_s", t)

    def url(self) -> str:
        return f"http://127.0.0.1:{self.cluster.broker_ports[0]}/query"

    def compile_lines(self) -> int:
        """'Compiling' lines in the server's log so far: JAX logs one
        for each program a process meets for the first time, whether
        XLA or the persistent cache then supplies it."""
        with open(self.cluster.log_path("server:Server_0"), "rb") as fh:
            return fh.read().count(b"Compiling ")

    def warm(self, tr):
        """First run of every program the window can meet, each request
        with an explicit deadline. trace=true keeps an answer out of
        the result caches; the bursts are not traced (traced queries
        never batch) and use literals the window never reaches."""
        t = time.monotonic()
        spec = self.traffic_spec["warm"]
        ms, enough = int(spec["timeout_ms"]), int(spec["quiet_rounds"])

        def one(request, **opts):
            rec = client.post_query(
                self.url(), client.with_options(request.pql, timeoutMs=ms,
                                                **opts), ms / 1e3)
            if rec.get("error"):
                raise RunFailure(f"warm-up of {request.shape.name} failed: "
                                 f"{rec['error']}")
            return rec

        def traced(request):
            return one(request, trace="true")
        # round 0: every shape once; then a shape is run with new
        # literals until it has made the server compile nothing
        # `quiet_rounds` times in a row
        quiet = {s.name: 0 for s in tr.shapes}
        rounds, seen = 0, 0
        while rounds < int(spec["max_rounds"]) and \
                min(quiet.values()) < enough:
            for request in tr.warm_round(rounds):
                name = request.shape.name
                if quiet[name] >= enough:
                    continue
                rec = traced(request)
                now = self.compile_lines()
                quiet[name] = quiet[name] + 1 if now == seen and rounds \
                    else 0
                seen = now
                if rounds == 0:
                    say(f"  warm {name}: "
                        f"{(rec['t_recv'] - rec['t_send']) * 1e3:.0f} ms")
            if rounds == 0:
                self.phase("warm_first_s", t)
            rounds += 1
        with ThreadPoolExecutor(max_workers=tr.clients) as pool:
            for burst in tr.warm_bursts():
                list(pool.map(one, burst))
        say(f"  warm-up: {rounds} rounds, {self.compile_lines()} programs "
            f"met, still compiling: "
            f"{[n for n, q in quiet.items() if q < enough]}")
        self.warm_rounds = rounds
        self.phase("warm_s", t)

    # -- counters ----------------------------------------------------------
    def counters(self) -> dict:
        """Flat {name: number} of what the program counts, scraped from
        its debug endpoints."""
        c = self.cluster
        broker = f"http://127.0.0.1:{c.broker_ports[0]}"
        server = ("http://127.0.0.1:"
                  f"{next(iter(c.server_admin_ports.values()))}")
        out = {}

        def fold(prefix, doc):
            for k, v in doc.items():
                if isinstance(v, bool):
                    continue
                if isinstance(v, (int, float)):
                    out[f"{prefix}.{k}"] = v
                elif isinstance(v, dict):
                    fold(f"{prefix}.{k}", v)
        stats = client.http_json(
            f"{broker}/debug/tableStats/{self.config['table']}")
        stats.pop("recent", None)
        fold("broker.tableStats", stats)
        fold("broker.resultCache",
             client.http_json(f"{broker}/debug/resultCache"))
        fold("server.metrics",
             client.http_json(f"{server}/metrics?format=json"))
        residency = client.http_json(f"{server}/debug/residency")
        fold("server.residency", {k: v for k, v in residency.items()
                                  if not isinstance(v, (list, dict))})
        self.health = client.http_json(f"{server}/debug/health")
        fold("server.device", self.health["device"])
        return out

    # -- the traced slice --------------------------------------------------
    def trace_slice(self, seconds: float, state: dict) -> None:
        """Trace the middle of the window (`trace_slice_bounds`)."""
        def touch(name):
            with open(os.path.join(self.work_dir, name), "w"):
                pass
        start, length = trace_slice_bounds(seconds, self.config)
        time.sleep(start)
        touch("trace.start")
        time.sleep(length)
        touch("trace.stop")
        t = time.monotonic()
        done = os.path.join(self.work_dir, "trace.done")
        deadline = t + TRACE_WRITE_WAIT_S
        while not os.path.exists(done) and time.monotonic() < deadline:
            time.sleep(0.05)
        state["done"] = os.path.exists(done)
        self.phase("trace_write_s", t)

    def read_trace(self) -> dict:
        """The slice's events, read in a child so that this process
        stays off JAX's backends."""
        with open(os.path.join(self.work_dir, "trace.started")) as fh:
            started = float(fh.read())
        stopped = load_json(self.work_dir, "trace.done")["stopped"]
        out = os.path.join(self.work_dir, "trace_events.json")
        t = time.monotonic()
        try:
            subprocess.run(
                [sys.executable,
                 os.path.join(BENCH_DIR, "harness", "trace_extract.py"),
                 os.path.join(self.work_dir, "profile"), out],
                check=True, timeout=TRACE_EXTRACT_WAIT_S,
                env=dict(os.environ, JAX_PLATFORMS="cpu"))
            doc = load_json(out)
        finally:
            self.phase("trace_extract_s", t)
        if self.args.keep:
            os.makedirs(self.args.keep, exist_ok=True)
            shutil.copy(out, self.args.keep)
        # wall clock -> this process's monotonic clock
        shift = time.monotonic() - time.time()
        return {"events": doc["events"], "planes": doc["planes"],
                "file_bytes": doc["file_bytes"],
                "window_s": stopped - started,
                "busy_s": trace_reduce.busy_seconds(doc["events"]),
                "slice": (started + shift, stopped + shift)}

    # -- one run -----------------------------------------------------------
    def run(self) -> dict:
        os.environ.pop("JAX_ENABLE_X64", None)   # the deployed mode is x32
        shutil.rmtree(self.work_dir, ignore_errors=True)
        os.makedirs(self.work_dir)
        seed, seconds = self.args.seed, self.args.seconds
        with ThreadPoolExecutor(max_workers=12) as pool:
            # cluster start, the reference's lanes and the segment build
            # run side by side
            starting = pool.submit(self.start_cluster)
            table_f = pool.submit(self.make_table, pool)
            dirs = self.build_segments()
            starting.result()
            boot = self.cluster.server_boots["Server_0"]["device"]
            say(f"server device: {json.dumps(boot)}")
            self.require_device(boot)
            self.upload(dirs, pool)
            table = table_f.result()
            family = shapes.load_family(BENCH_DIR,
                                        self.traffic_spec["shapes"],
                                        table.pools)
            tr = traffic.Traffic(self.traffic_spec, family, seed)
            self.warm(tr)
            before = self.counters()
            self.require_device(self.health["device"])
            log = self.cluster.log_path("server:Server_0")
            log_from = os.path.getsize(log)

            slice_state, slicer = {}, None
            if self.args.trace:
                slicer = threading.Thread(target=self.trace_slice,
                                          args=(seconds, slice_state))
                slicer.start()
            setup_s = time.monotonic() - T0
            say(f"window opens after {setup_s:.1f}s of set-up")
            driver = importlib.import_module(
                f"drivers.{self.traffic_spec['driver']}")
            window = driver.run_window(
                self.url(), tr, seconds,
                int(self.traffic_spec["traced_share"])
                if self.args.trace else 0,
                float(self.traffic_spec["client_timeout_s"]))
            say(f"window closed: {len(window['requests'])} requests")
            if slicer is not None:
                slicer.join()
                if not slice_state["done"]:
                    raise RunFailure(
                        f"the server wrote no trace in the "
                        f"{TRACE_WRITE_WAIT_S} s after trace.stop: the "
                        f"profiler's export grows with the device ops of "
                        f"the slice; a configuration whose device is this "
                        f"busy states a shorter `trace_slice_s`")
            log_to = os.path.getsize(log)
            after = self.counters()
            with open(log, "rb") as fh:
                fh.seek(log_from)
                log_window = fh.read(log_to - log_from).decode(
                    "utf-8", "replace")

        cluster, self.cluster = self.cluster, None
        t = time.monotonic()
        codes = cluster.stop(wait_s=CHILDREN_STOP_WAIT_S)
        self.phase("children_stop_s", t)
        say(f"children stopped: {codes}")
        if any(c != 0 for c in codes.values()):
            raise RunFailure(f"child exit codes: {codes}")
        stats = load_json(self.work_dir, "device_stats.json")
        trace = self.read_trace() if self.args.trace else None

        # the reference runs only now: window closed, memory read,
        # the program's processes gone
        t = time.monotonic()
        requests = window["requests"]
        picked = compare.sample(requests, int(self.config["sample_per_shape"]),
                                seed)
        numbers = compare.compare_answers(picked, tr.by_name, table)
        self.phase("reference_s", t)
        delta = {k: after.get(k, 0) - before.get(k, 0) for k in after}
        say("counters grown in the window: " + json.dumps(
            {k: v for k, v in delta.items() if v and "ache" in k}))
        paths = {p: delta.get(f"broker.tableStats.paths.{p}", 0)
                 for p in self.config["paths"]}
        numbers["failed_requests"] = sum(1 for r in requests
                                         if r.get("error"))
        numbers["path_violations"] = sum(
            1 for p, rule in self.config["paths"].items()
            if (rule == "none") != (paths[p] == 0))
        for r in requests:
            if r.get("error"):
                say(f"  FAILED {r['shape']} {r['literals']}: {r['error']}")
        verdict = compare.verdict(numbers, self.config["correct"])

        lat = np.array([(r["t_recv"] - r["t_send"]) * 1e3
                        if not r.get("error") else
                        max((r["t_recv"] - r["t_send"]) * 1e3,
                            float(self.traffic_spec["client_timeout_s"]) * 1e3)
                        for r in requests])
        answered = sum(1 for r in requests if not r.get("error")
                       and r["t_recv"] <= window["t_close"])
        ctx = {
            "end_to_end": {
                "queries_per_s": answered / seconds,
                "latency_p50_ms": float(np.percentile(lat, 50)),
                "latency_p90_ms": float(np.percentile(lat, 90)),
                "setup_s": setup_s},
            "requests": requests, "window": window, "phases": self.phases,
            "counters": {"before": before, "after": after, "delta": delta},
            "trace": trace, "server_log_window": log_window,
            "config": self.config, "shapes": tr.by_name,
            "pools": table.pools, "value_ranges": table.value_ranges(),
            "bench_dir": BENCH_DIR,
            "device_kind": boot["deviceKind"],
        }
        if self.args.trace:
            specs = layer_metric_specs(self.bench, self.cell["name"])
        else:
            specs = [{"name": m["name"], "unit": m["unit"],
                      "reducer": "end_to_end"}
                     for m in self.bench["end_to_end"]
                     if "workloads" not in m or
                     self.cell["name"] in m["workloads"]]
        metrics = {}
        for spec in specs:
            reducer = importlib.import_module(f"reducers.{spec['reducer']}")
            value = reducer.reduce(ctx, spec)
            if value is not None:
                metrics[spec["name"]] = {"value": value,
                                         "unit": spec["unit"]}

        device = {"platform": boot["platform"], "kind": boot["deviceKind"],
                  "count": boot["count"],
                  "memory_peak_bytes": stats.get("peak_bytes_in_use")}
        result = {"correct": verdict["correct"],
                  "attempted": len(requests),
                  "failed": numbers["failed_requests"] +
                  numbers["keys_mismatched"],
                  "metrics": metrics, "device": device}
        if trace is not None:
            device.update(busy_s=trace["busy_s"], window_s=trace["window_s"])
            result["trace_file_bytes"] = trace["file_bytes"]
            result["breakdown"] = {
                "device_ops": trace_reduce.op_totals(trace["events"]),
                "idle_gaps": trace_reduce.idle_gaps(trace["events"])}
        by_shape = {}
        for r in requests:
            by_shape.setdefault(r["shape"], []).append(
                (r["t_recv"] - r["t_send"]) * 1e3)
        if self.args.keep:
            os.makedirs(self.args.keep, exist_ok=True)
            with open(os.path.join(self.args.keep, "requests.json"),
                      "w") as fh:
                json.dump([[r["client"], r["seq"], r["shape"],
                            r["t_send"] - window["t_open"],
                            r["t_recv"] - r["t_send"], r.get("error")]
                           for r in requests], fh)
        result.update(workload=self.cell["name"], seed=seed,
                      window_compiles=log_window.count("Compiling "),
                      window_compile_s=compile_seconds(log_window),
                      shape_mean_ms={k: round(sum(v) / len(v), 1)
                                     for k, v in sorted(by_shape.items())},
                      rows=self.config["rows"], phases=self.phases,
                      paths=paths, answers_compared=numbers[
                          "answers_compared"],
                      sums_compared=numbers["sums_compared"],
                      shapes_exhausted=tr.exhausted,
                      warm_rounds=self.warm_rounds, worst=numbers["worst"])
        if self.rehearsal:
            result["rehearsal"] = True
        result["compared"] = verdict["compared"]
        from jax._src import xla_bridge
        if xla_bridge.backends_are_initialized():
            raise RunFailure("the parent initialised a JAX backend")
        return result

    def cleanup(self) -> None:
        if self.cluster is not None:
            try:
                self.cluster.stop(wait_s=30.0)
            except Exception:                       # noqa: BLE001
                traceback.print_exc()
        if self.args.keep:
            logs = os.path.join(self.work_dir, "cluster", "logs")
            if os.path.isdir(logs):
                shutil.copytree(logs, os.path.join(self.args.keep, "logs"),
                                dirs_exist_ok=True)
        shutil.rmtree(self.work_dir, ignore_errors=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="REHEARSAL for the tests: tiny rows on the CPU "
                         "backend; never a device number")
    ap.add_argument("--rows", type=int, default=0,
                    help="a trial at another scale than the "
                         "configuration's; the result line says `rows`")
    ap.add_argument("--keep", default="",
                    help="a directory to keep the children's logs, every "
                         "request's shape, send time and latency, and the "
                         "traced slice's event list in")
    args = ap.parse_args(argv)
    if args.rehearse_cpu:
        say("*** CPU REHEARSAL: not a chip result ***")
    run = None
    try:
        run = Run(args)
        result = run.run()
    except Exception as e:                  # noqa: BLE001 - any failure
        traceback.print_exc()               # fails the run, no result
        say(f"FAILED: {type(e).__name__}: {e}")
        if run is not None:
            say(f"phases until then: {json.dumps(run.phases)}")
        return 1
    finally:
        if run is not None:
            run.cleanup()
    for name, c in result["compared"].items():
        print(f"compared {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
