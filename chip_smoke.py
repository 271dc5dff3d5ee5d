#!/usr/bin/env python3
"""chip_smoke: serve SSB from one chip-owning server process, end to end.

    python3 chip_smoke.py                  # on a machine with one TPU chip
    python3 chip_smoke.py --rehearse-cpu   # tiny CPU rehearsal (debugging)

Drives the main path once through the entry points a user calls, as
separate OS processes: StartController + StartBroker + ONE StartServer
(`pinot_tpu.tools.admin`). The flattened SSB `lineorder` table is built
from `--seed` WITHOUT star-tree cubes, uploaded through the controller's
REST API, loaded by the server into HBM, and queried over the broker's
HTTP `/query`: the 13 SSB queries, each compared with bench.py's numpy
reference, then concurrent bursts of q1.1-shaped queries that must
coalesce into at least one batched dispatch. A first phase compiles and
executes every registered kernel family in a child of its own.

This process is the PARENT: it builds segments (numpy + native/), starts
processes, drives HTTP and runs the numpy reference. It never
initialises a JAX backend — the chip belongs to one child at a time.

Exit 0 and a last stdout line `{"ok": true, "device": {...}}` only when
every phase passed on a TPU. No TPU, any flagged/partial/wrong answer,
any host-path execution, any dead child or any raised phase: non-zero.
"""
from __future__ import annotations

import argparse
import concurrent.futures
import json
import os
import re
import shutil
import subprocess
import sys
import time
import types
import urllib.request

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)

from bench import (SSB_PQLS, canon_response, check,  # noqa: E402
                   make_cpu_queries)
from pinot_tpu import native  # noqa: E402
from pinot_tpu.segment.loader import min_id_dtype, padded_size  # noqa: E402
from pinot_tpu.tools.cluster import MultiprocCluster  # noqa: E402
from pinot_tpu.tools.datagen import (SSB_RAW_COLS, SSB_TYPES,  # noqa: E402
                                     build_ssb_segment_dirs, ssb_pools,
                                     ssb_schema, ssb_table_config)
from pinot_tpu.utils.device import compile_cache_location  # noqa: E402

TABLE = "lineorder"
#: the BASELINE.md config-5 shape, run in full; a cut (`--rows`) goes
#: down bench.py's ladder and is printed under `reduced`
FULL_ROWS = 100_000_000
SEGMENTS = 8
REHEARSAL_ROWS = 80_000
#: explicit deadline for queries that may compile (first run of a shape);
#: checked queries run at the broker/server default (15 s)
COMPILE_TIMEOUT_MS = 600_000
#: the contract's limit is 1200 s; leave room to stop children and report
WALL_LIMIT_S = 1140.0
#: 1 solo + k followers per round, sized so the vmapped batch buckets
#: 8, 4 and 2 are each likely to seal at least once
BURST_ROUNDS = (16, 9, 5, 3)
MAX_BURST_ATTEMPTS = 3

T0 = time.monotonic()


def say(msg: str) -> None:
    print(f"smoke[{time.monotonic() - T0:7.1f}s] {msg}", flush=True)


class SmokeFailure(Exception):
    pass


def require(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def reduced(rows: int, rehearsal: bool) -> list:
    """Cuts of scale against the source deployment, each with its why."""
    cuts = []
    if rehearsal:
        cuts.append(f"rows {FULL_ROWS} -> {rows}: CPU rehearsal, not a "
                    "measurement")
    elif rows < FULL_ROWS:
        cuts.append(f"rows {FULL_ROWS} -> {rows}: the 1200 s limit "
                    "(see CHANGES.md for the measured phase times)")
    cuts.append("replicas 1, servers 1: one chip, one chip-owning process")
    return cuts


def with_options(pql: str, **opts) -> str:
    extra = ", ".join(f"{k}={v}" for k, v in opts.items())
    if pql.rstrip().endswith(")") and " OPTION(" in pql:
        return pql.rstrip()[:-1] + ", " + extra + ")"
    return f"{pql} OPTION({extra})"


def http_json(url: str, timeout: float = 30.0):
    with urllib.request.urlopen(url, timeout=timeout) as resp:
        return json.loads(resp.read())


def as_response(body: dict):
    """Broker JSON → the attribute shape bench.canon_response reads."""
    return types.SimpleNamespace(aggregation_results=[
        types.SimpleNamespace(value=a.get("value"),
                              group_by_result=a.get("groupByResult"))
        for a in body.get("aggregationResults", [])])


def require_clean(name: str, body: dict) -> None:
    require(not body.get("exceptions"),
            f"{name}: response carries exceptions: {body.get('exceptions')}")
    require(not body.get("partialResponse"), f"{name}: partialResponse")
    require(body["numServersResponded"] == body["numServersQueried"] >= 1,
            f"{name}: {body['numServersResponded']} of "
            f"{body['numServersQueried']} servers responded")


def max_rel_err(got, exp) -> list:
    """Largest relative distance from the reference, per aggregate
    (0.0 = every value equal) — how close the chip came, beyond `check`'s
    pass/fail."""
    if not isinstance(exp, dict):
        got, exp = {(): (got,)}, {(): (exp,)}
    n_aggs = len(next(iter(exp.values()), ()))
    return [max((abs(got[k][i] - v[i]) / max(abs(v[i]), 1e-12)
                 for k, v in exp.items()), default=0.0)
            for i in range(n_aggs)]


def q1_shape(year: int, dlo: int, dhi: int, qmax: int) -> str:
    return ("SELECT SUM(lo_revenue) FROM lineorder WHERE "
            f"d_year = {year} AND lo_discount BETWEEN {dlo} AND {dhi} "
            f"AND lo_quantity < {qmax}")


def q1_shape_reference(pools, ids, year, dlo, dhi, qmax) -> float:
    """numpy reference for `q1_shape`, id-domain like bench.py's."""
    def left(col, v):
        return int(np.searchsorted(pools[col], v, side="left"))
    disc = ids["lo_discount"]
    mask = (ids["d_year"] == left("d_year", year)) & \
        (disc >= left("lo_discount", dlo)) & \
        (disc < int(np.searchsorted(pools["lo_discount"], dhi,
                                    side="right"))) & \
        (ids["lo_quantity"] < left("lo_quantity", qmax))
    hist = np.bincount(ids["lo_revenue"][mask],
                       minlength=len(pools["lo_revenue"]))
    return float(hist @ pools["lo_revenue"].astype(np.float64))


def expected_lane_bytes(rows: int, segments: int, pools, x64: bool) -> int:
    """Lower bound on the HBM the 13 queries' forward lanes occupy: one
    padded id lane (narrowest id dtype) per referenced dictionary column
    and one value lane per referenced raw column, per segment. The
    ledger also holds part/value tables on top of this."""
    cols = [c for c in SSB_TYPES
            if any(re.search(rf"\b{c}\b", q) for q in SSB_PQLS.values())]
    per = rows // segments
    total = 0
    for i in range(segments):
        n = per if i < segments - 1 else rows - per * (segments - 1)
        for c in cols:
            width = (8 if x64 else 4) if c in SSB_RAW_COLS else \
                min_id_dtype(len(pools[c])).itemsize
            total += padded_size(n) * width
    return total


class Smoke:
    def __init__(self, args):
        self.args = args
        self.rehearsal = args.rehearse_cpu
        self.rows = args.rows or (REHEARSAL_ROWS if self.rehearsal
                                  else FULL_ROWS)
        self.work = args.work_dir
        self.out_dir = args.out_dir
        self.child_env = {"JAX_PLATFORMS": "cpu"} if self.rehearsal else {}
        self.cluster = None
        self.report = {"rehearsal": self.rehearsal, "rows": self.rows,
                       "segments": SEGMENTS, "seed": args.seed,
                       "reduced": reduced(self.rows, self.rehearsal),
                       "phases": {}}
        # wide enough for a whole burst round in flight plus its references
        self.pool = concurrent.futures.ThreadPoolExecutor(max_workers=40)

    # -- plumbing ----------------------------------------------------------
    def phase(self, name, fn):
        require(time.monotonic() - T0 < WALL_LIMIT_S,
                f"wall limit {WALL_LIMIT_S:.0f}s reached before {name}")
        t = time.monotonic()
        say(f"phase {name} ...")
        fn()
        self.check_children(name)
        dt = time.monotonic() - t
        self.report["phases"][name] = round(dt, 1)
        say(f"phase {name} done in {dt:.1f}s")

    def check_children(self, where: str) -> None:
        if self.cluster is None:
            return
        dead = {n: c for n, c in self.cluster.exit_codes().items()
                if c is not None}
        require(not dead, f"child process(es) exited during {where}: {dead}")

    def table_stats(self) -> dict:
        port = self.cluster.broker_ports[0]
        return http_json(
            f"http://127.0.0.1:{port}/debug/tableStats/{TABLE}")

    def server_get(self, path: str) -> dict:
        port = next(iter(self.cluster.server_admin_ports.values()))
        return http_json(f"http://127.0.0.1:{port}{path}")

    # -- phases ------------------------------------------------------------
    def preflight(self):
        lib = native.lib()
        self.report["nativeSeglib"] = lib is not None
        say(f"native/seglib loaded: {lib is not None}")
        require(lib is not None or shutil.which("g++") is None,
                "g++ is present but native/seglib failed to build or load")
        shutil.rmtree(self.work, ignore_errors=True)
        os.makedirs(self.work)
        shutil.rmtree(self.out_dir, ignore_errors=True)
        os.makedirs(self.out_dir)
        free = shutil.disk_usage(self.work).free
        say(f"work dir {self.work} ({free / 2**30:.1f} GiB free), "
            f"rows {self.rows}, segments {SEGMENTS}, seed {self.args.seed}")
        for cut in self.report["reduced"]:
            say(f"reduced: {cut}")
        cache = compile_cache_location()
        entries = len(os.listdir(cache)) if os.path.isdir(cache) else 0
        self.report["compileCache"] = {"dir": cache, "entriesAtStart": entries}
        say(f"compile cache {cache}: "
            f"{'warm, ' + str(entries) + ' entries' if entries else 'cold'}")

    def kernel_sweep(self):
        """Every registered kernel family compiles for, and runs on, the
        backend a child of this machine gets. The child exits (and
        frees the chip) before the cluster starts."""
        env = dict(os.environ, PYTHONPATH=REPO, **self.child_env)
        with open(os.path.join(self.out_dir, "kernel_sweep.log"),
                  "wb") as log:
            proc = subprocess.run(
                [sys.executable, "-m", "pinot_tpu.analysis",
                 "--compile-kernels"], cwd=REPO, env=env,
                stdout=subprocess.PIPE, stderr=log, text=True,
                timeout=600)
        lines = proc.stdout.strip().splitlines()
        require(bool(lines), f"kernel sweep printed nothing "
                f"(rc={proc.returncode}; see kernel_sweep.log)")
        out = json.loads(lines[-1])
        self.report["kernelSweep"] = out
        dev = out["device"]
        say(f"sweep child ran on {dev['platform']} ({dev['deviceKind']})"
            f" x{dev['count']}, compile cache {dev['compileCacheDir']}")
        self.require_device(dev)
        for c in out["cases"]:
            if "error" in c:
                say(f"  FAILED {c['name']}@P={c['padded']}: {c['error']}")
        total = sum(c.get("compileS", 0.0) for c in out["cases"])
        say(f"{len(out['cases']) - out['failed']} of {len(out['cases'])} "
            f"kernel cases compiled and ran; compile total {total:.1f}s")
        require(out["failed"] == 0 and proc.returncode == 0,
                f"kernel sweep: {out['failed']} case(s) failed, "
                f"rc={proc.returncode}")

    def require_device(self, dev: dict) -> None:
        want = "cpu" if self.rehearsal else "tpu"
        require(dev["platform"] == want,
                f"device platform is {dev['platform']!r}, not {want!r}")
        require(not dev["x64"], "x64 is on; the deployed mode is x32")

    def build(self):
        self.pools = ssb_pools(self.args.seed)
        dirs, self.ids, self.cost = build_ssb_segment_dirs(
            os.path.join(self.work, "built"), self.rows, SEGMENTS,
            seed=self.args.seed, star_tree=False)
        self.dirs = dirs
        # the numpy reference runs beside the server's compiles
        cpu = make_cpu_queries(self.pools, self.ids, self.cost)
        self.expected = {n: self.pool.submit(cpu[n]) for n in SSB_PQLS}

    def start_cluster(self):
        self.cluster = MultiprocCluster(
            os.path.join(self.work, "cluster"), num_brokers=1,
            num_servers=1, env=self.child_env)
        boot = self.cluster.server_boots["Server_0"]
        self.report["serverBoot"] = boot
        say(f"server boot line: {json.dumps(boot)}")
        self.require_device(boot["device"])

    def upload(self):
        c = self.cluster
        c.add_schema(ssb_schema())
        c.add_table(ssb_table_config(star_tree=False))
        list(self.pool.map(
            lambda d: c.upload_segment(f"{TABLE}_OFFLINE", d), self.dirs))
        shutil.rmtree(os.path.join(self.work, "built"))
        c.await_ready(TABLE, self.rows, timeout_s=600)

    def run_checked(self, name: str, pql: str, timeout_s: float,
                    keep_trace: bool = False) -> dict:
        """One query that must do device work: clean response, equal to
        the reference, every segment on the device scan path."""
        before = self.table_stats().get("queries", 0)
        t = time.monotonic()
        body = self.cluster.query(pql, timeout=timeout_s)
        wall_ms = (time.monotonic() - t) * 1e3
        require_clean(name, body)
        got = canon_response(name, as_response(body))
        exp = self.expected[name].result()
        check(name, got, exp)
        stats = self.table_stats()
        require(stats.get("queries", 0) == before + 1,
                f"{name}: no execution profile reached the broker — "
                "answered from a cache, not the device")
        prof = stats["recent"][-1]
        paths = prof.get("paths", {})
        require(paths.get("host", 0) == 0 and paths.get("cube", 0) == 0
                and paths.get("scan", 0) > 0
                and prof.get("kernelDispatches", 0) > 0,
                f"{name}: not served by device scans: {prof}")
        entry = {"wallMs": round(wall_ms, 1), "paths": paths,
                 "kernelDispatches": prof["kernelDispatches"],
                 "maxRelErr": max_rel_err(got, exp), "profile": prof}
        if keep_trace:
            entry["traceTree"] = body.get("traceTree")
        return entry

    def warm(self):
        """First execution of each shape compiles; it gets an explicit
        deadline. trace=true keeps the answer out of the result caches,
        so the checked pass below has to go to the device again."""
        out = {}
        for name, pql in SSB_PQLS.items():
            out[name] = self.run_checked(
                name, with_options(pql, trace="true",
                                   timeoutMs=COMPILE_TIMEOUT_MS),
                COMPILE_TIMEOUT_MS / 1e3)
            say(f"  warm {name}: {out[name]['wallMs']:.0f} ms "
                f"(compile included), paths {out[name]['paths']}")
        self.report["warm"] = out

    def queries(self):
        out = {}
        for name, pql in SSB_PQLS.items():
            out[name] = self.run_checked(name, pql, 30.0)
            say(f"  {name}: ok, {out[name]['wallMs']:.1f} ms, paths "
                f"{out[name]['paths']}, dispatches "
                f"{out[name]['kernelDispatches']}, max rel err "
                f"{out[name]['maxRelErr']}")
        self.report["queries"] = out

    def traced(self):
        """The same 13 once more with trace=true, compiled and resident:
        the per-layer span tree of a served query on this chip, kept in
        the report (traced queries bypass the result caches)."""
        self.report["traced"] = {
            name: self.run_checked(name, with_options(pql, trace="true"),
                                   30.0, keep_trace=True)
            for name, pql in SSB_PQLS.items()}

    def burst(self):
        """Concurrent same-shape, different-literal queries: the
        coalescer must seal at least one batch of 2 or more, and every
        member's answer must equal the reference."""
        seen, rounds = [], []
        rng = np.random.default_rng(self.args.seed + 1)
        used = {(1993, 1, 3, 25)}                    # q1.1 itself
        for attempt in range(MAX_BURST_ATTEMPTS):
            for size in BURST_ROUNDS:
                lits = []
                while len(lits) < size:
                    dlo = int(rng.integers(0, 9))
                    lit = (int(rng.integers(1992, 1999)), dlo, dlo + 2,
                           int(rng.integers(10, 50)))
                    if lit not in used:
                        used.add(lit)
                        lits.append(lit)
                wall_s, sizes = self.burst_round(lits)
                rounds.append({"queries": size, "wallS": round(wall_s, 2),
                               "batchSizes": sorted(sizes)})
                say(f"  burst round of {size}: all answered in "
                    f"{wall_s:.2f}s, batch sizes {sorted(sizes)}")
                seen += sizes
            if any(b >= 2 for b in seen):
                break
            say(f"  burst attempt {attempt + 1}: no batch sealed, retrying")
        sizes = sorted(set(seen))
        self.report["burst"] = {"queries": len(seen), "batchSizes": sizes,
                                "rounds": rounds}
        say(f"  burst: {len(seen)} queries, batch sizes seen {sizes}")
        require(any(b >= 2 for b in seen),
                f"no coalesced batch in {MAX_BURST_ATTEMPTS} attempts")

    def burst_round(self, lits):
        """→ (seconds until the last answer, batch size each query rode)."""
        before = self.table_stats().get("queries", 0)
        # vmapped kernels compile on first use of each batch bucket
        pqls = [with_options(q1_shape(*lit), timeoutMs=COMPILE_TIMEOUT_MS)
                for lit in lits]
        t = time.monotonic()
        futs = [self.pool.submit(self.cluster.query, p,
                                 timeout=COMPILE_TIMEOUT_MS / 1e3)
                for p in pqls]
        refs = [self.pool.submit(q1_shape_reference, self.pools, self.ids,
                                 *lit) for lit in lits]
        bodies = [f.result() for f in futs]
        wall_s = time.monotonic() - t
        for lit, body, ref in zip(lits, bodies, refs):
            name = f"q1-shape{lit}"
            require_clean(name, body)
            check("q1", canon_response("q1", as_response(body)),
                  ref.result())
        stats = self.table_stats()
        require(stats.get("queries", 0) == before + len(lits),
                f"burst: {stats.get('queries', 0) - before} of "
                f"{len(lits)} queries executed")
        profs = stats["recent"][-len(lits):]
        for prof in profs:
            require(prof.get("paths", {}).get("host", 0) == 0 and
                    prof.get("paths", {}).get("scan", 0) > 0,
                    f"burst member not served by device scans: {prof}")
        return wall_s, [int(p.get("batchSize", 1)) for p in profs]

    def residency(self):
        health = self.server_get("/debug/health")
        dev = health["device"]
        self.require_device(dev)
        ledger = self.server_get("/debug/residency")
        ledgered = int(ledger["totalDeviceBytesResident"])
        want = expected_lane_bytes(self.rows, SEGMENTS, self.pools,
                                   dev["x64"])
        self.report["device"] = dev
        self.report["ledgeredDeviceBytes"] = ledgered
        self.report["expectedLaneBytesAtLeast"] = want
        self.report["ledgerByKind"] = ledger.get("byKind")
        say(f"  ledgered device bytes {ledgered} (by kind "
            f"{ledger.get('byKind')}); forward lanes need >= {want}; "
            f"backend bytes_in_use {dev['bytesInUse']}")
        require(ledgered >= want,
                f"ledger holds {ledgered} B, the lanes need >= {want} B")

    def stop_cluster(self):
        cluster, self.cluster = self.cluster, None
        codes = cluster.stop(wait_s=60.0)
        self.report["exitCodes"] = codes
        say(f"  child exit codes: {codes}")
        require(all(c == 0 for c in codes.values()),
                f"child exit codes: {codes}")

    # -- driver ------------------------------------------------------------
    def run(self) -> dict:
        try:
            self.phase("preflight", self.preflight)
            self.phase("kernel_sweep", self.kernel_sweep)
            self.phase("build", self.build)
            self.phase("start_cluster", self.start_cluster)
            self.phase("upload_and_load", self.upload)
            self.phase("warm", self.warm)
            self.phase("queries", self.queries)
            self.phase("traced", self.traced)
            self.phase("burst", self.burst)
            self.phase("residency", self.residency)
            self.phase("stop_cluster", self.stop_cluster)
        finally:
            self.pool.shutdown(wait=False, cancel_futures=True)
            if self.cluster is not None:
                self.cluster.stop(wait_s=30.0)
            self.save()
        from jax._src import xla_bridge
        require(not xla_bridge.backends_are_initialized(),
                "the parent initialised a JAX backend")
        return self.report["device"]

    def save(self):
        """Report + child logs where a chip run brings them back."""
        self.report["wallS"] = round(time.monotonic() - T0, 1)
        with open(os.path.join(self.out_dir, "report.json"), "w") as fh:
            json.dump(self.report, fh, indent=1)
        logs = os.path.join(self.work, "cluster", "logs")
        if os.path.isdir(logs):
            shutil.copytree(logs, os.path.join(self.out_dir, "logs"),
                            dirs_exist_ok=True)
        shutil.rmtree(self.work, ignore_errors=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", type=int, default=0,
                    help=f"lineorder rows (default {FULL_ROWS}; "
                         f"{REHEARSAL_ROWS} with --rehearse-cpu)")
    ap.add_argument("--seed", type=int, default=3)
    ap.add_argument("--work-dir", default=os.path.join(REPO, ".chip_smoke"))
    ap.add_argument("--out-dir",
                    default=os.path.join(REPO, "chiprun_out", "chip_smoke"),
                    help="report.json and the children's logs land here")
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="REHEARSAL on the CPU backend at a tiny size: "
                         "debugs this script, proves nothing about a chip")
    args = ap.parse_args()
    # the deployed mode is the server's own default (x32)
    os.environ.pop("JAX_ENABLE_X64", None)
    if args.rehearse_cpu:
        say("*** CPU REHEARSAL — not a chip result ***")
    smoke = Smoke(args)
    try:
        dev = smoke.run()
    except Exception as e:  # noqa: BLE001 — any failure fails the smoke
        import traceback
        traceback.print_exc()
        say(f"FAILED: {type(e).__name__}: {e}")
        return 1
    say(f"phases (s): {json.dumps(smoke.report['phases'])}")
    say(f"total wall {smoke.report['wallS']}s; report in {smoke.out_dir}")
    result = {"ok": True,
              "device": {"platform": dev["platform"],
                         "kind": dev["deviceKind"], "count": dev["count"]}}
    if args.rehearse_cpu:
        say("*** CPU REHEARSAL — not a chip result ***")
        result["rehearsal"] = True
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
