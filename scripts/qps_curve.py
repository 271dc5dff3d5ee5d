"""Throughput scaling curve: SSB queries through real multi-process
clusters — N brokers × M servers behind the client's
DynamicBrokerSelector — driven by the QueryRunner perf harness in
increasingQPS mode.

Parity: pinot-tools/.../perf/QueryRunner.java targetQPS/increasingQPS and
contrib/pinot-druid-benchmark PinotThroughput — the reference's benchmark
culture records p50/p99 vs offered QPS and the saturation knee, not just
single-query latency. Writes QPS_r11.json + PROFILE_r11.json at the repo
root (override with QPS_ARTIFACT / PROFILE_ARTIFACT).

Cluster shapes (QPS_SHAPES, default "1x2,2x4,4x8" = brokers×servers):
controller, each broker and each server run as their OWN process via the
admin CLI (StartController/StartServer/StartBroker parity). The client
discovers the broker fleet from the property store through the SAME
DynamicBrokerSelector production clients use — broker processes joining
or dying re-balance the offered load with zero client reconfiguration.
QPS_MULTIPROC=0 instead runs the legacy single-process EmbeddedCluster
shape (the pre-r11 artifacts' topology).

Serving-plane config under test (exported to every spawned process and
recorded in the artifact):
- PINOT_TPU_BROKER_INLINE=1      — single-loop broker pipeline (no
  cross-thread self-pipe wakeups; ~1ms/query each on a 1-core host)
- PINOT_TPU_BROKER_CACHE_OFFLINE=1 — exact offline result cache
  (segment-lifecycle-flushed, canonical-fingerprint-keyed)
- PINOT_TPU_SHM_MIN_BYTES        — colocated replies ≥ this ride the
  shared-memory transport instead of the TCP copy

The query mix is SSB replay plus a QPS_JITTER fraction (default 0.005)
of cache-busting variants (a fresh literal per slot): those always execute
end to end — server scan, columnar serde, vectorized reduce — so every
rung measures the full path and the PROFILE phase attribution at the
knee reflects real executions, while the replayed remainder exercises
the result-cache serving path production traffic hits.

Runs on the CPU backend (the serving plane under test is broker routing +
scatter/gather + scheduler + reduce; bench.py covers the chip plane), on
purpose at a row count small enough that per-query work doesn't mask the
serving-path costs.
"""
import json
import os
import subprocess
import sys
import tempfile
import time
import urllib.request

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


# HARD override: this is the CPU gate for the serving plane's counts and
# behaviour (every spawned server would otherwise claim the one chip).
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
# the serving-plane configuration under test (inherited by every
# spawned broker/server process; recorded in the artifact)
os.environ.setdefault("PINOT_TPU_BROKER_INLINE", "1")
os.environ.setdefault("PINOT_TPU_BROKER_CACHE_OFFLINE", "1")
os.environ.setdefault("PINOT_TPU_SHM_MIN_BYTES", str(256 * 1024))

from pinot_tpu.tools.cluster import MultiprocCluster as _ProcCluster  # noqa: E402

ROWS = int(os.environ.get("QPS_ROWS", 2_000_000))
SEGMENTS = int(os.environ.get("QPS_SEGMENTS", 4))
STEP_S = float(os.environ.get("QPS_STEP_S", 4.0))
THREADS = int(os.environ.get("QPS_THREADS", 7))
JITTER = float(os.environ.get("QPS_JITTER", "0.005"))
MULTIPROC = os.environ.get("QPS_MULTIPROC", "1") != "0"
SHAPES = [tuple(int(x) for x in s.split("x"))
          for s in os.environ.get("QPS_SHAPES", "1x2,2x4,4x8").split(",")]
LADDER = [float(x) for x in os.environ.get(
    "QPS_LADDER", "25,50,100,200,400,500,650,800,1000").split(",")]
TABLE = "lineorder_OFFLINE"


def _http(method, url, body=None, ctype="application/json", timeout=60):
    req = urllib.request.Request(
        url, data=body, method=method,
        headers={"Content-Type": ctype} if body else {})
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return json.loads(resp.read())


class MultiprocCluster(_ProcCluster):
    """controller + num_servers servers + num_brokers brokers, one
    process each (shared harness: pinot_tpu.tools.cluster); server
    admin APIs started so per-rung PROFILE attribution covers the
    server-side phases too. This wrapper only loads the SSB data."""

    def __init__(self, base: str, dirs, schema, table_config,
                 num_brokers: int = 1, num_servers: int = 2):
        super().__init__(base, num_brokers=num_brokers,
                         num_servers=num_servers)
        self.num_brokers = num_brokers
        self.num_servers = num_servers
        self.add_schema(schema)
        self.add_table(table_config)
        for d in dirs:
            self.upload_segment(TABLE, d)

    def await_ready(self, expected_rows: int, timeout_s: float = 300.0):
        super().await_ready("lineorder", expected_rows,
                            timeout_s=timeout_s)


class EmbeddedShape:
    """Legacy single-process shape (QPS_MULTIPROC=0): one interpreter,
    TCP data plane, HTTP broker — the pre-r11 artifacts' topology."""

    def __init__(self, base, dirs, schema, table_config, num_servers=2):
        from pinot_tpu.tools.cluster import EmbeddedCluster
        self.c = EmbeddedCluster(base, num_servers=num_servers,
                                 tcp=True, http=True)
        self.c.add_schema(schema)
        self.c.add_table(table_config)
        for d in dirs:
            self.c.upload_segment(TABLE, d)
        self.broker_ports = [self.c.broker_port]
        self.num_brokers, self.num_servers = 1, num_servers
        self.store_port = None

    def await_ready(self, *_a, **_k):
        pass

    def metrics_snapshots(self):
        return {"brokers": {"Broker_0": self.c.broker.metrics.snapshot()},
                "servers": {name: s.metrics.snapshot()
                            for name, s in self.c.servers.items()}}

    def stop(self):
        self.c.stop()


# phase attribution (VERDICT.md #1: "where does the time go") — broker
# pipeline stages + server-side stages, each summed across that plane's
# process registries
BROKER_PHASES = ("requestCompilation", "authorization", "queryRouting",
                 "scatterGather", "serverResponseDeserialization",
                 "reduce", "queryTotal")
SERVER_PHASES = ("requestDeserialization", "schedulerWait",
                 "queryProcessing", "responseSerialization")


def _phase_means(prev, cur):
    """Mean per-query milliseconds per phase over one rung window
    (delta of the cumulative timers between two snapshots, summed
    across every process of that plane)."""

    def plane_mean(prev_regs, cur_regs, phase):
        dc = dt = 0.0
        for name, cur_reg in cur_regs.items():
            prev_reg = prev_regs.get(name, {})
            dc += cur_reg.get(f"timer.{phase}.count", 0) - \
                prev_reg.get(f"timer.{phase}.count", 0)
            dt += cur_reg.get(f"timer.{phase}.totalMs", 0.0) - \
                prev_reg.get(f"timer.{phase}.totalMs", 0.0)
        return round(dt / dc, 3) if dc > 0 else None

    out = {}
    for phase in BROKER_PHASES:
        out[f"broker.{phase}"] = plane_mean(prev["brokers"],
                                            cur["brokers"], phase)
    for phase in SERVER_PHASES:
        out[f"server.{phase}"] = plane_mean(prev["servers"],
                                            cur["servers"], phase)
    return out


def _attribution_profile(phase_rungs, rungs, knee):
    """The per-phase attribution note: what dominates at the knee."""
    knee_idx = next((i for i, r in enumerate(rungs)
                     if knee is not None and r["target_qps"] == knee),
                    len(rungs) - 1)
    at_knee = phase_rungs[knee_idx] if phase_rungs else {}
    total = at_knee.get("broker.queryTotal")
    breakdown = {k: v for k, v in at_knee.items()
                 if k != "broker.queryTotal" and v is not None}
    dominant = max((k for k in breakdown if k.startswith("broker.")),
                   key=lambda k: breakdown[k], default=None)
    # scatterGather CONTAINS the server-side time: compare the server
    # queryProcessing mean (per executed query) against it to judge
    # whether compute or plumbing dominates the gather
    sg = breakdown.get("broker.scatterGather")
    qp = breakdown.get("server.queryProcessing")
    compute_ratio = round(qp / sg, 3) if sg and qp is not None else None
    note = None
    if dominant is not None:
        note = (f"at the {rungs[knee_idx]['target_qps']:g}-QPS rung "
                f"(knee={knee}), mean per-query queryTotal="
                f"{total}ms; dominant broker phase: {dominant} "
                f"({breakdown[dominant]}ms)")
        if sg is not None and qp is not None:
            note += (f" — scatterGather mean {sg}ms vs server "
                     f"queryProcessing mean {qp}ms per executed query "
                     f"(compute/gather ratio {compute_ratio})")
    return {
        "artifact": "phase_attribution_profile",
        "kneeQps": knee,
        "kneeRungOfferedQps": rungs[knee_idx]["target_qps"],
        "phaseMeansMsAtKnee": at_knee,
        "dominantBrokerPhase": dominant,
        "serverComputeOverScatterGather": compute_ratio,
        "note": note,
        "rungs": [{"offered_qps": r["target_qps"],
                   "phaseMeansMs": pm}
                  for r, pm in zip(rungs, phase_rungs)],
    }


def _query_provider(queries, rows):
    """Slot → PQL: SSB replay with a JITTER fraction of cache-busting
    variants (a literal no prior query ever used → fresh canonical
    fingerprint → full execution through scan, serde and reduce). The
    variant counter is global across rungs, so every rung's jitter
    share truly executes instead of hitting the previous rung's cache
    entries."""
    import itertools
    n = len(queries)
    period = max(1, int(round(1.0 / JITTER))) if JITTER > 0 else 0
    fresh = itertools.count(1)

    def provider(i: int) -> str:
        if period and i % period == 0:
            # literal INSIDE the lo_revenue pool range [10k, 999.9k]:
            # a literal past the segment max would min/max-prune every
            # segment and measure nothing
            lit = 10_000 + (next(fresh) * 2654435761) % 980_000
            return ("SELECT COUNT(*), SUM(lo_revenue), "
                    "SUM(lo_supplycost), AVG(lo_quantity) FROM "
                    f"lineorder WHERE lo_revenue > {lit}")
        return queries[i % n]

    return provider


def _run_shape(dirs, schema, table_config, base, num_brokers,
               num_servers, queries):
    from pinot_tpu.client.connection import connect_dynamic
    from pinot_tpu.tools.perf import QueryRunner, http_query_fn

    if MULTIPROC:
        cluster = MultiprocCluster(base, dirs, schema, table_config,
                                   num_brokers=num_brokers,
                                   num_servers=num_servers)
        shape = (f"controller + {num_brokers} broker(s) + "
                 f"{num_servers} servers, one process each "
                 "(DynamicBrokerSelector client)")
    else:
        cluster = EmbeddedShape(base, dirs, schema, table_config,
                                num_servers=num_servers)
        shape = (f"controller + broker(http) + {num_servers} servers "
                 "over TCP, single process")
    conn = None
    try:
        cluster.await_ready(ROWS)
        if MULTIPROC and cluster.store_port is not None:
            # production client path: brokers discovered (and followed)
            # from the property store via DynamicBrokerSelector
            conn = connect_dynamic("127.0.0.1", cluster.store_port)
            fn = lambda pql: conn.execute(pql)          # noqa: E731
        else:
            fn = http_query_fn(
                [f"127.0.0.1:{p}" for p in cluster.broker_ports])
        provider = _query_provider(queries, ROWS)
        runner = QueryRunner(fn, queries, query_provider=provider)

        # warm every query's plan/kernel/result caches — including the
        # jitter SHAPE (one XLA compile per filter structure; later
        # jitter literals reuse the compiled kernel)
        warm = runner.single_thread(num_times=2)
        if JITTER > 0:
            for _ in range(2):
                fn(provider(0))
            # warm the BATCHED buckets too: concurrent same-shape
            # bursts form real coalescer groups at the servers, so the
            # pow2 batch-axis buckets (2/4/8) compile here instead of
            # inside a measured rung (the single-thread warm above can
            # never overlap, so it only ever compiles batch=1 kernels)
            from concurrent.futures import ThreadPoolExecutor
            with ThreadPoolExecutor(max_workers=48) as pool:
                for _ in range(4):
                    list(pool.map(fn, [provider(0) for _ in range(48)]))
        print(f"warm[{num_brokers}x{num_servers}]: {warm}",
              file=sys.stderr, flush=True)

        rungs, phase_rungs = [], []
        knee = None
        snap = cluster.metrics_snapshots()
        for qps in LADDER:
            r = runner.target_qps(qps=qps, duration_s=STEP_S,
                                  num_threads=THREADS)
            print(str(r), file=sys.stderr, flush=True)
            rungs.append(r.to_json())
            next_snap = cluster.metrics_snapshots()
            phase_rungs.append(_phase_means(snap, next_snap))
            snap = next_snap
            if knee is None and (r.qps < 0.9 * qps or
                                 r.missed_slots > r.num_queries // 2):
                knee = qps
                break        # saturated: higher rungs only repeat it
        runner.close()
        return {
            "brokers": num_brokers, "servers": num_servers,
            "cluster": shape,
            "warmup": warm.to_json(),
            "rungs": rungs,
            "saturation_knee_qps": knee,
            "max_sustained_qps": max(
                (r["qps"] for r in rungs
                 if r["qps"] >= 0.9 * r["target_qps"] and
                 r["missed_slots"] <= r["num_queries"] // 2),
                default=0.0),
        }, phase_rungs
    finally:
        if conn is not None:
            conn.close()
        cluster.stop()


def main() -> None:
    from bench import SSB_PQLS
    from pinot_tpu.tools.datagen import (build_ssb_segment_dirs,
                                         ssb_schema, ssb_table_config)

    t0 = time.time()
    base = tempfile.mkdtemp()
    print(f"building {ROWS} rows / {SEGMENTS} segments...",
          file=sys.stderr, flush=True)
    dirs, _ids, _sc = build_ssb_segment_dirs(
        os.path.join(base, "segs"), ROWS, SEGMENTS, seed=7, star_tree=True)
    schema = ssb_schema()
    queries = list(SSB_PQLS.values())

    shapes_out = []
    best = None
    best_phase_rungs = None
    shape_list = SHAPES if MULTIPROC else [(1, 2)]
    for num_brokers, num_servers in shape_list:
        print(f"=== shape {num_brokers} broker(s) x {num_servers} "
              "servers ===", file=sys.stderr, flush=True)
        # full replication + replica-group routing: every query's whole
        # segment set is served by ONE server per routing table (the
        # reference's replica-group builders exist exactly for this
        # fan-out reduction), so adding servers adds independent
        # replicas of the whole table instead of splitting every query
        # across every server
        from pinot_tpu.common.table_config import RoutingConfig
        tconf = ssb_table_config(star_tree=True)
        tconf.segments_config.replication = num_servers
        tconf.routing_config = RoutingConfig("replicaGroup")
        result, phase_rungs = _run_shape(
            dirs, schema, tconf,
            os.path.join(base, f"cluster_{num_brokers}x{num_servers}"),
            num_brokers, num_servers, queries)
        shapes_out.append(result)
        if best is None or result["max_sustained_qps"] > \
                best["max_sustained_qps"]:
            best = result
            best_phase_rungs = phase_rungs

    knee = max((s["saturation_knee_qps"] for s in shapes_out
                if s["saturation_knee_qps"] is not None),
               default=None)
    from pinot_tpu.server.instance import DEFAULT_BATCH_WINDOW_MS
    out = {
        "artifact": "ssb13_throughput_scaling_curve",
        "rows": ROWS, "segments": SEGMENTS,
        "shapes": shapes_out,
        "backend": "cpu (serving-plane benchmark; chip plane is "
                   "bench.py)",
        "mode": "increasingQPS (QueryRunner.java parity)",
        "step_duration_s": STEP_S,
        "client_threads": THREADS,
        "query_mix": {"replayed": "SSB 13-query set",
                      "cacheBustingFraction": JITTER},
        "serving_config": {
            "wireFormat": "DataTable v3 (zero-copy columnar)",
            "brokerInline":
                os.environ["PINOT_TPU_BROKER_INLINE"] != "0",
            "brokerOfflineResultCache":
                os.environ["PINOT_TPU_BROKER_CACHE_OFFLINE"] != "0",
            "shmMinBytes": int(os.environ["PINOT_TPU_SHM_MIN_BYTES"]),
            "batchWindowMs": float(os.environ.get(
                "PINOT_TPU_BATCH_WINDOW_MS", DEFAULT_BATCH_WINDOW_MS)),
        },
        "saturation_knee_qps": knee,
        "max_sustained_qps": max(s["max_sustained_qps"]
                                 for s in shapes_out),
        "wall_s": round(time.time() - t0, 1),
    }
    path = os.path.join(REPO,
                        os.environ.get("QPS_ARTIFACT", "QPS_r11.json"))
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    # the phase-attribution profile (obs subsystem): which pipeline
    # stage the per-query time actually goes to at the BEST shape's knee
    profile = _attribution_profile(best_phase_rungs, best["rungs"],
                                   best["saturation_knee_qps"])
    profile.update({"rows": ROWS, "segments": SEGMENTS,
                    "cluster": best["cluster"],
                    "qps_artifact": os.path.basename(path)})
    ppath = os.path.join(REPO, os.environ.get("PROFILE_ARTIFACT",
                                              "PROFILE_r11.json"))
    with open(ppath, "w") as f:
        json.dump(profile, f, indent=1)
    print(f"profile: {profile['note']}", file=sys.stderr, flush=True)
    print(json.dumps({"artifact": path,
                      "profile_artifact": ppath,
                      "saturation_knee_qps": knee,
                      "max_sustained_qps": out["max_sustained_qps"],
                      "dominant_phase_at_knee":
                          profile["dominantBrokerPhase"]}))


if __name__ == "__main__":
    main()
