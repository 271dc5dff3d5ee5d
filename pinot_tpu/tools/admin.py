"""pinot-tpu-admin: the admin command surface.

Parity: pinot-tools PinotAdministrator (tools/admin/command/ — StartServer
/AddTable/AddSchema/CreateSegment/UploadSegment/PostQuery/RebalanceTable/
DeleteSegment/Quickstart...). Commands speak to the controller/broker
REST APIs so they work against any running cluster; `quickstart` boots an
embedded cluster in-process (parity: tools/Quickstart.java:125-144).

Usage:
    python -m pinot_tpu.tools.admin <command> [options]
"""
from __future__ import annotations

import argparse
import json
import sys
import urllib.request
from typing import Optional


def _http(method: str, url: str, body: Optional[bytes] = None,
          content_type: str = "application/json") -> dict:
    req = urllib.request.Request(url, data=body, method=method,
                                 headers={"Content-Type": content_type}
                                 if body else {})
    with urllib.request.urlopen(req, timeout=60) as resp:
        data = resp.read()
    try:
        return json.loads(data)
    except ValueError:
        return {"raw": data.decode("utf-8", "replace")}


def cmd_add_schema(args) -> int:
    with open(args.schema_file) as f:
        body = f.read().encode()
    out = _http("POST", f"http://{args.controller}/schemas", body)
    print(json.dumps(out))
    return 0


def cmd_add_table(args) -> int:
    with open(args.table_config_file) as f:
        body = f.read().encode()
    out = _http("POST", f"http://{args.controller}/tables", body)
    print(json.dumps(out))
    return 0


def cmd_create_segment(args) -> int:
    from pinot_tpu.common.schema import Schema
    from pinot_tpu.common.table_config import TableConfig
    from pinot_tpu.tools.create_segment import create_segment_from_file
    with open(args.schema_file) as f:
        schema = Schema.from_json(json.load(f))
    table_config = None
    if args.table_config_file:
        with open(args.table_config_file) as f:
            table_config = TableConfig.from_json(json.load(f))
    meta = create_segment_from_file(
        args.input, args.format, schema, args.out_dir,
        table_config=table_config, segment_name=args.segment_name)
    print(json.dumps({"segmentName": meta.segment_name,
                      "totalDocs": meta.total_docs}))
    return 0


def cmd_upload_segment(args) -> int:
    from pinot_tpu.controller.http_api import pack_segment_dir
    body = pack_segment_dir(args.segment_dir)
    out = _http("POST",
                f"http://{args.controller}/segments/{args.table}",
                body, content_type="application/octet-stream")
    print(json.dumps(out))
    return 0


def cmd_post_query(args) -> int:
    body = json.dumps({"pql": args.query}).encode()
    out = _http("POST", f"http://{args.broker}/query", body)
    print(json.dumps(out, indent=2))
    return 0


def cmd_startree_viewer(args) -> int:
    """Parity: StarTreeIndexViewer — dump a segment's pre-aggregated
    cubes: split order, group counts, per-metric stats, reduction vs
    raw docs."""
    from pinot_tpu.segment.loader import ImmutableSegmentLoader
    seg = ImmutableSegmentLoader.load(args.segment_dir)
    if not seg.star_trees:
        print(json.dumps({"segmentName": seg.segment_name,
                          "starTrees": []}))
        return 0
    out = []
    for i, cube in enumerate(seg.star_trees):
        import numpy as np
        dims = {d: {"activeValues": int(np.unique(cube.dim_ids[d]).size)}
                for d in cube.dimensions}
        out.append({
            "index": i,
            "dimensionsSplitOrder": cube.dimensions,
            "metrics": cube.metrics,
            "numGroups": cube.n_groups,
            "rawDocs": seg.num_docs,
            "reductionFactor": round(seg.num_docs /
                                     max(cube.n_groups, 1), 2),
            "dimensions": dims,
            "statKinds": {m: sorted(st.keys())
                          for m, st in cube.metric_stats.items()},
        })
    print(json.dumps({"segmentName": seg.segment_name,
                      "totalDocs": seg.num_docs, "starTrees": out},
                     indent=2))
    return 0


def cmd_realtime_provisioning(args) -> int:
    """Parity: RealtimeProvisioningHelperCommand — estimate per-host
    memory for consuming segments across (numHosts, hoursToFlush)
    combinations, from a SAMPLE completed segment's measured bytes/row
    and the table's ingestion rate."""
    from pinot_tpu.segment.loader import (ImmutableSegmentLoader,
                                          segment_host_bytes)
    seg = ImmutableSegmentLoader.load(args.sample_segment)
    n = max(seg.num_docs, 1)
    # measured bytes/row of the columnar artifact (consuming segments
    # hold roughly this in arrival-order form, plus dictionary overhead)
    bytes_per_row = segment_host_bytes(seg) / n * 1.3   # mutable overhead
    rows_per_hour = args.rows_per_hour
    hosts_list = [int(x) for x in args.num_hosts.split(",")]
    hours_list = [int(x) for x in args.num_hours.split(",")]
    if any(h <= 0 for h in hosts_list) or any(h <= 0 for h in hours_list):
        print(json.dumps({"error": "--num-hosts/--num-hours must be "
                          "positive integers"}))
        return 1
    matrix = {}
    for hosts in hosts_list:
        per_host = {}
        parts_per_host = -(-args.num_partitions * args.replication
                           // hosts)
        for hours in hours_list:
            rows_per_seg = rows_per_hour * hours / max(
                args.num_partitions, 1)
            consuming_mb = parts_per_host * rows_per_seg * \
                bytes_per_row / 1e6
            retained_mb = parts_per_host * \
                (args.retention_hours / max(hours, 1)) * \
                rows_per_seg * bytes_per_row / 1e6
            per_host[f"{hours}h"] = {
                "consumingMB": round(consuming_mb, 1),
                "retainedMB": round(retained_mb, 1),
                "totalMB": round(consuming_mb + retained_mb, 1),
            }
        matrix[f"{hosts}hosts"] = per_host
    print(json.dumps({
        "sampleSegmentRows": seg.num_docs,
        "bytesPerRow": round(bytes_per_row, 1),
        "rowsPerHour": rows_per_hour,
        "numPartitions": args.num_partitions,
        "replication": args.replication,
        "retentionHours": args.retention_hours,
        "memoryPerHost": matrix}, indent=2))
    return 0


def cmd_query_runner(args) -> int:
    """Replay a query file against a broker at a latency/QPS report.

    Parity: tools/perf/QueryRunner.java:43-90 — modes singleThread /
    multiThreads / targetQPS / increasingQPS."""
    from pinot_tpu.tools.perf import (QueryRunner, http_query_fn,
                                      load_query_file)
    runner = QueryRunner(http_query_fn(args.broker),
                         load_query_file(args.query_file))
    if args.mode == "singleThread":
        reports = [runner.single_thread(num_times=args.num_times)]
    elif args.mode == "multiThreads":
        reports = [runner.multi_threads(num_threads=args.num_threads,
                                        num_times=args.num_times)]
    elif args.mode == "targetQPS":
        reports = [runner.target_qps(args.qps, args.duration,
                                     num_threads=args.num_threads)]
    else:
        reports = runner.increasing_qps(
            args.qps, args.step_qps, args.steps, args.duration,
            num_threads=args.num_threads)
    for r in reports:
        print(r)
    print(json.dumps([r.to_json() for r in reports]))
    return 0


def _print_http(method: str, url: str, body=None,
                content_type: str = "application/json") -> int:
    """Run a controller call, printing error BODIES (the 400/409
    responses carry the reason, e.g. 'tenant X is in use by t') instead
    of dying with a traceback."""
    import urllib.error
    try:
        out = _http(method, url, body, content_type=content_type)
    except urllib.error.HTTPError as e:
        print(json.dumps({"status": e.code,
                          "error": e.read().decode("utf-8", "replace")},
                         indent=2))
        return 1
    print(json.dumps(out, indent=2))
    return 0


def cmd_add_tenant(args) -> int:
    """Parity: AddTenantCommand → PinotTenantRestletResource POST."""
    return _print_http(
        "POST", f"http://{args.controller}/tenants",
        json.dumps({"tenantName": args.name,
                    "tenantRole": args.role.upper(),
                    "instances": args.instances}).encode())


def cmd_list_tenants(args) -> int:
    return _print_http("GET", f"http://{args.controller}/tenants")


def cmd_delete_tenant(args) -> int:
    return _print_http("DELETE", f"http://{args.controller}/tenants/"
                       f"{args.name}?type={args.role.lower()}")


def cmd_rebalance_table(args) -> int:
    out = _http("POST",
                f"http://{args.controller}/tables/{args.table}/rebalance"
                f"?dryRun={'true' if args.dry_run else 'false'}"
                f"&downtime={'true' if args.downtime else 'false'}")
    print(json.dumps(out, indent=2))
    return 0


def cmd_delete_segment(args) -> int:
    out = _http("DELETE",
                f"http://{args.controller}/segments/{args.table}/"
                f"{args.segment}")
    print(json.dumps(out))
    return 0


def cmd_delete_table(args) -> int:
    """Parity: DeleteTableCommand → DELETE /tables/{name}."""
    return _print_http("DELETE",
                       f"http://{args.controller}/tables/{args.table}")


def cmd_backfill_segment(args) -> int:
    """Parity: the backfill tooling — download a served segment's
    artifact from the deep store, optionally point at a replacement
    directory, and re-push it (a refresh bounce reloads it on servers).
    With no --segment-dir this re-pushes the deep-store copy as-is
    (useful to heal a corrupted local replica)."""
    import tempfile as _tempfile
    import urllib.parse as _p
    import urllib.request as _req

    from pinot_tpu.common.segment_tar import (pack_segment_dir,
                                              unpack_segment_tar)
    import urllib.error as _err
    seg_dir = args.segment_dir
    if seg_dir is None:
        url = (f"http://{args.controller}/deepstore/download?"
               + _p.urlencode({"path": f"{args.table}/{args.segment}"}))
        try:
            with _req.urlopen(url, timeout=60) as r:
                blob = r.read()
        except _err.HTTPError as e:
            print(json.dumps({"status": e.code,
                              "error": e.read().decode("utf-8",
                                                       "replace")},
                             indent=2))
            return 1
        seg_dir = _tempfile.mkdtemp(prefix="backfill_")
        unpack_segment_tar(blob, seg_dir)
    return _print_http(
        "POST", f"http://{args.controller}/segments/{args.table}",
        pack_segment_dir(seg_dir),
        content_type="application/octet-stream")


def cmd_show_cluster(args) -> int:
    tables = _http("GET", f"http://{args.controller}/tables")["tables"]
    out = {}
    for t in tables:
        ev = _http("GET",
                   f"http://{args.controller}/tables/{t}/externalview")
        out[t] = ev
    print(json.dumps(out, indent=2))
    return 0


def cmd_change_num_replicas(args) -> int:
    """Parity: ChangeNumReplicasCommand — update replication in the table
    config, then rebalance to apply it."""
    cfg = _http("GET", f"http://{args.controller}/tables/{args.table}")
    cfg["segmentsConfig"]["replication"] = str(args.replicas)
    _http("PUT", f"http://{args.controller}/tables/{args.table}",
          json.dumps(cfg).encode())
    out = _http("POST",
                f"http://{args.controller}/tables/{args.table}/rebalance")
    print(json.dumps(out, indent=2))
    return 0


def cmd_verify_cluster_state(args) -> int:
    """Parity: VerifyClusterStateCommand — every table's external view must
    converge to its ideal state. Exit 0 iff converged."""
    tables = _http("GET", f"http://{args.controller}/tables")["tables"]
    bad = {}
    for t in tables:
        ideal = _http("GET",
                      f"http://{args.controller}/tables/{t}/idealstate")
        view = _http("GET",
                     f"http://{args.controller}/tables/{t}/externalview")
        if ideal != view:
            bad[t] = {"idealstate": ideal, "externalview": view}
    if bad:
        print(json.dumps({"converged": False, "tables": bad}, indent=2))
        return 1
    print(json.dumps({"converged": True, "tables": len(tables)}))
    return 0


def cmd_segment_dump(args) -> int:
    """Parity: SegmentDumpTool — print a segment's metadata and per-column
    index summary from its on-disk artifact."""
    from pinot_tpu.segment.loader import ImmutableSegmentLoader
    seg = ImmutableSegmentLoader.load(args.segment_dir)
    meta = seg.metadata
    cols = {}
    for name in seg.column_names:
        cm = seg.data_source(name).metadata
        cols[name] = {
            "dataType": cm.data_type.name,
            "cardinality": cm.cardinality,
            "singleValue": cm.single_value,
            "hasDictionary": cm.has_dictionary,
            "sorted": cm.sorted,
            "hasInvertedIndex": cm.has_inverted_index,
            "hasBloomFilter": cm.has_bloom_filter,
        }
    print(json.dumps({
        "segmentName": meta.segment_name,
        "totalDocs": meta.total_docs,
        "timeRange": [meta.start_time, meta.end_time],
        "crc": meta.crc,
        "columns": cols,
    }, indent=2))
    return 0


def _run_until_interrupt(stop) -> int:
    import time
    try:
        while True:
            time.sleep(3600)
    except KeyboardInterrupt:
        pass
    finally:
        stop()
    return 0


def cmd_start_controller(args) -> int:
    """Controller process: resource manager + store server (+ admin HTTP).

    Parity: StartControllerCommand (the store server plays ZooKeeper).
    With --store-addr the controller joins an EXTERNAL store instead —
    the HA shape where a lead and --standby peers share one durable
    store and the leader lease (TTL + fencing token) decides who runs
    the periodic tasks and the segment commit protocol."""
    from pinot_tpu.tools.distributed import DistributedController
    store_addr = None
    if args.store_addr:
        host, port = args.store_addr.rsplit(":", 1)
        store_addr = (host, int(port))
    ctrl = DistributedController(args.dir, store_port=args.store_port,
                                 http=True, periodic=True,
                                 store_addr=store_addr,
                                 standby=args.standby,
                                 instance_id=args.instance_id,
                                 lease_s=args.lease_s)
    print(json.dumps({"storePort": ctrl.store_port,
                      "httpPort": ctrl.http_port,
                      "deepStore": ctrl.deep_store_dir,
                      "instanceId": ctrl.instance_id,
                      "standby": ctrl.standby}), flush=True)
    return _run_until_interrupt(ctrl.stop)


def cmd_start_store(args) -> int:
    """Standalone durable store server — the ZooKeeper role for HA
    controller deployments (the store must outlive any one controller)."""
    from pinot_tpu.tools.distributed import StandaloneStore
    store = StandaloneStore(args.dir, port=args.store_port)
    print(json.dumps({"storePort": store.port}), flush=True)
    return _run_until_interrupt(store.stop)


def cmd_start_server(args) -> int:
    """Server process joined to the cluster through the remote store.

    Parity: StartServerCommand. SIGTERM triggers the graceful DRAIN
    path (seal consuming segments, deregister, finish in-flight work,
    then exit) — a planned restart costs zero query errors; only
    kill -9 exercises the self-healing chaos path."""
    import signal
    import threading

    from pinot_tpu.tools.distributed import DistributedServer
    host, port = args.store.rsplit(":", 1)
    srv = DistributedServer(args.instance_id, host, int(port),
                            args.deep_store, work_dir=args.dir,
                            port=args.port, scheduler=args.scheduler,
                            controller_http=args.controller_http)
    # the boot line says what this server runs on (and takes the device
    # now: a server that cannot reach its chip fails here, not mid-query)
    from pinot_tpu.utils.device import device_report
    boot = {"instanceId": args.instance_id, "queryPort": srv.port,
            "device": device_report()}
    api = None
    if args.admin_port is not None:
        from pinot_tpu.server.http_api import ServerApiServer
        api = ServerApiServer(srv.server)
        boot["adminPort"] = api.start(port=args.admin_port)
    print(json.dumps(boot), flush=True)

    done = {"drained": False}
    drain_lock = threading.Lock()

    def shutdown(drain: bool = False) -> bool:
        """Returns whether THIS call performed the shutdown (the flag
        is claimed before the long drain, outside the lock, so a
        repeated signal returns immediately instead of re-entering)."""
        with drain_lock:
            if done["drained"]:
                return False
            done["drained"] = True
        if api is not None:
            api.stop()
        if drain:
            srv.drain()
        else:
            srv.stop()
        return True

    def on_sigterm(_sig, _frame):
        if not shutdown(drain=True):
            # repeated SIGTERM while the drain runs in the interrupted
            # frame below: ignore — raising here would abort the seal
            # mid-commit (supervisors escalate to SIGKILL on their own)
            return
        raise SystemExit(0)

    signal.signal(signal.SIGTERM, on_sigterm)
    return _run_until_interrupt(shutdown)


def cmd_start_broker(args) -> int:
    """Broker process: spectator + HTTP /query endpoint.

    Parity: StartBrokerCommand."""
    from pinot_tpu.tools.distributed import DistributedBroker
    host, port = args.store.rsplit(":", 1)
    broker = DistributedBroker(host, int(port), args.deep_store, http=True)
    print(json.dumps({"httpPort": broker.http_port}), flush=True)
    return _run_until_interrupt(broker.stop)


def cmd_start_minion(args) -> int:
    """Minion process: task executor polling the cluster task queue.

    Parity: StartMinionCommand. SIGTERM finishes the in-flight task
    then exits; kill -9 mid-swap exercises the intent-log recovery
    path (the task queue requeues the lease, the swap protocol resumes
    or rolls back from the logged intent)."""
    import signal

    from pinot_tpu.tools.distributed import DistributedMinion
    host, port = args.store.rsplit(":", 1)
    minion = DistributedMinion(args.instance_id, host, int(port),
                               args.deep_store, work_dir=args.dir)
    print(json.dumps({"instanceId": args.instance_id}), flush=True)

    def on_sigterm(_sig, _frame):
        minion.stop()
        raise SystemExit(0)

    signal.signal(signal.SIGTERM, on_sigterm)
    return _run_until_interrupt(minion.stop)


def cmd_quickstart(args) -> int:
    """Boot an embedded cluster with demo data and run sample queries.

    Parity: tools/Quickstart.java (offline baseballStats quickstart).
    """
    import os
    import tempfile

    from pinot_tpu.common.table_config import TableConfig
    from pinot_tpu.segment.creator import SegmentCreator
    from pinot_tpu.tools.cluster import EmbeddedCluster

    work = args.dir or tempfile.mkdtemp(prefix="pinot_tpu_quickstart_")
    schema = _demo_schema()
    config = TableConfig("baseballStats")
    cluster = EmbeddedCluster(work, num_servers=2, tcp=True, http=True)
    cluster.add_schema(schema)
    cluster.add_table(config)
    for i in range(2):
        rows = _demo_rows(args.rows, seed=7 + i, year_lo=1990,
                          year_hi=2020)
        d = os.path.join(work, f"quickstart_{i}")
        SegmentCreator(schema, config,
                       segment_name=f"quickstart_{i}").build(rows, d)
        cluster.upload_segment("baseballStats_OFFLINE", d)
    print(f"Controller REST: http://127.0.0.1:{cluster.controller_port}")
    print(f"Broker query:    http://127.0.0.1:{cluster.broker_port}/query")
    _run_samples(cluster, (
        "SELECT COUNT(*) FROM baseballStats",
        "SELECT SUM(runs) FROM baseballStats WHERE league = 'AL'",
        "SELECT SUM(hits), COUNT(*) FROM baseballStats "
        "GROUP BY teamID TOP 5"))
    return _hold_or_stop(cluster, args.exit_after)


def _demo_schema():
    from pinot_tpu.common.datatype import DataType
    from pinot_tpu.common.schema import (Schema, TimeUnit, dimension,
                                         metric, time_field)
    return Schema("baseballStats", [
        dimension("playerName", DataType.STRING),
        dimension("teamID", DataType.STRING),
        dimension("league", DataType.STRING),
        metric("runs", DataType.INT),
        metric("hits", DataType.LONG),
        # a real TIME field: segments record start/end times and the
        # hybrid quickstart's broker computes a true time boundary
        time_field("yearID", DataType.INT, TimeUnit.DAYS),
    ])


def _demo_rows(n: int, seed: int, year_lo: int, year_hi: int):
    import numpy as np
    rng = np.random.default_rng(seed)
    return [{
        "playerName": f"player{int(j):04d}",
        "teamID": f"T{int(t):02d}",
        "league": ("AL", "NL")[int(lg)],
        "runs": int(r), "hits": int(h), "yearID": int(y),
    } for j, t, lg, r, h, y in zip(
        rng.integers(0, 500, n), rng.integers(0, 30, n),
        rng.integers(0, 2, n), rng.integers(0, 150, n),
        rng.integers(0, 250, n), rng.integers(year_lo, year_hi, n))]


def _wait_count(cluster, expect: int, timeout_s: float = 60.0) -> int:
    import time
    deadline = time.monotonic() + timeout_s
    got = -1
    while time.monotonic() < deadline:
        resp = cluster.query("SELECT COUNT(*) FROM baseballStats")
        if not resp.exceptions:
            got = int(resp.aggregation_results[0].value)
            if got >= expect:
                break
        time.sleep(0.1)
    return got


def _run_samples(cluster, queries) -> None:
    for q in queries:
        resp = cluster.query(q)
        print(f"\n> {q}")
        print(json.dumps(resp.to_json(), indent=2)[:800])


def _hold_or_stop(cluster, exit_after: bool) -> int:
    if exit_after:
        cluster.stop()
        return 0
    print("\nquickstart cluster running — Ctrl-C to stop")
    try:
        import time
        while True:
            time.sleep(3600)
    except KeyboardInterrupt:
        cluster.stop()
    return 0


def _realtime_table_config(factory_name: str, topic: str, flush_rows: int):
    from pinot_tpu.common.table_config import (IndexingConfig,
                                               SegmentsConfig, TableConfig,
                                               TableType)
    idx = IndexingConfig(stream_configs={
        "stream.factory.name": factory_name,
        "stream.topic.name": topic,
        "realtime.segment.flush.threshold.size": str(flush_rows),
        "realtime.segment.flush.threshold.time.ms": "600000000",
    })
    return TableConfig("baseballStats", table_type=TableType.REALTIME,
                       indexing_config=idx,
                       segments_config=SegmentsConfig(
                           replication=1, time_column_name="yearID"))


def cmd_realtime_quickstart(args) -> int:
    """Embedded cluster consuming a live in-process stream.

    Parity: tools/RealtimeQuickStart.java (meetup-RSVP → Kafka demo) —
    here rows stream through the in-memory log into LLC consumers and
    are queryable mid-consumption, before any segment commits.
    """
    import tempfile

    from pinot_tpu.realtime import registry
    from pinot_tpu.realtime.stream import (MemoryStream,
                                           MemoryStreamConsumerFactory)
    from pinot_tpu.tools.cluster import EmbeddedCluster

    work = args.dir or tempfile.mkdtemp(prefix="pinot_tpu_rt_quickstart_")
    stream = MemoryStream("events", num_partitions=2)
    registry.register_stream_factory(
        "quickstart_mem", MemoryStreamConsumerFactory(stream,
                                                      batch_size=200))
    cluster = EmbeddedCluster(work, num_servers=2, tcp=True, http=True)
    cluster.add_schema(_demo_schema())
    cluster.add_table(_realtime_table_config(
        "quickstart_mem", "events", flush_rows=max(args.rows // 3, 100)))
    for row in _demo_rows(args.rows, seed=11, year_lo=2015, year_hi=2026):
        stream.publish(row)
    got = _wait_count(cluster, args.rows)
    if got < args.rows:
        print(f"ERROR: consumed only {got}/{args.rows} rows before the "
              "timeout", file=sys.stderr)
        cluster.stop()
        return 1
    print(f"consumed {got}/{args.rows} rows "
          f"(some segments already committed, the tail is CONSUMING)")
    print(f"Controller REST: http://127.0.0.1:{cluster.controller_port}")
    print(f"Broker query:    http://127.0.0.1:{cluster.broker_port}/query")
    _run_samples(cluster, (
        "SELECT COUNT(*) FROM baseballStats",
        "SELECT SUM(runs) FROM baseballStats WHERE yearID >= 2020",
        "SELECT COUNT(*) FROM baseballStats GROUP BY league TOP 5"))
    return _hold_or_stop(cluster, args.exit_after)


def cmd_hybrid_quickstart(args) -> int:
    """Embedded HYBRID cluster: an offline table with historical segments
    plus a realtime table consuming recent rows; the broker splits
    queries at the time boundary and merges both sides.

    Parity: tools/HybridQuickstart.java.
    """
    import os
    import tempfile

    from pinot_tpu.common.table_config import SegmentsConfig, TableConfig
    from pinot_tpu.realtime import registry
    from pinot_tpu.realtime.stream import (MemoryStream,
                                           MemoryStreamConsumerFactory)
    from pinot_tpu.segment.creator import SegmentCreator
    from pinot_tpu.tools.cluster import EmbeddedCluster

    work = args.dir or tempfile.mkdtemp(prefix="pinot_tpu_hy_quickstart_")
    schema = _demo_schema()
    cluster = EmbeddedCluster(work, num_servers=2, tcp=True, http=True)
    cluster.add_schema(schema)
    # offline side: historical years
    cluster.add_table(TableConfig(
        "baseballStats",
        segments_config=SegmentsConfig(replication=1,
                                       time_column_name="yearID")))
    n_off = args.rows
    rows_off = _demo_rows(n_off, seed=5, year_lo=1990, year_hi=2015)
    d = os.path.join(work, "hybrid_offline_0")
    SegmentCreator(schema, None, segment_name="hybrid_offline_0"
                   ).build(rows_off, d)
    cluster.upload_segment("baseballStats_OFFLINE", d)
    # realtime side: recent years streaming in, OVERLAPPING the last
    # offline year — the broker's time boundary (max offline end time
    # minus one day) serves each row from exactly one side (offline
    # <= boundary, realtime > boundary), so the overlap never double
    # counts (HelixExternalViewBasedTimeBoundaryService parity)
    stream = MemoryStream("events", num_partitions=2)
    registry.register_stream_factory(
        "quickstart_mem_hy", MemoryStreamConsumerFactory(stream,
                                                         batch_size=200))
    cluster.add_table(_realtime_table_config(
        "quickstart_mem_hy", "events", flush_rows=10 ** 9))
    n_rt = max(args.rows // 2, 100)
    rows_rt = _demo_rows(n_rt, seed=6, year_lo=2013, year_hi=2026)
    for row in rows_rt:
        stream.publish(row)
    boundary = max(r["yearID"] for r in rows_off) - 1
    expected = sum(1 for r in rows_off if r["yearID"] <= boundary) + \
        sum(1 for r in rows_rt if r["yearID"] > boundary)
    got = _wait_count(cluster, expected)
    if got != expected:
        print(f"ERROR: hybrid table serving {got} rows, expected "
              f"{expected} before the timeout", file=sys.stderr)
        cluster.stop()
        return 1
    print(f"hybrid table serving {got} rows "
          f"({n_off} offline + {n_rt} realtime, overlapping years "
          f"deduplicated at the time boundary {boundary})")
    _run_samples(cluster, (
        "SELECT COUNT(*) FROM baseballStats",
        "SELECT MIN(yearID), MAX(yearID) FROM baseballStats",
        "SELECT SUM(hits) FROM baseballStats WHERE yearID >= 2010"))
    return _hold_or_stop(cluster, args.exit_after)


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(prog="pinot-tpu-admin",
                                description=__doc__)
    sub = p.add_subparsers(dest="command", required=True)

    def ctrl(sp):
        sp.add_argument("--controller", default="127.0.0.1:9000")

    sp = sub.add_parser("AddSchema", help="upload a schema JSON")
    ctrl(sp)
    sp.add_argument("--schema-file", required=True)
    sp.set_defaults(fn=cmd_add_schema)

    sp = sub.add_parser("AddTable", help="create a table from config JSON")
    ctrl(sp)
    sp.add_argument("--table-config-file", required=True)
    sp.set_defaults(fn=cmd_add_table)

    sp = sub.add_parser("CreateSegment",
                        help="build a segment from CSV/JSON input")
    sp.add_argument("--input", required=True)
    sp.add_argument("--format", default="csv",
                    choices=["csv", "json", "avro", "parquet", "orc"])
    sp.add_argument("--schema-file", required=True)
    sp.add_argument("--table-config-file")
    sp.add_argument("--out-dir", required=True)
    sp.add_argument("--segment-name")
    sp.set_defaults(fn=cmd_create_segment)

    sp = sub.add_parser("UploadSegment", help="push a segment dir")
    ctrl(sp)
    sp.add_argument("--table", required=True)
    sp.add_argument("--segment-dir", required=True)
    sp.set_defaults(fn=cmd_upload_segment)

    sp = sub.add_parser("PostQuery", help="run a PQL query via broker")
    sp.add_argument("--broker", default="127.0.0.1:8099")
    sp.add_argument("--query", required=True)
    sp.set_defaults(fn=cmd_post_query)

    sp = sub.add_parser("StarTreeIndexViewer",
                        help="dump a segment's star-tree cubes")
    sp.add_argument("--segment-dir", required=True)
    sp.set_defaults(fn=cmd_startree_viewer)

    sp = sub.add_parser("RealtimeProvisioningHelper",
                        help="estimate consuming-memory per host")
    sp.add_argument("--sample-segment", required=True,
                    help="a completed segment dir to measure bytes/row")
    sp.add_argument("--rows-per-hour", type=int, required=True)
    sp.add_argument("--num-partitions", type=int, default=1)
    sp.add_argument("--replication", type=int, default=1)
    sp.add_argument("--retention-hours", type=int, default=72)
    sp.add_argument("--num-hosts", default="2,4,6,8")
    sp.add_argument("--num-hours", default="2,4,6,8,10,12")
    sp.set_defaults(fn=cmd_realtime_provisioning)

    sp = sub.add_parser("QueryRunner",
                        help="replay a query file; latency/QPS report")
    sp.add_argument("--broker", default="127.0.0.1:8099")
    sp.add_argument("--query-file", required=True)
    sp.add_argument("--mode", default="singleThread",
                    choices=["singleThread", "multiThreads", "targetQPS",
                             "increasingQPS"])
    sp.add_argument("--num-times", type=int, default=1)
    sp.add_argument("--num-threads", type=int, default=8)
    sp.add_argument("--qps", type=float, default=10.0)
    sp.add_argument("--duration", type=float, default=10.0,
                    help="seconds per (step-)run in the QPS modes")
    sp.add_argument("--step-qps", type=float, default=10.0)
    sp.add_argument("--steps", type=int, default=3)
    sp.set_defaults(fn=cmd_query_runner)

    sp = sub.add_parser("AddTenant",
                        help="tag instances as a server/broker tenant")
    ctrl(sp)
    sp.add_argument("--name", required=True)
    sp.add_argument("--role", default="SERVER",
                    choices=["SERVER", "BROKER", "server", "broker"])
    sp.add_argument("--instances", nargs="+", required=True)
    sp.set_defaults(fn=cmd_add_tenant)

    sp = sub.add_parser("ListTenants", help="list tenants")
    ctrl(sp)
    sp.set_defaults(fn=cmd_list_tenants)

    sp = sub.add_parser("DeleteTenant", help="untag a tenant")
    ctrl(sp)
    sp.add_argument("--name", required=True)
    sp.add_argument("--role", default="SERVER",
                    choices=["SERVER", "BROKER", "server", "broker"])
    sp.set_defaults(fn=cmd_delete_tenant)

    sp = sub.add_parser("RebalanceTable", help="rebalance segments")
    ctrl(sp)
    sp.add_argument("--table", required=True)
    sp.add_argument("--dry-run", action="store_true")
    sp.add_argument("--downtime", action="store_true",
                    help="one-shot write instead of no-downtime stepping")
    sp.set_defaults(fn=cmd_rebalance_table)

    sp = sub.add_parser("DeleteTable", help="drop a table")
    ctrl(sp)
    sp.add_argument("--table", required=True)
    sp.set_defaults(fn=cmd_delete_table)

    sp = sub.add_parser("BackfillSegment",
                        help="re-push a segment (from deep store or a "
                             "local replacement dir)")
    ctrl(sp)
    sp.add_argument("--table", required=True)
    sp.add_argument("--segment", required=True)
    sp.add_argument("--segment-dir", default=None)
    sp.set_defaults(fn=cmd_backfill_segment)

    sp = sub.add_parser("DeleteSegment", help="delete one segment")
    ctrl(sp)
    sp.add_argument("--table", required=True)
    sp.add_argument("--segment", required=True)
    sp.set_defaults(fn=cmd_delete_segment)

    sp = sub.add_parser("ShowCluster", help="tables + external views")
    ctrl(sp)
    sp.set_defaults(fn=cmd_show_cluster)

    sp = sub.add_parser("ChangeNumReplicas",
                        help="update replication + rebalance")
    ctrl(sp)
    sp.add_argument("--table", required=True)
    sp.add_argument("--replicas", type=int, required=True)
    sp.set_defaults(fn=cmd_change_num_replicas)

    sp = sub.add_parser("VerifyClusterState",
                        help="check external views converged to ideal")
    ctrl(sp)
    sp.set_defaults(fn=cmd_verify_cluster_state)

    sp = sub.add_parser("SegmentDump",
                        help="print a segment artifact's metadata")
    sp.add_argument("--segment-dir", required=True)
    sp.set_defaults(fn=cmd_segment_dump)

    sp = sub.add_parser("StartController",
                        help="run a controller (+ store server + REST)")
    sp.add_argument("--dir", required=True,
                    help="work dir (deep store lives under it)")
    sp.add_argument("--store-port", type=int, default=2181)
    sp.add_argument("--store-addr",
                    help="host:port of an EXTERNAL store server (HA "
                         "shape: lease-elected lead + standbys; this "
                         "controller hosts no store of its own)")
    sp.add_argument("--standby", action="store_true",
                    help="hot standby: takes over the lead role (and "
                         "its periodic tasks + commit protocol) when "
                         "the current lease expires")
    sp.add_argument("--instance-id")
    sp.add_argument("--lease-s", type=float,
                    help="leader-lease TTL override")
    sp.set_defaults(fn=cmd_start_controller)

    sp = sub.add_parser("StartStore",
                        help="run a standalone durable store server "
                             "(the ZooKeeper role for HA controllers)")
    sp.add_argument("--dir", required=True)
    sp.add_argument("--store-port", type=int, default=2181)
    sp.set_defaults(fn=cmd_start_store)

    sp = sub.add_parser("StartServer",
                        help="run a query server joined via the store")
    sp.add_argument("--store", default="127.0.0.1:2181",
                    help="controller's store host:port")
    sp.add_argument("--deep-store", required=True,
                    help="shared deep-store path")
    sp.add_argument("--instance-id", default="Server_0")
    sp.add_argument("--port", type=int, default=0,
                    help="query service port (0 = ephemeral)")
    sp.add_argument("--scheduler", default="fcfs",
                    choices=["fcfs", "bounded_fcfs", "tokenbucket"])
    sp.add_argument("--dir", help="realtime work dir")
    sp.add_argument("--controller-http",
                    help="controller REST host:port (enables realtime "
                         "tables: LLC completion over HTTP)")
    sp.add_argument("--admin-port", type=int,
                    help="start the admin/debug HTTP API on this port "
                         "(0 = ephemeral; omitted = disabled)")
    sp.set_defaults(fn=cmd_start_server)

    sp = sub.add_parser("StartBroker",
                        help="run a broker with an HTTP /query endpoint")
    sp.add_argument("--store", default="127.0.0.1:2181")
    sp.add_argument("--deep-store", required=True)
    sp.set_defaults(fn=cmd_start_broker)

    sp = sub.add_parser("StartMinion",
                        help="run a minion task executor joined via "
                             "the store")
    sp.add_argument("--store", default="127.0.0.1:2181")
    sp.add_argument("--deep-store", required=True)
    sp.add_argument("--instance-id", default="Minion_0")
    sp.add_argument("--dir", help="task work dir")
    sp.set_defaults(fn=cmd_start_minion)

    sp = sub.add_parser("Quickstart",
                        help="embedded demo cluster with sample data")
    sp.add_argument("--rows", type=int, default=10_000)
    sp.add_argument("--dir")
    sp.add_argument("--exit-after", action="store_true",
                    help="stop the cluster after the sample queries")
    sp.set_defaults(fn=cmd_quickstart)

    for name, fn, default_rows in (
            ("RealtimeQuickstart", cmd_realtime_quickstart, 3000),
            ("HybridQuickstart", cmd_hybrid_quickstart, 5000)):
        sp = sub.add_parser(name, help=f"embedded {name.lower()} demo")
        sp.add_argument("--rows", type=int, default=default_rows)
        sp.add_argument("--dir")
        sp.add_argument("--exit-after", action="store_true")
        sp.set_defaults(fn=fn)
    return p


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":
    raise SystemExit(main())
