"""Cluster harnesses: embedded (one process) and multi-process.

Parity: the reference's ClusterTest harness (pinot-integration-tests/.../
ClusterTest.java:85 — real Controller/Broker/Server instances in one JVM)
and the Quickstart wiring (tools/Quickstart.java:125-144). The full
production plumbing runs: property store, state transitions, deep store,
scatter-gather (in-process or TCP), broker reduce.

Membership churn is programmable — ``add_server()`` / ``remove_server()``
/ ``drain_server()`` — so chaos suites and scale-out benchmarks can grow,
kill and drain servers mid-workload (the ClusterTest analogue of the
reference's ChaosMonkey-style integration tests).

`MultiprocCluster` is the production shape: every plane its own OS
process via the admin CLI (StartStore / StartController / StartServer /
StartBroker / StartMinion), with chaos verbs that act on REAL processes
— ``kill_server`` is SIGKILL, ``drain_server`` is SIGTERM into the
admin CLI's drain handler, ``fail_controller`` SIGKILLs the ACTIVE
lead so the standby's lease takeover is what recovery measures, and
``net_latency``/``net_drop`` arm FaultInjectingTransport windows inside
the broker processes over their /debug/faults endpoints. It implements
the `common/chaos.py` adapter surface (verbs + ``targets`` +
``clear_fault`` + ``recovery_probe``), so a ChaosCoordinator drives it
directly.
"""
from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import time
import urllib.request
from typing import Dict, List, Optional

from pinot_tpu.broker.cluster_watcher import BrokerClusterWatcher
from pinot_tpu.broker.request_handler import (BrokerRequestHandler,
                                              InProcessTransport,
                                              TcpTransport)
from pinot_tpu.common.response import BrokerResponse
from pinot_tpu.common.schema import Schema
from pinot_tpu.common.table_config import TableConfig
from pinot_tpu.controller.controller import Controller
from pinot_tpu.server.instance import ServerInstance
from pinot_tpu.server.participant import ServerParticipant


class EmbeddedCluster:
    """controller + num_servers query servers + one broker."""

    def __init__(self, work_dir: str, num_servers: int = 2,
                 tcp: bool = False, mesh=None, scheduler: str = "fcfs",
                 http: bool = False, store_dir: str = None,
                 server_max_pending: int = None,
                 cache_freshness_ms: float = None):
        """`store_dir`: persist cluster state (property-store WAL +
        snapshots) under this directory — a cluster rebuilt over the
        same work_dir/store_dir recovers its tables and segments."""
        from pinot_tpu.broker.quota import QueryQuotaManager
        self.work_dir = work_dir
        self._tcp = tcp
        self._mesh = mesh
        self._scheduler = scheduler
        self._http = http
        self._server_max_pending = server_max_pending
        self.controller = Controller(os.path.join(work_dir, "deepstore"),
                                     store_dir=store_dir)
        self.servers: Dict[str, ServerInstance] = {}
        self.participants: Dict[str, ServerParticipant] = {}
        if tcp:
            self.transport = TcpTransport({})
        else:
            # InProcessTransport shares the live server dict, so
            # add_server/remove_server mutate its view too
            self.transport = InProcessTransport(self.servers)
        # ONE quota manager shared by the watcher (which converges
        # table-config quotas into it) and the broker (which enforces)
        self.quota = QueryQuotaManager()
        self.watcher = BrokerClusterWatcher(self.controller.coordinator,
                                            self.controller.manager,
                                            quota=self.quota)
        self.broker = BrokerRequestHandler(
            self.watcher.routing, self.transport,
            time_boundary=self.watcher.time_boundary,
            quota=self.quota,
            segment_pruner=self.watcher.partition_pruner,
            cache_freshness_ms=cache_freshness_ms)
        # segment lifecycle (upload/replace/drop) flushes the broker
        # result cache — the freshness bound only covers consuming-
        # ingestion staleness, not an offline backfill
        self.watcher.register_result_cache(self.broker.result_cache)
        # a deregistered server's breaker/health state drops in the
        # same watch event as its live record
        self.watcher.attach_fault_tolerance(self.broker.fault_tolerance)
        self.broker_api = None
        self.controller_api = None
        self.server_apis: Dict[str, object] = {}
        self.broker_port: Optional[int] = None
        self.controller_port: Optional[int] = None
        self.server_http_ports: Dict[str, int] = {}
        for i in range(num_servers):
            self.add_server(f"Server_{i}")
        if http:
            from pinot_tpu.broker.http_api import BrokerApiServer
            from pinot_tpu.controller.http_api import ControllerApiServer
            self.broker_api = BrokerApiServer(self.broker)
            self.broker_port = self.broker_api.start()
            self.controller_api = ControllerApiServer(self.controller)
            self.controller_port = self.controller_api.start()

    # -- membership churn ---------------------------------------------------
    def add_server(self, name: Optional[str] = None) -> str:
        """Start a new query server, join it to the cluster (live
        record + state transitions), and wire it into the broker's
        data plane. Returns its instance id."""
        if name is None:
            i = len(self.servers)
            while f"Server_{i}" in self.servers:
                i += 1
            name = f"Server_{i}"
        if name in self.servers:
            raise ValueError(f"server {name} already exists")
        server = ServerInstance(name, scheduler=self._scheduler,
                                mesh=self._mesh,
                                max_pending=self._server_max_pending)
        participant = ServerParticipant(
            server, self.controller.manager,
            completion=self.controller.realtime,
            work_dir=os.path.join(self.work_dir, "server_work", name))
        self.servers[name] = server
        self.participants[name] = participant
        if self._tcp:
            port = server.start(port=0)
            self.transport.set_endpoint(name, "127.0.0.1", port)
        # registration LAST: the reconcile it triggers may immediately
        # assign segments / consuming partitions to the new server
        self.controller.coordinator.register_participant(name, participant)
        if self._http:
            from pinot_tpu.server.http_api import ServerApiServer
            api = ServerApiServer(server)
            self.server_apis[name] = api
            self.server_http_ports[name] = api.start()
        return name

    def remove_server(self, name: str) -> None:
        """Abrupt death (the embedded analogue of kill -9 / session
        expiry): the live record and current states vanish with no
        drain and no seal — the self-healing plane must repair."""
        server = self.servers.pop(name)
        participant = self.participants.pop(name)
        # ephemeral-loss first: views, routing, broker ft state all
        # react to the membership event while the "process" disappears
        self.controller.coordinator.deregister_participant(name)
        participant.shutdown()
        server.stop()
        api = self.server_apis.pop(name, None)
        if api is not None:
            api.stop()
        self.server_http_ports.pop(name, None)

    def drain_server(self, name: str, seal_timeout_s: float = 20.0,
                     settle_s: float = 0.3) -> bool:
        """Planned departure: seal consuming segments where possible,
        deregister (brokers reroute on the watch event), let in-flight
        work finish, then stop — zero query errors by construction.
        Returns whether every sealable consumer actually sealed."""
        import time
        server = self.servers[name]
        participant = self.participants[name]
        sealed = participant.seal_consuming(seal_timeout_s)
        self.controller.coordinator.deregister_participant(name)
        # the embedded watch chain is synchronous, but the broker's
        # in-flight scatters are not: hold the FULL settle window. A
        # depth()==0 early exit raced queries already scattered but not
        # yet admitted (in transit they hold no admission slot), so the
        # stop below turned them into execution errors on a loaded box.
        deadline = time.monotonic() + max(settle_s, 0.05)
        while time.monotonic() < deadline:
            time.sleep(0.02)
        while server.admission.depth() > 0 and \
                time.monotonic() < deadline + seal_timeout_s:
            time.sleep(0.02)
        # only NOW leave the transport's server map: the seal and the
        # settle window above still serve queries, and the in-process
        # transport shares self.servers — popping first turned routed
        # dispatches into KeyErrors during the seal
        self.servers.pop(name, None)
        self.participants.pop(name, None)
        participant.shutdown()
        server.stop()
        api = self.server_apis.pop(name, None)
        if api is not None:
            api.stop()
        self.server_http_ports.pop(name, None)
        return sealed

    # -- admin facade (parity: controller REST) ----------------------------
    def add_schema(self, schema: Schema) -> None:
        self.controller.manager.add_schema(schema)

    def add_table(self, config: TableConfig, **kw) -> str:
        from pinot_tpu.common.table_config import TableType
        if config.table_type == TableType.REALTIME:
            return self.controller.realtime.setup_table(config, **kw)
        return self.controller.manager.add_table(config, **kw)

    def upload_segment(self, table: str, segment_dir: str) -> str:
        return self.controller.manager.add_segment(table, segment_dir)

    def query(self, pql: str) -> BrokerResponse:
        return self.broker.handle(pql)

    def stop(self) -> None:
        if self.broker_api is not None:
            self.broker_api.stop()
        if self.controller_api is not None:
            self.controller_api.stop()
        for api in self.server_apis.values():
            api.stop()
        self.controller.stop()
        self.watcher.close()
        self.broker.close()
        for participant in self.participants.values():
            participant.shutdown()
        for server in self.servers.values():
            server.stop()


# ---------------------------------------------------------------------------
# multi-process cluster + chaos verbs
# ---------------------------------------------------------------------------

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _http_json(method: str, url: str, body: Optional[bytes] = None,
               ctype: str = "application/json", timeout: float = 60.0):
    req = urllib.request.Request(
        url, data=body, method=method,
        headers={"Content-Type": ctype} if body else {})
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return json.loads(resp.read())


class MultiprocCluster:
    """The production process shape, drivable by a ChaosCoordinator.

    Topology: one StandaloneStore (the ZK role, outliving every
    controller), a lead + optional standby controller joined to it
    (``ha=True``), ``num_servers`` query servers with admin APIs,
    ``num_brokers`` HTTP brokers, and optionally one minion. Every
    component is its own OS process spawned through the admin CLI, so
    the chaos verbs below are real signals against real pids.

    ``broker_faults=True`` starts brokers with
    PINOT_TPU_BROKER_FAULTS=1: their data plane runs through a
    FaultInjectingTransport whose arm/clear surface is the broker's
    /debug/faults endpoints — that is how ``net_latency`` / ``net_drop``
    windows reach inside a real broker process.
    """

    def __init__(self, base: str, num_brokers: int = 1,
                 num_servers: int = 2, ha: bool = False,
                 minion: bool = False, lease_s: float = 2.0,
                 broker_faults: bool = False,
                 env: Optional[dict] = None):
        self.base = base
        self.ha = ha
        self.lease_s = lease_s
        self.broker_faults = broker_faults
        self._env = dict(os.environ, PYTHONPATH=_REPO)
        if env:
            self._env.update(env)
        os.makedirs(os.path.join(base, "logs"), exist_ok=True)
        self._procs: Dict[str, subprocess.Popen] = {}
        self.controllers: Dict[str, dict] = {}    # id -> {httpPort}
        self.server_admin_ports: Dict[str, int] = {}
        self.server_boots: Dict[str, dict] = {}   # id -> boot line
        self.broker_ports: List[int] = []
        self.minion_ids: List[str] = []
        self._store_client = None

        if ha:
            boot = self._spawn("store", "StartStore",
                               "--dir", os.path.join(base, "storehost"),
                               "--store-port", "0")
            self.store_port = boot["storePort"]
            store_addr = f"127.0.0.1:{self.store_port}"
            lead = self._spawn(
                "controller:Controller_lead", "StartController",
                "--dir", os.path.join(base, "controller"),
                "--store-addr", store_addr,
                "--instance-id", "Controller_lead",
                "--lease-s", str(lease_s))
            self.deep_store = lead["deepStore"]
            self.controllers["Controller_lead"] = \
                {"httpPort": lead["httpPort"]}
            standby = self._spawn(
                "controller:Controller_standby", "StartController",
                "--dir", os.path.join(base, "controller"),
                "--store-addr", store_addr,
                "--instance-id", "Controller_standby", "--standby",
                "--lease-s", str(lease_s))
            self.controllers["Controller_standby"] = \
                {"httpPort": standby["httpPort"]}
        else:
            ctrl = self._spawn("controller:Controller_0",
                               "StartController",
                               "--dir", os.path.join(base, "controller"),
                               "--store-port", "0")
            self.store_port = ctrl["storePort"]
            self.deep_store = ctrl["deepStore"]
            self.controllers["Controller_0"] = \
                {"httpPort": ctrl["httpPort"]}
        self._store_addr = f"127.0.0.1:{self.store_port}"

        for i in range(num_servers):
            self.start_server(f"Server_{i}")
        for _ in range(num_brokers):
            self._start_broker()
        if minion:
            self.start_minion("Minion_0")

    # -- process plumbing --------------------------------------------------
    def _spawn(self, name: str, *cmd: str) -> dict:
        log = open(os.path.join(self.base, "logs",
                                f"{name.replace(':', '_')}.log"), "ab")
        p = subprocess.Popen(
            [sys.executable, "-m", "pinot_tpu.tools.admin", *cmd],
            stdout=subprocess.PIPE, stderr=log, env=self._env,
            cwd=_REPO, text=True)
        log.close()
        self._procs[name] = p
        line = p.stdout.readline().strip()
        if not line:
            raise RuntimeError(
                f"process {name} died on boot (see "
                f"{self.base}/logs/{name.replace(':', '_')}.log)")
        return json.loads(line)

    def _reap(self, name: str, sig: Optional[int] = None,
              wait_s: float = 0.0) -> None:
        p = self._procs.get(name)
        if p is None:
            return
        if sig is not None and p.poll() is None:
            p.send_signal(sig)
        if wait_s:
            try:
                p.wait(timeout=wait_s)
            except subprocess.TimeoutExpired:
                pass

    def store(self):
        """Store client for the driver process (lazy; the standalone
        store outlives controller failovers, so one client serves the
        whole run)."""
        if self._store_client is None:
            from pinot_tpu.controller.store_client import \
                RemotePropertyStore
            self._store_client = RemotePropertyStore("127.0.0.1",
                                                     self.store_port)
        return self._store_client

    # -- admin facade ------------------------------------------------------
    def active_controller_http(self) -> Optional[str]:
        """Base URL of the ACTIVE controller. HA: the store's published
        /CONTROLLER/ENDPOINT record (written on every takeover);
        non-HA: the only controller."""
        if not self.ha:
            port = next(iter(self.controllers.values()))["httpPort"]
            return f"http://127.0.0.1:{port}"
        try:
            rec = self.store().get("/CONTROLLER/ENDPOINT")
        except Exception:  # noqa: BLE001 — store racing failover
            rec = None
        return rec["base"] if rec else None

    def active_controller_id(self) -> Optional[str]:
        base = self.active_controller_http()
        if base is None:
            return None
        port = int(base.rsplit(":", 1)[1])
        for cid, rec in self.controllers.items():
            if rec["httpPort"] == port:
                return cid
        return None

    def add_schema(self, schema) -> None:
        _http_json("POST", f"{self.active_controller_http()}/schemas",
                   json.dumps(schema.to_json()).encode())

    def add_table(self, config) -> None:
        _http_json("POST", f"{self.active_controller_http()}/tables",
                   json.dumps(config.to_json()).encode())

    def upload_segment(self, table: str, segment_dir: str) -> None:
        from pinot_tpu.common.segment_tar import pack_segment_dir
        _http_json("POST",
                   f"{self.active_controller_http()}/segments/{table}",
                   pack_segment_dir(segment_dir),
                   ctype="application/octet-stream", timeout=120)

    def query(self, pql: str, broker: int = 0, timeout: float = 30.0):
        port = self.broker_ports[broker % len(self.broker_ports)]
        return _http_json("POST", f"http://127.0.0.1:{port}/query",
                          json.dumps({"pql": pql}).encode(),
                          timeout=timeout)

    def await_ready(self, table: str, expected_rows: int,
                    timeout_s: float = 300.0) -> None:
        """Every broker serves the FULL table (views converged)."""
        deadline = time.monotonic() + timeout_s
        last = None
        pending = list(range(len(self.broker_ports)))
        while time.monotonic() < deadline and pending:
            try:
                out = self.query(f"SELECT COUNT(*) FROM {table}",
                                 broker=pending[0], timeout=10)
                last = out
                if not out.get("exceptions") and \
                        out["aggregationResults"][0]["value"] == \
                        str(expected_rows):
                    pending.pop(0)
                    continue
            except Exception as e:  # noqa: BLE001 — still booting
                last = str(e)
            time.sleep(0.3)
        if pending:
            raise RuntimeError(
                f"cluster not ready in {timeout_s}s: {last}")

    def metrics_snapshots(self) -> dict:
        out = {"brokers": {}, "servers": {}}
        for i, port in enumerate(self.broker_ports):
            try:
                out["brokers"][f"Broker_{i}"] = _http_json(
                    "GET",
                    f"http://127.0.0.1:{port}/metrics?format=json",
                    timeout=10)
            except Exception:  # noqa: BLE001 — best-effort
                pass
        for name, port in self.server_admin_ports.items():
            try:
                out["servers"][name] = _http_json(
                    "GET",
                    f"http://127.0.0.1:{port}/metrics?format=json",
                    timeout=10)
            except Exception:  # noqa: BLE001
                pass
        return out

    def health_rollups(self) -> dict:
        """GET /debug/health from every process that serves it — the
        one-scrape-per-process leak-gate poll the soak samples."""
        out: Dict[str, dict] = {}
        for i, port in enumerate(self.broker_ports):
            try:
                out[f"Broker_{i}"] = _http_json(
                    "GET", f"http://127.0.0.1:{port}/debug/health",
                    timeout=10)
            except Exception:  # noqa: BLE001
                pass
        for name, port in self.server_admin_ports.items():
            try:
                out[name] = _http_json(
                    "GET", f"http://127.0.0.1:{port}/debug/health",
                    timeout=10)
            except Exception:  # noqa: BLE001
                pass
        base = self.active_controller_http()
        if base is not None:
            try:
                out["controller"] = _http_json(
                    "GET", f"{base}/debug/health", timeout=10)
            except Exception:  # noqa: BLE001
                pass
        return out

    # -- membership / chaos verbs ------------------------------------------
    # every verb takes (target, **params) — the ChaosCoordinator calls
    # them positionally with its (possibly seeded) target choice

    def start_server(self, target: str, **params) -> str:
        boot = self._spawn(
            f"server:{target}", "StartServer",
            "--store", self._store_addr,
            "--deep-store", self.deep_store,
            "--instance-id", target,
            "--dir", os.path.join(self.base, "server_work", target),
            "--controller-http", "auto" if self.ha else
            self.active_controller_http().split("//", 1)[1],
            "--admin-port", "0")
        self.server_admin_ports[target] = boot["adminPort"]
        self.server_boots[target] = boot
        return target

    def kill_server(self, target: str, **params) -> str:
        """kill -9: no drain, no seal — the self-healing plane and the
        brokers' failover must mask it."""
        self._reap(f"server:{target}", signal.SIGKILL, wait_s=10)
        self._procs.pop(f"server:{target}", None)
        self.server_admin_ports.pop(target, None)
        return target

    def drain_server(self, target: str, **params) -> str:
        """SIGTERM: the admin CLI's graceful drain (seal consuming,
        deregister, bleed in-flight, exit). Returns immediately — the
        recovery probe watches the process actually exit."""
        self._reap(f"server:{target}", signal.SIGTERM)
        self.server_admin_ports.pop(target, None)
        return target

    def _start_broker(self) -> int:
        env_keys = {}
        if self.broker_faults:
            env_keys["PINOT_TPU_BROKER_FAULTS"] = "1"
        idx = len(self.broker_ports)
        old_env = self._env
        if env_keys:
            self._env = dict(self._env, **env_keys)
        try:
            boot = self._spawn(f"broker:{idx}", "StartBroker",
                               "--store", self._store_addr,
                               "--deep-store", self.deep_store)
        finally:
            self._env = old_env
        self.broker_ports.append(boot["httpPort"])
        return boot["httpPort"]

    def start_controller(self, target: str, standby: bool = True,
                         **params) -> str:
        """(Re)join a controller — chaos runs restart the failed lead
        as the NEW standby."""
        cmd = ["StartController",
               "--dir", os.path.join(self.base, "controller"),
               "--store-addr", self._store_addr,
               "--instance-id", target,
               "--lease-s", str(self.lease_s)]
        if standby:
            cmd.append("--standby")
        boot = self._spawn(f"controller:{target}", *cmd)
        self.controllers[target] = {"httpPort": boot["httpPort"]}
        return target

    def fail_controller(self, target: Optional[str] = None,
                        **params) -> str:
        """SIGKILL the ACTIVE lead controller (or a named one): the
        lease must expire on its TTL and the standby must take over —
        publishing the new /CONTROLLER/ENDPOINT — within the recovery
        deadline."""
        cid = target or self.active_controller_id()
        if cid is None:
            raise RuntimeError("no active controller resolvable")
        self._reap(f"controller:{cid}", signal.SIGKILL, wait_s=10)
        self._procs.pop(f"controller:{cid}", None)
        self.controllers.pop(cid, None)
        return cid

    def start_minion(self, target: str = "Minion_0", **params) -> str:
        self._spawn(f"minion:{target}", "StartMinion",
                    "--store", self._store_addr,
                    "--deep-store", self.deep_store,
                    "--instance-id", target,
                    "--dir", os.path.join(self.base, "minion_work",
                                          target))
        if target not in self.minion_ids:
            self.minion_ids.append(target)
        return target

    def kill_minion(self, target: str = "Minion_0", **params) -> str:
        """kill -9, possibly mid-swap: the task lease requeues and the
        intent-logged swap protocol must resume or roll back."""
        self._reap(f"minion:{target}", signal.SIGKILL, wait_s=10)
        self._procs.pop(f"minion:{target}", None)
        if target in self.minion_ids:
            self.minion_ids.remove(target)
        return target

    # transport fault windows (armed inside every broker process)
    def _broker_fault(self, method: str, path: str,
                      body: Optional[dict] = None) -> None:
        for port in self.broker_ports:
            try:
                _http_json(method,
                           f"http://127.0.0.1:{port}{path}",
                           json.dumps(body).encode() if body else None,
                           timeout=10)
            except Exception:  # noqa: BLE001 — a dead broker has no arm
                pass

    def net_latency(self, target: str, latency_s: float = 0.25,
                    probability: float = 1.0, **params) -> str:
        """Inject per-dispatch latency toward one server on EVERY
        broker's data plane (window; disarmed via clear_fault)."""
        self._broker_fault("POST", "/debug/faults",
                           {"server": target, "kind": "latency",
                            "latencyS": latency_s,
                            "probability": probability})
        return target

    def net_drop(self, target: str, probability: float = 0.5,
                 **params) -> str:
        """Probabilistically drop broker→server connections (window)."""
        self._broker_fault("POST", "/debug/faults",
                           {"server": target, "kind": "drop",
                            "probability": probability})
        return target

    def clear_fault(self, target: str, **params) -> None:
        self._broker_fault("DELETE",
                           f"/debug/faults?server={target}")

    # -- chaos adapter surface ---------------------------------------------
    def targets(self, kind: str):
        if kind in ("kill_server", "drain_server", "net_latency",
                    "net_drop"):
            return list(self.server_admin_ports)
        if kind in ("fail_controller",):
            cid = self.active_controller_id()
            return [cid] if cid else []
        if kind in ("kill_minion",):
            return list(self.minion_ids)
        return []

    def recovery_probe(self, event, target: str):
        """Callable the ChaosCoordinator polls until recovery.

        kill_server — the cluster healed: replication deficit back to
        zero AND a broker answers clean. fail_controller — a DIFFERENT
        controller published the active endpoint and answers /health.
        drain_server — the process exited (the drain path runs in its
        SIGTERM handler). Others: untracked."""
        kind = event.kind
        if kind == "kill_server":
            return self._probe_healed
        if kind == "fail_controller":
            old_http = self.active_controller_http()
            return lambda: self._probe_controller_takeover(old_http)
        if kind == "drain_server":
            name = f"server:{target}"

            def exited() -> bool:
                p = self._procs.get(name)
                if p is None or p.poll() is not None:
                    self._procs.pop(name, None)
                    return True
                return False
            return exited
        return None

    def _probe_healed(self) -> bool:
        base = self.active_controller_http()
        if base is None:
            return False
        try:
            snap = _http_json("GET", f"{base}/metrics?format=json",
                              timeout=10)
        except Exception:  # noqa: BLE001
            return False
        deficits = [v for k, v in snap.items()
                    if k.startswith("gauge.") and
                    k.endswith("clusterReplicationDeficit")]
        return bool(deficits) and all(v == 0 for v in deficits)

    def _probe_controller_takeover(self, old_http: Optional[str]) -> bool:
        base = self.active_controller_http()
        if base is None or base == old_http:
            return False
        try:
            req = urllib.request.Request(f"{base}/health")
            with urllib.request.urlopen(req, timeout=5) as resp:
                return resp.status == 200
        except Exception:  # noqa: BLE001
            return False

    def exit_codes(self) -> Dict[str, Optional[int]]:
        """{process name: exit code, None while it runs}."""
        return {name: p.poll() for name, p in self._procs.items()}

    def stop(self, wait_s: float = 15.0) -> Dict[str, int]:
        """Stop every process; returns {process name: exit code}.

        Servers and minions go first and are waited for: SIGTERM is
        their drain path (exit 0), it needs the controller still up to
        see the view clear, and a process that holds a chip must be
        gone before anything else may claim it. Brokers, controllers
        and the store then take SIGINT, their clean-stop path (exit 0).
        A process still alive after ``wait_s`` is killed."""
        if self._store_client is not None:
            try:
                self._store_client.close()
            except Exception:  # noqa: BLE001
                pass
        first = [n for n in self._procs
                 if n.startswith(("server:", "minion:"))]
        rest = [n for n in self._procs if n not in first]
        for names, sig in ((first, signal.SIGTERM), (rest, signal.SIGINT)):
            for n in names:
                if self._procs[n].poll() is None:
                    self._procs[n].send_signal(sig)
            for n in names:
                try:
                    self._procs[n].wait(timeout=wait_s)
                except subprocess.TimeoutExpired:
                    self._procs[n].kill()
                    self._procs[n].wait()
        codes = {n: p.returncode for n, p in self._procs.items()}
        self._procs.clear()
        return codes
