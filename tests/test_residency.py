"""HBM residency: the runtime ledger (obs/residency.py), its gauge
wiring, the loader/exchange accounting it observes, and the lifecycle
analysis tier (device-ledger, cache-bound) that keeps every upload and
cache on the books.

The acceptance-critical test here is the cross-check: after
``warm_device()`` the ledger's bytes for a segment must agree with the
ACTUAL ``nbytes`` of the uploaded device lanes (within 5%; in practice
exact) — an accounting layer that drifts from reality is worse than
none.
"""
import os

import pytest

from pinot_tpu.analysis import analyze_paths, analyze_source
from pinot_tpu.obs import residency
from pinot_tpu.obs.residency import LEDGER, ResidencyLedger

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SERVING_PATH = "pinot_tpu/query/_fixture.py"      # lifecycle scope
PLAIN_PATH = "pinot_tpu/tools/_fixture.py"        # out of scope


def lifecycle_findings(source: str, path: str = SERVING_PATH,
                       rule: str = None):
    res = analyze_source(source, path, tiers=("ast", "lifecycle"))
    return [f for f in res.findings
            if rule is None or f.rule == rule]


# ---------------------------------------------------------------------------
# ResidencyLedger accounting
# ---------------------------------------------------------------------------


def test_register_is_owner_replace_not_leak():
    led = ResidencyLedger()
    led.register("a", table="t", segment="s", kind="scan", nbytes=100)
    led.register("b", table="t", segment="s", kind="vdoc", nbytes=50)
    assert led.total_bytes() == 150
    # re-upload of the same lane replaces the entry
    led.register("a", table="t", segment="s", kind="scan", nbytes=40)
    assert led.total_bytes() == 90
    assert led.kind_bytes("scan") == 40
    assert led.kind_bytes("vdoc") == 50
    assert led.release("a") == 40
    assert led.release("a") == 0          # double release is a no-op
    assert led.total_bytes() == 50


def test_release_prefix_drops_one_owners_lanes():
    led = ResidencyLedger()
    for i in range(3):
        led.register(f"ds:1:lane{i}", table="t", segment="s",
                     kind="scan", nbytes=10)
    led.register("ds:2:lane0", table="t", segment="s2", kind="scan",
                 nbytes=7)
    assert led.release_prefix("ds:1:") == 30
    assert led.total_bytes() == 7
    assert led.kind_bytes("scan") == 7


def test_snapshot_shape_and_totals():
    led = ResidencyLedger()
    led.register("x", table="tbl", segment="s0", kind="scan",
                 nbytes=100)
    led.register("y", table="tbl", segment="s0", kind="vector",
                 nbytes=30)
    led.register("z", table="", segment="", kind="exchange", nbytes=5)
    snap = led.snapshot()
    assert snap["totalDeviceBytesResident"] == 135
    assert snap["byKind"] == {"exchange": 5, "scan": 100, "vector": 30}
    assert snap["tables"]["tbl"] == {"scan": 100, "vector": 30}
    assert snap["entryCount"] == 3
    # entries are the largest-first spill, each fully attributed
    assert snap["entries"][0] == {"owner": "x", "table": "tbl",
                                  "segment": "s0", "kind": "scan",
                                  "bytes": 100}
    assert {e["owner"] for e in snap["entries"]} == {"x", "y", "z"}


def test_snapshot_respects_max_entries_but_not_totals():
    led = ResidencyLedger()
    for i in range(10):
        led.register(f"o{i}", table="t", segment="s", kind="scan",
                     nbytes=i + 1)
    snap = led.snapshot(max_entries=3)
    assert len(snap["entries"]) == 3
    assert [e["bytes"] for e in snap["entries"]] == [10, 9, 8]
    assert snap["entryCount"] == 10
    assert snap["totalDeviceBytesResident"] == sum(range(1, 11))


def test_sweepers_run_on_scrape_and_exchange_reads_only():
    led = ResidencyLedger()
    calls = []

    def sweeper():
        calls.append(1)
        return 0

    led.add_sweeper(sweeper)
    led.snapshot()                   # scrape path sweeps
    led.kind_bytes("exchange")       # exchange gauge read sweeps
    led.kind_bytes("scan")           # plain kind read must NOT
    led.total_bytes()
    assert len(calls) == 2
    led.remove_sweeper(sweeper)
    led.remove_sweeper(sweeper)      # idempotent
    led.snapshot()
    assert len(calls) == 2


def test_bind_registry_preregisters_every_kind_series():
    from pinot_tpu.common.metrics import MetricsRegistry
    from pinot_tpu.obs.prometheus import render_prometheus
    reg = MetricsRegistry("server")
    residency.bind_registry(reg)
    text = render_prometheus(reg)
    # the bare total plus one series per kind, scrapeable BEFORE any
    # upload happens (empty-registry exposition was a real bug class)
    assert "device_bytes_resident" in text
    for kind in residency.KINDS:
        assert f'"{kind}"' in text, (kind, text)


# ---------------------------------------------------------------------------
# runtime cross-check: ledger totals vs actual uploaded lane bytes
# ---------------------------------------------------------------------------


def _segment_device_bytes(seg):
    """Ground truth: sum of nbytes over every device array the segment
    is holding right now."""
    total = 0
    for ds in seg._data_sources.values():
        total += sum(int(arr.nbytes) for arr in ds._dev.values())
    if seg._valid_dev is not None:
        total += int(seg._valid_dev[1].nbytes)
    return total


def _segment_ledgered_bytes(seg):
    prefixes = tuple(f"ds:{id(ds)}:" for ds in
                     seg._data_sources.values())
    prefixes += (f"seg:{id(seg)}:",)
    snap = LEDGER.snapshot(max_entries=1_000_000)
    return sum(e["bytes"] for e in snap["entries"]
               if e["owner"].startswith(prefixes))


def test_warm_device_ledger_matches_actual_lane_bytes(tmp_path):
    from fixtures import build_segment
    seg, _cols = build_segment(str(tmp_path), n=2000, seed=3)
    try:
        seg.warm_device()
        actual = _segment_device_bytes(seg)
        ledgered = _segment_ledgered_bytes(seg)
        assert actual > 0
        # acceptance bar is 5%; the ledger is registered AT the upload
        # choke point so in practice the match is exact
        assert abs(ledgered - actual) <= 0.05 * actual, \
            (ledgered, actual)
        assert ledgered == actual
    finally:
        seg.destroy()
    assert _segment_ledgered_bytes(seg) == 0


def test_destroy_releases_every_ledgered_lane(tmp_path):
    from fixtures import build_segment
    seg, _cols = build_segment(str(tmp_path), n=1000, seed=5)
    seg.warm_device()
    assert _segment_ledgered_bytes(seg) > 0
    before = LEDGER.total_bytes()
    released = _segment_device_bytes(seg)
    seg.destroy()
    assert _segment_ledgered_bytes(seg) == 0
    assert LEDGER.total_bytes() == before - released


# ---------------------------------------------------------------------------
# the lane cache (DataSource._device): a hit is one look-up and no host
# work; only a miss builds the padded operand, uploads and registers
# ---------------------------------------------------------------------------

#: operand kind -> (accessor, residency ledger kind)
LANE_KINDS = {
    "ids": ("device_dict_ids", "scan"),
    "mv": ("device_mv_dict_ids", "scan"),
    "vals": ("device_dict_values", "scan"),
    "raw": ("device_raw_values", "scan"),
    "parts": ("device_part_lanes", "scan"),
    "vlane": ("device_value_lane", "scan"),
    "vec": ("device_vec_values", "vector"),
    "ivfa": ("device_ivf_assign", "vector"),
    "ivfc": ("device_ivf_centroids", "vector"),
    "ivfv": ("device_ivf_valid", "vector"),
    "hllidx": ("device_hll_idx", "hll"),
    "hllrank": ("device_hll_rank", "hll"),
}


class _LazyChunks:
    """Stands in for a chunked raw reader: `raw_values` decodes it on
    first use, under the DataSource's lane lock."""

    def __init__(self, values):
        self._values = values
        self.decodes = 0

    def decode_all(self):
        import time
        self.decodes += 1
        time.sleep(0.01)
        return self._values


def _lane_source(kind, n=3000, lazy_raw=False):
    """A DataSource that serves `kind`, built by hand the way the
    loader builds one (ds._segment None: unnamed ledger entries)."""
    import numpy as np
    from pinot_tpu.common.datatype import DataType
    from pinot_tpu.segment.dictionary import Dictionary
    from pinot_tpu.segment.loader import DataSource
    from pinot_tpu.segment.metadata import ColumnMetadata
    rng = np.random.default_rng(7)
    if kind == "raw":
        cm = ColumnMetadata("c", DataType.INT, n, 32, has_dictionary=False,
                            total_number_of_entries=n)
        ds = DataSource(cm, None)
        values = rng.integers(0, 1 << 20, n).astype(np.int32)
        if lazy_raw:
            ds.raw_chunks = _LazyChunks(values)
        else:
            ds.raw_values = values
    elif kind == "mv":
        cm = ColumnMetadata("c", DataType.INT, 40, 6, single_value=False,
                            total_number_of_entries=n)
        ds = DataSource(cm, None)
        ds.dictionary = Dictionary(DataType.INT, np.arange(40, dtype=np.int32))
        ds.mv_dict_ids = rng.integers(0, 41, (n, 3)).astype(np.int32)
    elif kind in ("vec", "ivfa", "ivfc", "ivfv"):
        cm = ColumnMetadata("c", DataType.VECTOR, n, 32, has_dictionary=False,
                            total_number_of_entries=n, vector_dimension=6)
        ds = DataSource(cm, None)
        ds.vec_values = rng.random((n, 6)).astype(np.float32)
        ds.ivf_centroids = rng.random((5, 6)).astype(np.float32)
        ds.ivf_assignments = rng.integers(0, 5, n).astype(np.int32)
    else:
        values = np.unique(rng.integers(-500, 70_000, 900)).astype(np.int32)
        cm = ColumnMetadata("c", DataType.INT, len(values), 10,
                            total_number_of_entries=n)
        ds = DataSource(cm, None)
        ds.dictionary = Dictionary(DataType.INT, values)
        ds.dict_ids = rng.integers(0, len(values), n).astype(np.int32)
    return ds


def _count_host_operands(ds, monkeypatch):
    """Every kind `host_operand` is entered with, in order."""
    calls, real = [], ds.host_operand

    def counted(kind):
        calls.append(kind)
        return real(kind)

    monkeypatch.setattr(ds, "host_operand", counted)
    return calls


def _lane_entries(ds):
    snap = LEDGER.snapshot(max_entries=1_000_000)
    return [e for e in snap["entries"]
            if e["owner"].startswith(f"ds:{id(ds)}:")]


@pytest.mark.parametrize("kind", LANE_KINDS)
def test_cold_lane_builds_uploads_and_registers_exactly_once(
        kind, monkeypatch):
    import numpy as np
    ds = _lane_source(kind)
    accessor, ledger_kind = LANE_KINDS[kind]
    want = ds.host_operand(kind)
    calls = _count_host_operands(ds, monkeypatch)
    try:
        assert _lane_entries(ds) == []
        lane = getattr(ds, accessor)()
        assert calls.count(kind) == 1
        # the miss path's padding and dtype are host_operand's own
        assert lane.shape == want.shape and lane.dtype == want.dtype
        np.testing.assert_array_equal(np.asarray(lane), want)
        entry, = _lane_entries(ds)
        assert entry["bytes"] == int(lane.nbytes) == want.nbytes
        assert entry["kind"] == ledger_kind
        assert list(ds._dev.values()) == [lane]
    finally:
        ds.release_device()
    assert _lane_entries(ds) == []


@pytest.mark.parametrize("kind", LANE_KINDS)
def test_warm_lane_hit_is_the_same_array_and_never_builds_a_host_operand(
        kind, monkeypatch):
    ds = _lane_source(kind)
    accessor = getattr(ds, LANE_KINDS[kind][0])
    try:
        lane = accessor()
        calls = _count_host_operands(ds, monkeypatch)
        assert accessor() is lane and accessor() is lane
        assert calls == []
        assert len(_lane_entries(ds)) == 1
        # the host rows gone to the disk tier: a hit needs none of them
        ds.release_host()
        assert accessor() is lane
        assert calls == []
    finally:
        ds.release_device()


@pytest.mark.parametrize("kind", LANE_KINDS)
def test_release_device_then_access_rebuilds_and_reregisters(
        kind, monkeypatch):
    ds = _lane_source(kind)
    accessor = getattr(ds, LANE_KINDS[kind][0])
    try:
        first = accessor()
        nbytes = int(first.nbytes)
        ds.release_device()
        assert ds._dev == {} and _lane_entries(ds) == []
        calls = _count_host_operands(ds, monkeypatch)
        again = accessor()
        assert again is not first and calls.count(kind) == 1
        assert [e["bytes"] for e in _lane_entries(ds)] == [nbytes]
        assert accessor() is again and calls.count(kind) == 1
    finally:
        ds.release_device()


@pytest.mark.parametrize("kind", ["raw", "parts", "hllidx"])
def test_eight_cold_racers_agree_on_one_lane_without_deadlock(kind):
    """`raw` over a lazily decoded reader, `parts` (int_part_info) and
    the HLL tables all take `_lane_lock` while the host operand is
    built: building it inside that lock would hang every racer."""
    import sys
    import threading
    ds = _lane_source(kind, lazy_raw=True)
    accessor = getattr(ds, LANE_KINDS[kind][0])
    n_threads = 8
    barrier = threading.Barrier(n_threads)
    got, errors = [], []

    def racer():
        try:
            barrier.wait(timeout=30)
            for _ in range(3):
                got.append(accessor())
        except Exception as e:          # surfaced by the assert below
            errors.append(e)

    threads = [threading.Thread(target=racer, daemon=True)
               for _ in range(n_threads)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    try:
        assert not any(t.is_alive() for t in threads), "deadlock"
        assert errors == []
        assert len(got) == 3 * n_threads
        assert all(lane is got[0] for lane in got)
        # the losers dropped their copies unregistered
        assert [e["bytes"] for e in _lane_entries(ds)] == \
            [int(got[0].nbytes)]
        assert list(ds._dev.values()) == [got[0]]
        if kind == "raw":
            assert ds.raw_chunks.decodes == 1
    finally:
        ds.release_device()


def test_lane_cache_meters_bound_at_zero_and_marked_on_live_registries():
    import gc
    from pinot_tpu.common.metrics import MetricsRegistry
    reg, other = MetricsRegistry("server"), MetricsRegistry("server")
    residency.bind_registry(reg)
    residency.bind_registry(reg)
    residency.bind_registry(other)
    for r in (reg, other):
        snap = r.snapshot()
        assert snap["meter.laneCacheHits.count"] == 0
        assert snap["meter.laneCacheMisses.count"] == 0
    ds = _lane_source("ids")
    try:
        ds.device_dict_ids()
        ds.device_dict_ids()
        ds.device_dict_ids()
    finally:
        ds.release_device()
    for r in (reg, other):          # bound twice, marked once a lane
        assert r.meter("laneCacheMisses").count == 1
        assert r.meter("laneCacheHits").count == 2
    # registries are held weakly: a dead one's meters leave at the
    # next bind
    dead = id(other.meter("laneCacheHits"))
    del other, r
    gc.collect()
    residency.bind_registry(reg)
    assert dead not in {id(hits) for hits, _ in residency._LANE_METERS}
    assert any(hits is reg.meter("laneCacheHits")
               for hits, _ in residency._LANE_METERS)


# ---------------------------------------------------------------------------
# exchange budget regression: publish -> overflow -> sweep -> zero
# ---------------------------------------------------------------------------


def _xchg_ledger_bytes(mgr):
    snap = LEDGER.snapshot(max_entries=1_000_000)
    return sum(e["bytes"] for e in snap["entries"]
               if e["owner"].startswith(f"xchg:{mgr.xkey}:"))


def test_exchange_budget_credit_overflow_and_ttl_sweep():
    """The full budget lifecycle the protocol model checks, executed
    for real: a typed overflow reject leaves the books untouched, a
    replace-put is judged against the budget it will actually occupy
    (credit-before-compare), and a ledger scrape sweeps the expired
    entry to quiescent zero without any put/get running."""
    from pinot_tpu.query.stages.errors import ExchangeError
    from pinot_tpu.query.stages.exchange import ExchangeManager
    t = [0.0]
    mgr = ExchangeManager(ttl_s=10.0, max_bytes=100,
                          clock=lambda: t[0])
    try:
        mgr.put("x", b"a" * 60)
        assert mgr.held_bytes() == 60
        assert _xchg_ledger_bytes(mgr) == 60
        # oversized publish: typed reject, books unchanged
        with pytest.raises(ExchangeError):
            mgr.put("y", b"b" * 50)
        assert mgr.held_bytes() == 60
        assert _xchg_ledger_bytes(mgr) == 60
        # replace-put: 90 > 100-60 gross, but the 60 it replaces is
        # credited before the compare — must be admitted
        mgr.put("x", b"c" * 90)
        assert mgr.held_bytes() == 90
        assert _xchg_ledger_bytes(mgr) == 90
        # replace-put over the REAL budget still rejects typed
        with pytest.raises(ExchangeError):
            mgr.put("x", b"d" * 101)
        assert mgr.held_bytes() == 90
        assert mgr.get("x") == b"c" * 90
        # expire, then observe via the ledger scrape ONLY: the sweeper
        # hook must bring held bytes to zero at quiescence
        t[0] = 1000.0
        assert LEDGER.kind_bytes("exchange") >= 0   # scrape sweeps
        assert mgr.held_bytes() == 0
        assert _xchg_ledger_bytes(mgr) == 0
        assert mgr.get("x") is None
    finally:
        mgr.close()
    assert _xchg_ledger_bytes(mgr) == 0


def test_exchange_close_releases_ledger_entries():
    from pinot_tpu.query.stages.exchange import ExchangeManager
    mgr = ExchangeManager(ttl_s=60.0, max_bytes=1000)
    mgr.put("a", b"x" * 10)
    mgr.put("b", b"y" * 20)
    assert _xchg_ledger_bytes(mgr) == 30
    mgr.close()
    assert _xchg_ledger_bytes(mgr) == 0


# ---------------------------------------------------------------------------
# device-ledger rule fixtures
# ---------------------------------------------------------------------------


_UNLEDGERED = '''
import jax
import jax.numpy as jnp

def upload(host):
    return jnp.asarray(host)

def place(host, sharding):
    return jax.device_put(host, sharding)
'''


def test_unledgered_uploads_flagged():
    found = lifecycle_findings(_UNLEDGERED, rule="device-ledger")
    assert len(found) == 2
    assert all("unledgered device upload" in f.message for f in found)


def test_jit_scope_uploads_exempt():
    src = '''
import jax
import jax.numpy as jnp
from jax.experimental.shard_map import shard_map

@jax.jit
def kernel(x):
    return jnp.asarray(x) + 1

def sharded(host, mesh, specs):
    def fn(x):
        return jnp.asarray(x)
    return jax.jit(shard_map(fn, mesh, in_specs=specs,
                             out_specs=specs))(host)
'''
    assert lifecycle_findings(src, rule="device-ledger") == []


def test_ledgered_choke_points_pass():
    src = '''
from pinot_tpu.obs import residency

def upload(host):
    return residency.ledgered_asarray(
        host, owner="o", table="t", segment="s", kind="scan")

def place(host, sharding):
    return residency.ledgered_put(
        host, owner="o", table="t", segment="s", kind="scan",
        sharding=sharding)
'''
    assert lifecycle_findings(src, rule="device-ledger") == []


def test_device_ledger_scoped_to_serving_path():
    # a datagen/tool upload is not resident serving state
    assert lifecycle_findings(_UNLEDGERED, path=PLAIN_PATH,
                              rule="device-ledger") == []


def test_lifecycle_tier_is_opt_in():
    # the default fast tier must not run lifecycle rules
    res = analyze_source(_UNLEDGERED, SERVING_PATH)
    assert [f for f in res.findings
            if f.rule in ("device-ledger", "cache-bound")] == []


# ---------------------------------------------------------------------------
# cache-bound rule fixtures
# ---------------------------------------------------------------------------


_UNBOUNDED_CACHES = '''
class Planner:
    def __init__(self):
        self._plans = {}
        self._stats: dict = {}

    def plan(self, key):
        cached = self._plans.get(key)
        if cached is None:
            cached = self._plans[key] = object()
        return cached

    def stat(self, key):
        if key in self._stats:
            return self._stats[key]
        self._stats[key] = 1
        return 1

_GLOBAL_CACHE = {}

def lookup(key):
    if key not in _GLOBAL_CACHE:
        _GLOBAL_CACHE[key] = key
    return _GLOBAL_CACHE[key]
'''


def test_unbounded_memoization_flagged():
    found = lifecycle_findings(_UNBOUNDED_CACHES, rule="cache-bound")
    msgs = " ".join(f.message for f in found)
    assert len(found) == 3, found
    assert "_plans" in msgs and "_stats" in msgs and \
        "_GLOBAL_CACHE" in msgs


def test_structural_bounds_pass():
    src = '''
import collections

class Bounded:
    def __init__(self):
        self._lru = {}
        self._ring = collections.deque(maxlen=64)
        self._gen = {}
        self._capped = {}

    def get(self, key):
        v = self._lru.get(key)
        if v is None:
            v = self._lru[key] = object()
            if len(self._lru) > 128:
                self._lru.pop(next(iter(self._lru)))
        return v

    def push(self, item):
        if item in self._ring:
            return
        self._ring.append(item)

    def swap(self, key):
        if key not in self._gen:
            self._gen[key] = 1
        self._gen = {}

    def add(self, key):
        self._capped.setdefault(key, 0)
        del self._capped[key]
'''
    assert lifecycle_findings(src, rule="cache-bound") == []


def test_cache_bound_suppression_states_invariant():
    src = '''
_CONNS = {}  # tpulint: disable=cache-bound -- bounded by cluster membership

def conn(key):
    c = _CONNS.get(key)
    if c is None:
        c = _CONNS[key] = object()
    return c
'''
    res = analyze_source(src, SERVING_PATH,
                         tiers=("ast", "lifecycle"))
    assert [f for f in res.findings if f.rule == "cache-bound"] == []
    assert any(f.rule == "cache-bound" for f in res.suppressed)


# ---------------------------------------------------------------------------
# live tree: the lifecycle tier is clean (zero findings, the stated
# extrinsic bounds all suppressed inline)
# ---------------------------------------------------------------------------


def test_live_tree_lifecycle_clean(monkeypatch):
    monkeypatch.chdir(REPO_ROOT)
    result = analyze_paths(["pinot_tpu"], lifecycle=True)
    lifecycle = [f for f in result.findings
                 if f.rule in ("device-ledger", "cache-bound")]
    assert lifecycle == [], [(f.path, f.line, f.message)
                             for f in lifecycle]
    assert "lifecycle" in result.timings
