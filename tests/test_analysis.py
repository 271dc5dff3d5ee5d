"""tpulint analyzer tests: fixture corpus (≥1 positive + 1 negative per
rule family), suppression/baseline machinery, baseline freshness against
the committed tpulint.baseline.json, and the transfer-guard runtime
complement."""
import json
import os
import subprocess
import sys
import tempfile

import pytest

from pinot_tpu.analysis import (all_rules, analyze_paths, analyze_source,
                                diff_baseline, load_baseline,
                                write_baseline)
from pinot_tpu.analysis.core import count_keys, split_by_baseline

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BASELINE = os.path.join(REPO_ROOT, "tpulint.baseline.json")

KERNEL_PATH = "pinot_tpu/query/_fixture.py"       # host-sync scope
SERVER_PATH = "pinot_tpu/server/_fixture.py"      # concurrency scope
PLAIN_PATH = "pinot_tpu/common/_fixture.py"       # out of both scopes


def rules_of(source: str, path: str = KERNEL_PATH):
    return sorted({f.rule for f in analyze_source(source, path).findings})


def findings_of(source: str, path: str = KERNEL_PATH):
    return analyze_source(source, path).findings


# ---------------------------------------------------------------------------
# rule registry / framework
# ---------------------------------------------------------------------------


def test_rule_families_registered():
    assert set(all_rules()) == {
        # PR 1 AST families
        "host-sync", "retrace", "dtype-drift", "concurrency",
        "api-compat",
        # deep-analysis AST families (lock graph + event-loop safety)
        "lock-order", "lock-blocking", "async-blocking", "cross-loop",
        # global deep tier (jaxpr contracts, wire surface)
        "kernel-contract", "wire-schema",
        # global protocol tier (durability discipline, crash coverage,
        # metrics exposition contract, crash-interleaving model check)
        "durability-order", "crash-coverage", "metrics-contract",
        "protocol-invariants", "protocol-model",
        # per-file lifecycle tier (HBM residency accounting)
        "device-ledger", "cache-bound"}


def test_deep_rules_are_deep_tier_only():
    rules = all_rules()
    assert rules["kernel-contract"].tier == "deep"
    assert rules["wire-schema"].tier == "deep"
    # fast analyze_source must not invoke them (they are global)
    assert analyze_source("x = 1\n", PLAIN_PATH).findings == []


def test_fixture_corpus_fires_at_least_three_families():
    # the acceptance bar: ≥ 3 distinct rule families on purpose-built
    # fixtures (each family is also covered individually below)
    fired = set()
    fired |= set(rules_of(HOST_SYNC_POS))
    fired |= set(rules_of(RETRACE_POS, PLAIN_PATH))
    fired |= set(rules_of(DTYPE_POS, PLAIN_PATH))
    fired |= set(rules_of(CONCURRENCY_POS, SERVER_PATH))
    fired |= set(rules_of(API_DENY_POS, PLAIN_PATH))
    assert len(fired) >= 3
    assert {"host-sync", "retrace", "dtype-drift", "concurrency",
            "api-compat"} <= fired


# ---------------------------------------------------------------------------
# host-sync
# ---------------------------------------------------------------------------

HOST_SYNC_POS = """
import numpy as np

def combine(run):
    outs = run()
    return int(np.asarray(outs.get("group.overflow", 0)))
"""

HOST_SYNC_POS_JIT = """
import jax
import numpy as np

@jax.jit
def kernel(x):
    return np.asarray(x) + 1
"""

HOST_SYNC_POS_ITEM = """
def finish(outs):
    return outs["stats"].item()
"""

HOST_SYNC_NEG = """
import jax
import numpy as np

def combine(run):
    outs = jax.device_get(run())           # ONE batched transfer
    total = int(outs.get("group.overflow", 0))
    hist = np.asarray(outs["agg0"])[: 8]
    return total + int(np.nonzero(hist)[0].sum())
"""


def test_host_sync_positive():
    assert rules_of(HOST_SYNC_POS) == ["host-sync"]
    assert rules_of(HOST_SYNC_POS_JIT) == ["host-sync"]
    assert rules_of(HOST_SYNC_POS_ITEM) == ["host-sync"]


def test_host_sync_negative():
    assert rules_of(HOST_SYNC_NEG) == []


def test_host_sync_out_of_scope_module_is_quiet():
    # common/ is not on the kernel path: no jit decorator → no findings
    assert rules_of(HOST_SYNC_POS, PLAIN_PATH) == []


def test_host_sync_device_tainted_asarray():
    src = """
import jax.numpy as jnp
import numpy as np

def f(ids):
    mask = jnp.equal(ids, 3)
    return np.asarray(mask)
"""
    assert rules_of(src) == ["host-sync"]


# ---------------------------------------------------------------------------
# retrace
# ---------------------------------------------------------------------------

RETRACE_POS = """
import jax

@jax.jit
def f(x, opts=[]):
    return x
"""

RETRACE_POS_LOOP = """
import jax

def compile_loop(fns):
    out = []
    for fn in fns:
        out.append(jax.jit(fn))
    return out
"""

RETRACE_POS_GLOBAL = """
import jax

CACHE = {}

@jax.jit
def f(x):
    return x * len(CACHE)
"""

RETRACE_NEG = """
import functools
import jax

@functools.partial(jax.jit, static_argnums=0)
def f(n, x):
    return x * n
"""


def test_retrace_positive():
    assert "retrace" in rules_of(RETRACE_POS, PLAIN_PATH)
    assert "retrace" in rules_of(RETRACE_POS_LOOP, PLAIN_PATH)
    assert "retrace" in rules_of(RETRACE_POS_GLOBAL, PLAIN_PATH)


def test_retrace_negative():
    assert rules_of(RETRACE_NEG, PLAIN_PATH) == []


# ---------------------------------------------------------------------------
# dtype-drift
# ---------------------------------------------------------------------------

DTYPE_POS = """
import jax.numpy as jnp

def f(n):
    return jnp.zeros((n,), dtype=jnp.int64)
"""

DTYPE_POS_NARROW = """
import numpy as np

def doc_offsets(doc_ids, widths):
    return (doc_ids * widths).astype(np.int32)
"""

DTYPE_NEG = """
import jax.numpy as jnp
import numpy as np

def f(n):
    host = np.zeros((n,), dtype=np.int64)     # host 64-bit math is fine
    const = np.int32(2**31 - 1)               # literal: can't overflow
    return jnp.zeros((n,), dtype=jnp.float32), host, const
"""


def test_dtype_drift_positive():
    assert rules_of(DTYPE_POS, PLAIN_PATH) == ["dtype-drift"]
    assert rules_of(DTYPE_POS_NARROW, PLAIN_PATH) == ["dtype-drift"]


def test_dtype_drift_negative():
    assert rules_of(DTYPE_NEG, PLAIN_PATH) == []


# ---------------------------------------------------------------------------
# concurrency
# ---------------------------------------------------------------------------

CONCURRENCY_POS = """
import threading

class Scheduler:
    def __init__(self):
        self._lock = threading.Lock()
        self.pending = 0

    def submit(self):
        self.pending += 1          # unguarded in a lock-declaring class

class NoLock:
    def __init__(self):
        self.state = "INIT"
        self._t = threading.Thread(target=self._run)
        self._t.start()

    def _run(self):
        self.state = "RUNNING"     # consumer-thread writer ...

    def advance(self):
        self.state = "DONE"        # ... races the external writer
"""

CONCURRENCY_NEG = """
import threading

class Scheduler:
    def __init__(self):
        self._lock = threading.Lock()
        self.pending = 0
        self._groups = {}

    def submit(self, name):
        with self._lock:
            self.pending += 1
            self._groups[name] = 1
"""

# the v2 upgrade: a spawned thread being the SOLE writer is a VERIFIED
# single-writer invariant, not a finding (v1 flagged every lock-free
# mutation — 26 of the 33 grandfathered findings were this shape)
CONCURRENCY_SINGLE_WRITER = """
import threading

class Consumer:
    def __init__(self):
        self.offset = 0
        self._t = threading.Thread(target=self._run)
        self._t.start()

    def _run(self):
        while True:
            self.offset += 1       # only the spawned thread writes

    def position(self):
        return self.offset         # readers don't mutate
"""

# fan-in through one sole writing method is the structural
# single-writer pattern (append delegating to extend)
CONCURRENCY_FANIN = """
class Growable:
    def __init__(self):
        self.n = 0

    def append(self, v):
        self.extend([v])

    def extend(self, arr):
        self.n += len(arr)         # the one writer path
"""


def test_concurrency_positive():
    found = findings_of(CONCURRENCY_POS, SERVER_PATH)
    assert {f.rule for f in found} == {"concurrency"}
    msgs = " ".join(f.message for f in found)
    assert "Scheduler.submit" in msgs
    assert "NoLock._run" in msgs and "NoLock.advance" in msgs
    assert "spawn:_run" in msgs     # the thread-entry map is cited


def test_concurrency_negative():
    assert rules_of(CONCURRENCY_NEG, SERVER_PATH) == []


def test_concurrency_verified_single_writer_is_quiet():
    assert rules_of(CONCURRENCY_SINGLE_WRITER, SERVER_PATH) == []


def test_concurrency_sole_writer_fanin_is_quiet():
    assert rules_of(CONCURRENCY_FANIN, SERVER_PATH) == []


def test_concurrency_out_of_scope_module_is_quiet():
    assert rules_of(CONCURRENCY_POS, PLAIN_PATH) == []


# ---------------------------------------------------------------------------
# api-compat
# ---------------------------------------------------------------------------

API_DENY_POS = """
import jax

def f(tree):
    return jax.tree_map(lambda x: x + 1, tree)
"""

# a spelling the installed jax does not resolve (the class of skew that
# broke the seed's 33 tier-1 tests)
API_ABSENT_POS = """
import jax

def f(fn, mesh, specs):
    return jax.sharded_map(fn, mesh=mesh, in_specs=specs, out_specs=specs)
"""

API_NEG = """
import jax
import jax.numpy as jnp

def f(fn, mesh, specs, x):
    g = jax.shard_map(fn, mesh=mesh, in_specs=specs, out_specs=specs)
    return g(jax.jit(jnp.sum)(x))
"""


def test_api_compat_denylist():
    found = findings_of(API_DENY_POS, PLAIN_PATH)
    assert [f.rule for f in found] == ["api-compat"]
    assert "denylisted" in found[0].message


def test_api_compat_absent_symbol():
    found = findings_of(API_ABSENT_POS, PLAIN_PATH)
    assert [f.rule for f in found] == ["api-compat"]
    assert "jax.sharded_map" in found[0].message


def test_api_compat_negative():
    assert rules_of(API_NEG, PLAIN_PATH) == []


# ---------------------------------------------------------------------------
# suppressions + baseline
# ---------------------------------------------------------------------------


def test_per_line_suppression():
    src = HOST_SYNC_POS.replace(
        'return int(np.asarray(outs.get("group.overflow", 0)))',
        'return int(np.asarray(outs.get("group.overflow", 0)))'
        "  # tpulint: disable=host-sync -- fixture")
    res = analyze_source(src, KERNEL_PATH)
    assert res.findings == []
    assert [f.rule for f in res.suppressed] == ["host-sync"]


def test_per_file_suppression():
    src = "# tpulint: disable-file=host-sync -- fixture\n" + HOST_SYNC_POS
    res = analyze_source(src, KERNEL_PATH)
    assert res.findings == []
    assert [f.rule for f in res.suppressed] == ["host-sync"]


def test_baseline_roundtrip_and_diff(tmp_path):
    res = analyze_source(HOST_SYNC_POS, KERNEL_PATH)
    path = str(tmp_path / "baseline.json")
    write_baseline(path, res.findings)
    baseline = load_baseline(path)
    assert baseline == count_keys(res.findings)
    new, stale = split_by_baseline(res.findings, baseline)
    assert new == [] and stale == []
    # a second identical finding in the same file is NEW (count-aware)
    doubled = HOST_SYNC_POS + HOST_SYNC_POS.replace("combine", "combine2")
    res2 = analyze_source(doubled, KERNEL_PATH)
    new2, _ = split_by_baseline(res2.findings, baseline)
    assert len(new2) == 1
    # fixing the code makes the baseline entry stale
    new3, stale3 = split_by_baseline([], baseline)
    assert new3 == [] and len(stale3) == 1


def test_committed_baseline_matches_fresh_run(monkeypatch):
    """The committed baseline must exactly match a fresh run over
    pinot_tpu/: no new findings (CI gate) and no stale entries (the
    grandfather list only ever shrinks — regenerate on fixes)."""
    assert os.path.exists(BASELINE), "tpulint.baseline.json not committed"
    monkeypatch.chdir(REPO_ROOT)
    result = analyze_paths(["pinot_tpu"])
    assert result.errors == []
    new, stale = diff_baseline(result, load_baseline(BASELINE))
    assert new == [], [f.render() for f in new]
    assert stale == [], stale


# ---------------------------------------------------------------------------
# CLI + CI wiring
# ---------------------------------------------------------------------------


def test_scripts_exist_and_are_executable():
    for name in ("lint.sh", "check.sh"):
        path = os.path.join(REPO_ROOT, "scripts", name)
        assert os.path.exists(path), path
        assert os.access(path, os.X_OK), f"{path} not executable"


@pytest.mark.slow
def test_cli_end_to_end_exits_zero_against_baseline():
    proc = subprocess.run(
        [sys.executable, "-m", "pinot_tpu.analysis", "pinot_tpu/",
         "--baseline", "tpulint.baseline.json", "--strict-baseline"],
        cwd=REPO_ROOT, capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "0 new" in proc.stdout


@pytest.mark.slow
def test_cli_catches_injected_regression(tmp_path):
    """api-compat (not just pytest) must catch a call site the
    installed jax does not resolve: a NEW finding vs the baseline."""
    bad = tmp_path / "pinot_tpu_query_bad.py"
    bad.write_text("import jax\n\n"
                   "def f(fn, mesh, s):\n"
                   "    return jax.sharded_map(fn, mesh=mesh, in_specs=s, "
                   "out_specs=s)\n")
    proc = subprocess.run(
        [sys.executable, "-m", "pinot_tpu.analysis", str(bad),
         "--baseline", os.path.join(REPO_ROOT, "tpulint.baseline.json")],
        cwd=REPO_ROOT, capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "api-compat" in proc.stdout


# ---------------------------------------------------------------------------
# runtime transfer guard
# ---------------------------------------------------------------------------


def test_transfer_guard_off_is_nullcontext(monkeypatch):
    import contextlib
    from pinot_tpu.analysis import runtime
    monkeypatch.delenv(runtime.ENV_VAR, raising=False)
    assert isinstance(runtime.debug_transfer_guard(),
                      contextlib.nullcontext)


def test_transfer_guard_rejects_unknown_mode(monkeypatch):
    from pinot_tpu.analysis import runtime
    monkeypatch.setenv(runtime.ENV_VAR, "everything")
    with pytest.raises(ValueError, match=runtime.ENV_VAR):
        runtime.debug_transfer_guard()


def test_transfer_guard_allows_explicit_batched_device_get(monkeypatch):
    import jax
    import jax.numpy as jnp
    from pinot_tpu.analysis import runtime
    monkeypatch.setenv(runtime.ENV_VAR, "1")
    with runtime.debug_transfer_guard():
        x = jnp.arange(8) * 2
        outs = jax.device_get({"sum": x.sum(), "lanes": x})
    assert int(outs["sum"]) == 56


def test_queries_run_under_transfer_guard(monkeypatch):
    """The per-segment execution path only uses explicit batched
    transfers: a real query must survive disallow mode end to end."""
    from fixtures import build_segment
    from pinot_tpu.engine import QueryEngine
    from pinot_tpu.analysis import runtime
    monkeypatch.setenv(runtime.ENV_VAR, "1")
    with tempfile.TemporaryDirectory() as tmp:
        segment, cols = build_segment(tmp, n=512, seed=3)
        engine = QueryEngine([segment])
        resp = engine.query(
            "SELECT COUNT(*) FROM baseballStats WHERE yearID > 1990")
        assert float(resp.aggregation_results[0].value) > 0
