"""chip_smoke.py's CPU-checkable contract, the compile-cache placement
rule, and the device report a server gives of itself.

What only a chip can show (kernels compiling for the TPU backend, lanes
in HBM) is `python chip_smoke.py` through the chip tool — never here.
"""
import json
import os
import subprocess
import sys
import urllib.request

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")

_CACHE_PROBE = (
    "import json, sys\n"
    "from pinot_tpu.utils.device import configure_compile_cache\n"
    "returned = configure_compile_cache()\n"
    "touched = 'jax' in sys.modules\n"
    "import jax\n"
    "print(json.dumps({'returned': returned, 'codeTouchedJax': touched,\n"
    "                  'config': jax.config.jax_compilation_cache_dir}))\n")


def _cache_probe(env_dir, cwd):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = REPO
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    proc = subprocess.run([sys.executable, "-c", _CACHE_PROBE], env=env,
                          cwd=cwd, capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_compile_cache_env_set_is_left_to_jax(tmp_path):
    placed = str(tmp_path / "placed_from_outside")
    out = _cache_probe(placed, str(tmp_path))
    # JAX's own handling of the variable, and no directory set in code:
    # the function returned before it ever imported jax
    assert out == {"returned": placed, "codeTouchedJax": False,
                   "config": placed}


def test_compile_cache_default_is_fixed_in_checkout(tmp_path):
    a = _cache_probe(None, str(tmp_path))
    b = _cache_probe(None, REPO)           # another process, another cwd
    want = os.path.join(REPO, ".jax_cache")
    assert a["returned"] == a["config"] == want
    assert b == a


def test_server_debug_health_reports_device():
    from pinot_tpu.server.http_api import ServerApiServer
    from pinot_tpu.server.instance import ServerInstance
    srv = ServerInstance("Server_dev")
    api = ServerApiServer(srv)
    port = api.start()
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/debug/health", timeout=10) as r:
            dev = json.loads(r.read())["device"]
    finally:
        api.stop()
        srv.stop()
    assert dev["platform"] == "cpu" and dev["count"] >= 1
    assert isinstance(dev["deviceKind"], str) and dev["deviceKind"]
    assert dev["x64"] is True              # tests/conftest.py turns it on
    assert "bytesInUse" in dev and "compileCacheDir" in dev


def _run_smoke(tmp_path, *flags):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, SMOKE, "--work-dir", str(tmp_path / "work"),
         "--out-dir", str(tmp_path / "out"), *flags],
        env=env, cwd=str(tmp_path), capture_output=True, text=True,
        timeout=600)


def test_chip_smoke_cpu_rehearsal_completes_and_says_so(tmp_path):
    proc = _run_smoke(tmp_path, "--rehearse-cpu", "--rows", "80000")
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-2000:]
    lines = proc.stdout.strip().splitlines()
    assert "REHEARSAL" in lines[0] and "REHEARSAL" in lines[-2]
    last = json.loads(lines[-1])
    assert last["ok"] is True and last["rehearsal"] is True
    assert last["device"]["platform"] == "cpu"
    report = json.loads((tmp_path / "out" / "report.json").read_text())
    assert report["rehearsal"] is True and report["rows"] == 80000
    # all 13 answers matched the reference on the device scan path
    assert sorted(report["queries"]) == sorted(report["warm"]) and \
        len(report["queries"]) == 13
    assert all(q["paths"].get("scan", 0) > 0 and "host" not in q["paths"]
               for q in report["queries"].values())
    assert max(report["burst"]["batchSizes"]) >= 2
    assert report["kernelSweep"]["failed"] == 0
    # the server says what it runs on: boot line and /debug/health
    for dev in (report["serverBoot"]["device"], report["device"]):
        assert dev["platform"] == "cpu" and dev["count"] >= 1
        assert dev["deviceKind"] and dev["x64"] is False
    assert set(report["exitCodes"].values()) == {0}
    assert not (tmp_path / "work").exists()      # nothing left behind


def test_chip_smoke_without_a_chip_fails(tmp_path):
    proc = _run_smoke(tmp_path)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "FAILED" in proc.stdout and "'tpu'" in proc.stdout
