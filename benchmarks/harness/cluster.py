"""The cluster a run drives: the program's processes, one wrapped.

`MultiprocCluster` (the program's own rig) starts controller, broker
and servers through `python -m pinot_tpu.tools.admin`. This subclass
starts `server:*` processes through `server_launcher.py` instead, with
the same arguments, so the chip's holder can be traced. It overrides a
private method (`_spawn`): a refactor of `tools/cluster.py` can break
it, and an admin-API profiler hook in the program would remove the need.
"""
from __future__ import annotations

import io
import json
import os
import subprocess
import sys
import tarfile
import urllib.request

from pinot_tpu.tools.cluster import MultiprocCluster

SERVER_LAUNCHER = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               "server_launcher.py")


class BenchCluster(MultiprocCluster):
    def __init__(self, base: str, checkout: str, server_env: dict,
                 env: dict):
        self._checkout = checkout
        self._server_env = server_env
        super().__init__(base, num_brokers=1, num_servers=1, env=env)

    def log_path(self, name: str) -> str:
        return os.path.join(self.base, "logs",
                            f"{name.replace(':', '_')}.log")

    def _spawn(self, name: str, *cmd: str) -> dict:
        if not name.startswith("server:"):
            return super()._spawn(name, *cmd)
        with open(self.log_path(name), "ab") as log:
            p = subprocess.Popen(
                [sys.executable, SERVER_LAUNCHER, *cmd],
                stdout=subprocess.PIPE, stderr=log,
                env=dict(self._env, **self._server_env),
                cwd=self._checkout, text=True)
        self._procs[name] = p
        line = p.stdout.readline().strip()
        if not line:
            raise RuntimeError(f"process {name} died on boot (see "
                               f"{self.log_path(name)})")
        return json.loads(line)

    def upload_segment(self, table: str, segment_dir: str) -> None:
        """The controller's REST upload, as the program's own rig does
        it, with the artifact packed at gzip level 1: the format is the
        program's tar.gz, the level is the uploader's choice, and level
        9 (tarfile's default) costs a run minutes of set-up."""
        buf = io.BytesIO()
        with tarfile.open(fileobj=buf, mode="w:gz", compresslevel=1) as tar:
            for entry in sorted(os.listdir(segment_dir)):
                tar.add(os.path.join(segment_dir, entry), arcname=entry)
        req = urllib.request.Request(
            f"{self.active_controller_http()}/segments/{table}",
            data=buf.getvalue(), method="POST",
            headers={"Content-Type": "application/octet-stream"})
        with urllib.request.urlopen(req, timeout=300) as resp:
            json.loads(resp.read())
