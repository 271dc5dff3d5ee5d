"""Paths the benchmark's tests share. The benchmark's own modules
(`harness`, `reducers`, `run`, `control`) are imported from
`benchmarks/`, as `benchmarks/run.py` itself imports them."""
import importlib.util
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH_DIR = os.path.join(REPO, "benchmarks")
for p in (REPO, BENCH_DIR):
    if p not in sys.path:
        sys.path.insert(0, p)


def load_module(name: str, path: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="session")
def bench_run():
    return load_module("bench_run", os.path.join(BENCH_DIR, "run.py"))
