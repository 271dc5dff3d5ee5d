"""Finding a cell's files by the names `BENCHMARK.json` gives."""
from __future__ import annotations

import json
import os

from . import traffic


def load_json(*parts):
    with open(os.path.join(*parts)) as fh:
        return json.load(fh)


def find(entries, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


def load_cell(checkout: str, bench_dir: str, workload: str):
    """-> (BENCHMARK.json, the cell's entry, its configuration file,
    its traffic file)."""
    bench = load_json(checkout, "BENCHMARK.json")
    cell = find(bench["workloads"], workload, "workload")
    entry = find(bench["configs"], cell["config"], "configuration")
    return (bench, cell, load_json(checkout, entry["file"]),
            traffic.load(bench_dir, cell["traffic"]))
