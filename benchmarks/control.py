#!/usr/bin/env python3
"""The control of `correct`: the reference in the program's place, one
precision down.

    python benchmarks/control.py --workload <name> --seeds 1,2,3 [--rows N]

The configurations state x32: `lo_revenue`, a raw INT column, is served
from a 32-bit lane and accumulated in float32, and `lo_supplycost`'s
integers are summed exactly behind their dictionary. The step below
that, the one that would tempt a later PR (half the lane's bytes, the
MXU's own type), is bfloat16. So the control answers the cell's own
requests with the numpy reference over summed values rounded to
bfloat16, and is compared with the exact reference as a run's answers
are: its widest gaps have to lie above the configuration's limits.
Host numpy only; no cluster, no chip.
"""
from __future__ import annotations

import argparse
import itertools
import json
import os
import sys
from concurrent.futures import ThreadPoolExecutor

import numpy as np

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
if BENCH_DIR not in sys.path:
    sys.path.insert(0, BENCH_DIR)

from harness import (cells, compare, shapes, tables,  # noqa: E402
                     traffic)


def to_bfloat16(lane: np.ndarray) -> np.ndarray:
    import ml_dtypes
    return lane.astype(ml_dtypes.bfloat16).astype(np.float64)


def control_numbers(config: dict, traffic_spec: dict, seed: int,
                    per_shape: int, rows: int = 0) -> dict:
    """The numbers `compare` would read had the control answered the
    first `per_shape` requests of every shape."""
    gen = tables.load_generator(config["generator"])
    with ThreadPoolExecutor(max_workers=8) as pool:
        table = tables.make_table(gen, rows or config["rows"],
                                  config["segments"], seed, pool)
        family = shapes.load_family(BENCH_DIR, traffic_spec["shapes"],
                                    table.pools)
        tr = traffic.Traffic(traffic_spec, family, seed)
        wanted = {s.name: per_shape for s in family}
        picked = []
        streams = [tr.client_stream(k) for k in range(tr.clients)]
        for request in itertools.chain.from_iterable(zip(*streams)):
            if wanted.get(request.shape.name, 0) > 0:
                wanted[request.shape.name] -= 1
                picked.append(request)
            if not any(wanted.values()):
                break

        def one(request):
            n = len(request.shape.spec["aggregates"])
            exact = request.shape.reference(request.literals, table)
            ctrl = request.shape.reference(request.literals, table,
                                           lower=to_bfloat16)
            return (request,) + compare.rel_errs(ctrl, exact, n)
        out = compare.fresh_numbers(family)
        out.update(failed_requests=0, path_violations=0)
        for request, differ, errs in pool.map(one, picked):
            compare.fold(out, request.shape, request.literals, differ, errs)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--rows", type=int, default=0)
    ap.add_argument("--per-shape", type=int, default=10)
    args = ap.parse_args(argv)
    _bench, _cell, config, spec = cells.load_cell(
        os.path.dirname(BENCH_DIR), BENCH_DIR, args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        numbers = control_numbers(config, spec, seed, args.per_shape,
                                  args.rows)
        verdict = compare.verdict(numbers, config["correct"])
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "control": "summed values in bfloat16",
                          "correct": verdict["correct"],
                          "compared": verdict["compared"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
