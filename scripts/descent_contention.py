"""Cube descents side by side, on the host alone: what one costs beside
others under the interpreter lock, and how many array calls it makes.

Builds the benchmark configuration's segments with their star-tree
cubes (`benchmarks/harness/build.py`, rows drawn from --seed), loads
them, and answers drawn literal tuples of every shape that a cube
covers (Q2.x-Q4.x of `benchmarks/shapes/ssb.json`) through
`try_star_tree_execute_multi`, as the server's executor does: first
once a shape under `sys.setprofile`, counting the calls into numpy and
into the native library, then from 1, 2, 3 and 4 threads at once. No
server, no broker, no device: a host measurement, never a device
metric. It runs on any checkout that has the benchmark (copy it into a
parent's tree to compare).

    python scripts/descent_contention.py [--rows N] [--per-shape 30]

At the configuration's 50M rows the build takes tens of GB and about
half a minute on 13 cores: run that on the chip's host; --rows 400000
is a rehearsal.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import threading
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")      # host work only

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_DIR = os.path.join(REPO, "benchmarks")
for _p in (REPO, BENCH_DIR):
    if _p not in sys.path:
        sys.path.insert(0, _p)


class ArrayCalls:
    """`with ArrayCalls() as c:` counts, on this thread, the calls that
    leave the interpreter for numpy (`c_call` events of builtins that
    numpy owns or that are bound to an array, `searchsorted` apart) and
    for the native library (a call of a `pinot_tpu.native` wrapper makes
    one foreign call)."""

    def __init__(self):
        self.numpy = self.searchsorted = self.native = 0

    @property
    def total(self) -> int:
        return self.numpy + self.native

    def _event(self, frame, event, arg):
        if event == "c_call":
            owner = getattr(arg, "__self__", None)
            mod = getattr(arg, "__module__", None) or \
                type(owner).__module__
            if mod.startswith("numpy") or isinstance(owner, np.ndarray):
                self.numpy += 1
                if arg.__name__ == "searchsorted":
                    self.searchsorted += 1
        elif event == "call" and frame.f_globals.get("__name__") == \
                "pinot_tpu.native" and not \
                frame.f_code.co_name.startswith("_") and \
                frame.f_code.co_name not in ("lib", "loaded"):
            self.native += 1

    def __enter__(self):
        sys.setprofile(self._event)
        return self

    def __exit__(self, *exc):
        sys.setprofile(None)


def compile_request(pql: str):
    from pinot_tpu.pql.optimizer import BrokerRequestOptimizer
    from pinot_tpu.pql.parser import compile_pql
    return BrokerRequestOptimizer().optimize(compile_pql(pql))


def cube_requests(segments, shapes, seed: int, per_shape: int):
    """{shape name: [BrokerRequest, ...]} at literal tuples drawn from
    the seed, for every shape a cube of every segment covers."""
    from pinot_tpu.startree.executor import try_star_tree_execute_multi
    out = {}
    for i, shape in enumerate(shapes):
        rng = np.random.default_rng([seed, 3000 + i])
        picks = rng.choice(shape.domain_size,
                           min(per_shape, shape.domain_size), replace=False)
        reqs = [compile_request(shape.pql(shape.literals(int(p))))
                for p in picks]
        if try_star_tree_execute_multi(segments, reqs[0]) is not None:
            out[shape.name] = reqs
    return out


def count_calls(segments, requests):
    """Per shape: the array calls of one descent, and whether the native
    call answered (None on a program that does not say)."""
    from pinot_tpu.startree.executor import try_star_tree_execute_multi
    out = {}
    for name, reqs in requests.items():
        try_star_tree_execute_multi(segments, reqs[0])     # caches warm
        with ArrayCalls() as calls:
            blk = try_star_tree_execute_multi(segments, reqs[-1])
        out[name] = {"array_calls": calls.total,
                     "searchsorted": calls.searchsorted,
                     "native_calls": calls.native,
                     "native": getattr(blk, "cube_native", None)}
    return out


def run_threads(segments, requests, n_threads: int, rounds: int):
    """n_threads threads, each answering every request `rounds` times in
    an order of its own -> (ms a descent, descents a second together)."""
    from pinot_tpu.startree.executor import try_star_tree_execute_multi
    flat = [r for reqs in requests.values() for r in reqs]
    start = threading.Barrier(n_threads + 1)
    spent = [0.0] * n_threads

    def work(k: int) -> None:
        order = np.random.default_rng(k).permutation(len(flat))
        start.wait()
        t0 = time.perf_counter()
        for _ in range(rounds):
            for i in order:
                try_star_tree_execute_multi(segments, flat[i])
        spent[k] = time.perf_counter() - t0

    threads = [threading.Thread(target=work, args=(k,))
               for k in range(n_threads)]
    for t in threads:
        t.start()
    start.wait()
    t0 = time.perf_counter()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    n = rounds * len(flat)
    return 1e3 * sum(spent) / (n * n_threads), n * n_threads / wall


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", default="ssb_flat_startree")
    ap.add_argument("--rows", type=int, default=0,
                    help="another scale than the configuration's")
    ap.add_argument("--seed", type=int, default=2147485001)
    ap.add_argument("--per-shape", type=int, default=30)
    ap.add_argument("--rounds", type=int, default=3)
    ap.add_argument("--threads", default="1,2,3,4")
    ap.add_argument("--workers", type=int,
                    default=max(1, (os.cpu_count() or 2) - 3))
    args = ap.parse_args(argv)

    from harness import build, shapes as shapes_mod, tables
    from pinot_tpu.segment.loader import ImmutableSegmentLoader
    with open(os.path.join(BENCH_DIR, "configs",
                           f"{args.config}.json")) as fh:
        config = json.load(fh)
    if args.rows:
        config["rows"] = args.rows
    work = tempfile.mkdtemp(prefix="descent_contention.")
    try:
        t0 = time.perf_counter()
        dirs = build.build_all(config, args.seed, work, REPO, args.workers)
        segments = [ImmutableSegmentLoader.load(d) for d in dirs]
        print(f"{config['rows']} rows, {len(segments)} segments, "
              f"{sum(len(s.star_trees) for s in segments)} cubes: built "
              f"and loaded in {time.perf_counter() - t0:.1f} s",
              file=sys.stderr)
        gen = tables.load_generator(config["generator"])
        shapes = shapes_mod.load_family(BENCH_DIR, "ssb", gen.pools())
        requests = cube_requests(segments, shapes, args.seed,
                                 args.per_shape)
        result = {"rows": config["rows"], "segments": len(segments),
                  "seed": args.seed, "host_cpus": os.cpu_count(),
                  "descents_a_thread": args.rounds * sum(
                      len(r) for r in requests.values()),
                  "calls_a_descent": count_calls(segments, requests),
                  "threads": {}}
        for n in [int(t) for t in args.threads.split(",")]:
            ms, rate = run_threads(segments, requests, n, args.rounds)
            result["threads"][n] = {"ms_a_descent": round(ms, 3),
                                    "descents_per_s": round(rate, 1)}
            print(f"{n} thread(s): {ms:.2f} ms a descent, "
                  f"{rate:.0f} descents a second together",
                  file=sys.stderr)
        print(json.dumps(result))
    finally:
        shutil.rmtree(work, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
