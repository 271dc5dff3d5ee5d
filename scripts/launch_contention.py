"""A Q1.x scan's eight launches, walked two ways, beside threads that
want the interpreter lock: what a query costs when four pool threads
each launch and pull a segment, and when one thread launches all eight
and pulls once; and what one launch costs alone.

Builds the benchmark configuration's segments (`benchmarks/harness/
build.py`, rows drawn from --seed, without the cubes: no Q1.x query
descends one), loads them, plans drawn literal tuples of the shapes
q1.1-q1.3 of `benchmarks/shapes/ssb.json` with the program's planner,
warms every program, and then answers each query's eight plans

  (a) `pool`: on 4 pool threads, a segment a task, each task the solo
      path (`plan.execute()`: gather, launch, its own blocking pull,
      finish), the caller gathering the futures: what the pool walk
      of `query/executor.py` did up to PR 37 (`_run_parallel`);
  (b) `one_thread`: on the calling thread alone, every program
      launched before ONE pull, with this script's own steps from the
      program's `gather_operands`, `run_segment_kernel` and finishers
      (the walk PR 33 tried and took out again; since PR 38
      `_walk_scans` does it with the program's own
      `execution.execute_segment_plans`, group-by ladders included:
      `ladder_contention.py`);

each with 0 and with 3 background threads that run pure Python and so
want the lock all the time (what a server's other runner threads do
while they group a cube answer's rows or encode a reply), and beside
each one launch alone, to its asynchronous return, and one host scalar's
way to the device (`jnp.int32(num_docs)`), the outputs pulled outside
the clock. A launch that costs a lock's switch interval (5 ms) more
beside the spinners than without them gave the lock away inside.

It runs the device the process is given: on the chip's host the TPU, so
the milliseconds are host times of a real launch path (never a kernel
time or a device metric); with JAX_PLATFORMS=cpu a rehearsal. It runs on
any checkout that has the benchmark (copy it into a parent's tree to
compare).

    python scripts/launch_contention.py [--rows N] [--queries 120]

At the configuration's 50M rows the build takes about 20 GB and half a
minute on 13 cores: run that on the chip's host; --rows 400000 is a
rehearsal.
"""
from __future__ import annotations

import argparse
import concurrent.futures
import json
import os
import shutil
import statistics
import sys
import tempfile
import threading
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_DIR = os.path.join(REPO, "benchmarks")
for _p in (REPO, BENCH_DIR):
    if _p not in sys.path:
        sys.path.insert(0, _p)

Q1_SHAPES = ("q1.1", "q1.2", "q1.3")
POOL_THREADS = 4          # the server's segment pool


def compile_request(pql: str):
    from pinot_tpu.pql.optimizer import BrokerRequestOptimizer
    from pinot_tpu.pql.parser import compile_pql
    return BrokerRequestOptimizer().optimize(compile_pql(pql))


def q1_plans(segments, shapes, seed: int, queries: int):
    """[[one plan a segment] a query]: `queries` literal tuples drawn
    from the seed, the three shapes in turn."""
    import numpy as np
    from pinot_tpu.query.plan import InstancePlanMaker
    maker = InstancePlanMaker()
    shapes = [s for s in shapes if s.name in Q1_SHAPES]
    picks = {s.name: iter(np.random.default_rng([seed, 4000 + i]).choice(
        s.domain_size, min(queries, s.domain_size), replace=False))
        for i, s in enumerate(shapes)}
    out = []
    for q in range(queries):
        shape = shapes[q % len(shapes)]
        request = compile_request(
            shape.pql(shape.literals(int(next(picks[shape.name])))))
        out.append([maker.make_segment_plan(seg, request)
                    for seg in segments])
    return out


def scalar_operands(plan, cols):
    """The plan's params and doc count as the checkout's own launch
    hands them over: since PR 33 integer scalars come from a table of
    device scalars (`execution._scalar_operands`), before it they went
    as host scalars."""
    from pinot_tpu.query import execution
    own = getattr(execution, "_scalar_operands", None)
    if own is not None:
        return own(plan, cols, ())
    return tuple(plan.params), plan.segment.num_docs


def launch(plan, cols):
    from pinot_tpu.ops import kernels
    seg = plan.segment
    params, num_docs = scalar_operands(plan, cols)
    return kernels.run_segment_kernel(
        seg.padded_docs, plan.filter_spec, plan.agg_specs, None,
        plan.select_spec, cols, params, num_docs)


def one_thread(plans):
    """One thread, every launch before one pull."""
    import jax
    from pinot_tpu.query import execution
    from pinot_tpu.query.blocks import IntermediateResultsBlock
    launched = [launch(plan, execution.gather_operands(plan))
                for plan in plans]
    outs_each = jax.device_get(launched)
    del launched
    blocks = []
    for plan, outs in zip(plans, outs_each):
        blk = IntermediateResultsBlock()
        execution._finish_aggregation(plan, outs, blk)
        execution._finish_selection_and_stats(plan, outs, blk, 0.0)
        blocks.append(blk)
    return blocks


def pooled(pool, plans):
    """A segment a task on the pool, each the solo path."""
    futures = [pool.submit(plan.execute) for plan in plans]
    return [f.result() for f in futures]


class Spinners:
    """`with Spinners(n):` n threads running pure Python meanwhile."""

    def __init__(self, n: int):
        self._stop = threading.Event()
        self._threads = [threading.Thread(target=self._spin, daemon=True)
                         for _ in range(n)]

    def _spin(self) -> None:
        x = 0
        while not self._stop.is_set():
            for _ in range(1000):
                x += 1

    def __enter__(self):
        for t in self._threads:
            t.start()
        return self

    def __exit__(self, *exc):
        self._stop.set()
        for t in self._threads:
            t.join(timeout=10)


def timed(walk, queries):
    """ms a query over `queries`, each answered once by `walk`."""
    ms = []
    for plans in queries:
        t0 = time.perf_counter()
        walk(plans)
        ms.append((time.perf_counter() - t0) * 1e3)
    q = statistics.quantiles(ms, n=10)
    return {"mean_ms": round(statistics.fmean(ms), 3),
            "p50_ms": round(statistics.median(ms), 3),
            "p90_ms": round(q[8], 3), "queries": len(ms)}


def launch_alone(queries):
    """One launch to its asynchronous return, its operands handed over
    as the checkout's own launch does (`scalar_operands`), and a host
    scalar's way to the device (`jnp.int32(num_docs)`); the outputs are
    pulled outside the clock so the device's queue stays short."""
    import jax
    import jax.numpy as jnp
    from pinot_tpu.query import execution
    launches, gather, scalar = [], [], []
    for plans in queries:
        for plan in plans:
            t0 = time.perf_counter()
            cols = execution.gather_operands(plan)
            t1 = time.perf_counter()
            outs = launch(plan, cols)
            t2 = time.perf_counter()
            jax.device_get(outs)
            t3 = time.perf_counter()
            n = jnp.int32(plan.segment.num_docs)
            t4 = time.perf_counter()
            jax.block_until_ready(n)
            gather.append((t1 - t0) * 1e3)
            launches.append((t2 - t1) * 1e3)
            scalar.append((t4 - t3) * 1e3)
    return {"launches": len(launches),
            "launch_mean_ms": round(statistics.fmean(launches), 4),
            "launch_p50_ms": round(statistics.median(launches), 4),
            "gather_mean_ms": round(statistics.fmean(gather), 4),
            "host_scalar_mean_ms": round(statistics.fmean(scalar), 4)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", default="ssb_flat_startree")
    ap.add_argument("--rows", type=int, default=0,
                    help="another scale than the configuration's")
    ap.add_argument("--seed", type=int, default=2147485003)
    ap.add_argument("--queries", type=int, default=120)
    ap.add_argument("--spinners", default="0,3")
    ap.add_argument("--workers", type=int,
                    default=max(1, (os.cpu_count() or 2) - 3))
    args = ap.parse_args(argv)

    from harness import build, shapes as shapes_mod, tables
    with open(os.path.join(BENCH_DIR, "configs",
                           f"{args.config}.json")) as fh:
        config = json.load(fh)
    if args.rows:
        config["rows"] = args.rows
    config["star_tree_configs"] = []
    work = tempfile.mkdtemp(prefix="launch_contention.")
    pool = concurrent.futures.ThreadPoolExecutor(POOL_THREADS)
    try:
        t0 = time.perf_counter()
        # spawned workers held to the CPU backend, before this process
        # has touched a device
        dirs = build.build_all(config, args.seed, work, REPO, args.workers)
        import jax
        from pinot_tpu.segment.loader import ImmutableSegmentLoader
        from pinot_tpu.utils.device import configure_compile_cache
        configure_compile_cache()
        segments = [ImmutableSegmentLoader.load(d) for d in dirs]
        device = jax.devices()[0]
        print(f"{config['rows']} rows, {len(segments)} segments: built "
              f"and loaded in {time.perf_counter() - t0:.1f} s; device "
              f"{device.platform} {device.device_kind}", file=sys.stderr)
        gen = tables.load_generator(config["generator"])
        shapes = shapes_mod.load_family(BENCH_DIR, "ssb", gen.pools())
        queries = q1_plans(segments, shapes, args.seed, args.queries)
        # every lane uploaded, every program compiled, both walks met
        for plans in queries[:6]:
            pooled(pool, plans)
            one_thread(plans)
        result = {"rows": config["rows"], "segments": len(segments),
                  "seed": args.seed, "host_cpus": os.cpu_count(),
                  "device": f"{device.platform} {device.device_kind}",
                  "scalars": "device table" if hasattr(
                      sys.modules["pinot_tpu.query.execution"],
                      "_scalar_operands") else "host",
                  "walks": {}}
        for n in [int(s) for s in args.spinners.split(",")]:
            with Spinners(n):
                row = {"pool": timed(lambda p: pooled(pool, p), queries),
                       "one_thread": timed(one_thread, queries),
                       "launch_alone": launch_alone(queries[:15])}
            result["walks"][f"{n}_spinners"] = row
            print(f"{n} thread(s) of pure Python beside: pool "
                  f"{row['pool']['mean_ms']:.2f} ms a query, one thread "
                  f"{row['one_thread']['mean_ms']:.2f}", file=sys.stderr)
        print(json.dumps(result))
    finally:
        pool.shutdown(wait=True)
        shutil.rmtree(work, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
