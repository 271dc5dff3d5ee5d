"""A group-by's ladder over eight segments, walked two ways, with one and
with four queries in flight: what a query costs when four pool threads
each drive a segment's ladder to its end (a launch, a pull, a launch, a
pull), and when one thread launches every segment's program of a rung
before ONE pull; how busy each walk keeps the device; and whether a
program's temp bytes are taken when it is queued or when it runs.

The twin of `launch_contention.py`, which does the same for a Q1.x scan's
one launch a segment. Builds a no-cube configuration's segments
(`benchmarks/harness/build.py`, rows drawn from --seed), loads them,
plans drawn literal tuples of the shapes q2.2, q3.2, q4.1 and q1.1 of
`benchmarks/shapes/ssb.json` with the program's planner, answers every
query once by each walk (lanes uploaded, programs compiled, the two
walks' blocks compared), and then answers the deck

  (i)  `pool`: a segment a task on 4 pool threads, each task the solo
       path (`plan.execute()`: gather, then every rung launched and
       pulled by the task itself), the caller gathering the futures:
       the pool walk of scan routes that `query/executor.py` had up to
       PR 37;
  (ii) `walk`: ONE task of the same pool, the caller waiting on it,
       which runs `execution.execute_segment_plans`: every segment's
       program of a rung launched before the rung's one pull (what
       `_walk_scans` does since PR 38; a checkout without it skips the
       variant);

each with 1 and with 4 caller threads (the server's runner threads: 4
clients keep 4 queries in flight), all callers sharing the ONE pool.
Per variant: ms a query, queries a second, programs and pulls a query
(counted at `kernels.run_segment_kernel` and `jax.device_get`), and,
with 4 in flight, the device's busy share over a short `jax.profiler`
slice of its own (the union of the `XLA Ops` intervals over the slice's
wall time, `benchmarks/harness/trace_reduce.py`).

The temp-bytes question (`--queue N`, 0 to skip): q3.1's DENSE table at
g 2048, the widest program a no-cube cell runs (about 0.8 GB of temp
with a value lane, 1.0 GB with 4 part lanes: `PERF.md` section 4), is
compiled (`memory_analysis().temp_size_in_bytes`), run once, and then
queued N times over the eight segments WITHOUT a pull: each launch's
host milliseconds and the device's `bytes_in_use` / `peak_bytes_in_use`
after it are printed, and what the N-th did (returned, waited, raised).
N x temp must pass the device's `bytes_limit` for the answer to show.

It runs the device the process is given: on the chip's host the TPU, so
the milliseconds are host times of a real launch path and the busy share
is the chip's; with JAX_PLATFORMS=cpu a rehearsal (no device plane: the
busy share is left out, and `memory_stats()` is None).

    python scripts/ladder_contention.py [--rows N] [--queries 24]

At the configuration's 50M rows the build takes about 20 GB and half a
minute on 13 cores: run that on the chip's host; --rows 400000 is a
rehearsal.
"""
from __future__ import annotations

import argparse
import concurrent.futures
import itertools
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

SHAPES = ("q2.2", "q3.2", "q4.1", "q1.1")
POOL_THREADS = 4          # the server's segment pool
IN_FLIGHT = (1, 4)        # caller threads: one client, the cell's four


def compile_request(pql: str):
    from pinot_tpu.pql.optimizer import BrokerRequestOptimizer
    from pinot_tpu.pql.parser import compile_pql
    return BrokerRequestOptimizer().optimize(compile_pql(pql))


def shape_plans(segments, shapes, names, seed: int, queries: int):
    """[(shape name, [one plan a segment])]: `queries` literal tuples a
    shape drawn from the seed, the shapes in turn."""
    import numpy as np
    from pinot_tpu.query.plan import InstancePlanMaker, preprocess_request
    maker = InstancePlanMaker()
    by_shape = []
    for i, shape in enumerate(s for s in shapes if s.name in names):
        picks = np.random.default_rng([seed, 5000 + i]).choice(
            shape.domain_size, min(queries, shape.domain_size),
            replace=False)
        requests = [preprocess_request(segments, compile_request(
            shape.pql(shape.literals(int(pick))))) for pick in picks]
        by_shape.append([(shape.name, [maker.make_segment_plan(seg, request)
                                       for seg in segments])
                         for request in requests])
    # the shapes in turn, as a client's deck deals them
    return [query for turn in zip(*by_shape) for query in turn]


def pooled(pool, plans):
    """A segment a task on the pool, each the solo path."""
    futures = [pool.submit(plan.execute) for plan in plans]
    return [f.result() for f in futures]


def walked(pool, plans):
    """One task of the pool: every launch of a rung before its pull."""
    from pinot_tpu.query import execution
    return pool.submit(execution.execute_segment_plans, plans).result()


class Counted:
    """`with Counted() as n:` counts the launches and the pulls made
    meanwhile, whatever thread makes them: `n.programs`, `n.pulls`."""

    def __enter__(self):
        import jax
        from pinot_tpu.ops import kernels
        self._jax, self._kernels = jax, kernels
        self._get, self._run = jax.device_get, kernels.run_segment_kernel
        programs, pulls = itertools.count(), itertools.count()
        self._counters = programs, pulls

        def run(*a, **k):
            next(programs)
            return self._run(*a, **k)

        def get(x):
            next(pulls)
            return self._get(x)

        kernels.run_segment_kernel, jax.device_get = run, get
        return self

    def __exit__(self, *exc):
        self._kernels.run_segment_kernel = self._run
        self._jax.device_get = self._get
        self.programs, self.pulls = (next(c) for c in self._counters)


def drive(answer, deck, callers: int, seconds: float = 0.0):
    """`callers` threads answer the deck's queries, each one query at a
    time: once through the deck, or round it for `seconds`.
    -> ([ms a query], wall seconds)."""
    turn = iter(deck) if not seconds else itertools.cycle(deck)
    lock, ms = threading.Lock(), []
    t_end = time.perf_counter() + seconds if seconds else None

    def caller():
        while t_end is None or time.perf_counter() < t_end:
            with lock:
                plans = next(turn, None)
            if plans is None:
                return
            t0 = time.perf_counter()
            answer(plans[1])
            ms.append((time.perf_counter() - t0) * 1e3)

    threads = [threading.Thread(target=caller) for _ in range(callers)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return ms, time.perf_counter() - t0


def timed(answer, deck, callers: int) -> dict:
    with Counted() as n:
        ms, wall = drive(answer, deck, callers)
    q = statistics.quantiles(ms, n=10)
    return {"mean_ms": round(statistics.fmean(ms), 3),
            "p50_ms": round(statistics.median(ms), 3),
            "p90_ms": round(q[8], 3), "queries": len(ms),
            "queries_per_s": round(len(ms) / wall, 3),
            "programs_a_query": round(n.programs / len(ms), 3),
            "pulls_a_query": round(n.pulls / len(ms), 3)}


def busy_share(answer, deck, callers: int, seconds: float, work: str):
    """The device's busy share while `callers` threads answer the deck
    for `seconds` under a `jax.profiler` session of its own; None where
    the trace holds no device plane (the CPU rehearsal)."""
    import jax
    from harness import trace_extract, trace_reduce
    log_dir = tempfile.mkdtemp(prefix="trace.", dir=work)
    options = jax.profiler.ProfileOptions()
    options.host_tracer_level = 0
    options.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=options)
    try:
        ms, wall = drive(answer, deck, callers, seconds)
    finally:
        jax.profiler.stop_trace()
    events = trace_extract.extract(log_dir)["events"]
    shutil.rmtree(log_dir, ignore_errors=True)
    if not trace_reduce.device_ops(events):
        return None
    busy = trace_reduce.busy_seconds(events)
    return {"busy_s": round(busy, 4), "slice_s": round(wall, 4),
            "busy_pct": round(100 * busy / wall, 2),
            "queries_per_s": round(len(ms) / wall, 3)}


def widest_table(plan, g_side: int = 16):
    """(jitted program, its operands) of `plan`'s DENSE table over a key
    space of g_side x g_side x 8 = 2048 groups: the phase-B spec that
    `adaptive_phase_b_spec` derives for a q3.1 whose nations span 16
    dictIds a side and whose filter keeps a twentieth of the rows (kmax
    0: dense). Only its bytes are asked about, not its answer."""
    from pinot_tpu.ops import kernels
    from pinot_tpu.query import execution
    from pinot_tpu.query.plan import adaptive_phase_b_spec
    seg = plan.segment
    scout = [("bounds", 0, g_side - 1), ("bounds", 0, g_side - 1),
             ("bounds", 0, 7)]
    kspec, _fspec, extra, empty = adaptive_phase_b_spec(
        plan.group_spec, scout, seg.num_docs // 20, seg.padded_docs,
        seg.num_docs)
    assert not empty and kspec[2] == 2048 and not kspec[4], kspec
    cols = execution.gather_operands(plan)
    params, num_docs = execution._scalar_operands(plan, cols, extra)
    fn = kernels.get_segment_kernel(seg.padded_docs, plan.filter_spec, (),
                                    kspec, plan.select_spec)
    return fn, (cols, tuple(params), num_docs)


def queue_without_pulling(plans, n: int) -> dict:
    """The temp-bytes question: the widest table queued `n` times over
    the segments with no pull between; see the module's docstring."""
    import jax
    device = jax.devices()[0]

    def stats():
        s = device.memory_stats() or {}
        return {k: s.get(k) for k in ("bytes_in_use", "peak_bytes_in_use")}

    programs = [widest_table(plan) for plan in plans]
    fn, args = programs[0]
    mem = fn.lower(*args).compile().memory_analysis()
    out = {"temp_bytes": int(getattr(mem, "temp_size_in_bytes", -1)),
           "output_bytes": int(getattr(mem, "output_size_in_bytes", -1)),
           "bytes_limit": (device.memory_stats() or {}).get("bytes_limit"),
           "queued": n}
    t0 = time.perf_counter()
    for fn, args in programs:                    # compiled, run, dropped
        jax.block_until_ready(fn(*args))
    out["one_each_ms"] = round((time.perf_counter() - t0) * 1e3
                               / len(programs), 3)
    out["resident"] = stats()
    launched, rows = [], []
    for k in range(n):
        fn, args = programs[k % len(programs)]
        t0 = time.perf_counter()
        try:
            launched.append(fn(*args))
            did = "returned"
        except Exception as exc:                 # noqa: BLE001 - the reading
            did = f"raised {type(exc).__name__}: {str(exc)[:200]}"
        rows.append({"k": k + 1, "did": did, "launch_ms": round(
            (time.perf_counter() - t0) * 1e3, 3), **stats()})
        if did != "returned":
            break
    t0 = time.perf_counter()
    try:
        jax.block_until_ready(launched)
        out["drain"] = "done"
    except Exception as exc:                     # noqa: BLE001 - the reading
        out["drain"] = f"raised {type(exc).__name__}: {str(exc)[:200]}"
    out["drain_ms"] = round((time.perf_counter() - t0) * 1e3, 3)
    out["launches"] = rows
    out["after"] = stats()
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", default="ssb_flat_nocube")
    ap.add_argument("--rows", type=int, default=0,
                    help="another scale than the configuration's")
    ap.add_argument("--seed", type=int, default=2147485003)
    ap.add_argument("--queries", type=int, default=24,
                    help="literal tuples a shape")
    ap.add_argument("--trace-seconds", type=float, default=1.5)
    ap.add_argument("--queue", type=int, default=24,
                    help="launches of the widest table without a pull")
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
    work = tempfile.mkdtemp(prefix="ladder_contention.")
    pool = concurrent.futures.ThreadPoolExecutor(POOL_THREADS)
    try:
        t0 = time.perf_counter()
        # spawned workers held to the CPU backend, before this process
        # has touched a device
        dirs = build.build_all(config, args.seed, work, REPO, args.workers)
        import jax
        from pinot_tpu.query import execution
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
        deck = shape_plans(segments, shapes, SHAPES, args.seed,
                           args.queries)
        walks = {"pool": lambda plans: pooled(pool, plans)}
        if hasattr(execution, "execute_segment_plans"):
            walks["walk"] = lambda plans: walked(pool, plans)
        # every lane uploaded, every program compiled, and the walks'
        # answers the same
        t0 = time.perf_counter()
        for _name, plans in deck:
            answers = [[(b.group_map, b.agg_intermediates) for b in w(plans)]
                       for w in walks.values()]
            assert all(a == answers[0] for a in answers), _name
        print(f"{len(deck)} queries answered once by each walk in "
              f"{time.perf_counter() - t0:.1f} s", file=sys.stderr)
        result = {"config": args.config, "rows": config["rows"],
                  "segments": len(segments), "seed": args.seed,
                  "host_cpus": os.cpu_count(),
                  "device": f"{device.platform} {device.device_kind}",
                  "shapes": list(SHAPES), "walks": {}}
        for callers in IN_FLIGHT:
            row = {name: timed(w, deck, callers)
                   for name, w in walks.items()}
            if callers == max(IN_FLIGHT) and args.trace_seconds:
                for name, w in walks.items():
                    row[name]["device"] = busy_share(
                        w, deck, callers, args.trace_seconds, work)
            result["walks"][f"{callers}_in_flight"] = row
            print(f"{callers} in flight: " + ", ".join(
                f"{name} {r['mean_ms']:.2f} ms a query "
                f"({r['pulls_a_query']:.1f} pulls)"
                for name, r in row.items()), file=sys.stderr)
        if args.queue:
            q31 = [s for s in shapes if s.name == "q3.1"]
            (_name, plans), = shape_plans(segments, q31, ("q3.1",),
                                          args.seed, 1)
            result["queue"] = queue_without_pulling(plans, args.queue)
        print(json.dumps(result))
    finally:
        pool.shutdown(wait=True)
        shutil.rmtree(work, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
