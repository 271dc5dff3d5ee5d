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
       which walks the ladders a segment program each: every segment's
       program of a rung launched before the rung's one pull (a
       checkout without `execute_segment_plans` skips the variant);
  (iii) `stacked`: the same task over the table's stack of lanes, ONE
       program a rung and spec over the segments (what `_walk_scans`
       does where `segment/loader.py` `LaneStacks` exists; a checkout
       without it skips the variant);

each with 1 and with 4 caller threads (the server's runner threads: 4
clients keep 4 queries in flight), all callers sharing the ONE pool.
Per variant: ms a query, queries a second, programs and pulls a query
(counted at `kernels.run_segment_kernel` and `jax.device_get`), and,
with 4 in flight, the device's busy share over a short `jax.profiler`
slice of its own (the union of the `XLA Ops` intervals over the slice's
wall time, `benchmarks/harness/trace_reduce.py`).

Work and waiting (PR 39): the walk runs under `obs/profiler.py`
`walk_clock`, as `_walk_scans` runs it, and a registry of this process
bound with `bind_server_metrics` gives, per variant, the walk's thread
CPU time outside its pulls (`walk_cpu_ms`) and its off-CPU time outside
them (`walk_offcpu_ms`) a walk, and the collections and their pause:
alone, the off-CPU time outside the pulls should be near 0; with 4 in
flight it is what the interpreter lock costs a walk without a broker.
`--gc-rounds N` then runs the walk with ONE query in flight with the
collector's hook off and on in turn, N rounds, and times a youngest
collection each way (`gc_hook_ab`); `annotation` is what a span costs
on the traced path, alone and with the server's `TraceAnnotation`,
without and with a profiler session open; `gap_cpu` is where a traced
walk's CPU time outside its spans goes: the spans' own bookkeeping, or
the walk's code between two spans, by boundary.

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

    python scripts/ladder_contention.py [--rows N] [--queries 24] \
        [--gc-rounds 3] [--queue 24]

At the configuration's 50M rows the build takes about 20 GB and half a
minute on 13 cores: run that on the chip's host; --rows 400000 is a
rehearsal.
"""
from __future__ import annotations

import argparse
import concurrent.futures
import gc
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


def answer_gap(a, b) -> float:
    """The widest relative gap between two answers' floats; keys,
    integers and structure must be equal (else an AssertionError)."""
    if isinstance(a, float) or isinstance(b, float):
        return abs(a - b) / max(abs(a), abs(b), 1e-300) if a != b else 0.0
    if isinstance(a, dict):
        assert a.keys() == b.keys(), "group keys differ"
        return max((answer_gap(a[k], b[k]) for k in a), default=0.0)
    if isinstance(a, (list, tuple)):
        assert len(a) == len(b), "lengths differ"
        return max((answer_gap(x, y) for x, y in zip(a, b)), default=0.0)
    assert a == b, (a, b)
    return 0.0


def pooled(pool, plans):
    """A segment a task on the pool, each the solo path."""
    futures = [pool.submit(plan.execute) for plan in plans]
    return [f.result() for f in futures]


def walked(pool, plans, stacks=None):
    """One task of the pool: every launch of a rung before its pull,
    under the walk's clock (`walkHostCpu` / `walkOffCpu`), as
    `_walk_scans` runs it: over the table's stack of lanes in `stacks`
    (a server's `segment/loader.py` `LaneStacks`), a program a rung and
    spec, or (None) a segment program a ladder over each segment's own
    lanes."""
    from pinot_tpu.obs.profiler import walk_clock
    from pinot_tpu.query import execution

    def walk():
        with walk_clock():
            if stacks is None:
                return execution.execute_segment_plans(plans)
            return execution.execute_segment_plans(plans, stacks=stacks)

    return pool.submit(walk).result()


class HostTimers:
    """The server's walk and collector timers, bound on a registry of
    this process (`bind_server_metrics`):
    `grown()` -> their growth since the last call, as ms a walk of work
    and of off-CPU time outside the pulls, and the collections' count
    and pause."""

    NAMES = ("walkHostCpu", "walkOffCpu", "gcPause")

    def __init__(self):
        from pinot_tpu.common.metrics import MetricsRegistry
        from pinot_tpu.obs.profiler import bind_server_metrics
        self.registry = MetricsRegistry("server")
        bind_server_metrics(self.registry)
        self.last = self.read()

    def read(self):
        return {n: (self.registry.timer(n).count,
                    self.registry.timer(n).total_ms) for n in self.NAMES}

    def grown(self) -> dict:
        now, last = self.read(), self.last
        self.last = now
        d = {n: (now[n][0] - last[n][0], now[n][1] - last[n][1])
             for n in self.NAMES}
        walks = d["walkHostCpu"][0]
        return {"walks": walks,
                "walk_cpu_ms": round(d["walkHostCpu"][1] / walks, 3)
                if walks else None,
                "walk_offcpu_ms": round(d["walkOffCpu"][1] / walks, 3)
                if walks else None,
                "gc_collections": d["gcPause"][0],
                "gc_pause_ms": round(d["gcPause"][1], 3)}


class Counted:
    """`with Counted() as n:` counts the launches and the pulls made
    meanwhile, whatever thread makes them: `n.programs`, `n.pulls`."""

    def __enter__(self):
        import jax
        from pinot_tpu.ops import kernels
        self._jax, self._kernels = jax, kernels
        self._get, self._run = jax.device_get, kernels.run_segment_kernel
        self._stacked = getattr(kernels, "run_stacked_segment_kernel", None)
        programs, pulls = itertools.count(), itertools.count()
        self._counters = programs, pulls

        def counted(launch):
            def run(*a, **k):
                next(programs)
                return launch(*a, **k)
            return run

        def get(x):
            next(pulls)
            return self._get(x)

        kernels.run_segment_kernel = counted(self._run)
        jax.device_get = get
        if self._stacked is not None:
            kernels.run_stacked_segment_kernel = counted(self._stacked)
        return self

    def __exit__(self, *exc):
        self._kernels.run_segment_kernel = self._run
        if self._stacked is not None:
            self._kernels.run_stacked_segment_kernel = self._stacked
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


def timed(answer, deck, callers: int, timers=None) -> dict:
    if timers is not None:
        timers.grown()
    with Counted() as n:
        ms, wall = drive(answer, deck, callers)
    q = statistics.quantiles(ms, n=10)
    return {"mean_ms": round(statistics.fmean(ms), 3),
            "p50_ms": round(statistics.median(ms), 3),
            "p90_ms": round(q[8], 3), "queries": len(ms),
            "queries_per_s": round(len(ms) / wall, 3),
            "programs_a_query": round(n.programs / len(ms), 3),
            "pulls_a_query": round(n.pulls / len(ms), 3),
            **(timers.grown() if timers is not None else {})}


def gc_hook_ab(answer, deck, rounds: int) -> dict:
    """The collector's hook off and on, in turn, around ONE query in
    flight (`rounds` of each): the rate each way, and what a collection
    of the youngest generation costs with and without the hook."""
    from pinot_tpu.obs.profiler import _on_gc as hook

    def young_collection_us(n: int = 20000) -> float:
        t0 = time.perf_counter()
        for _ in range(n):
            gc.collect(0)
        return round((time.perf_counter() - t0) / n * 1e6, 3)

    out = {"off": [], "on": [], "gen0_us": {"off": [], "on": []}}
    try:
        for _ in range(rounds):
            for side in ("off", "on"):
                if side == "off":
                    gc.callbacks.remove(hook)
                else:
                    gc.callbacks.append(hook)
                out["gen0_us"][side].append(young_collection_us())
                out[side].append(timed(answer, deck, 1)["queries_per_s"])
    finally:
        if hook not in gc.callbacks:
            gc.callbacks.append(hook)
    out["on_over_off"] = round(statistics.median(out["on"]) /
                               statistics.median(out["off"]), 4)
    return out


def annotation_cost(n: int = 20000) -> dict:
    """us a span costs on the traced path: a `TraceContext.span` alone
    (its ids, clocks, `cpuMs`) and with the server's
    `jax.profiler.TraceAnnotation` entered and left beside it, without
    and with a profiler session open. The annotation's enter runs
    before the span's clocks start and its exit after they stop: its
    cost lies outside the span's `ms`, in its parent's uncovered time."""
    import jax
    from pinot_tpu.obs.tracing import TraceContext

    def per_span(annotate) -> float:
        t = TraceContext(root_name="server", annotate=annotate)
        t0 = time.perf_counter()
        for _ in range(n):
            with t.span("kernelLaunch", segment="s"):
                pass
        return round((time.perf_counter() - t0) / n * 1e6, 3)

    out = {"span_us": per_span(None),
           "annotated_span_us": per_span(jax.profiler.TraceAnnotation)}
    log_dir = tempfile.mkdtemp(prefix="annotation.")
    options = jax.profiler.ProfileOptions()
    options.host_tracer_level = 1
    options.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=options)
    try:
        out["annotated_span_in_session_us"] = per_span(
            jax.profiler.TraceAnnotation)
        out["span_in_session_us"] = per_span(None)
    finally:
        jax.profiler.stop_trace()
        shutil.rmtree(log_dir, ignore_errors=True)
    return out


def gap_cpu(deck) -> dict:
    """Where a traced walk's uncovered CPU goes: the deck walked once,
    one query in flight, under a trace with the server's annotation and
    a `queryPlanExecution` span, as a traced request runs `_walk_scans`.
    Each span's open and close are read on its thread's CPU clock
    outside its bookkeeping as well as inside its clocks, so the CPU
    time that `queryPlanExecution`'s and the phases' children leave
    uncovered (`walk_unspanned_ms`, as CPU) splits into `bookkeeping`,
    the spans' own open and close outside their clocks (ids, the list,
    the annotation's enter and exit), and `glue`, the walk's code
    between two spans, by boundary "parent: before>after".
    -> ms a walk, by shape and in all; the wrapper's own four clock
    reads and its generator a span are in them."""
    import collections
    from contextlib import contextmanager
    from pinot_tpu.common.metrics import ServerQueryPhase
    from pinot_tpu.obs import profiler
    from pinot_tpu.obs.tracing import TraceContext
    from pinot_tpu.query import execution
    from pinot_tpu.server.query_executor import _trace_annotation
    real, clock = TraceContext.span, time.thread_time_ns
    parents = {ServerQueryPhase.QUERY_PLAN_EXECUTION, "groupScout",
               "groupHist", "groupTable"}

    @contextmanager
    def span(self, name, parent_id=None, **attrs):
        outer = clock()
        with real(self, name, parent_id, **attrs) as s:
            s["cpu_"] = [outer, clock()]
            try:
                yield s
            finally:
                s["cpu_"].append(clock())
        s["cpu_"].append(clock())

    def boundaries(trace):
        """(parent: before>after, bookkeeping ns, glue ns) a boundary."""
        kids = collections.defaultdict(list)
        for s in trace.spans:
            if "cpu_" in s:
                kids[s["parentId"]].append(s)
        for p in trace.spans:
            if p["name"] not in parents or "cpu_" not in p:
                continue
            end_in = end_out = p["cpu_"][1]
            before = "open"
            for k in kids[p["spanId"]] + [None]:
                start_out, start_in, after = (
                    (p["cpu_"][2], p["cpu_"][2], "close") if k is None
                    else (k["cpu_"][0], k["cpu_"][1], k["name"]))
                yield (f"{p['name']}: {before}>{after}",
                       (end_out - end_in) + (start_in - start_out),
                       start_out - end_out)
                if k is not None:
                    end_in, end_out, before = k["cpu_"][2], k["cpu_"][3], \
                        k["name"]

    shapes = collections.defaultdict(lambda: [0, 0, 0, 0])
    by_boundary = collections.Counter()
    TraceContext.span = span
    try:
        for name, plans in deck:
            trace = TraceContext(root_name="server",
                                 annotate=_trace_annotation)
            with profiler.active(profiler.QueryProfile(), trace), \
                    profiler.obs_span(
                        ServerQueryPhase.QUERY_PLAN_EXECUTION,
                        segments=len(plans)):
                execution.execute_segment_plans(plans)
            row = shapes[name]
            row[0] += 1
            for where, book, glue in boundaries(trace):
                row[1] += 1
                row[2] += book
                row[3] += glue
                by_boundary[where] += glue
    finally:
        TraceContext.span = real
    walks = sum(r[0] for r in shapes.values())

    def ms_a_walk(ns, n):
        return round(ns / n / 1e6, 4)

    out = {name: {"walks": n, "boundaries": round(b / n, 1),
                  "uncovered_cpu_ms": ms_a_walk(book + glue, n),
                  "bookkeeping_ms": ms_a_walk(book, n),
                  "glue_ms": ms_a_walk(glue, n)}
           for name, (n, b, book, glue) in shapes.items()}
    out["glue_by_boundary_ms"] = {
        where: ms_a_walk(ns, walks)
        for where, ns in by_boundary.most_common(10)}
    return out


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
    ap.add_argument("--gc-rounds", type=int, default=3,
                    help="rounds of the collector hook off and on with "
                    "one query in flight (0 to skip)")
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
        from pinot_tpu.segment import loader
        from pinot_tpu.utils.device import configure_compile_cache
        configure_compile_cache()
        segments = [loader.ImmutableSegmentLoader.load(d) for d in dirs]
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
        if hasattr(loader, "LaneStacks"):
            stacks = loader.LaneStacks()
            walks["stacked"] = lambda plans: walked(pool, plans, stacks)
        # every lane uploaded, every program compiled, and the walks'
        # answers the same: integers and keys to the digit, float sums to
        # their rounding (a stacked program's members may round a block
        # sum otherwise than their segment's own program on a TPU)
        t0 = time.perf_counter()
        float_gap = 0.0
        for _name, plans in deck:
            answers = [[(b.group_map, b.agg_intermediates) for b in w(plans)]
                       for w in walks.values()]
            for a in answers[1:]:
                float_gap = max(float_gap, answer_gap(a, answers[0]))
            assert float_gap < 1e-6, (_name, float_gap)
        print(f"{len(deck)} queries answered once by each walk in "
              f"{time.perf_counter() - t0:.1f} s; widest float gap "
              f"{float_gap:.3g}", file=sys.stderr)
        result = {"config": args.config, "rows": config["rows"],
                  "segments": len(segments), "seed": args.seed,
                  "float_gap": float_gap,
                  "host_cpus": os.cpu_count(),
                  "device": f"{device.platform} {device.device_kind}",
                  "shapes": list(SHAPES), "walks": {}}
        timers = HostTimers()
        for callers in IN_FLIGHT:
            row = {name: timed(w, deck, callers, timers)
                   for name, w in walks.items()}
            if callers == max(IN_FLIGHT) and args.trace_seconds:
                for name, w in walks.items():
                    row[name]["device"] = busy_share(
                        w, deck, callers, args.trace_seconds, work)
            result["walks"][f"{callers}_in_flight"] = row
            print(f"{callers} in flight: " + ", ".join(
                f"{name} {r['mean_ms']:.2f} ms a query "
                f"({r['pulls_a_query']:.1f} pulls; work "
                f"{r.get('walk_cpu_ms')}, off-CPU {r.get('walk_offcpu_ms')} "
                f"ms a walk)"
                for name, r in row.items()), file=sys.stderr)
        if args.gc_rounds and "walk" in walks:
            result["gc_hook_ab"] = gc_hook_ab(walks["walk"], deck,
                                              args.gc_rounds)
        result["annotation"] = annotation_cost()
        if "walk" in walks:
            result["gap_cpu"] = gap_cpu(deck)
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
