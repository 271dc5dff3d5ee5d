"""Share of the traced slice's idle device time, in %, during which a
span of a traced request was open: every leaf span, or only the spans
named in `params.spans`.

Two clocks have to be put together. Device events (`ctx["trace"]
["events"]`) are relative to the start of the profiler's session; spans
(`obs/tracing.py`) carry `startUs`, the wall clock; the slice's stamp
`ctx["trace"]["slice"][0]` was taken after `jax.profiler.start_trace`
had returned, so the session's zero lies up to `SEARCH_S` before it.
The zero is found from the run itself: every traced scan gives pairs
(start of a `kernelLaunch` span, end of the `kernelDispatch` span that
follows it under the same parent), and once the clocks are aligned a
`MODULE_PREFIX` program of the line `XLA Modules` lies inside each. The
zero is the one that puts most pairs right; unless at least `MIN_SHARE`
of the pairs then hold a program, nothing is returned and the reason is
said on standard error. Spans without `startUs` (a program from before
it existed) give no pairs, so nothing is returned.

The search (`find_zero`, `find_alignment`, `align`) is scaffolding: it
exists because `run.py` takes its trace through a launcher that cannot
say when the session began. The server's `POST /debug/profiler/stop`
returns that (`anchorWallNs`, which alone placed 192 of 192 scan
programs on the chip, PERF.md section 6). The `benchmark` PR that moves
`run.py` onto the endpoint (ROADMAP C12) deletes the three functions
and reads the zero from the stop's answer: one way, not two.

Only the requests a driver traced carry spans (`traced_share`), so the
share has a ceiling below 100: the share of the idle time with a traced
request in flight, printed on standard error beside the zero found.
"""
import bisect
import sys
import time

from harness import trace_reduce
from reducers.trace_module_share import module_events

#: how far before the slice's stamp the session's zero is looked for
SEARCH_S = 3.0
#: the least share of launch/dispatch pairs that must hold a program
MIN_SHARE = 0.95
#: the programs a launch/dispatch pair of a scan must hold
MODULE_PREFIX = "jit_pinot_scan"


def say(msg: str) -> None:
    print(f"trace_idle_spans: {msg}", file=sys.stderr, flush=True)


def walk(node, parent=None):
    yield node, parent
    for child in node.get("children") or ():
        yield from walk(child, node)


def span_interval(node):
    """(start_ns, end_ns) on the wall clock, or None without startUs."""
    start = node.get("startUs")
    if start is None:
        return None
    return int(start) * 1000, int(start) * 1000 + int(
        float(node.get("ms", 0.0)) * 1e6)


def traced_trees(ctx):
    for r in ctx["requests"]:
        tree = (r.get("body") or {}).get("traceTree")
        if r.get("traced") and not r.get("error") and tree:
            yield r, tree


def launch_pairs(tree):
    """[(launch start, dispatch end)] in wall ns: each `kernelLaunch`
    with the `kernelDispatch` that follows it under the same parent."""
    pairs = []
    for node, _parent in walk(tree):
        steps = sorted(
            (c for c in node.get("children") or ()
             if c.get("name") in ("kernelLaunch", "kernelDispatch")
             and c.get("startUs") is not None),
            key=lambda c: c["startUs"])
        for a, b in zip(steps, steps[1:]):
            if a["name"] == "kernelLaunch" and b["name"] == "kernelDispatch":
                pairs.append((span_interval(a)[0], span_interval(b)[1]))
    return pairs


def merged(intervals):
    return trace_reduce.union(sorted(intervals))


def overlap(a, b) -> int:
    """Total length of the intersection of two sorted disjoint lists."""
    i = j = total = 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def find_zero(pairs, modules, lo, hi):
    """The session's zero on the wall clock (ns) within [lo, hi] under
    which most pairs hold a module, and how many do. Pair (a, b) holds
    module (s, e) under zero z iff a - s <= z <= b - e: each pair
    contributes the union of those ranges; a sweep finds the deepest
    point."""
    starts = [s for s, _e in modules]
    edges = []
    for a, b in pairs:
        # modules whose start could fall at or after `a` for a z in range
        first = bisect.bisect_left(starts, a - hi)
        last = bisect.bisect_right(starts, b - lo)
        ranges = []
        for s, e in modules[first:last]:
            z0, z1 = max(a - s, lo), min(b - e, hi)
            if z1 >= z0:
                ranges.append((z0, z1))
        for z0, z1 in merged(ranges):
            edges.append((z0, 0))        # opens sort before closes
            edges.append((z1, 1))
    edges.sort()
    best, depth, best_range = 0, 0, None
    for k, (z, closing) in enumerate(edges):
        if closing:
            depth -= 1
            continue
        depth += 1
        if depth > best:
            best = depth
            best_range = (z, edges[k + 1][0])
    if best_range is None:
        return None, 0
    return (best_range[0] + best_range[1]) // 2, best


def align(ctx):
    """-> (zero on the wall clock in ns, the slice on the session
    clock, parent-monotonic-to-wall shift in ns), or None. Found once a
    run and kept on the trace for the metrics that share it."""
    trace = ctx["trace"]
    if "_idle_spans_zero" not in trace:
        trace["_idle_spans_zero"] = find_alignment(ctx)
    return trace["_idle_spans_zero"]


def find_alignment(ctx):
    trace = ctx["trace"]
    modules = [(s, e) for s, e, name in module_events(trace["events"])
               if name.startswith(MODULE_PREFIX)]
    pairs = [pair for _r, tree in traced_trees(ctx)
             for pair in launch_pairs(tree)]
    if not modules or not pairs:
        say(f"nothing to align: {len(modules)} scan programs in the "
            f"trace, {len(pairs)} kernelLaunch/kernelDispatch pairs")
        return None
    # the parent's monotonic clock -> the wall clock
    shift_ns = time.time_ns() - time.monotonic_ns()
    stamp = int(trace["slice"][0] * 1e9) + shift_ns
    stopped = int(trace["slice"][1] * 1e9) + shift_ns
    # pairs that ended before the stamp or began after the stop cannot
    # hold a module of the session
    pairs = [(a, b) for a, b in pairs if a >= stamp and b <= stopped]
    zero, held = find_zero(pairs, modules, stamp - int(SEARCH_S * 1e9),
                           stamp)
    share = held / len(pairs) if pairs else 0.0
    say(f"{held} of {len(pairs)} pairs hold a scan program "
        f"({100 * share:.1f}%) with the session's zero "
        f"{(stamp - zero) / 1e6 if zero is not None else float('nan'):.3f}"
        " ms before the slice's stamp")
    if zero is None or share < MIN_SHARE:
        say("the clocks could not be aligned: the metric is left out")
        return None
    return zero, (stamp - zero, stopped - zero), shift_ns


def reduce(ctx, spec):
    trace = ctx["trace"]
    if not trace or trace["busy_s"] <= 0:
        return None
    aligned = align(ctx)
    if aligned is None:
        return None
    zero, (s0, s1), shift_ns = aligned
    planes = trace_reduce.device_ops(trace["events"])
    busy = trace_reduce.union(
        (max(s, s0), min(e, s1))
        for s, e, _n in planes[sorted(planes)[0]] if e > s0 and s < s1)
    idle, at = [], s0
    for s, e in busy:
        if s > at:
            idle.append((at, s))
        at = max(at, e)
    if s1 > at:
        idle.append((at, s1))
    idle_ns = sum(e - s for s, e in idle)
    if idle_ns <= 0:
        return None
    only = set(spec["params"].get("spans") or ())
    spans, flights = [], []
    for r, tree in traced_trees(ctx):
        flights.append((int(r["t_send"] * 1e9) + shift_ns - zero,
                        int(r["t_recv"] * 1e9) + shift_ns - zero))
        for node, _parent in walk(tree):
            wanted = node.get("name") in only if only \
                else not node.get("children")
            span = span_interval(node) if wanted else None
            if span is not None and span[1] > span[0]:
                spans.append((span[0] - zero, span[1] - zero))
    if not only:
        say("ceiling: a traced request was in flight during "
            f"{100.0 * overlap(idle, merged(flights)) / idle_ns:.2f}% of "
            f"the slice's idle time ({idle_ns / 1e9:.3f} s idle)")
    return 100.0 * overlap(idle, merged(spans)) / idle_ns
