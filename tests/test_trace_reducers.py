"""The reducers PR 26 adds to `benchmarks/reducers/`, on synthetic
events: the growth of a counter, the named share of the device's
program time, and the alignment of spans (`startUs`, the wall clock)
with a device trace (relative to the profiler session's start) that
names the device's idle time. Nothing here is a measurement."""
import copy
import os
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_DIR = os.path.join(REPO, "benchmarks")
for p in (REPO, BENCH_DIR):
    if p not in sys.path:
        sys.path.insert(0, p)

from reducers import (counter_delta, trace_idle_spans,      # noqa: E402
                      trace_module_share)

PLANE = "/device:TPU:0"
US, MS, S = 1_000, 1_000_000, 1_000_000_000


def test_counter_delta_sums_growth_and_is_silent_without_the_counter():
    key = "server.metrics.meter.xlaCompiles.count"
    spec = {"params": {"keys": [key]}}
    ctx = {"counters": {"after": {key: 7, "x": 1},
                        "delta": {key: 3, "x": 1}}}
    assert counter_delta.reduce(ctx, spec) == 3
    ctx["counters"]["delta"][key] = 0
    assert counter_delta.reduce(ctx, spec) == 0
    # a program from before the counter existed: left out, not 0
    assert counter_delta.reduce(
        {"counters": {"after": {"x": 1}, "delta": {"x": 1}}}, spec) is None


def test_module_share_counts_program_time_by_name_prefix():
    events = [
        [PLANE, "XLA Modules", "jit_pinot_scan_agg(1)", 0, 900],
        [PLANE, "XLA Modules", "jit_convert_element_type(2)", 1000, 100],
        [PLANE, "XLA Ops", "%fusion", 0, 900],             # not a program
        ["/host:CPU", "XLA Modules", "jit_pinot_x(3)", 0, 5000],
    ]
    spec = {"params": {"prefix": "jit_pinot_"}}
    assert trace_module_share.reduce({"trace": {"events": events}},
                                     spec) == pytest.approx(90.0)
    assert trace_module_share.reduce({"trace": {"events": []}}, spec) is None
    assert trace_module_share.reduce({"trace": None}, spec) is None


def synthetic(zero_before_stamp_s=0.4, broken_pairs=0, start_us=True):
    """A 10 s slice whose session began `zero_before_stamp_s` before the
    slice's stamp: 20 scan programs of 100 us, one every 0.5 s, each
    inside the (kernelLaunch, kernelDispatch) pair of one segment of
    one traced request; one `operandGather` span over the slice's first
    5 s."""
    shift = time.time_ns() - time.monotonic_ns()
    zero = time.time_ns() - 100 * S               # the session's zero
    stamp = int(zero_before_stamp_s * S)          # on the session clock
    stop = stamp + 10 * S
    events, segments = [], []
    for k in range(20):
        t = stamp + 100 * MS + k * 500 * MS
        events.append([PLANE, "XLA Modules", "jit_pinot_scan_agg(7)", t,
                       100 * US])
        events.append([PLANE, "XLA Ops", "%fusion.1", t, 100 * US])
        events.append([PLANE, "XLA Modules", "jit_convert_element_type(9)",
                       t - 400 * US, 0])
        off = 10 * MS if k < broken_pairs else 0  # a pair off its module
        launch = {"name": "kernelLaunch", "ms": 0.2, "spanId": f"l{k}",
                  "startUs": (zero + t - 300 * US + off) // 1000}
        dispatch = {"name": "kernelDispatch", "ms": 0.3, "spanId": f"d{k}",
                    "startUs": (zero + t - 50 * US + off) // 1000}
        if not start_us:
            del launch["startUs"], dispatch["startUs"]
        segments.append({"name": "queryPlanExecution", "ms": 0.6,
                         "startUs": (zero + t - 320 * US) // 1000,
                         "children": [launch, dispatch]})
    gather = {"name": "operandGather", "ms": 5000.0,
              "startUs": (zero + stamp) // 1000}
    tree = {"name": "query", "ms": 10000.0, "startUs": (zero + stamp) // 1000,
            "children": [gather] + segments}
    request = {"traced": True, "error": None, "body": {"traceTree": tree},
               "t_send": (zero + stamp - shift) / 1e9,
               "t_recv": (zero + stop - shift) / 1e9}
    untraced = dict(request, traced=False, body={})
    return {"requests": [request, untraced],
            "trace": {"events": events, "busy_s": 20 * 100e-6,
                      "window_s": 10.0,
                      "slice": ((zero + stamp - shift) / 1e9,
                                (zero + stop - shift) / 1e9)}}, zero


def test_the_sessions_zero_is_recovered_to_a_millisecond(capfd):
    ctx, zero = synthetic()
    found, (s0, s1), _shift = trace_idle_spans.align(ctx)
    assert abs(found - zero) < 1 * MS
    assert abs(s0 - 400 * MS) < 1 * MS and abs(s1 - s0 - 10 * S) < 1 * MS
    assert "20 of 20 pairs hold a scan program (100.0%)" in \
        capfd.readouterr().err


def test_a_span_over_half_of_the_idle_time_reads_fifty():
    ctx, _zero = synthetic()
    spec = {"params": {"spans": ["operandGather"]}}
    # the span covers 5 s of the slice's 10 s, 10 of the 20 programs
    # inside it: 4.999 s of 9.998 s idle
    assert trace_idle_spans.reduce(ctx, spec) == pytest.approx(50.0,
                                                               abs=1e-3)
    # every leaf: the pairs' own spans add the idle time round a program
    leaves = trace_idle_spans.reduce(ctx, {"params": {}})
    assert 50.0 < leaves < 50.1
    # a span nobody opened
    assert trace_idle_spans.reduce(
        ctx, {"params": {"spans": ["starTreeExecute"]}}) == 0.0


@pytest.mark.parametrize("case,kwargs", [
    ("two of twenty pairs hold no program: 90% is under 95%",
     {"broken_pairs": 2}),
    ("the session began further before the stamp than the search goes",
     {"zero_before_stamp_s": 5.0}),
    ("spans of a program from before startUs existed", {"start_us": False}),
])
def test_no_alignment_no_number(case, kwargs, capfd):
    ctx, _zero = synthetic(**kwargs)
    assert trace_idle_spans.reduce(ctx, {"params": {}}) is None
    err = capfd.readouterr().err
    assert "left out" in err or "nothing to align" in err


def test_one_broken_pair_of_twenty_still_aligns():
    ctx, zero = synthetic(broken_pairs=1)
    found, _slice, _shift = trace_idle_spans.align(ctx)
    assert abs(found - zero) < 1 * MS


def test_no_device_trace_no_number():
    ctx, _zero = synthetic()
    cpu = copy.deepcopy(ctx)
    cpu["trace"].update(events=[], busy_s=0.0)      # the CPU rehearsal
    assert trace_idle_spans.reduce(cpu, {"params": {}}) is None
    assert trace_idle_spans.reduce(dict(ctx, trace=None),
                                   {"params": {}}) is None


def test_launch_pairs_follow_the_group_by_ladder():
    """A group-by plan launches several programs a segment: each
    kernelLaunch pairs with the kernelDispatch that follows it."""
    def span(name, start, ms):
        return {"name": name, "startUs": start, "ms": ms}
    node = {"name": "queryPlanExecution", "startUs": 0, "ms": 10,
            "children": [span("kernelDispatch", 2000, 1.0),
                         span("kernelLaunch", 1000, 0.5),
                         span("operandGather", 0, 1.0),
                         span("kernelLaunch", 4000, 0.5),
                         span("kernelDispatch", 5000, 2.0)]}
    assert trace_idle_spans.launch_pairs(node) == [
        (1000 * US, 3000 * US), (4000 * US, 7000 * US)]
