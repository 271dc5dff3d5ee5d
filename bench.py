"""Benchmark: SSB Q1.1–Q4.3 (13 queries), TPU engine vs CPU columnar scan.

Matches BASELINE.md's north star ("≥8× p50 latency vs CPU on SSB Q1.1–Q4.3,
identical result rows") and the reference's contrib/pinot-druid-benchmark
harness shape (flattened star schema, PQL aggregations — PQL 0.2.0 has no
expression aggregations, so Q1.x sums lo_revenue and Q4.x returns
SUM(lo_revenue), SUM(lo_supplycost) as separate aggregations, the standard
Pinot adaptation).

Two stages:
1. STORAGE PATH (the headline): PINOT_TPU_BENCH_STORE_ROWS rows (default
   50M, 8 segments — the BASELINE config-#5 shape at the largest size the
   single-core host build affords) go through the framework's OWN path
   end-to-end — rows → SegmentCreator (per-segment dictionary build,
   bit-packed fwd) → disk → ImmutableSegmentLoader → union-dictionary
   stack → HBM upload (throughput reported as its own metric). Every
   query's result is checked against the numpy oracle, then timed: device
   timing is PIPELINED (N back-to-back dispatches, one final sync — steady
   state of a loaded server) plus the measured host finish (group decode /
   reduce). CPU baseline: vectorized numpy over id-domain columns of the
   same table.
2. LARGE SYNTH (secondary, PINOT_TPU_BENCH_ROWS rows, default 100M —
   auto-skipped when stage 1 already runs at that scale): same 13 queries
   with column lanes synthesized directly in HBM (the host-side 100M-row
   build exceeds the single-core wall budget; the storage path itself is
   exercised and timed in stage 1, and its HBM-upload rate lets the
   claims compose). CPU baseline runs on an identically-distributed host
   table at the same row count.

Prints ONE JSON line:
  {"metric": "ssb13_storage_path_p50_speedup_vs_cpu", "value": p50 speedup
   over the 13 queries through the framework's own load path, "unit": "x",
   "vs_baseline": value / 8.0, ...per-query and large-synth detail...}

Env knobs: PINOT_TPU_BENCH_STORE_ROWS (100_000_000 — auto-scaled DOWN to
fit the wall budget from a measured creator-rate probe; at the default the
storage path runs at reference scale and stage 2 is skipped),
PINOT_TPU_BENCH_ROWS (100_000_000), PINOT_TPU_BENCH_SEGMENTS (8),
PINOT_TPU_BENCH_REPS (5), PINOT_TPU_BENCH_SKIP_BIG (0),
PINOT_TPU_BENCH_TOTAL_BUDGET_S (2400 — global wall-clock watchdog).

Exits non-zero when JAX finds no TPU and on any error; the compact JSON
line printed then carries what was measured before the failure.
"""
from __future__ import annotations

import json
import os
import signal
import sys
import tempfile
import time

import numpy as np


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def median(xs):
    return float(np.median(np.asarray(xs)))


# ---------------------------------------------------------------------------
# Wall-clock discipline: the driver kills the process at an unknown window
# (r2+r3 post-mortems: rc=124 with the summary unprinted, and the recorded
# 2000-char output tail truncated the per-query JSON mid-line). Three rules:
#   1. a GLOBAL deadline (PINOT_TPU_BENCH_TOTAL_BUDGET_S, default 2400s)
#      drives row-count auto-scaling and per-query skip decisions;
#   2. the final line printed is a COMPACT JSON (<~1800 chars) so it
#      survives whole inside a 2000-char tail, with full detail in
#      bench_detail.json next to this file;
#   3. SIGTERM/SIGINT emit whatever has been measured so far, then exit
#      with the signal's code (a killed run is not a completed one).
# ---------------------------------------------------------------------------

T_START = time.monotonic()
TOTAL_BUDGET_S = float(os.environ.get("PINOT_TPU_BENCH_TOTAL_BUDGET_S",
                                      "2400"))
DEADLINE = T_START + TOTAL_BUDGET_S
_RESULT: dict = {"metric": "ssb13_storage_path_p50_speedup_vs_cpu",
                 "value": 0.0, "unit": "x", "vs_baseline": 0.0,
                 "note": "startup"}
_EMITTED = False


def remaining_s() -> float:
    return DEADLINE - time.monotonic()


def _compact(result: dict) -> dict:
    """Headline + per-query entries small enough that the driver's
    2000-char tail holds the whole line."""
    out = {k: result[k] for k in ("metric", "value", "unit", "vs_baseline")
           if k in result}
    for k in ("storage_rows", "min_query_speedup", "storage_build_s",
              "note", "error"):
        if k in result:
            out[k] = result[k]
    def shrink(pq):
        # [device_p50_ms, cpu_p50_ms, speedup] triplets (see pq_cols);
        # "skip"/"err" strings for queries that didn't complete
        c = {}
        for name, e in (pq or {}).items():
            if "speedup" in e:
                c[name] = [e["device_p50_ms"], e["cpu_p50_ms"],
                           e["speedup"]]
            else:
                c[name] = "skip" if "skipped" in e else "err"
        return c
    if "per_query" in result:
        out["pq_cols"] = ["device_p50_ms", "cpu_p50_ms", "speedup"]
        out["per_query"] = shrink(result["per_query"])
    vec = result.get("vector")
    if isinstance(vec, dict):
        out["vector"] = {
            "value": vec.get("value"), "pass": vec.get("pass"),
            "rungs": {name: (r.get("speedup") if "speedup" in r
                             else "skip" if "skipped" in r else "err")
                      for name, r in (vec.get("rungs") or {}).items()}}
    big = result.get("big_synth")
    if isinstance(big, dict) and big.get("per_query"):
        out["big_synth"] = {"rows": big.get("rows"),
                            "p50_speedup": big.get("p50_speedup"),
                            "per_query": shrink(big["per_query"])}
    elif isinstance(big, dict):
        # skipped/errored stage 2 must be distinguishable from
        # "not configured" in the tail-surviving line
        out["big_synth"] = {k: big[k] for k in ("skipped", "error")
                            if k in big}
    return out


def emit_final(result: dict) -> None:
    """Full detail → bench_detail.json + stdout; compact line LAST."""
    global _EMITTED
    if _EMITTED:
        return
    _EMITTED = True
    try:
        detail_path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                   "bench_detail.json")
        with open(detail_path, "w") as fh:
            json.dump(result, fh, indent=1)
        log(f"bench: full detail written to {detail_path}")
    except OSError as e:
        log(f"bench: could not write detail file ({e})")
    sys.stderr.flush()
    print(json.dumps(_compact(result)), flush=True)


def _on_term(signum, frame):  # noqa: ARG001 — signal signature
    log(f"bench: signal {signum} — emitting measured-so-far and exiting")
    emit_final(_RESULT)
    sys.stdout.flush()
    os._exit(128 + signum)


# ---------------------------------------------------------------------------
# The 13 SSB queries, flattened-lineorder PQL
# ---------------------------------------------------------------------------

SSB_PQLS = {
    "q1.1": "SELECT SUM(lo_revenue) FROM lineorder WHERE d_year = 1993 AND "
            "lo_discount BETWEEN 1 AND 3 AND lo_quantity < 25",
    "q1.2": "SELECT SUM(lo_revenue) FROM lineorder WHERE d_yearmonthnum = "
            "199401 AND lo_discount BETWEEN 4 AND 6 AND lo_quantity "
            "BETWEEN 26 AND 35",
    "q1.3": "SELECT SUM(lo_revenue) FROM lineorder WHERE d_weeknuminyear = "
            "6 AND d_year = 1994 AND lo_discount BETWEEN 5 AND 7 AND "
            "lo_quantity BETWEEN 26 AND 35",
    "q2.1": "SELECT SUM(lo_revenue) FROM lineorder WHERE p_category = "
            "'MFGR#12' AND s_region = 'AMERICA' GROUP BY d_year, p_brand1 "
            "TOP 10000",
    "q2.2": "SELECT SUM(lo_revenue) FROM lineorder WHERE p_brand1 BETWEEN "
            "'MFGR#2221' AND 'MFGR#2228' AND s_region = 'ASIA' GROUP BY "
            "d_year, p_brand1 TOP 10000",
    "q2.3": "SELECT SUM(lo_revenue) FROM lineorder WHERE p_brand1 = "
            "'MFGR#2221' AND s_region = 'EUROPE' GROUP BY d_year, p_brand1 "
            "TOP 10000",
    "q3.1": "SELECT SUM(lo_revenue) FROM lineorder WHERE c_region = 'ASIA' "
            "AND s_region = 'ASIA' AND d_year BETWEEN 1992 AND 1997 GROUP "
            "BY c_nation, s_nation, d_year TOP 10000",
    # c_city × s_city × d_year spans 437k potential groups — past the
    # default numGroupsLimit; the per-query option (reference parity)
    # routes these to the scatter group path instead of the host
    "q3.2": "SELECT SUM(lo_revenue) FROM lineorder WHERE c_nation = "
            "'UNITED STATES' AND s_nation = 'UNITED STATES' AND d_year "
            "BETWEEN 1992 AND 1997 GROUP BY c_city, s_city, d_year "
            "TOP 10000 OPTION(numGroupsLimit=4194304)",
    "q3.3": "SELECT SUM(lo_revenue) FROM lineorder WHERE c_city IN "
            "('UNITED KI1', 'UNITED KI5') AND s_city IN ('UNITED KI1', "
            "'UNITED KI5') AND d_year BETWEEN 1992 AND 1997 GROUP BY "
            "c_city, s_city, d_year TOP 10000 "
            "OPTION(numGroupsLimit=4194304)",
    "q3.4": "SELECT SUM(lo_revenue) FROM lineorder WHERE c_city IN "
            "('UNITED KI1', 'UNITED KI5') AND s_city IN ('UNITED KI1', "
            "'UNITED KI5') AND d_yearmonth = 'Dec1997' GROUP BY c_city, "
            "s_city, d_year TOP 10000 OPTION(numGroupsLimit=4194304)",
    "q4.1": "SELECT SUM(lo_revenue), SUM(lo_supplycost) FROM lineorder "
            "WHERE c_region = 'AMERICA' AND s_region = 'AMERICA' AND "
            "p_mfgr IN ('MFGR#1', 'MFGR#2') GROUP BY d_year, c_nation "
            "TOP 10000",
    "q4.2": "SELECT SUM(lo_revenue), SUM(lo_supplycost) FROM lineorder "
            "WHERE c_region = 'AMERICA' AND s_region = 'AMERICA' AND "
            "d_year IN (1997, 1998) AND p_mfgr IN ('MFGR#1', 'MFGR#2') "
            "GROUP BY d_year, s_nation, p_category TOP 10000",
    "q4.3": "SELECT SUM(lo_revenue), SUM(lo_supplycost) FROM lineorder "
            "WHERE c_region = 'AMERICA' AND s_nation = 'UNITED STATES' "
            "AND d_year IN (1997, 1998) AND p_category = 'MFGR#14' GROUP "
            "BY d_year, s_city, p_brand1 TOP 10000 "
            "OPTION(numGroupsLimit=4194304)",
}


# ---------------------------------------------------------------------------
# CPU baseline + oracle: vectorized numpy over id-domain columns
# ---------------------------------------------------------------------------


def make_cpu_queries(pools, ids, supplycost):
    """name → fn; scalar queries return float, group queries return
    {(decoded key strings...): (sum_revenue[, sum_supplycost])}."""
    rev_vals = pools["lo_revenue"].astype(np.float64)

    def vid(col, value):
        i = int(np.searchsorted(pools[col], value))
        assert str(pools[col][i]) == str(value), (col, value)
        return i

    def vids(col, values):
        return np.array([vid(col, v) for v in values], np.int32)

    def rng_ids(col, lo, hi):
        """[lo, hi] inclusive value range → [lo_id, hi_id) id interval."""
        a = int(np.searchsorted(pools[col], lo, side="left"))
        b = int(np.searchsorted(pools[col], hi, side="right"))
        return a, b

    def revenue_sum(mask):
        h = np.bincount(ids["lo_revenue"][mask],
                        minlength=len(rev_vals))
        return float(h @ rev_vals)

    def group(mask, gcols, with_cost):
        key = np.zeros(int(mask.sum()), np.int64)
        cards = []
        for c in gcols:
            card = len(pools[c])
            key = key * card + ids[c][mask]
            cards.append(card)
        n_groups = int(np.prod([len(pools[c]) for c in gcols]))
        rev = np.bincount(key, weights=rev_vals[ids["lo_revenue"][mask]],
                          minlength=n_groups)
        cost = np.bincount(key, weights=supplycost[mask],
                           minlength=n_groups) if with_cost else None
        nz = np.nonzero(np.bincount(key, minlength=n_groups))[0]
        out = {}
        for gi in nz:
            rem, parts = int(gi), []
            for c in reversed(gcols):
                card = len(pools[c])
                parts.append(str(pools[c][rem % card]))
                rem //= card
            k = tuple(reversed(parts))
            out[k] = (float(rev[gi]),) + (
                (float(cost[gi]),) if with_cost else ())
        return out

    y = ids["d_year"]
    disc = ids["lo_discount"]
    qty = ids["lo_quantity"]

    # Scalar dictionary lookups (value → id bound) are precomputed — that
    # is O(log card) planner work. The ROW-SCALE filter evaluation happens
    # inside each timed closure, like it does on the device side.
    d1, d3 = rng_ids("lo_discount", 1, 3)
    d4, d6 = rng_ids("lo_discount", 4, 6)
    d5, d7 = rng_ids("lo_discount", 5, 7)
    q25 = vid("lo_quantity", 25)
    q26, q35 = rng_ids("lo_quantity", 26, 35)
    y93 = vid("d_year", 1993)
    y94 = vid("d_year", 1994)
    y92, y97 = rng_ids("d_year", 1992, 1997)
    ym9401 = vid("d_yearmonthnum", 199401)
    wk6 = vid("d_weeknuminyear", 6)
    b21, b28 = rng_ids("p_brand1", "MFGR#2221", "MFGR#2228")
    us = vid("c_nation", "UNITED STATES")
    ki = vids("c_city", ["UNITED KI1", "UNITED KI5"])
    mf12 = vids("p_mfgr", ["MFGR#1", "MFGR#2"])
    y9798 = vids("d_year", [1997, 1998])

    mask_fns = {
        "q1.1": lambda: (y == y93) & (disc >= d1) & (disc < d3) &
                        (qty < q25),
        "q1.2": lambda: (ids["d_yearmonthnum"] == ym9401) &
                        (disc >= d4) & (disc < d6) &
                        (qty >= q26) & (qty < q35),
        "q1.3": lambda: (ids["d_weeknuminyear"] == wk6) & (y == y94) &
                        (disc >= d5) & (disc < d7) &
                        (qty >= q26) & (qty < q35),
        "q2.1": lambda: (ids["p_category"] == vid("p_category",
                                                  "MFGR#12")) &
                        (ids["s_region"] == vid("s_region", "AMERICA")),
        "q2.2": lambda: (ids["p_brand1"] >= b21) &
                        (ids["p_brand1"] < b28) &
                        (ids["s_region"] == vid("s_region", "ASIA")),
        "q2.3": lambda: (ids["p_brand1"] == vid("p_brand1",
                                                "MFGR#2221")) &
                        (ids["s_region"] == vid("s_region", "EUROPE")),
        "q3.1": lambda: (ids["c_region"] == vid("c_region", "ASIA")) &
                        (ids["s_region"] == vid("s_region", "ASIA")) &
                        (y >= y92) & (y < y97),
        "q3.2": lambda: (ids["c_nation"] == us) &
                        (ids["s_nation"] == us) & (y >= y92) & (y < y97),
        "q3.3": lambda: np.isin(ids["c_city"], ki) &
                        np.isin(ids["s_city"], ki) &
                        (y >= y92) & (y < y97),
        "q3.4": lambda: np.isin(ids["c_city"], ki) &
                        np.isin(ids["s_city"], ki) &
                        (ids["d_yearmonth"] == vid("d_yearmonth",
                                                   "Dec1997")),
        "q4.1": lambda: (ids["c_region"] == vid("c_region", "AMERICA")) &
                        (ids["s_region"] == vid("s_region", "AMERICA")) &
                        np.isin(ids["p_mfgr"], mf12),
        "q4.2": lambda: (ids["c_region"] == vid("c_region", "AMERICA")) &
                        (ids["s_region"] == vid("s_region", "AMERICA")) &
                        np.isin(ids["p_mfgr"], mf12) & np.isin(y, y9798),
        "q4.3": lambda: (ids["c_region"] == vid("c_region", "AMERICA")) &
                        (ids["s_nation"] == us) & np.isin(y, y9798) &
                        (ids["p_category"] == vid("p_category",
                                                  "MFGR#14")),
    }

    fns = {}
    for q in ("q1.1", "q1.2", "q1.3"):
        fns[q] = (lambda mf: (lambda: revenue_sum(mf())))(mask_fns[q])
    for q, gcols in (("q2.1", ["d_year", "p_brand1"]),
                     ("q2.2", ["d_year", "p_brand1"]),
                     ("q2.3", ["d_year", "p_brand1"]),
                     ("q3.1", ["c_nation", "s_nation", "d_year"]),
                     ("q3.2", ["c_city", "s_city", "d_year"]),
                     ("q3.3", ["c_city", "s_city", "d_year"]),
                     ("q3.4", ["c_city", "s_city", "d_year"])):
        fns[q] = (lambda mf, gc: (lambda: group(mf(), gc, False)))(
            mask_fns[q], gcols)
    for q, gcols in (("q4.1", ["d_year", "c_nation"]),
                     ("q4.2", ["d_year", "s_nation", "p_category"]),
                     ("q4.3", ["d_year", "s_city", "p_brand1"])):
        fns[q] = (lambda mf, gc: (lambda: group(mf(), gc, True)))(
            mask_fns[q], gcols)
    return fns


def canon_response(name: str, resp):
    """BrokerResponse → the CPU functions' canonical result shape."""
    if name.startswith("q1"):
        v = resp.aggregation_results[0].value
        return 0.0 if v == "null" else float(v)
    n_aggs = len(resp.aggregation_results)
    out = {}
    for ai in range(n_aggs):
        for g in resp.aggregation_results[ai].group_by_result:
            k = tuple(str(x) for x in g["group"])
            out.setdefault(k, [0.0] * n_aggs)[ai] = float(g["value"])
    return {k: tuple(v) for k, v in out.items()}


def check(name: str, got, exp) -> None:
    if name.startswith("q1"):
        assert abs(got - exp) <= max(1e-6 * abs(exp), 1e-6), \
            f"{name}: {got} != {exp}"
        return
    assert set(got) == set(exp), \
        f"{name}: group keys differ ({len(got)} vs {len(exp)}); " \
        f"e.g. {list(set(exp) - set(got))[:3]} missing"
    for k, ev in exp.items():
        gv = got[k]
        # dense group paths (psums) are exact; past DENSE_G_LIMIT the
        # scatter path accumulates in device f32 (~1e-5 rel at this scale),
        # as does the supplycost carry — tolerance covers both
        assert abs(gv[0] - ev[0]) <= max(1e-4 * abs(ev[0]), 1e-6), \
            f"{name} {k}: revenue {gv[0]} != {ev[0]}"
        if len(ev) > 1:
            assert abs(gv[1] - ev[1]) <= max(2e-4 * abs(ev[1]), 1e-3), \
                f"{name} {k}: supplycost {gv[1]} != {ev[1]}"


# ---------------------------------------------------------------------------


def time_cpu(fn, reps: int):
    """(median_s, samples) — sample count recorded so the artifact shows
    exactly how many baseline iterations backed each number."""
    ts = []
    for _ in range(max(3, reps)):
        t = time.perf_counter()
        fn()
        ts.append(time.perf_counter() - t)
        if ts[-1] > 2.0 and len(ts) >= 2:
            # multi-second numpy baselines (q3.3/q3.4/q4.x at 100M rows)
            # are stable run-to-run; extra reps only burn the driver's
            # wall budget (round-2 post-mortem: 5 reps x 6.6s for q3.4)
            break
    return median(ts), ts


def measure_rtt(sample) -> float:
    """Dispatch + sync round-trip of a trivial program."""
    import jax
    import jax.numpy as jnp

    fn = jax.jit(lambda x: x.reshape(-1)[0])
    jax.device_get(fn(sample))
    ts = []
    for _ in range(3):
        t0 = time.perf_counter()
        jax.device_get(fn(sample))
        ts.append(time.perf_counter() - t0)
    return median(ts)


def bench_queries(mesh, stack, cpu, reps, rows, stage: str,
                  budget_s: float = float("inf")):
    """Device timing: N kernel executions inside ONE dispatch (lax.scan over
    a runtime-zero perturbation so XLA cannot hoist the body), minus the
    measured dispatch round-trip, plus the measured host finish."""
    import jax
    import jax.numpy as jnp

    from pinot_tpu.parallel.sharded import get_sharded_kernel
    from pinot_tpu.pql.parser import compile_pql
    from pinot_tpu.pql.optimizer import BrokerRequestOptimizer
    from pinot_tpu.query import execution
    from pinot_tpu.query.blocks import IntermediateResultsBlock
    from pinot_tpu.query.plan import (InstancePlanMaker,
                                      drive_group_execution,
                                      set_group_kmax)

    t_stage = time.monotonic()
    plan_maker = InstancePlanMaker()
    optimizer = BrokerRequestOptimizer()
    # 64 back-to-back executions per timed dispatch: the dispatch
    # round-trip is subtracted from each sample, so sub-ms queries need
    # the executed work to dominate its variance
    n_exec = 64
    per_query = {}
    speedups = []
    rtt = None
    for name, pql in SSB_PQLS.items():
        if time.monotonic() - t_stage > budget_s or remaining_s() < 60:
            # compiles at this scale are minutes each; emit honest
            # partial results rather than risk the whole run's budget
            log(f"bench[{stage}] {name}: SKIPPED (stage budget "
                f"{budget_s:.0f}s / global remaining {remaining_s():.0f}s)")
            per_query[name] = {"skipped": "time budget"}
            continue
        request = optimizer.optimize(compile_pql(pql))
        # plan against the UNION view when the stack carries one:
        # storage-path segments build their own dictionaries, so
        # literal→id binding and part encodings must live in the
        # union id domain the stacked lanes use (stage 2's synth
        # stack has global dictionaries and no plan_segment)
        # fast paths (star-tree cubes / metadata answers) are
        # per-segment host work in the LOCAL id domain — probe
        # them on segment 0 (the sequential executor re-plans
        # per segment)
        plan = plan_maker.make_segment_plan(stack.segments[0],
                                            request)
        if plan.fast_path_result is None and \
                hasattr(stack, "plan_segment"):
            plan = plan_maker.make_segment_plan(
                stack.plan_segment(), request)
        if plan.fast_path_result is not None:
            # star-tree cube (or metadata) answer: O(groups) host work —
            # time the full sequential executor over every segment
            from pinot_tpu.query.executor import ServerQueryExecutor
            ex = ServerQueryExecutor()
            samples = []
            for _ in range(max(3, reps)):
                t0 = time.perf_counter()
                ex.execute(request, stack.segments)
                samples.append(time.perf_counter() - t0)
            d50 = median(samples)
            d99 = float(np.percentile(samples, 99))
            c, cpu_ts = time_cpu(cpu[name], reps)
            speedups.append(c / d50)
            per_query[name] = {
                "device_p50_ms": round(d50 * 1e3, 3),
                "device_p99_ms": round(d99 * 1e3, 3),
                "device_min_ms": round(min(samples) * 1e3, 3),
                "device_max_ms": round(max(samples) * 1e3, 3),
                "n_device": len(samples),
                "cpu_p50_ms": round(c * 1e3, 3),
                "cpu_min_ms": round(min(cpu_ts) * 1e3, 3),
                "cpu_max_ms": round(max(cpu_ts) * 1e3, 3),
                "n_cpu": len(cpu_ts),
                "speedup": round(c / d50, 2),
                "rows_per_s_per_chip": round(rows / d50),
                "path": "star-tree",
            }
            log(f"bench[{stage}] {name}: star-tree p50 {d50 * 1e3:.3f}ms, "
                f"cpu {c * 1e3:.2f}ms, speedup {c / d50:.1f}x")
            continue
        cols = stack.gather(plan.needed_cols)
        nd = stack.device_num_docs()
        if rtt is None:
            rtt = measure_rtt(nd)
            log(f"bench[{stage}] dispatch RTT {rtt * 1e3:.1f}ms "
                f"(subtracted from scan-of-{n_exec} totals)")
        lane_keys = tuple(sorted(cols.keys()))
        group_spec = plan.group_spec
        if group_spec is not None:
            # the plan may come from a small template segment; size the
            # compaction to the lanes actually executed
            group_spec = set_group_kmax(group_spec, stack.padded_docs)

        # the kernels each query rep must execute (adaptive
        # group-bys run 2-3 dispatches: phase-A min/max scout,
        # the conditional hist rung, the phase-B group kernel)
        fns = []

        def run(agg_specs, spec, extra_params=()):
            fn = get_sharded_kernel(mesh, stack.padded_docs,
                                    plan.filter_spec,
                                    tuple(agg_specs or ()), spec,
                                    plan.select_spec, lane_keys)
            full = tuple(plan.params) + tuple(extra_params)
            fns.append((fn, full, spec))
            return jax.device_get(fn(cols, full, nd))

        fin_plan = plan
        if group_spec is not None:
            fns.clear()
            outs_h, spec_used = drive_group_execution(
                run, group_spec, stack.padded_docs,
                int(stack.num_docs.sum()), plan.segment)
            # steady state = every scout dispatch (spec None:
            # phase A min/max + the conditional hist rung) plus
            # the final escalation-ladder rung
            scouts = [f for f in fns[:-1] if f[2] is None]
            fns = scouts + [fns[-1]]
            fin_plan = execution._with_group_spec(plan, spec_used)
        else:
            fns.clear()
            outs_h = run(plan.agg_specs, None)

        # host finish (group decode / reduce): median of 3 (first call pays
        # one-time numpy/cache effects)
        finish_ts = []
        for _ in range(3):
            t0 = time.perf_counter()
            blk = IntermediateResultsBlock()
            if fin_plan.group_spec is not None:
                execution._finish_group_by(fin_plan, outs_h, blk)
            else:
                execution._finish_aggregation(fin_plan, outs_h, blk)
            finish_ts.append(time.perf_counter() - t0)
        finish_s = median(finish_ts)

        zs = jnp.zeros(n_exec, jnp.int32)
        only_fns = tuple(f[0] for f in fns)
        all_fparams = tuple(f[1] for f in fns)

        @jax.jit
        def timed(cols, nd, zs, all_fparams):
            # params are jit ARGUMENTS (not constants) so the timed
            # program is operand-driven exactly like production dispatch
            def body(c, z):
                s = jnp.float32(0)
                for fn, fparams in zip(only_fns, all_fparams):
                    o = fn(cols, fparams, nd + z)  # z == 0 at runtime only
                    for v in o.values():
                        s = s + v.astype(jnp.float32).sum()
                return c + s, None
            out, _ = jax.lax.scan(body, jnp.float32(0), zs)
            return out

        jax.device_get(timed(cols, nd, zs, all_fparams))    # compile
        samples = []
        for _ in range(max(3, reps)):
            t0 = time.perf_counter()
            jax.device_get(timed(cols, nd, zs, all_fparams))
            total = time.perf_counter() - t0
            samples.append(max(total - rtt, 1e-5) / n_exec + finish_s)
        d50, d99 = median(samples), float(np.percentile(samples, 99))
        c, cpu_ts = time_cpu(cpu[name], reps)
        speedups.append(c / d50)
        per_query[name] = {
            "device_p50_ms": round(d50 * 1e3, 3),
            "device_p99_ms": round(d99 * 1e3, 3),
            "device_min_ms": round(min(samples) * 1e3, 3),
            "device_max_ms": round(max(samples) * 1e3, 3),
            # each device sample is a scan of n_exec executions
            "n_device": len(samples), "execs_per_sample": n_exec,
            "cpu_p50_ms": round(c * 1e3, 3),
            "cpu_min_ms": round(min(cpu_ts) * 1e3, 3),
            "cpu_max_ms": round(max(cpu_ts) * 1e3, 3),
            "n_cpu": len(cpu_ts),
            "speedup": round(c / d50, 2),
            "rows_per_s_per_chip": round(rows / d50),
        }
        log(f"bench[{stage}] {name}: device p50 {d50 * 1e3:.3f}ms "
            f"(finish {finish_s * 1e3:.2f}ms), cpu {c * 1e3:.2f}ms, "
            f"speedup {c / d50:.1f}x, {rows / d50 / 1e9:.2f}B rows/s/chip")

    return per_query, speedups


# ---------------------------------------------------------------------------
# Vector rung: filtered exact top-k over embeddings vs the numpy host
# baseline (ISSUE 13 — same ≥150x discipline as q1.x). Artifact:
# VEC_r10.json next to this file.
# ---------------------------------------------------------------------------

VEC_DIM = 128
VEC_K = 10
VEC_ARTIFACT = os.environ.get("PINOT_TPU_VEC_ARTIFACT", "VEC_r10.json")


def _np_tree(x):
    x = np.asarray(x, np.float32)
    while x.shape[-1] > 1:
        x = x[..., 0::2] + x[..., 1::2]
    return x[..., 0]


def _np_vec_baseline(mat, shard, q):
    """The numpy host baseline AND oracle: filtered cosine top-k with
    the engine's f32 balanced-tree score contract."""
    def run():
        scores = _np_tree(mat * q[None, :])
        denom = np.sqrt(_np_tree(mat * mat)).astype(np.float32) * \
            np.float32(np.sqrt(_np_tree(q * q)))
        with np.errstate(divide="ignore", invalid="ignore"):
            s = (scores / denom).astype(np.float32)
        s[~(denom > 0)] = -np.inf
        docs = np.nonzero(shard < 2)[0]
        sv = s[docs]
        order = np.lexsort((docs, -sv))[:VEC_K]
        return [(int(docs[i]), float(sv[i])) for i in order]
    return run


def vector_rung(mesh, budget_s: float = 900.0) -> dict:
    """Build → load → stack → time the filtered vector top-k at the
    100k and 1M rungs; returns the artifact dict (also written to
    VEC_ARTIFACT)."""
    import jax
    import jax.numpy as jnp

    from pinot_tpu.common.datatype import DataType
    from pinot_tpu.common.schema import Schema, dimension, metric, vector
    from pinot_tpu.parallel.sharded import (ShardedQueryExecutor,
                                            get_sharded_kernel)
    from pinot_tpu.pql.parser import compile_pql
    from pinot_tpu.query.plan import InstancePlanMaker
    from pinot_tpu.segment.creator import SegmentCreator
    from pinot_tpu.segment.loader import ImmutableSegmentLoader

    t_stage = time.monotonic()
    reps = int(os.environ.get("PINOT_TPU_VEC_REPS", "5"))
    n_exec = int(os.environ.get("PINOT_TPU_VEC_EXECS", "32"))
    schema = Schema("vectab", [dimension("shard", DataType.INT),
                               metric("rid", DataType.INT),
                               vector("emb", VEC_DIM)])
    out = {"metric": "vector_topk_speedup_vs_numpy_host",
           "unit": "x", "target": 150.0, "dim": VEC_DIM, "k": VEC_K,
           "metric_fn": "COSINE", "filter": "shard < 2 (50%)",
           "backend": jax.devices()[0].platform,
           "n_devices": len(jax.devices()),
           "rungs": {}}
    plan_maker = InstancePlanMaker()
    for label, rows, n_segs in (("100k_128d", 100_000, 2),
                                ("1m_128d", 1_000_000, 4)):
        if time.monotonic() - t_stage > budget_s or remaining_s() < 120:
            out["rungs"][label] = {"skipped": "time budget"}
            continue
        rng = np.random.default_rng(10)
        per = rows // n_segs
        segs = []
        try:
            _vector_rung_one(out, label, rows, n_segs, per, rng, schema,
                             plan_maker, mesh, segs, reps, n_exec)
        finally:
            for s in segs:
                s.destroy()
    big = out["rungs"].get("1m_128d", {})
    out["value"] = big.get("speedup", 0.0)
    out["vs_target"] = round(out["value"] / 150.0, 4)
    out["pass"] = bool(big.get("parity")) and out["value"] >= 150.0
    try:
        path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            VEC_ARTIFACT)
        with open(path, "w") as fh:
            json.dump(out, fh, indent=1, sort_keys=True)
            fh.write("\n")
        log(f"bench[vec]: artifact written to {path}")
    except OSError as e:
        log(f"bench[vec]: could not write artifact ({e})")
    return out


def _vector_rung_one(out, label, rows, n_segs, per, rng, schema,
                     plan_maker, mesh, segs, reps, n_exec) -> None:
    import jax
    import jax.numpy as jnp

    from pinot_tpu.parallel.sharded import (ShardedQueryExecutor,
                                            get_sharded_kernel)
    from pinot_tpu.pql.parser import compile_pql
    from pinot_tpu.segment.creator import SegmentCreator
    from pinot_tpu.segment.loader import ImmutableSegmentLoader

    if True:
        with tempfile.TemporaryDirectory() as base:
            t0 = time.perf_counter()
            mats, shards = [], []
            for s in range(n_segs):
                mat = rng.standard_normal((per, VEC_DIM)).astype(np.float32)
                shard = rng.integers(0, 4, per).astype(np.int32)
                d = os.path.join(base, f"v{s}")
                SegmentCreator(schema, segment_name=f"v{s}").build(
                    {"shard": shard,
                     "rid": np.arange(per, dtype=np.int32) + s * per,
                     "emb": mat}, d)
                segs.append(ImmutableSegmentLoader.load(d))
                mats.append(mat)
                shards.append(shard)
            build_s = time.perf_counter() - t0
            q = rng.standard_normal(VEC_DIM).astype(np.float32)
            qs = ", ".join(repr(float(x)) for x in q)
            pql = (f"SELECT rid, VECTOR_SIMILARITY(emb, [{qs}], {VEC_K}, "
                   "'COSINE') FROM vectab WHERE shard < 2")
            request = compile_pql(pql)
            sharded = ShardedQueryExecutor(mesh=mesh)
            stack = sharded.stack_for(segs)
            # parity gate BEFORE timing: engine result == numpy oracle
            blk = sharded.execute(request, segs)
            got = [(row[1], row[2], row[3]) for row in blk.selection_rows]
            cand = []
            for s in range(n_segs):
                for doc, score in _np_vec_baseline(mats[s], shards[s], q)():
                    cand.append((-score, f"v{s}", doc, score))
            cand.sort()
            exp = [(doc, name, score) for _ns, name, doc, score
                   in cand[:VEC_K]]
            parity = got == exp
            if not parity:
                out["rungs"][label] = {"parity": False, "got": got[:3],
                                       "exp": exp[:3]}
                return

            # device timing: scan of n_exec dispatches, minus dispatch RTT
            plan = plan_maker.make_segment_plan(stack.plan_segment(),
                                                request)
            cols = stack.gather(plan.needed_cols)
            nd = stack.device_num_docs()
            lane_keys = tuple(sorted(cols.keys()))
            fn = get_sharded_kernel(mesh, stack.padded_docs,
                                    plan.filter_spec, (), None,
                                    plan.select_spec, lane_keys)
            fparams = tuple(plan.params)
            rtt = measure_rtt(nd)
            zs = jnp.zeros(n_exec, jnp.int32)

            @jax.jit
            def timed(cols, nd, zs, fparams):
                def body(c, z):
                    o = fn(cols, fparams, nd + z)
                    s = jnp.float32(0)
                    for v in o.values():
                        s = s + v.astype(jnp.float32).sum()
                    return c + s, None
                acc, _ = jax.lax.scan(body, jnp.float32(0), zs)
                return acc

            jax.device_get(timed(cols, nd, zs, fparams))     # compile
            samples = []
            for _ in range(max(3, reps)):
                t0 = time.perf_counter()
                jax.device_get(timed(cols, nd, zs, fparams))
                total = time.perf_counter() - t0
                samples.append(max(total - rtt, 1e-5) / n_exec)
            d50 = median(samples)

            # numpy host baseline over ONE contiguous table (the shape a
            # host serving stack would scan), same score contract
            mat_all = np.concatenate(mats)
            shard_all = np.concatenate(shards)
            cpu_fn = _np_vec_baseline(mat_all, shard_all, q)
            c50, cpu_ts = time_cpu(cpu_fn, reps)
            out["rungs"][label] = {
                "rows": rows, "segments": n_segs,
                "build_s": round(build_s, 1),
                "parity": True,
                "device_p50_ms": round(d50 * 1e3, 3),
                "device_min_ms": round(min(samples) * 1e3, 3),
                "n_device": len(samples), "execs_per_sample": n_exec,
                "cpu_p50_ms": round(c50 * 1e3, 3),
                "n_cpu": len(cpu_ts),
                "speedup": round(c50 / d50, 2),
                "rows_per_s_per_chip": round(rows / d50),
            }
            log(f"bench[vec] {label}: device p50 {d50 * 1e3:.3f}ms, "
                f"numpy {c50 * 1e3:.2f}ms, speedup {c50 / d50:.1f}x")


def probe_creator_rate() -> float:
    """rows/s through build_ssb_segment_dirs on THIS box (1M-row probe) —
    drives the row-count auto-scale so build+measure provably fits the
    wall budget on whatever machine the driver runs."""
    from pinot_tpu.tools.datagen import build_ssb_segment_dirs
    with tempfile.TemporaryDirectory() as d:
        t0 = time.perf_counter()
        build_ssb_segment_dirs(d, 1_000_000, 1, seed=3, star_tree=True)
        return 1_000_000 / (time.perf_counter() - t0)


def autoscale_rows(requested: int, rate: float) -> int:
    """Largest quantized row count whose projected build+load+measure
    fits the remaining global budget. Quantized so the padded lane
    shapes stay within the set the compilation cache was warmed at
    (an off-ladder shape would cold-compile for ~10 min per kernel)."""
    ladder = [100_000_000, 50_000_000, 25_000_000, 12_500_000]
    if requested not in ladder:
        ladder.insert(0, requested)
    ladder = [r for r in ladder if r <= requested]
    for rows in ladder:
        # build at the probed rate; load ≈ 2M rows/s; fixed overhead for
        # ids gen + upload + oracle checks + the 13 timed queries
        projected = rows / rate + rows / 2e6 + 600
        if projected <= 0.85 * remaining_s():
            return rows
    return ladder[-1]


def main() -> None:
    store_rows = int(os.environ.get("PINOT_TPU_BENCH_STORE_ROWS",
                                    100_000_000))
    big_rows = int(os.environ.get("PINOT_TPU_BENCH_ROWS", 100_000_000))
    n_segs = int(os.environ.get("PINOT_TPU_BENCH_SEGMENTS", 8))
    reps = int(os.environ.get("PINOT_TPU_BENCH_REPS", 5))
    skip_big = os.environ.get("PINOT_TPU_BENCH_SKIP_BIG", "0") == "1"

    log(f"bench: global wall budget {TOTAL_BUDGET_S:.0f}s "
        "(PINOT_TPU_BENCH_TOTAL_BUDGET_S)")

    import jax

    from pinot_tpu.utils.device import configure_compile_cache
    cache_dir = configure_compile_cache()
    platform = jax.devices()[0].platform
    if platform != "tpu":
        # before any work: a CPU run is not a measurement of this system
        raise SystemExit(f"bench: no TPU (JAX platform {platform!r})")

    if os.environ.get("PINOT_TPU_BENCH_VECTOR_ONLY") == "1":
        # standalone vector rung (artifact refresh / device evidence)
        from pinot_tpu.parallel import make_mesh
        vec = vector_rung(make_mesh(), budget_s=TOTAL_BUDGET_S)
        _RESULT.clear()
        _RESULT.update({"metric": vec["metric"], "value": vec["value"],
                        "unit": "x", "vs_baseline": vec["vs_target"],
                        "vector": vec})
        emit_final(_RESULT)
        return

    rate = probe_creator_rate()
    scaled = autoscale_rows(store_rows, rate)
    if scaled != store_rows:
        log(f"bench: STORE_ROWS {store_rows} → {scaled} (creator rate "
            f"{rate / 1e6:.2f}M rows/s, {remaining_s():.0f}s remaining)")
        store_rows = scaled
    else:
        log(f"bench: creator rate {rate / 1e6:.2f}M rows/s — "
            f"{store_rows} rows fits the budget")
    _RESULT["storage_rows"] = store_rows
    if store_rows >= big_rows:
        # the storage path already runs at (or past) the synth stage's
        # scale: stage 2 would re-measure the same shapes on synthetic
        # lanes — skip it rather than spend the driver's wall budget
        skip_big = True

    from pinot_tpu.engine import QueryEngine
    from pinot_tpu.parallel import make_mesh
    from pinot_tpu.segment.loader import ImmutableSegmentLoader
    from pinot_tpu.tools.datagen import (build_ssb_segment_dirs,
                                         make_ssb_ids, ssb_pools)

    mesh = make_mesh()
    log(f"bench: devices={jax.devices()} compile cache {cache_dir}")

    # ---- stage 1: the framework's own storage path -----------------------
    pools = ssb_pools(3)
    t0 = time.perf_counter()
    star_tree = os.environ.get("PINOT_TPU_BENCH_STARTREE", "1") == "1"
    with tempfile.TemporaryDirectory() as base:
        _RESULT["note"] = "stage1: building segments"
        dirs, ids, supplycost = build_ssb_segment_dirs(
            base, store_rows, n_segs, seed=3, log=log, star_tree=star_tree)
        if star_tree:
            log("bench: segments built WITH star-tree cubes (the "
                "reference benchmark's star-tree segment variant); "
                "PINOT_TPU_BENCH_STARTREE=0 disables")
        build_s = time.perf_counter() - t0
        log(f"bench: {store_rows} rows built via SegmentCreator in "
            f"{build_s:.1f}s")
        t0 = time.perf_counter()
        _RESULT["note"] = "stage1: loading segments"
        _RESULT["storage_build_s"] = round(build_s, 1)
        segments = [ImmutableSegmentLoader.load(d) for d in dirs]
        load_s = time.perf_counter() - t0
        log(f"bench: loaded via ImmutableSegmentLoader in {load_s:.1f}s")

        cpu = make_cpu_queries(pools, ids, supplycost)
        engine = QueryEngine(segments, mesh=mesh)

        # loader→HBM upload, measured as its own metric (BASELINE
        # composition: configs past the host-build budget extrapolate
        # storage numbers through this rate): gather every lane the 13
        # queries touch and time the device_put + settle
        from pinot_tpu.pql.parser import compile_pql as _compile
        from pinot_tpu.pql.optimizer import \
            BrokerRequestOptimizer as _Opt
        from pinot_tpu.query.plan import InstancePlanMaker as _PM
        t0 = time.perf_counter()
        stack = engine.sharded.stack_for(segments)
        _pm, _opt = _PM(), _Opt()
        lanes_up: dict = {}
        for pql in SSB_PQLS.values():
            plan = _pm.make_segment_plan(stack.plan_segment(),
                                         _opt.optimize(_compile(pql)))
            lanes_up.update(stack.gather(plan.needed_cols))
        jax.block_until_ready(list(lanes_up.values()))
        up_s = time.perf_counter() - t0
        up_bytes = int(sum(v.nbytes for v in lanes_up.values()))
        log(f"bench: {up_bytes / 1e6:.0f}MB of column lanes "
            f"loader→HBM in {up_s:.1f}s = {up_bytes / 1e6 / up_s:.0f}MB/s "
            "(includes stack build + union remap)")
        del lanes_up

        t0 = time.perf_counter()
        _RESULT["note"] = "stage1: oracle checks"
        for name, pql in SSB_PQLS.items():
            check(name, canon_response(name, engine.query(pql)),
                  cpu[name]())
        log(f"bench: all 13 SSB queries match the numpy oracle through the "
            f"full engine path ({time.perf_counter() - t0:.1f}s)")

        # reuse the engine's already-uploaded stack
        _RESULT["note"] = "stage1: timing queries"
        store_pq, store_speedups = bench_queries(
            mesh, engine.sharded.stack_for(segments), cpu, reps,
            store_rows, "storage")
        # release stage-1 HBM before the 100M-row synth stage
        del engine
        for s in segments:
            s.destroy()
        del segments, cpu
        import gc
        gc.collect()

    p50 = median(store_speedups) if store_speedups else 0.0
    result = {
        "metric": "ssb13_storage_path_p50_speedup_vs_cpu",
        "value": round(p50, 3),
        "unit": "x",
        "vs_baseline": round(p50 / 8.0, 4),
        "storage_rows": store_rows,
        "min_query_speedup": (round(min(store_speedups), 2)
                              if store_speedups else None),
        "storage_build_s": round(build_s, 1),
        "storage_load_s": round(load_s, 1),
        "hbm_upload_mb": round(up_bytes / 1e6, 1),
        "hbm_upload_mbps": round(up_bytes / 1e6 / up_s, 1),
        "per_query": store_pq,
    }
    # ---- vector rung (ISSUE 13): filtered exact top-k vs numpy host ------
    _RESULT.clear()
    _RESULT.update(result)      # a later stage's failure emits this much
    if os.environ.get("PINOT_TPU_BENCH_VECTOR", "1") == "1" and \
            remaining_s() > 180:
        result["vector"] = vector_rung(mesh)
    elif os.environ.get("PINOT_TPU_BENCH_VECTOR", "1") == "1":
        result["vector"] = {"skipped": "global time budget"}

    _RESULT.clear()
    _RESULT.update(result)      # SIGTERM from here on emits the headline
    # print the storage headline NOW: a hard kill (SIGKILL after the
    # grace period, OOM) during stage 2 skips the SIGTERM handler, and
    # the already-measured result must survive (r2 post-mortem). The
    # parser takes the LAST valid JSON line, so the final emit wins
    # when the run completes.
    print(json.dumps(_compact(result)), flush=True)

    # ---- stage 2: reference-scale synth table ----------------------------
    if not skip_big and remaining_s() < 900:
        log(f"bench[big]: SKIPPED — {remaining_s():.0f}s left of the "
            "global budget (stage 2 needs ~900s)")
        skip_big = True
        result["big_synth"] = {"skipped": "global time budget"}
    if not skip_big:
        from pinot_tpu.tools.datagen import make_ssb_device_stack

        t0 = time.perf_counter()
        lanes, num_docs_dev, plan_table, padded = make_ssb_device_stack(
            big_rows, n_segs, mesh, seed=3)
        jax.block_until_ready(list(lanes.values()))
        log(f"bench[big]: {big_rows} rows synthesized in HBM in "
            f"{time.perf_counter() - t0:.1f}s (the storage path is "
            "exercised and timed in stage 1)")
        t0 = time.perf_counter()
        # same seed as the device stack: big_ids index the same value
        # pools make_cpu_queries receives (a different seed would build a
        # different-sized lo_revenue pool and misalign the id domain)
        big_ids, big_cost = make_ssb_ids(big_rows, seed=3)
        log(f"bench[big]: host baseline table in "
            f"{time.perf_counter() - t0:.1f}s")
        big_cpu = make_cpu_queries(pools, big_ids, big_cost)

        # lane-override stack: plans build against the small plan_table
        # segment (same dictionaries); lanes are the HBM-synthesized ones
        class _SynthStack:
            padded_docs = padded
            segments = plan_table.segments
            num_docs = np.asarray(jax.device_get(num_docs_dev))

            def gather(self, needed_cols):
                import jax.numpy as jnp
                out = {}
                for col, kind in needed_cols:
                    key = f"{col}.{kind}"
                    if key not in lanes and kind == "vals":
                        # replicated dictionary value table (tiny)
                        lanes[key] = jnp.asarray(
                            plan_table.segments[0].data_source(col)
                            .host_operand("vals"))
                    out[key] = lanes[key]
                return out

            def device_num_docs(self):
                return num_docs_dev

        big_budget = float(os.environ.get(
            "PINOT_TPU_BENCH_BIG_BUDGET_S", "2400"))
        _RESULT["note"] = "stage2: timing queries"
        big_pq, big_speedups = bench_queries(
            mesh, _SynthStack(), big_cpu, reps, big_rows, "big",
            budget_s=big_budget)
        result["big_synth"] = {
            "rows": big_rows,
            "p50_speedup": (round(median(big_speedups), 3)
                            if big_speedups else None),
            "min_query_speedup": (round(min(big_speedups), 2)
                                  if big_speedups else None),
            "per_query": big_pq,
        }

    _RESULT.clear()
    _RESULT.update(result)
    _RESULT.pop("note", None)
    emit_final(_RESULT)


if __name__ == "__main__":
    signal.signal(signal.SIGTERM, _on_term)
    signal.signal(signal.SIGINT, _on_term)
    try:
        main()
    except Exception as e:  # noqa: BLE001 — emit what was measured,
        # then fail: a crashed run is not a result
        import traceback
        log("bench: FATAL " + "".join(traceback.format_exception(e))[-1500:])
        _RESULT.setdefault("error", f"{type(e).__name__}: {str(e)[:300]}")
        emit_final(_RESULT)
        sys.exit(1)
