"""Segment plan execution: run the device kernel, finish results host-side.

Parity: the operator-tree execution in pinot-core (Plan.execute →
InstanceResponseOperator.nextBlock, SURVEY.md §3.2) collapsed into one device
call + exact host finishing (histogram·dictionary dots in f64, dictId→value
decodes, group-key mixed-radix decode).
"""
from __future__ import annotations

import functools
import time
from typing import Dict, List, Optional, Tuple

import jax
import numpy as np

from pinot_tpu.analysis.runtime import debug_transfer_guard
from pinot_tpu.common.metrics import ServerQueryPhase
from pinot_tpu.obs.profiler import (mark_sum_lanes, obs_span,
                                    profiled_device_get, sum_lane_attrs)
from pinot_tpu.ops import kernels
from pinot_tpu.query.blocks import ExecutionStats, IntermediateResultsBlock
from pinot_tpu.segment.loader import ImmutableSegment


def _count_filter_leaves(spec) -> int:
    if spec is None or spec[0] in ("match_all", "empty"):
        return 0
    if spec[0] in ("and", "or"):
        return sum(_count_filter_leaves(c) for c in spec[1])
    if spec[0] == "pred" and spec[1] in ("vdoc", "ivf_probe"):
        return 0      # engine-injected (upsert mask / ANN probe), not a
    return 1          # query leaf


def gather_operands(plan) -> Dict[str, object]:
    """The plan's lanes as device arrays, under an `operandGather`
    span: one look-up in the lane cache a lane and, only on a miss,
    the build of its padded host operand and the upload."""
    segment = plan.segment
    with obs_span(ServerQueryPhase.OPERAND_GATHER):
        # upsert validDocIds ("vdoc"): a pseudo-column liveness lane
        # served by the segment itself (version-cached device upload);
        # every other kind is a row of the loader's DataSource.LANES
        return {f"{col}.{kind}": segment.device_valid_lane()
                if kind == "vdoc"
                else segment.data_source(col).device_lane(kind)
                for col, kind in plan.needed_cols}


@functools.lru_cache(maxsize=4096)
def _device_scalar(dtype: str, value: int, device):
    """An integer scalar operand on `device` (None: the default one),
    uploaded once a value. A scan's runtime operands are a handful of
    dictId bounds and the segment's doc count; handed to the jitted
    program as host scalars, each is a host→device transfer of its own
    a launch (6-7 a Q1.x segment, 0.2-0.35 ms each on the chip's host,
    and each gives the interpreter lock away:
    `scripts/launch_contention.py`). DictIds and doc counts are small
    integers that recur from query to query, so the launch finds them
    already there; the program's avals are the same, so it is the same
    program. Uncommitted, as the lanes are (`obs/residency.py`
    `device_put`)."""
    with jax.default_device(device):
        return jax.device_put(np.dtype(dtype).type(value))  # tpulint: disable=device-ledger -- scalars of 4-8 bytes, at most 4096 of them: no lane; the ledger counts what residency can demote


def _scalar_operands(plan, cols, extra_params):
    """The plan's runtime params and its doc count as the program takes
    them: integer numpy scalars from the `_device_scalar` table of the
    device the lanes live on, everything else (arrays, floats, weakly
    typed Python numbers) as it is."""
    lane = next(iter(cols.values()), None)
    devices = lane.devices() if lane is not None else ()
    if len(devices) > 1:                    # no one device to put it on
        return (*plan.params, *extra_params), plan.segment.num_docs
    device = next(iter(devices), None)
    return (tuple(_device_scalar(p.dtype.name, int(p), device)
                  if isinstance(p, np.integer) else p
                  for p in (*plan.params, *extra_params)),
            # run_segment_kernel's jnp.int32() hands a device int32 back
            _device_scalar("int32", plan.segment.num_docs, device))


def execute_segment_plan(plan) -> IntermediateResultsBlock:
    """ONE plan run to its block: `execute_segment_plans` with one
    ladder (a pull a rung); a plan that refuses while running raises."""
    (blk,) = execute_segment_plans([plan])
    if isinstance(blk, Exception):
        raise blk
    return blk


def execute_segment_plans(plans, deadline: Optional[float] = None) -> list:
    """The plans of ONE query's scan-route segments, walked by this
    thread in phases (`query/plan.py` `walk_ladders`): every plan's
    lanes gathered (a lane-cache look-up), every plan's next program
    launched without waiting, ONE pull a rung over all of them, then
    the exact host finishing a plan. The device has the query's
    programs queued while the host is between a pull and the next
    launches; a query stops for the host once a rung (one for a scan,
    two or three for a group-by), not once a program.

    -> a list aligned with `plans`: the plan's block; or the
    `UnsupportedOnDevice` / `GroupsLimitExceeded` it refused with while
    running (the caller's host twin answers it); or None where
    `deadline` (time.monotonic(), checked between gathers and between
    rungs) passed before the plan was done."""
    # PINOT_TPU_DEBUG_TRANSFERS=1 turns any implicit device→host pull in
    # the dispatch/finish path below into an error at the offending call
    # site (the explicit batched jax.device_get a rung still works)
    with debug_transfer_guard():
        return _execute_segment_plans(plans, deadline)


def _plan_ladder(plan):
    """The plan's ladder of device programs over its gathered lanes."""
    from pinot_tpu.query.plan import SegmentLadder
    segment = plan.segment
    cols = gather_operands(plan)

    def run(agg_specs, group_spec, extra_params=()):
        # returns DEVICE outs; the walk batches the device→host pull
        # into one explicit jax.device_get a rung (tpulint host-sync:
        # never per-scalar). kernelLaunch ends at the asynchronous
        # return: argument handling, jit cache look-up, any compile,
        # enqueue
        with obs_span(ServerQueryPhase.KERNEL_LAUNCH) as span:
            if span is not None:
                span["attrs"] = sum_lane_attrs(
                    group_spec[3] if group_spec else agg_specs, segment)
            params, num_docs = _scalar_operands(plan, cols, extra_params)
            return kernels.run_segment_kernel(
                segment.padded_docs, plan.filter_spec, agg_specs,
                group_spec, plan.select_spec, cols, params, num_docs)

    return SegmentLadder(run, plan.agg_specs, plan.group_spec,
                         segment.padded_docs, segment.num_docs, segment)


def _execute_segment_plans(plans, deadline) -> list:
    from pinot_tpu.query.plan import (GroupsLimitExceeded,
                                      UnsupportedOnDevice, walk_ladders)
    t0 = time.perf_counter()
    results = [plan.fast_path_result for plan in plans]
    ladders = {}
    for i, plan in enumerate(plans):
        if results[i] is not None:
            continue
        if deadline is not None and time.monotonic() >= deadline:
            break
        try:
            ladders[i] = _plan_ladder(plan)
        except (GroupsLimitExceeded, UnsupportedOnDevice) as exc:
            results[i] = exc
    walk_ladders(list(ladders.values()), deadline, keep_refusals=True)
    for i, ladder in ladders.items():
        if ladder.refused is not None:
            results[i] = ladder.refused
        elif ladder.phase is None:
            results[i] = _finish_ladder(plans[i], ladder, t0)
    return results


def _finish_ladder(plan, ladder, t0: float) -> IntermediateResultsBlock:
    """A done ladder's HOST outs -> the plan's block."""
    blk = IntermediateResultsBlock()
    outs = ladder.outs
    with obs_span(ServerQueryPhase.RESULT_FINISH):
        if plan.group_spec is not None:
            if ladder.finish_spec is None:
                blk.group_map = {}
            else:
                _finish_group_by(_with_group_spec(plan, ladder.finish_spec),
                                 outs, blk)
        elif plan.agg_specs:
            mark_sum_lanes(plan.agg_specs)
            _finish_aggregation(plan, outs, blk)
        _finish_selection_and_stats(plan, outs, blk, 0.0)
    blk.stats.time_used_ms = (time.perf_counter() - t0) * 1e3
    return blk


def _finish_selection_and_stats(plan, outs, blk, elapsed_ms: float) -> None:
    """The tail every path shares: the selection rows, if any, and the
    segment's ExecutionStats."""
    segment = plan.segment
    matched = int(outs["stats.num_docs_matched"])
    if plan.select_spec is not None:
        if plan.select_spec[0] == "vector":
            _finish_vector(plan, outs, blk, matched)
        else:
            _finish_selection(plan, outs, blk, matched)
    n_leaves = _count_filter_leaves(plan.filter_spec)
    n_project = len({c for c, _ in plan.needed_cols})
    blk.stats = ExecutionStats(
        num_docs_scanned=matched,
        num_entries_scanned_in_filter=n_leaves * segment.num_docs,
        num_entries_scanned_post_filter=matched * max(n_project - n_leaves, 0),
        num_segments_processed=1,
        num_segments_matched=1 if matched else 0,
        total_docs=segment.num_docs,
        time_used_ms=elapsed_ms)


def execute_segment_plans_batched(plans) -> List[IntermediateResultsBlock]:
    """One device dispatch serves N plans over ONE segment.

    Callers guarantee every plan shares a batch_signature (equal
    compiled specs, same segment — query/plan.py:batch_signature): the
    column lanes are gathered once and shared across the vmap lanes,
    each member contributes its params to the stacked leading axis, and
    the outputs are sliced back per member and fed through the same
    host finishers the sequential path uses — which is why batched and
    sequential results agree bit-for-bit on every path the coalescer
    admits (pinned by the contract tier).
    """
    if len(plans) == 1:
        return [execute_segment_plan(plans[0])]
    lead = plans[0]
    segment = lead.segment
    t0 = time.perf_counter()
    with debug_transfer_guard():
        cols = gather_operands(lead)
        if lead.params:
            with obs_span(ServerQueryPhase.KERNEL_LAUNCH):
                launched = kernels.run_segment_kernel_batched(
                    segment.padded_docs, lead.filter_spec, lead.agg_specs,
                    lead.select_spec, cols,
                    [tuple(p.params) for p in plans], segment.num_docs)
            outs_b = profiled_device_get(launched)
            per_member = [{k: v[b] for k, v in outs_b.items()}
                          for b in range(len(plans))]
        else:
            # param-free same-signature plans are identical programs:
            # one unbatched dispatch, every member reads the same outs
            with obs_span(ServerQueryPhase.KERNEL_LAUNCH):
                launched = kernels.run_segment_kernel(
                    segment.padded_docs, lead.filter_spec, lead.agg_specs,
                    None, lead.select_spec, cols, (), segment.num_docs)
            per_member = [profiled_device_get(launched)] * len(plans)
        with obs_span(ServerQueryPhase.OUTPUT_RELEASE):
            del launched
    elapsed_ms = (time.perf_counter() - t0) * 1e3
    blocks = []
    with obs_span(ServerQueryPhase.RESULT_FINISH):
        for plan, outs in zip(plans, per_member):
            mark_sum_lanes(plan.agg_specs)
            blk = IntermediateResultsBlock()
            if plan.agg_specs:
                _finish_aggregation(plan, outs, blk)
            # the dispatch was shared; each member reports the batch
            # wall time (it really waited that long) and its own stats
            _finish_selection_and_stats(plan, outs, blk, elapsed_ms)
            blocks.append(blk)
    return blocks


# ---------------------------------------------------------------------------


def _finish_aggregation(plan, outs, blk) -> None:
    inters: List = []
    for i, (f, spec) in enumerate(zip(plan.functions, plan.agg_specs)):
        fname, col, source, extra = spec
        base = f.info.base
        strategy = extra[0] if isinstance(extra, tuple) else None
        if fname in ("count", "countmv"):
            inters.append(int(outs[f"agg{i}"]))
        elif fname == "hll":
            # device-built sketch registers ([m] int32, already maxed
            # across shards on the sharded path) → the HyperLogLog
            # intermediate every combine/reduce layer merges by
            # register max
            from pinot_tpu.common.sketches import (DEFAULT_LOG2M,
                                                   HyperLogLog)
            regs = np.asarray(outs[f"agg{i}.hll"]).astype(np.uint8)
            inters.append(HyperLogLog(DEFAULT_LOG2M, regs))
        elif source == "sv" and fname in ("sum", "avg") and \
                strategy in ("parts", "vlane"):
            cnt = int(outs[f"agg{i}.count"])
            if strategy == "parts":
                n_parts, min_v = \
                    plan.segment.data_source(col).int_part_info()
                if f"agg{i}.parts" in outs:
                    # [..., n_parts] fully device-reduced sums
                    arr = np.asarray(outs[f"agg{i}.parts"]).astype(
                        np.int64).reshape(-1, n_parts).sum(axis=0)
                else:
                    # oversized-segment fallback: [..., n_parts, T]
                    # block partials, exact int64 combine
                    arr = np.asarray(outs[f"agg{i}.partsT"]).astype(
                        np.int64)
                    arr = arr.reshape(-1, n_parts, arr.shape[-1]).sum(
                        axis=(0, 2))
                s = float(sum(int(arr[k]) << (7 * k)
                              for k in range(n_parts)) + min_v * cnt)
            else:
                s = float(np.asarray(outs[f"agg{i}.vsum"],
                                     dtype=np.float64).sum())
            inters.append(s if fname == "sum" else (s, cnt))
        elif fname == "hist":
            # expression aggregation: transform the dictionary value table
            # (O(cardinality)) and finish from the device histogram
            from pinot_tpu.common import expression as expr_mod
            src_vals = np.asarray(
                plan.segment.data_source(col).dictionary.values)
            tv = np.asarray(expr_mod.evaluate(f.column, lambda _: src_vals))
            inters.append(f.from_histogram(np.asarray(outs[f"agg{i}"]), tv))
        elif source in ("sv", "mv") and fname in (
                "sum", "avg", "percentile", "distinctcount"):
            ds = plan.segment.data_source(col)
            dict_vals = ds.dictionary.values
            if f.info.base == "FASTHLL" and \
                    getattr(ds.metadata, "derived_metric_type",
                            None) == "HLL":
                # derived serialized-HLL column (BrokerRequestPreProcessor
                # rewrite): union the sketches of present dictionary values
                from pinot_tpu.common.sketches import union_serialized_hlls
                hist = np.asarray(outs[f"agg{i}"])[: len(dict_vals)]
                inters.append(union_serialized_hlls(
                    np.asarray(dict_vals)[np.nonzero(hist)[0]]))
            else:
                inters.append(f.from_histogram(np.asarray(outs[f"agg{i}"]),
                                               dict_vals))
        elif source in ("sv", "mv") and fname in ("min", "max", "minmaxrange"):
            dict_vals = plan.segment.data_source(col).dictionary.values
            card = len(dict_vals)
            mn = outs.get(f"agg{i}.min")
            mx = outs.get(f"agg{i}.max")
            inters.append(f.from_minmax_ids(
                None if mn is None else int(mn),
                None if mx is None else int(mx), dict_vals))
        elif source == "raw":
            if fname == "sum":
                inters.append(float(np.asarray(outs[f"agg{i}.vsum"],
                                               dtype=np.float64).sum()))
            elif fname == "avg":
                inters.append((float(np.asarray(outs[f"agg{i}.vsum"],
                                                dtype=np.float64).sum()),
                               int(outs[f"agg{i}.count"])))
            elif fname in ("min", "max", "minmaxrange"):
                mn = outs.get(f"agg{i}.min")
                mx = outs.get(f"agg{i}.max")
                mn = None if mn is None or not np.isfinite(mn) else float(mn)
                mx = None if mx is None or not np.isfinite(mx) else float(mx)
                if fname == "min":
                    inters.append(mn)
                elif fname == "max":
                    inters.append(mx)
                else:
                    inters.append((mn, mx))
            else:
                raise ValueError(f"unexpected raw agg {fname}")
        else:
            raise ValueError(f"unexpected agg spec {spec}")
    blk.agg_intermediates = inters


def _with_group_spec(plan, spec_used):
    """Plan view for finishing: plans are cached per query shape, so a
    value-dependent (adaptive-remap) group spec must not mutate them."""
    if spec_used is plan.group_spec:
        return plan
    import copy
    p = copy.copy(plan)
    p.group_spec = spec_used
    return p


def _decode_group_values(plan, nz: np.ndarray) -> List[np.ndarray]:
    """Mixed-radix decode of group keys `nz` into per-column value arrays.

    Expression group keys decode through their transformed value table
    (collisions — distinct source ids mapping to one transformed value —
    merge in the assembly loop); raw-binned keys decode as (binId + min).
    """
    gcols, strides, _g_pad, _specs, _kmax = plan.group_spec
    cards = [entry[3] for entry in gcols]
    id_cols = []
    for stride, card in zip(strides, cards):
        id_cols.append((nz // stride) % card)
    vtables = plan.group_value_tables or (None,) * len(gcols)
    value_cols = []
    for (c, gkind, off, _card), ids, tv in zip(gcols, id_cols, vtables):
        if gkind == "idoff":
            ids = ids + off              # re-base adaptive-remapped ids
        elif gkind == "idrank":
            # densifying remap: `off` carries the present-id array; only
            # nonzero-count groups reach here, so every rank is in range
            ids = np.asarray(off)[ids]
        elif gkind in ("jcode", "jraw"):
            # join group codes ARE the dim value-table indices already;
            # the value table (dim uniques) decodes them below
            pass
        if tv is not None:
            value_cols.append(tv[ids])
        elif gkind == "rawoff":
            value_cols.append(ids.astype(np.int64) + off)
        else:
            value_cols.append(
                plan.segment.data_source(c).dictionary.decode(ids))
    return value_cols


def _decode_extreme_ids(plan, spec, arr: np.ndarray, which: str
                        ) -> np.ndarray:
    """dictId-domain per-group extrema → float values (inf when empty)."""
    _fname, col, source, extra = spec
    if source == "sv" and isinstance(extra, tuple) and extra[0] == "ids":
        vals = plan.segment.data_source(col).dictionary.values
        card = len(vals)
        if which == "min":
            valid = arr < card
            sentinel = np.inf
        else:
            valid = arr >= 0
            sentinel = -np.inf
        out = np.full(len(arr), sentinel)
        safe = np.clip(arr, 0, card - 1)
        out[valid] = np.asarray(vals, dtype=np.float64)[safe][valid]
        return out
    return arr


def _assemble_group_map(plan, blk, value_cols, per_agg_arrays,
                        n_groups: int) -> None:
    group_map: Dict[Tuple, List] = {}
    for row in range(n_groups):
        key = tuple(_plain(vc[row]) for vc in value_cols)
        inters: List = []
        for kind, a, b in per_agg_arrays:
            if kind == "count":
                inters.append(int(a[row]))
            elif kind == "sum":
                inters.append(float(a[row]))
            elif kind == "avg":
                inters.append((float(a[row]), int(b[row])))
            elif kind in ("min", "max"):
                v = float(a[row])
                inters.append(None if not np.isfinite(v) else v)
            else:  # minmaxrange
                mn, mx = float(a[row]), float(b[row])
                inters.append((None if not np.isfinite(mn) else mn,
                               None if not np.isfinite(mx) else mx))
        old = group_map.get(key)
        if old is not None:
            # expression group keys can collide (non-injective transform):
            # merge with the same semantics as cross-segment combine
            inters = [f.merge(o, v) for f, o, v in
                      zip(plan.functions, old, inters)]
        group_map[key] = inters
    blk.group_map = group_map


def _finish_group_by(plan, outs, blk) -> None:
    if "group.rkeys" in outs:
        _finish_group_by_ranked(plan, outs, blk)
        return
    gcols, strides, g_pad, agg_specs, kmax = plan.group_spec
    counts = np.asarray(outs["group.count"])
    nz = np.nonzero(counts)[0]
    value_cols = _decode_group_values(plan, nz)

    def _sum_array(i, spec):
        """Exact f64 per-group sums from the device partials."""
        fname, col, source, extra = spec
        strategy = extra[0] if isinstance(extra, tuple) else None
        # all arithmetic below runs on the non-empty groups only — the
        # full [G] tables can be millions of slots with a handful occupied
        if strategy == "psums" and f"gagg{i}.cpsums.lo" in outs:
            # sharded compacted path: 16-bit halves psum'd across segments,
            # recombined exactly here in int64
            lo = np.asarray(outs[f"gagg{i}.cpsums.lo"])[:, nz]
            hi = np.asarray(outs[f"gagg{i}.cpsums.hi"])[:, nz]
            arr = (hi.astype(np.int64) << 16) + lo.astype(np.int64)
        elif strategy == "psums" and f"gagg{i}.cpsums" in outs:
            # compacted path: scatter-combined int32 [n_parts, G], or
            # [n_chunks, n_parts, G] when kmax exceeded the per-scatter
            # int32 bound — recombine chunks exactly in int64 here
            a = np.asarray(outs[f"gagg{i}.cpsums"]).astype(np.int64)
            if a.ndim == 3:
                a = a.sum(axis=0)
            arr = a[:, nz]
        elif strategy == "psums":
            arr = np.asarray(outs[f"gagg{i}.psums"])[..., nz]
            if arr.ndim == 3:                  # sharded: [S, n_parts, nz]
                arr = arr.astype(np.int64).sum(0)
            arr = arr.astype(np.int64)
        elif strategy == "csums" and f"gagg{i}.csums" in outs:
            arr = np.asarray(outs[f"gagg{i}.csums"])[..., nz]
            if arr.ndim == 2:                  # sharded: [S, nz] — combine
                arr = arr.sum(0, dtype=np.float64)   # in f64 on host
            return arr.astype(np.float64)
        else:
            return np.asarray(outs[f"gagg{i}.sum"])[nz].astype(np.float64)
        _, min_v = plan.segment.data_source(col).int_part_info()
        shifts = np.left_shift(np.int64(1),
                               7 * np.arange(arr.shape[0], dtype=np.int64))
        totals = (arr * shifts[:, None]).sum(0)
        totals = totals + np.int64(min_v) * counts[nz].astype(np.int64)
        return totals.astype(np.float64)

    def _extreme_array(i, spec, which):
        """Per-group min/max as float values (inf sentinels when empty)."""
        arr = np.asarray(outs[f"gagg{i}.{which}"])[nz]
        return _decode_extreme_ids(plan, spec, arr, which)

    per_agg_arrays = []
    for i, spec in enumerate(agg_specs):
        fname = spec[0]
        if fname == "count":
            per_agg_arrays.append(("count", counts[nz], None))
        elif fname == "sum":
            per_agg_arrays.append(("sum", _sum_array(i, spec), None))
        elif fname == "avg":
            per_agg_arrays.append(("avg", _sum_array(i, spec), counts[nz]))
        elif fname == "min":
            per_agg_arrays.append(("min", _extreme_array(i, spec, "min"),
                                   None))
        elif fname == "max":
            per_agg_arrays.append(("max", _extreme_array(i, spec, "max"),
                                   None))
        elif fname == "minmaxrange":
            per_agg_arrays.append(("minmaxrange",
                                   _extreme_array(i, spec, "min"),
                                   _extreme_array(i, spec, "max")))
        else:
            raise ValueError(fname)

    _assemble_group_map(plan, blk, value_cols, per_agg_arrays, len(nz))


def _finish_group_by_ranked(plan, outs, blk) -> None:
    """Finish the ranked compacted group-by (kernels.py: wide-key layout).

    Per-segment tables are addressed by group RANK with a parallel key
    lane, so the cross-segment combine happens here: concatenate every
    segment's valid (key, partial) entries and merge them columnar via
    np.unique + np.add.at / minimum.at / maximum.at — the
    CombineGroupByOperator merge without the g_pad-sized tables.
    """
    gcols, strides, g_pad, agg_specs, kmax = plan.group_spec
    rkeys = np.asarray(outs["group.rkeys"])
    rcount = np.asarray(outs["group.rcount"])
    single = rkeys.ndim == 1
    if single:                               # single segment → [S=1, K]
        rkeys, rcount = rkeys[None], rcount[None]
    valid = rkeys < g_pad                    # [S, K]
    nz, inverse = np.unique(rkeys[valid], return_inverse=True)
    counts_nz = np.zeros(len(nz), np.int64)
    np.add.at(counts_nz, inverse, rcount[valid].astype(np.int64))
    value_cols = _decode_group_values(plan, nz)

    def _sum_array(i, spec):
        fname, col, source, extra = spec
        strategy = extra[0] if isinstance(extra, tuple) else None
        if strategy == "psums":
            a = np.asarray(outs[f"gagg{i}.rpsums"]).astype(np.int64)
            if single:                       # [P, K] or [C, P, K] chunked
                a = (a.sum(axis=0) if a.ndim == 3 else a)[None]
            elif a.ndim == 4:                # [S, C, P, K] chunked
                a = a.sum(axis=1)
            vals = np.moveaxis(a, 1, 2)[valid]          # [M, P]
            sums = np.zeros((len(nz), vals.shape[1]), np.int64)
            np.add.at(sums, inverse, vals)
            _, min_v = plan.segment.data_source(col).int_part_info()
            shifts = np.left_shift(
                np.int64(1), 7 * np.arange(sums.shape[1], dtype=np.int64))
            totals = (sums * shifts[None, :]).sum(1)
            return (totals + np.int64(min_v) * counts_nz).astype(np.float64)
        a = np.asarray(outs[f"gagg{i}.rsum"], dtype=np.float64)
        if a.ndim == 1:
            a = a[None]
        sums = np.zeros(len(nz), np.float64)
        np.add.at(sums, inverse, a[valid])
        return sums

    def _extreme_array(i, spec, which):
        a = np.asarray(outs[f"gagg{i}.r{which}"])
        if a.ndim == 1:
            a = a[None]
        if a.dtype.kind in "iu":             # dictId domain
            _fname, col, _source, extra = spec
            sentinel = extra[1] if which == "min" else -1
            out = np.full(len(nz), sentinel, np.int64)
            red = np.minimum if which == "min" else np.maximum
            red.at(out, inverse, a[valid].astype(np.int64))
            return _decode_extreme_ids(plan, spec, out, which)
        sentinel = np.inf if which == "min" else -np.inf
        out = np.full(len(nz), sentinel, np.float64)
        red = np.minimum if which == "min" else np.maximum
        red.at(out, inverse, a[valid].astype(np.float64))
        return out

    per_agg_arrays = []
    for i, spec in enumerate(agg_specs):
        fname = spec[0]
        if fname == "count":
            per_agg_arrays.append(("count", counts_nz, None))
        elif fname == "sum":
            per_agg_arrays.append(("sum", _sum_array(i, spec), None))
        elif fname == "avg":
            per_agg_arrays.append(("avg", _sum_array(i, spec), counts_nz))
        elif fname == "min":
            per_agg_arrays.append(("min", _extreme_array(i, spec, "min"),
                                   None))
        elif fname == "max":
            per_agg_arrays.append(("max", _extreme_array(i, spec, "max"),
                                   None))
        elif fname == "minmaxrange":
            per_agg_arrays.append(("minmaxrange",
                                   _extreme_array(i, spec, "min"),
                                   _extreme_array(i, spec, "max")))
        else:
            raise ValueError(fname)

    _assemble_group_map(plan, blk, value_cols, per_agg_arrays, len(nz))


def vector_segment_identity(segment) -> Tuple[str, int]:
    """(logical segment name, doc-id base) for vector result rows.

    A consuming segment's device snapshot (`__frozen`, rows [0, start))
    and host tail (`__tail`, rows [start, n)) are ONE logical segment:
    stripping the suffix and offsetting tail docids by `start` makes
    (name, $docId) identical to what a whole-segment host pass reports —
    the bit-identical-ids contract across host/device/sharded paths.
    """
    name = getattr(segment, "segment_name", "?")
    for suffix in ("__frozen", "__tail"):
        if name.endswith(suffix):
            name = name[: -len(suffix)]
    return name, int(getattr(segment, "start", 0) or 0)


def _decode_gather_columns(segment, gather_cols, outs, plain=None):
    """Per-column decoded value arrays for selection/vector gather lanes."""
    plain = plain or _plain
    col_values = []
    for col, source in gather_cols:
        ds = segment.data_source(col)
        lane = np.asarray(outs[f"sel.{col}"])
        if source == "sv":
            vals = ds.dictionary.decode(np.clip(lane, 0,
                                                ds.metadata.cardinality - 1))
        elif source == "raw":
            vals = lane
        else:  # mv: [k, W] padded ids
            card = ds.metadata.cardinality
            vals = [[plain(ds.dictionary.get(i)) for i in row if i < card]
                    for row in lane]
        col_values.append(vals)
    return col_values


def vector_result_rows(decode_segment, select_spec, outs,
                       seg_name: str, doc_base: int) -> List[tuple]:
    """Rows (user cols..., $docId, $segmentName, $score) from one
    segment's kernel outputs. `decode_segment` supplies the dictionary
    decode tables (the union view on the sharded path); name/base name
    the rows' identity."""
    _kind, _k, _order, gather_cols = select_spec
    docids = np.asarray(outs["sel.docids"])
    scores = np.asarray(outs["sel.scores"])
    col_values = _decode_gather_columns(decode_segment, gather_cols, outs)
    rows = []
    for r in range(len(docids)):
        if docids[r] < 0:
            continue
        rows.append(tuple(_plain(cv[r]) for cv in col_values) +
                    (int(docids[r]) + doc_base, seg_name,
                     float(scores[r])))
    return rows


def _finish_vector(plan, outs, blk, matched: int) -> None:
    from pinot_tpu.common.request import VECTOR_RESULT_COLUMNS
    name, base = vector_segment_identity(plan.segment)
    blk.selection_rows = vector_result_rows(plan.segment, plan.select_spec,
                                            outs, name, base)
    blk.selection_columns = [c for c, _ in plan.select_spec[3]] + \
        list(VECTOR_RESULT_COLUMNS)
    blk.selection_display_cols = None
    blk.stats.num_docs_scanned = matched


def _finish_selection(plan, outs, blk, matched: int) -> None:
    kind, k, order, gather_cols = plan.select_spec
    docids = np.asarray(outs["sel.docids"])
    valid = docids >= 0
    n = int(valid.sum())
    columns = [c for c, _ in gather_cols]
    col_values = _decode_gather_columns(plan.segment, gather_cols, outs)
    rows = []
    for r in range(len(docids)):
        if not valid[r]:
            continue
        rows.append(tuple(_plain(cv[r]) for cv in col_values))
    blk.selection_rows = rows
    blk.selection_columns = columns
    blk.selection_display_cols = plan.select_display
    blk.stats.num_docs_scanned = matched


def _plain(v):
    if isinstance(v, np.generic):
        return v.item()  # tpulint: disable=host-sync -- np.generic scalar: isinstance-guarded, host value
    return v
