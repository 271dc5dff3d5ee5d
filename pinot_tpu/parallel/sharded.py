"""Mesh-sharded multi-segment query execution (segment data parallelism).

Parity: the reference's two combine layers — CombineOperator /
CombineGroupByOperator (pinot-core/.../operator/CombineOperator.java:27,
CombineGroupByOperator.java:107-156: per-segment plans on an ExecutorService,
merged into a shared ConcurrentHashMap) and the broker's scatter-gather
(SURVEY.md §2.18 #1/#2) — rebuilt the TPU way:

- Homogeneous segments (same schema, same padded doc count) are stacked
  onto a leading `seg` axis and sharded over a `jax.sharding.Mesh` with
  `shard_map`.
- Each device vmaps the single-segment kernel over its local shard, reduces
  locally, then combines across devices with XLA collectives over ICI:
  `psum` for counts/sums/histograms/group tables, `pmin`/`pmax` for id- or
  value-domain extrema, `all_gather` for selection lanes.
- Cross-segment combine in the dictId domain is only sound in ONE shared id
  space. Segments built independently (the normal storage path) have
  per-segment dictionaries, so the stacker builds a UNION DICTIONARY per
  column — the sorted merge of every segment's values — and remaps each
  segment's id lanes into the union domain at stack time, before upload
  (a monotonic id map: sortedness and range-filter semantics survive).
  Queries then plan against a union view of segment 0 and combine on
  device exactly as in the shared case. This is the value-domain merge of
  the reference's CombineGroupByOperator
  (core/operator/CombineGroupByOperator.java:107-156) moved to stack time:
  pay the remap once per (segment-set, column), not per query.
  `NotShardable` remains only for genuinely un-stackable sets (mutable
  segments, differing padded sizes/shapes, raw-column range mismatches).

One jitted shard_map executable serves every query with the same static spec
(shapes pow2-bucketed), mirroring the single-segment plan cache.
"""
from __future__ import annotations

import collections
import functools
import threading
import time
import weakref
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from pinot_tpu.analysis.runtime import debug_transfer_guard
from pinot_tpu.common.request import BrokerRequest
from pinot_tpu.obs import residency
from pinot_tpu.obs.profiler import mark_sum_lanes, profiled_device_get
from pinot_tpu.query import combine as combine_mod
from pinot_tpu.query import execution
from pinot_tpu.query.blocks import ExecutionStats, IntermediateResultsBlock
from pinot_tpu.query.plan import InstancePlanMaker, SegmentPlan
from pinot_tpu.segment.loader import ImmutableSegment

SEG_AXIS = "seg"


class NotShardable(Exception):
    """Segments are not homogeneous enough for id-domain device combine."""


def make_mesh(devices: Optional[Sequence] = None,
              axis: str = SEG_AXIS) -> Mesh:
    devices = list(devices) if devices is not None else jax.devices()
    return Mesh(np.array(devices), (axis,))


# ---------------------------------------------------------------------------
# Cross-segment combine rules, keyed by output name
# ---------------------------------------------------------------------------


def _combine_kind(key: str) -> str:
    if key.startswith("sel."):
        return "stack"          # per-segment; host merges selection rows
    if key.endswith((".parts", ".partsT", ".vsum", ".psums", ".csums")):
        return "stack"          # chunk partials: host combines in int64/f64
    if key.endswith((".rkeys", ".rcount", ".rpsums", ".rsum", ".rmin",
                     ".rmax")):
        return "stack"          # ranked group tables: per-segment rank
        #                         spaces; host merges by group key
    if key.endswith(".min"):
        return "min"
    if key.endswith((".max", ".hll")):
        return "max"            # HLL registers merge by elementwise max
    return "sum"                # counts, histograms, group tables


@functools.lru_cache(maxsize=256)
def get_sharded_kernel(mesh: Mesh, padded: int, filter_spec, agg_specs,
                       group_spec, select_spec, lane_keys: Tuple[str, ...]):
    """Jitted shard_map over the per-segment kernel with device combine.

    `lane_keys` is the static set of column-lane names; `.vals` lanes
    (shared dictionary value tables) are replicated, everything else is
    sharded over the `seg` axis.
    """
    from pinot_tpu.ops.kernels import build_segment_kernel
    kern = build_segment_kernel(padded, filter_spec, agg_specs, group_spec,
                                select_spec)
    # dictionary-scale tables (values, HLL idx/rank) are replicated;
    # row-scale lanes shard over the seg axis
    REPL = (".vals", ".hllidx", ".hllrank")
    col_specs = {k: P() if k.endswith(REPL) else P(SEG_AXIS)
                 for k in lane_keys}
    col_axes = {k: None if k.endswith(REPL) else 0 for k in lane_keys}

    def local(cols, params, num_docs):
        # cols leaves: [S_local, ...] (vals replicated); num_docs [S_local]
        outs = jax.vmap(lambda c, n: kern(c, params, n),
                        in_axes=(col_axes, 0))(cols, num_docs)
        combined = {}
        # per-segment matched counts (for numSegmentsMatched parity with
        # the sequential path), gathered alongside the global reduction
        per_seg = outs["stats.num_docs_matched"]
        combined["stats.seg_matched"] = jax.lax.all_gather(
            per_seg, SEG_AXIS).reshape(-1)
        for k, v in outs.items():
            kind = _combine_kind(k)
            if k.endswith(".cpsums"):
                # compacted int part sums: a straight int32 psum could
                # overflow past ~16.9M matched rows in one group, so split
                # each segment's table into 16-bit halves (each half's
                # cross-segment sum stays far inside int32) and let the
                # host recombine in int64
                flat = v.reshape((-1,) + v.shape[-2:])  # [S(*chunks), P, G]
                lo = (flat & 0xFFFF).sum(axis=0)
                hi = ((flat >> 16) & 0xFFFF).sum(axis=0)
                combined[f"{k}.lo"] = jax.lax.psum(lo, SEG_AXIS)
                combined[f"{k}.hi"] = jax.lax.psum(hi, SEG_AXIS)
                continue
            if kind == "sum":
                combined[k] = jax.lax.psum(v.sum(axis=0), SEG_AXIS)
            elif kind == "min":
                combined[k] = jax.lax.pmin(v.min(axis=0), SEG_AXIS)
            elif kind == "max":
                combined[k] = jax.lax.pmax(v.max(axis=0), SEG_AXIS)
            else:  # stack: gather all segments' lanes, restore global order
                g = jax.lax.all_gather(v, SEG_AXIS)      # [D, S_local, ...]
                combined[k] = g.reshape((-1,) + v.shape[1:])
        return combined

    # check_vma=False: outputs are replicated by construction (psum/pmin/
    # pmax/all_gather), but the static varying-axis check can't prove it
    # for the all_gather'd selection lanes.
    fn = jax.shard_map(local, mesh=mesh,
                       in_specs=(col_specs, P(), P(SEG_AXIS)),
                       out_specs=P(), check_vma=False)
    from pinot_tpu.ops.kernels import named_kernel, scan_family
    return jax.jit(named_kernel(
        fn, f"sharded_{scan_family(group_spec, select_spec)}"))


# ---------------------------------------------------------------------------
# Segment stacking
# ---------------------------------------------------------------------------


class _UnionColumn:
    """Union-dictionary remap artifacts for one column.

    values = sorted merge of every segment's dictionary values;
    remaps[s] maps segment s's local dictId (plus the local padding
    sentinel, id == local cardinality) into the union id domain (pad →
    union cardinality). The map is monotonic per segment, so range
    predicates and sorted-layout guarantees survive the remap.
    """

    def __init__(self, col: str, srcs):
        from pinot_tpu.segment.dictionary import Dictionary
        from pinot_tpu.segment.loader import (int_part_info_for,
                                              int_part_table,
                                              pad_dict_values)
        self.col = col
        per_seg = [np.asarray(s.dictionary.values) for s in srcs]
        union = np.unique(np.concatenate(per_seg))
        self.values = union
        self.cardinality = len(union)
        self.remaps = []
        for v in per_seg:
            r = np.searchsorted(union, v).astype(np.int32)
            self.remaps.append(
                np.concatenate([r, np.int32([self.cardinality])]))
        cm0 = srcs[0].metadata
        import dataclasses
        self.metadata = dataclasses.replace(
            cm0, cardinality=self.cardinality,
            min_value=union[0] if len(union) else cm0.min_value,
            max_value=union[-1] if len(union) else cm0.max_value,
            sorted=all(s.metadata.sorted for s in srcs),
            has_inverted_index=False, has_bloom_filter=False)
        self.dictionary = Dictionary(cm0.data_type, union)
        # segment-independent artifacts, built ONCE per union column
        self.padded_vals = pad_dict_values(union, cm0.data_type.np_dtype)
        self.part_info = int_part_info_for(union) \
            if cm0.data_type.np_dtype.kind in "iu" else None
        self.part_table = (int_part_table(union, *self.part_info)
                           if self.part_info is not None else None)
        self.f64_vals = np.concatenate(
            [np.asarray(union, dtype=np.float64), [0.0]]) \
            if cm0.data_type.is_numeric else None
        # HLL (idx, rank) tables in the union value domain, built lazily
        # (only DISTINCTCOUNTHLL queries pay)
        self.hll_tables = None


class _UnionDataSource:
    """Planning-time DataSource view in the union id domain.

    Everything a plan needs — metadata, literal→id binding, part
    encodings, decode tables — comes from the union dictionary; index
    structures that only exist per segment (inverted, bloom, sorted
    ranges) are absent so plans can't take per-segment fast paths."""

    def __init__(self, union: _UnionColumn):
        self.metadata = union.metadata
        self.dictionary = union.dictionary
        self.inverted_index = None
        self.bloom_filter = None
        self.sorted_ranges = None
        self._union = union

    def int_part_info(self) -> tuple:
        return self._union.part_info

    def host_operand(self, kind: str) -> np.ndarray:
        if kind == "vals":
            return self._union.padded_vals
        raise ValueError(
            f"union data source serves plans, not '{kind}' lanes")


class _UnionViewSegment:
    """Segment 0 with union-dictionary columns swapped in — the object
    queries plan against (and decode group/selection results with) when
    a stack spans per-segment dictionaries."""

    def __init__(self, stack: "StackedSegments"):
        self._stack = stack
        self._base = stack.segments[0]
        self._sources: Dict[str, object] = {}  # tpulint: disable=cache-bound -- bounded by the table's column count; dies with the stack (executor LRU)

    @property
    def metadata(self):
        return self._base.metadata

    @property
    def segment_name(self) -> str:
        return self._base.segment_name

    @property
    def num_docs(self) -> int:
        return self._base.num_docs

    @property
    def padded_docs(self) -> int:
        return self._base.padded_docs

    @property
    def column_names(self):
        return self._base.column_names

    @property
    def star_trees(self):
        # star-tree cubes are per-segment id-domain artifacts; the
        # sharded path never serves them (fast paths go sequential)
        return []

    def has_column(self, column: str) -> bool:
        return self._base.has_column(column)

    def data_source(self, column: str):
        ds = self._sources.get(column)
        if ds is None:
            base = self._base.data_source(column)
            union = self._stack.union_column(column) \
                if base.dictionary is not None else None
            ds = _UnionDataSource(union) if union is not None else base
            self._sources[column] = ds
        return ds


class StackedSegments:
    """Host-stacks homogeneous segments and caches sharded device arrays.

    The TPU-native replacement for the reference's per-segment mmap residency
    (PinotDataBuffer): column lanes live HBM-resident, sharded across the
    mesh, uploaded once and reused by every query.
    """

    def __init__(self, segments: Sequence[ImmutableSegment], mesh: Mesh):
        self.segments = list(segments)
        self.mesh = mesh
        n_dev = mesh.devices.size
        if not self.segments:
            raise NotShardable("no segments")
        if any(getattr(s, "is_mutable", False) for s in self.segments):
            raise NotShardable("mutable (consuming) segment in set")
        pads = {s.padded_docs for s in self.segments}
        if len(pads) != 1:
            raise NotShardable(f"padded doc counts differ: {sorted(pads)}")
        self.padded_docs = pads.pop()
        # pad segment count up to a mesh multiple with empty dummies
        self.n_real = len(self.segments)
        self.n_total = -(-self.n_real // n_dev) * n_dev
        self.num_docs = np.zeros(self.n_total, np.int32)
        self.num_docs[: self.n_real] = [s.num_docs for s in self.segments]
        self._dev_num_docs = None
        self._lanes: Dict[Tuple[str, str], object] = {}  # tpulint: disable=cache-bound -- bounded by columns x lane kinds; the whole stack is LRU-evicted by ShardedQueryExecutor (max_stacks)
        # upsert validDocIds lane: keyed by every segment's bitmap
        # version so invalidations landing after the stack was cached
        # re-upload a fresh [S, P] mask (other lanes are immutable);
        # the host array persists so only CHANGED segments' rows are
        # recomputed, and the lock keeps concurrent queries from
        # mutating it mid-upload
        self._vdoc_cache: Optional[Tuple[tuple, object]] = None
        self._vdoc_host: Optional[np.ndarray] = None
        # guards every cache publish on this stack (lanes, union
        # columns, plan segment, vdoc): queries build lanes from
        # concurrent scheduler workers; heavy builds happen OUTSIDE the
        # lock (first-writer-wins publish), only the vdoc rebuild holds
        # it (in-place host-array mutation)
        self._cache_lock = threading.Lock()
        # col -> None (dictionaries shared) | _UnionColumn (remap needed)
        self._union: Dict[str, Optional["_UnionColumn"]] = {}  # tpulint: disable=cache-bound -- bounded by the table's column count; dies with the stack (executor LRU)
        self._plan_segment = None
        # residency: one ledger prefix per stack. Eviction only drops
        # the executor's dict ref — in-flight queries keep the device
        # lanes alive — so release rides GC via the finalizer, which
        # tracks the actual HBM lifetime.
        self._ledger_prefix = f"stack:{id(self)}:"
        self._ledger_table = self.segments[0].metadata.table_name or ""
        self._ledger_seg = f"stack[{self.n_real}]"
        weakref.finalize(self, residency.LEDGER.release_prefix,
                         self._ledger_prefix)

    #: lane kind → residency ledger kind (everything else is a stacked
    #: scan lane)
    _LEDGER_KINDS = {"vec": "vector", "hllidx": "hll", "hllrank": "hll",
                     "ivfa": "vector", "ivfc": "vector", "ivfv": "vector",
                     "vdoc": "vdoc"}

    def _ledgered_put(self, host, owner_suffix: str, lane_kind: str,
                      sharding):
        return residency.ledgered_put(
            host, owner=self._ledger_prefix + owner_suffix,
            table=self._ledger_table, segment=self._ledger_seg,
            kind=self._LEDGER_KINDS.get(lane_kind, "stack"),
            sharding=sharding)

    def union_column(self, col: str) -> Optional["_UnionColumn"]:
        """None when every segment shares the column's dictionary; else
        the union-dictionary remap artifacts (built once per column).
        Racing builders duplicate work; the first published wins."""
        with self._cache_lock:
            if col in self._union:
                return self._union[col]
        srcs = [s.data_source(col) for s in self.segments]
        d0 = srcs[0].dictionary
        if d0 is None:
            union = None                  # raw column: no id domain
        elif all(np.array_equal(s.dictionary.values, d0.values)
                 for s in srcs[1:]):
            union = None
        else:
            union = _UnionColumn(col, srcs)
        with self._cache_lock:
            return self._union.setdefault(col, union)

    def plan_segment(self) -> ImmutableSegment:
        """Segment view queries plan against: segment 0 with every
        differing-dictionary column replaced by its union view, so
        literal→id binding, part encodings and group decode tables all
        live in the union id domain the stacked lanes use."""
        with self._cache_lock:
            if self._plan_segment is None:
                self._plan_segment = _UnionViewSegment(self)
            return self._plan_segment

    def device_num_docs(self):
        with self._cache_lock:
            if self._dev_num_docs is None:
                self._dev_num_docs = self._ledgered_put(
                    self.num_docs, "num_docs", "stack",
                    NamedSharding(self.mesh, P(SEG_AXIS)))
            return self._dev_num_docs

    def lane(self, col: str, kind: str):
        """Sharded [n_total, ...] device array for one column lane.
        Heavy stack/upload work runs outside the cache lock; racing
        builders duplicate the upload and the first published wins."""
        key = (col, kind)
        with self._cache_lock:
            if key in self._lanes:
                return self._lanes[key]
        union = self.union_column(col) \
            if kind in ("ids", "mv", "vals", "parts", "vlane",
                        "hllidx", "hllrank") else None
        if union is not None:
            arrs = [self._union_operand(union, i, kind)
                    for i in range(self.n_real)]
            card = union.cardinality
        else:
            arrs = [s.data_source(col).host_operand(kind)
                    for s in self.segments]
            card = self.segments[0].data_source(col).metadata.cardinality
        if kind in ("vals", "hllidx", "hllrank"):
            # dictionary-scale tables are identical (or the union
            # table); replicate instead of sharding
            out = self._ledgered_put(arrs[0], f"{col}.{kind}", kind,
                                     NamedSharding(self.mesh, P()))
            with self._cache_lock:
                return self._lanes.setdefault(key, out)
        if kind == "mv":
            w = max(a.shape[1] for a in arrs)
            arrs = [np.pad(a, ((0, 0), (0, w - a.shape[1])),
                           constant_values=card) for a in arrs]
        shapes = {a.shape for a in arrs}
        if len(shapes) != 1:
            raise NotShardable(f"column '{col}' lane shapes differ: {shapes}")
        stacked = np.stack(arrs)
        if self.n_total > self.n_real:
            pad_val = stacked.flat[0] * 0
            if kind in ("ids", "mv"):
                pad_val = card
            filler = np.full((self.n_total - self.n_real,) + stacked.shape[1:],
                             pad_val, stacked.dtype)
            stacked = np.concatenate([stacked, filler])
        out = self._ledgered_put(stacked, f"{col}.{kind}", kind,
                                 NamedSharding(self.mesh, P(SEG_AXIS)))
        with self._cache_lock:
            return self._lanes.setdefault(key, out)

    def _union_operand(self, union: _UnionColumn, i: int,
                       kind: str) -> np.ndarray:
        """Segment i's lane remapped into the union id domain (built
        host-side at stack time — the one-time cost that buys id-domain
        device combine for independently built segments)."""
        from pinot_tpu.segment.loader import min_id_dtype
        ds = self.segments[i].data_source(union.col)
        remap = union.remaps[i]
        if kind == "vals":
            return union.padded_vals
        if kind in ("hllidx", "hllrank"):
            from pinot_tpu.segment.loader import hll_tables_padded
            if union.hll_tables is None:
                union.hll_tables = hll_tables_padded(union.values)
            return union.hll_tables[0 if kind == "hllidx" else 1]
        if kind == "ids":
            local = ds.host_operand("ids")
            return remap[local.astype(np.int64)].astype(
                min_id_dtype(union.cardinality))
        if kind == "mv":
            local = ds.host_operand("mv")
            return remap[local.astype(np.int64)].astype(np.int32)
        if kind == "parts":
            # 7-bit part planes in the UNION encoding (offsets from the
            # union min) so every segment's parts add exactly
            ids = remap[ds.host_operand("ids").astype(np.int64)]
            return union.part_table[:, ids]
        if kind == "vlane":
            return union.f64_vals[
                remap[ds.host_operand("ids").astype(np.int64)]]
        raise ValueError(kind)

    def vdoc_lane(self):
        """Sharded [n_total, padded] bool upsert liveness lane; segments
        without a bitmap (or with none invalid) contribute all-True.
        Incremental: only segments whose bitmap version moved since the
        last build have their row recomputed (steady upserts bump ONE
        segment per batch; an O(S*P) rebuild per query would dwarf the
        mask's benefit)."""
        versions = tuple(
            vd.version if (vd := getattr(s, "valid_doc_ids", None))
            is not None else -1
            for s in self.segments)
        cached = self._vdoc_cache
        if cached is not None and cached[0] == versions:
            return cached[1]
        with self._cache_lock:
            cached = self._vdoc_cache
            if cached is not None and cached[0] == versions:
                return cached[1]
            old = cached[0] if cached is not None else None
            host = self._vdoc_host
            if host is None:
                host = np.zeros((self.n_total, self.padded_docs),
                                dtype=bool)
                old = None
            for i, s in enumerate(self.segments):
                if old is not None and old[i] == versions[i]:
                    continue
                vd = getattr(s, "valid_doc_ids", None)
                row = host[i]
                row[:] = False
                if vd is None:
                    row[: s.num_docs] = True
                else:
                    row[: s.num_docs] = vd.valid_mask(0, s.num_docs)
            # upload a COPY: newer jax CPU backends may zero-copy numpy
            # input, and the next incremental rebuild mutates `host` in
            # place — aliasing would corrupt the cached device lane
            out = self._ledgered_put(host.copy(), "vdoc", "vdoc",
                                     NamedSharding(self.mesh, P(SEG_AXIS)))
            self._vdoc_host = host
            self._vdoc_cache = (versions, out)
            return out

    def gather(self, needed_cols) -> Dict[str, object]:
        # lane keys are "<col>.<kind>" — the same names the kernels read
        cols: Dict[str, object] = {}
        for col, kind in needed_cols:
            if kind == "vdoc":
                cols[f"{col}.vdoc"] = self.vdoc_lane()
            else:
                cols[f"{col}.{kind}"] = self.lane(col, kind)
        return cols


# ---------------------------------------------------------------------------
# Sharded executor
# ---------------------------------------------------------------------------


class ShardedQueryExecutor:
    """Executes one BrokerRequest across all segments on a device mesh.

    Plans once against segment 0 (homogeneity is verified by the stacker),
    runs the sharded kernel, and finishes results host-side with the same
    code the single-segment path uses (shared dictionaries make segment 0's
    decode tables valid for the combined partials).
    """

    def __init__(self, mesh: Optional[Mesh] = None,
                 plan_maker: Optional[InstancePlanMaker] = None,
                 max_stacks: int = 4):
        self.mesh = mesh or make_mesh()
        self.plan_maker = plan_maker or InstancePlanMaker()
        # Bounded LRU keyed on the canonical (sorted) name tuple: with
        # randomized routing each server sees many orderings/subsets of the
        # same segment set; sorting collapses orderings to one stack and the
        # LRU bound caps HBM duplication across subsets. A hit additionally
        # requires segment object identity so a refreshed segment (same
        # name, new object) rebuilds instead of serving stale lanes.
        self.max_stacks = max_stacks
        self._stacks: "collections.OrderedDict[Tuple[str, ...], StackedSegments]" = \
            collections.OrderedDict()
        # Queries run on scheduler worker threads while evict_segment fires
        # from segment-transition threads; the lock guards the OrderedDict
        # and the generation counter closes the build/evict race (a stack
        # built concurrently with an eviction is served but never cached).
        self._lock = threading.Lock()
        self._evict_gen = 0

    def stack_for(self, segments: Sequence[ImmutableSegment]
                  ) -> StackedSegments:
        ordered = sorted(segments, key=lambda s: s.segment_name)
        key = tuple(s.segment_name for s in ordered)
        with self._lock:
            st = self._stacks.get(key)
            if st is not None and len(st.segments) == len(ordered) and \
                    all(a is b for a, b in zip(st.segments, ordered)):
                self._stacks.move_to_end(key)
                return st
            gen = self._evict_gen
        st = StackedSegments(ordered, self.mesh)
        with self._lock:
            if self._evict_gen == gen:
                self._stacks[key] = st
                self._stacks.move_to_end(key)
                while len(self._stacks) > self.max_stacks:
                    self._stacks.popitem(last=False)
        return st

    def evict_segment(self, segment_name: str) -> None:
        """Drop every cached stack containing `segment_name`.

        Wired as a segment-removal listener by the server data manager so a
        refreshed/deleted segment's HBM lanes are released promptly instead
        of lingering until LRU pressure.
        """
        with self._lock:
            self._evict_gen += 1
            for key in [k for k in self._stacks if segment_name in k]:
                del self._stacks[key]

    def evict_all(self) -> None:
        """Drop every cached stack. Wired as a residency-manager
        pressure hook: under device-budget pressure the duplicated
        stack lanes are the cheapest HBM to reclaim (stacks rebuild
        from retained host arrays on the next homogeneous query)."""
        with self._lock:
            self._evict_gen += 1
            self._stacks.clear()

    def execute(self, request: BrokerRequest,
                segments: Sequence[ImmutableSegment]
                ) -> IntermediateResultsBlock:
        """`request` arrives preprocessed, as for
        `ServerQueryExecutor.execute`, its one serving caller."""
        # debug complement to tpulint host-sync: implicit device→host
        # pulls raise under PINOT_TPU_DEBUG_TRANSFERS=1
        with debug_transfer_guard():
            return self._execute(request, segments)

    def _execute(self, request: BrokerRequest,
                 segments: Sequence[ImmutableSegment]
                 ) -> IntermediateResultsBlock:
        t0 = time.perf_counter()
        stack = self.stack_for(segments)
        # Fast paths (star-tree cubes, metadata/dictionary answers) are
        # per-segment host work in each segment's OWN id domain — probe
        # them against segment 0 directly and let the sequential
        # executor serve them (it re-plans per segment).
        plan0 = self.plan_maker.make_segment_plan(stack.segments[0],
                                                  request)
        if plan0.fast_path_result is not None:
            raise NotShardable("fast-path plan; no device work to shard")
        # Plan against the union view: every dictionary-encoded column the
        # request references resolves to the union id domain the stacked
        # lanes use — including predicates that constant-fold to
        # MATCH_ALL/EMPTY (folding against the union dictionary is valid
        # for every segment, which folding against segment 0 alone was
        # not). Fully shared-dictionary stacks reuse plan0 — the union
        # view would produce the identical plan, so don't plan twice.
        needs_union = any(
            stack.union_column(col) is not None
            for col in request.referenced_columns()
            if stack.segments[0].has_column(col) and
            stack.segments[0].data_source(col).dictionary is not None)
        seg0 = stack.plan_segment() if needs_union else stack.segments[0]
        if request.is_group_by:
            # raw group keys bin by segment 0's min/max — every segment
            # must share that range or rows would clip into wrong bins
            for col in request.group_by.columns:
                if not seg0.has_column(col):
                    continue
                cm0 = seg0.data_source(col).metadata
                if cm0.has_dictionary:
                    continue
                for s in stack.segments[1:]:
                    cm = s.data_source(col).metadata
                    if (cm.min_value, cm.max_value) != (cm0.min_value,
                                                        cm0.max_value):
                        raise NotShardable(
                            f"raw group column '{col}' min/max differ "
                            "across segments")
        plan = plan0 if not needs_union else \
            self.plan_maker.make_segment_plan(seg0, request)

        # ANN probe homogeneity: the shared plan (built against segment
        # 0) either carries the ivf_probe pred for EVERY stacked segment
        # or for none. A mixed stack would diverge from the sequential
        # path's per-segment index-vs-exact decision, so fall back; lane
        # shape disagreements (different padded codebooks) are caught by
        # the stacker's shape check during gather.
        vec = request.vector
        if vec is not None and int(getattr(vec, "nprobe", 0) or 0) > 0:
            presence = {
                getattr(s.data_source(vec.column), "ivf_centroids", None)
                is not None
                for s in stack.segments}
            if len(presence) > 1:
                raise NotShardable(
                    "stacked segments disagree on IVF index presence")

        # upsert validDocIds: if ANY stacked segment has superseded rows
        # the mask predicate must cover the WHOLE stack (planning against
        # segment 0 alone would miss other segments' masks). The wrap is
        # param-free, so plan params/strides are untouched; plans that
        # already carry the pred (segment 0 itself masked) pass through.
        from pinot_tpu.query.plan import (upsert_mask_active,
                                          with_valid_doc_mask,
                                          VALID_DOC_COLUMN)
        if any(upsert_mask_active(s) for s in stack.segments) and \
                plan.filter_spec is not None:
            import copy as _copy
            plan = _copy.copy(plan)
            plan.filter_spec = with_valid_doc_mask(plan.filter_spec)
            if (VALID_DOC_COLUMN, "vdoc") not in plan.needed_cols:
                plan.needed_cols = plan.needed_cols + (
                    (VALID_DOC_COLUMN, "vdoc"),)

        cols = stack.gather(plan.needed_cols)
        lane_keys = tuple(sorted(cols.keys()))

        def run(agg_specs, group_spec, extra_params=()):
            # returns DEVICE outs; drivers batch the device→host pull
            # into one explicit jax.device_get per dispatch
            fn = get_sharded_kernel(
                self.mesh, stack.padded_docs, plan.filter_spec,
                tuple(agg_specs or ()), group_spec, plan.select_spec,
                lane_keys)
            return fn(cols, tuple(plan.params) + tuple(extra_params),
                      stack.device_num_docs())

        from pinot_tpu.query.plan import (drive_group_execution,
                                          set_group_kmax)
        blk = IntermediateResultsBlock()
        if plan.group_spec is not None:
            spec0 = set_group_kmax(plan.group_spec, stack.padded_docs)
            outs, spec_used = drive_group_execution(
                run, spec0, stack.padded_docs, int(stack.num_docs.sum()),
                plan.segment)
            if spec_used is None:
                blk.group_map = {}
            else:
                execution._finish_group_by(
                    execution._with_group_spec(plan, spec_used), outs, blk)
        else:
            outs = profiled_device_get(run(plan.agg_specs, None, ()))
            mark_sum_lanes(plan.agg_specs)
            if plan.agg_specs:
                execution._finish_aggregation(plan, outs, blk)
        matched = int(outs["stats.num_docs_matched"])
        if plan.select_spec is not None:
            self._finish_selection(request, plan, stack, outs, blk)

        n_leaves = execution._count_filter_leaves(plan.filter_spec)
        n_project = len({c for c, _ in plan.needed_cols})
        total_docs = int(stack.num_docs.sum())
        seg_matched = np.asarray(outs["stats.seg_matched"])[: stack.n_real]
        blk.stats = ExecutionStats(
            num_docs_scanned=matched,
            num_entries_scanned_in_filter=n_leaves * total_docs,
            num_entries_scanned_post_filter=matched * max(
                n_project - n_leaves, 0),
            num_segments_processed=stack.n_real,
            num_segments_matched=int((seg_matched > 0).sum()),
            total_docs=total_docs,
            time_used_ms=(time.perf_counter() - t0) * 1e3)
        return blk

    def _finish_selection(self, request, plan, stack, outs, blk) -> None:
        """Per-segment selection finish + host top-k merge.

        Parity: CombineService selection merge — each segment returns its
        own (already ordered/limited) rows; the combiner re-sorts and trims.
        """
        if plan.select_spec[0] == "vector":
            self._finish_vector(request, plan, stack, outs, blk)
            return
        rows_all: List[tuple] = []
        columns = None
        seg_matched = np.asarray(outs["stats.seg_matched"])
        decode_seg = stack.plan_segment()   # union-domain decode tables
        for i, seg in enumerate(stack.segments):
            sub = {k: v[i] for k, v in outs.items() if k.startswith("sel.")}
            seg_plan = SegmentPlan(
                segment=decode_seg, request=request,
                select_spec=plan.select_spec, needed_cols=plan.needed_cols,
                select_display=plan.select_display)
            seg_blk = IntermediateResultsBlock()
            execution._finish_selection(seg_plan, sub, seg_blk,
                                        int(seg_matched[i]))
            columns = seg_blk.selection_columns
            if rows_all and seg_blk.selection_rows:
                # merge_selection_rows re-sorts (when ordered) and trims to
                # offset+size — the limit is enforced here
                rows_all = combine_mod.merge_selection_rows(
                    request, columns, rows_all, seg_blk.selection_rows)
            elif seg_blk.selection_rows:
                rows_all = seg_blk.selection_rows
        sel = request.selection
        blk.selection_rows = rows_all[: sel.offset + sel.size]
        blk.selection_columns = columns
        blk.selection_display_cols = plan.select_display

    def _finish_vector(self, request, plan, stack, outs, blk) -> None:
        """Per-shard local top-k → exact global merge by score.

        Each stacked segment's kernel lane already holds its own exact
        top-k (the per-shard local top-k); the global k is the score-
        ordered merge — identity (segment name, docid) comes from the
        REAL segment, while dictionary decode of ride-along columns goes
        through the union view (the stacked lanes' id domain)."""
        from pinot_tpu.common.request import VECTOR_RESULT_COLUMNS
        decode_seg = stack.plan_segment()
        columns = [c for c, _ in plan.select_spec[3]] + \
            list(VECTOR_RESULT_COLUMNS)
        rows_all: List[tuple] = []
        for i, seg in enumerate(stack.segments):
            sub = {k: v[i] for k, v in outs.items()
                   if k.startswith("sel.")}
            name, base = execution.vector_segment_identity(seg)
            rows = execution.vector_result_rows(
                decode_seg, plan.select_spec, sub, name, base)
            if rows_all and rows:
                rows_all = combine_mod.merge_selection_rows(
                    request, columns, rows_all, rows)
            elif rows:
                rows_all = rows
        sel = request.selection
        blk.selection_rows = rows_all[: sel.offset + sel.size]
        blk.selection_columns = columns
        blk.selection_display_cols = None
