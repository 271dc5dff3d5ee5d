"""Star-tree query execution: route eligible queries to a cube.

Parity: core/startree/ query side — StarTreeFilterOperator +
StarTreeAggregationExecutor/StarTreeGroupByExecutor and the plan nodes
that swap in when a query's dimensions/metrics are covered
(StarTreeV2's eligibility rules). Here the cube is a columnar grouped
table, so execution is: evaluate the filter over the cube's dictId lanes,
then weighted aggregation over the surviving groups.

Cube rows are SORTED by the split order (lexicographic in the packed
dictId key — the build's sorted factorize guarantees it), which is the
flattened form of the reference's tree: a conjunctive filter whose
leading split dimensions resolve to dictId intervals narrows to
contiguous row blocks by binary search (OffHeapStarTreeNode child lookup
≡ np.searchsorted on the sorted dim lane), and only the surviving block
rows are scanned for the residual predicates. A covering cube therefore
answers in O(log groups + matched rows) host time instead of O(groups).
"""
from __future__ import annotations

import threading
from typing import Dict, List, Optional, Tuple

import numpy as np

from pinot_tpu.common import expression as expr_mod
from pinot_tpu.common.request import (BrokerRequest, FilterOperator,
                                      FilterQueryTree)
from pinot_tpu.obs import profiler as obs_profiler
from pinot_tpu.query.aggregation import make_functions
from pinot_tpu.query.blocks import ExecutionStats, IntermediateResultsBlock

# covered aggregation base -> the cube stat lanes it reads (COUNT reads
# the counts alone)
_STAT_KINDS = {"SUM": ("sum",), "AVG": ("sum",), "MIN": ("min",),
               "MAX": ("max",), "MINMAXRANGE": ("min", "max")}
_COVERED_BASES = {"COUNT", *_STAT_KINDS}

# stop expanding prefix blocks past this fan-out: the residual scan over
# a bounded union of blocks is cheaper than deep enumeration
_PREFIX_BLOCK_LIMIT = 512


class _CubeDataSource:
    """Segment-DataSource-shaped view of one cube dimension lane."""

    def __init__(self, parent_ds, ids: np.ndarray):
        self.metadata = parent_ds.metadata
        self.dictionary = parent_ds.dictionary
        self.dict_ids = ids
        self.raw_values = None
        self.mv_dict_ids = None
        self.inverted_index = None
        self.bloom_filter = None
        self.sorted_ranges = None


class _CubeView:
    """Segment-shaped facade so host filter evaluation runs unchanged."""

    def __init__(self, segment, cube):
        self._segment = segment
        self._cube = cube
        self.num_docs = cube.n_groups
        self.segment_name = segment.segment_name

    def has_column(self, col: str) -> bool:
        return col in self._cube.dim_ids

    def data_source(self, col: str) -> _CubeDataSource:
        return _CubeDataSource(self._segment.data_source(col),
                               self._cube.dim_ids[col])


def _query_needs(request: BrokerRequest, functions
                 ) -> Optional[Tuple[set, set]]:
    """(dimensions, metrics) a covering cube must hold, or None where
    no cube can answer. Coverage: filter + group columns ⊆ dimensions
    (expressions allowed in filters when their source columns are
    dimensions); aggregations are COUNT(*) or covered-base functions
    over cube metrics."""
    if not request.is_aggregation or request.is_selection:
        return None
    if request.query_options.options.get("useStarTree") == "false":
        return None
    needed_dims = set()
    for c in request.filter_columns():
        needed_dims.update(expr_mod.referenced_columns(c))
    for c in (request.group_by.columns if request.group_by else ()):
        if expr_mod.is_expression(c):
            return None                       # group keys must be plain dims
        needed_dims.add(c)
    needed_metrics = set()
    for f in functions:
        if f.info.is_mv:
            return None
        if f.info.base == "COUNT":
            continue
        if f.info.base not in _COVERED_BASES:
            return None
        if expr_mod.is_expression(f.column):
            return None
        needed_metrics.add(f.column)
    return needed_dims, needed_metrics


def _eligible_cube(segment, needs, leaves: "_QueryLeaves", si: int):
    """(cube, descent levels, est. fraction of its rows left) of the
    best cube of segment `si` covering the query, or None."""
    best = None
    best_score = None
    for cube in getattr(segment, "star_trees", None) or ():
        if not (needs[0] <= set(cube.dimensions) and
                needs[1] <= set(cube.metrics)):
            continue
        levels = _descent_levels(cube, leaves, si)
        frac = _prefix_fraction(segment, levels)
        if cube.n_groups * frac * 8 > segment.num_docs:
            # a cube nearly as tall as the segment must be narrowed to a
            # genuinely small block before it beats the doc-scale kernel:
            # a prefix "hit" from one wide RANGE on the leading dim (e.g.
            # dim >= 'A') would otherwise degrade to a near-full host scan
            continue
        key = (len(levels), -cube.n_groups * frac)
        if best is None or key > best_score:
            best, best_score = (cube, levels, frac), key
    return best


def _descent_levels(cube, leaves: "_QueryLeaves", si: int
                    ) -> List[Tuple[str, FilterQueryTree, list]]:
    """(dimension, leaf, dictId intervals) of the leading split
    dimensions a conjunctive filter narrows in segment `si`: a level for
    each dimension in split order that some leaf resolves, ending with
    the first level that holds an interval wider than one id (rows
    inside a multi-id block aren't sorted by deeper dims). Cube choice,
    the stepwise descent and the native call all read this."""
    levels = []
    for dim in cube.dimensions:
        for lf in leaves.by_col.get(dim, ()):
            ivs = leaves.intervals(lf, si)
            if ivs is not None:
                break
        else:
            break                       # unconstrained dim: stop descent
        levels.append((dim, lf, ivs))
        if not all(b - a == 1 for a, b in ivs):
            break
    return levels


def _prefix_fraction(segment, levels) -> float:
    """Estimated fraction of cube rows left after the descent (product
    of per-dim dictId coverage under a uniform-ids assumption). The
    depth, len(levels), ranks cube choice; the fraction gates
    eligibility so a wide RANGE on the leading dim doesn't count as real
    narrowing."""
    frac = 1.0
    for dim, _, ivs in levels:
        d = segment.data_source(dim).dictionary
        card = max(1, len(d)) if d is not None else 1
        frac *= min(1.0, sum(b - a for a, b in ivs) / card)
    return frac


def _conjunctive_leaves(tree: Optional[FilterQueryTree]
                        ) -> Optional[List[FilterQueryTree]]:
    """Flatten an AND-only filter tree into its leaves; None when the
    tree contains OR (prefix narrowing needs a pure conjunction)."""
    if tree is None:
        return []
    if tree.is_leaf():
        return [tree]
    if tree.operator != FilterOperator.AND:
        return None
    out: List[FilterQueryTree] = []
    for c in tree.children:
        sub = _conjunctive_leaves(c)
        if sub is None:
            return None
        out.extend(sub)
    return out


def _leaf_id_intervals(leaf: FilterQueryTree, d
                       ) -> Optional[List[Tuple[int, int]]]:
    """Sorted-dictionary dictId intervals [a, b) of dictionary `d`
    equivalent to the leaf, or None when the leaf can't narrow a sorted
    cube lane (NOT/NOT_IN/REGEXP, expression columns, unsorted mutable
    dictionaries)."""
    if expr_mod.is_expression(leaf.column):
        return None
    if d is None or not getattr(d, "is_sorted", True):
        return None
    op = leaf.operator
    if op == FilterOperator.EQUALITY:
        i = d.index_of(leaf.values[0])
        return [] if i < 0 else [(i, i + 1)]
    if op == FilterOperator.IN:
        ids = sorted({d.index_of(v) for v in leaf.values} - {-1})
        return [(i, i + 1) for i in ids]
    if op == FilterOperator.RANGE:
        lo, hi = d.range_to_id_interval(
            leaf.lower, leaf.upper, leaf.lower_inclusive,
            leaf.upper_inclusive)
        return [] if hi <= lo else [(lo, hi)]
    return None


class _QueryLeaves:
    """One query's conjunctive filter leaves as dictId intervals of each
    of its segments, resolved once (on first demand: a query no cube
    covers resolves nothing) and read by cube choice and descent alike.
    Over several segments the literal is searched once, in the union of
    their dictionaries, and a segment's interval is two look-ups in the
    union's rank table."""

    def __init__(self, segments, tree: Optional[FilterQueryTree]):
        self.segments = segments
        self.leaves = _conjunctive_leaves(tree)   # None: the tree has an OR
        # the three maps live as long as the query: a key a filter leaf
        # or a column of it
        self.by_col: Dict[str, List[FilterQueryTree]] = {}  # tpulint: disable=cache-bound -- one query's object: bounded by its filter's leaves
        for lf in self.leaves or ():
            self.by_col.setdefault(lf.column, []).append(lf)
        self._tables: Dict[str, "_UnionTables"] = {}  # tpulint: disable=cache-bound -- one query's object: bounded by its filter and group columns
        self._intervals: Dict[int, Optional[list]] = {}  # tpulint: disable=cache-bound -- one query's object: bounded by its filter's leaves

    def tables(self, col: str) -> "_UnionTables":
        t = self._tables.get(col)
        if t is None:
            t = self._tables[col] = _union_tables(self.segments, col)
        return t

    def intervals(self, leaf: FilterQueryTree, si: int
                  ) -> Optional[List[Tuple[int, int]]]:
        """The leaf as intervals of segment `si`'s own dictIds; None
        where `_leaf_id_intervals` says it is none."""
        key = id(leaf)
        if key not in self._intervals:
            self._intervals[key] = self._resolve(leaf)
        per_seg = self._intervals[key]
        return None if per_seg is None else per_seg[si]

    def _resolve(self, leaf: FilterQueryTree) -> Optional[list]:
        if expr_mod.is_expression(leaf.column):
            return None
        t = self.tables(leaf.column)
        uivs = _leaf_id_intervals(leaf, t.dictionary)
        if uivs is None:
            return None
        if t.ranks is None:               # one segment: its own ids
            return [uivs]
        ranks = t.ranks
        return [[(ranks[si, a], ranks[si, b]) for a, b in uivs
                 if ranks[si, a] < ranks[si, b]]
                for si in range(len(self.segments))]


def _prefix_select(segment, cube, leaves: List[FilterQueryTree], levels
                   ) -> Optional[Tuple[np.ndarray, int]]:
    """(selected row indices, rows examined) via sorted-prefix descent,
    or None when the leading split dimension is unconstrained (full scan
    is then the only option). Parity: StarTreeFilterOperator's
    depth-first child matching over OffHeapStarTreeNode, done as binary
    searches on the sorted dim lanes."""
    blocks: List[Tuple[int, int]] = [(0, cube.n_groups)]
    consumed: set = set()
    for dim, src, ivs in levels:
        if len(blocks) * max(len(ivs), 1) > _PREFIX_BLOCK_LIMIT:
            break
        lane = cube.dim_ids[dim]
        new_blocks: List[Tuple[int, int]] = []
        dt = lane.dtype.type          # dim lanes are int32; ids fit
        for lo, hi in blocks:
            seg_lane = lane[lo:hi]
            for a, b in ivs:
                # dtype-matched scalars: a python-int key would make numpy
                # promote (copy+cast) the whole lane per call (~120x)
                s = lo + int(np.searchsorted(seg_lane, dt(a), side="left"))
                e = lo + int(np.searchsorted(seg_lane, dt(b), side="left"))
                if s < e:
                    new_blocks.append((s, e))
        blocks = new_blocks
        consumed.add(id(src))
        if not blocks:
            break
    if not consumed:
        return None

    sel = (np.concatenate([np.arange(lo, hi, dtype=np.int64)
                           for lo, hi in blocks])
           if blocks else np.zeros(0, np.int64))
    examined = int(sel.size)
    residual = [lf for lf in leaves if id(lf) not in consumed]
    if residual and sel.size:
        from pinot_tpu.query import host_exec
        view = _SlicedCubeView(segment, cube, sel)
        m = np.ones(sel.size, dtype=bool)
        for lf in residual:
            m &= host_exec._eval_leaf(lf, view)
        sel = sel[m]
    return sel, examined


class _SlicedCubeView:
    """_CubeView restricted to a row subset (residual predicate eval)."""

    def __init__(self, segment, cube, sel: np.ndarray):
        self._segment = segment
        self._cube = cube
        self._sel = sel
        self.num_docs = int(sel.size)
        self.segment_name = segment.segment_name

    def has_column(self, col: str) -> bool:
        return col in self._cube.dim_ids

    def data_source(self, col: str) -> _CubeDataSource:
        return _CubeDataSource(self._segment.data_source(col),
                               self._cube.dim_ids[col][self._sel])


def _cube_select(segment, cube, tree: Optional[FilterQueryTree],
                 leaves: Optional[List[FilterQueryTree]], levels
                 ) -> Tuple[np.ndarray, int]:
    """Selected cube row indices + rows-examined. Prefix descent when
    the filter is conjunctive and constrains the leading split dims;
    full member-gather scan otherwise. Raises for predicates the host
    evaluator can't resolve (callers fall back to the non-cube path)."""
    if levels:
        ps = _prefix_select(segment, cube, leaves, levels)
        if ps is not None:
            return ps
    from pinot_tpu.query import host_exec
    view = _CubeView(segment, cube)
    mask = host_exec._eval_filter(tree, view)
    return np.nonzero(mask)[0], cube.n_groups  # tpulint: disable=host-sync -- mask is host numpy (host_exec filter eval)


def try_star_tree_execute(segment, request: BrokerRequest
                          ) -> Optional[IntermediateResultsBlock]:
    """Execute over a covering cube; None when not eligible."""
    if not getattr(segment, "star_trees", None):
        return None
    return _cube_execute([segment], request, trim=False)


def try_star_tree_execute_multi(segments, request: BrokerRequest
                                ) -> Optional[IntermediateResultsBlock]:
    """Vectorized cube execution across MANY segments at once.

    Merging one group_map dict per segment entry-by-entry in Python is
    fine for two segments, dominant cost for many. Here the matched cube
    rows (union group codes, counts, stat lanes) from every segment come
    back as one set of arrays and are aggregated in one numpy group-by
    pass. Parity: the combine step of StarTreeAggregationExecutor
    outputs, done columnar.
    """
    return _cube_execute(list(segments), request, trim=True)


def _cube_execute(segments, request: BrokerRequest, trim: bool
                  ) -> Optional[IntermediateResultsBlock]:
    """One algorithm for one segment and for many: choose each segment's
    cube, select and gather its matched rows (`_gather_native` where the
    filter is a conjunction of interval leaves under a narrowed prefix,
    the stepwise `_gather_numpy` otherwise), aggregate them once."""
    functions = make_functions(request.aggregations)
    needs = _query_needs(request, functions)
    if needs is None:
        return None
    leaves = _QueryLeaves(segments, request.filter)
    plans = []
    for si, seg in enumerate(segments):
        plan = _eligible_cube(seg, needs, leaves, si)
        if plan is None:
            return None                   # all segments must be covered
        plans.append((seg,) + plan)

    gcols = list(request.group_by.columns) if request.group_by else []
    # per gcol: union value table + per-segment local-id -> union-id LUTs
    # — cached per (segment set, column); keeps the hot path free of
    # OBJECT-array uniques (python string compares dominated the q3.2
    # residual at 8 segments)
    unions = [leaves.tables(c) for c in gcols]
    lanes = _stat_lanes(functions)
    rows = _gather_native(plans, leaves, gcols, unions, lanes)
    native = rows is not None
    if not native:
        try:
            rows = _gather_numpy(plans, request.filter, leaves, gcols,
                                 unions, lanes)
        except Exception:  # noqa: BLE001 — unresolvable predicate
            return None
    codes, counts, stat_rows, matched_groups, scanned = rows

    blk = IntermediateResultsBlock()
    if not gcols:
        blk.agg_intermediates = [
            _cube_aggregate(counts, stat_rows, f) for f in functions]
    else:
        _multi_group_by([u.values for u in unions], codes, counts,
                        stat_rows, functions, blk)
        if trim:
            # same memory/parity bound combine_blocks applies on the
            # per-segment path (AggregationGroupByTrimmingService)
            from pinot_tpu.query.combine import (trim_group_map,
                                                 trim_size_for)
            t = trim_size_for(request.group_by.top_n)
            if len(blk.group_map) > 4 * t:
                blk.group_map = trim_group_map(blk.group_map, functions, t)
    blk.stats = ExecutionStats(
        num_docs_scanned=matched_groups,          # groups, not raw docs —
        # parity: star-tree queries report aggregated doc counts
        num_entries_scanned_in_filter=scanned,
        num_segments_processed=len(segments),
        num_segments_matched=len(segments) if matched_groups else 0,
        total_docs=sum(seg.num_docs for seg in segments))
    blk.cube_native = native
    obs_profiler.mark_cube_descents(native, len(segments))
    return blk


def _stat_lanes(functions) -> List[Tuple[str, str]]:
    """The (metric column, "sum"/"min"/"max") lanes the functions read,
    each once — two functions over one column (MIN(x), MAX(x)) share."""
    return sorted({(f.column, k) for f in functions
                   for k in _STAT_KINDS.get(f.info.base, ())})


def _gather_numpy(plans, tree, leaves: _QueryLeaves, gcols, unions, lanes):
    """The stepwise select and gather, a numpy call a step: the twin of
    `_gather_native`, and the path for what that does not take."""
    code_chunks: List[List[np.ndarray]] = [[] for _ in gcols]
    cnt_chunks: List[np.ndarray] = []
    stat_chunks: List[List[np.ndarray]] = [[] for _ in lanes]
    matched_groups = 0
    scanned = 0
    for si, (seg, cube, levels, _) in enumerate(plans):
        sel, examined = _cube_select(seg, cube, tree, leaves.leaves, levels)
        scanned += examined
        matched_groups += len(sel)
        cnt_chunks.append(cube.counts[sel])
        for i, c in enumerate(gcols):
            ids = cube.dim_ids[c][sel]
            lut = unions[i].luts[si]
            code_chunks[i].append(ids if lut is None else lut[ids])
        for chunks, (col, kind) in zip(stat_chunks, lanes):
            chunks.append(cube.metric_stats[col][kind][sel])
    codes = [np.concatenate(chunks).astype(np.int64)
             for chunks in code_chunks]
    stat_rows = {lane: np.concatenate(chunks)
                 for lane, chunks in zip(lanes, stat_chunks)}
    return (codes, np.concatenate(cnt_chunks), stat_rows, matched_groups,
            scanned)


def _gather_native(plans, leaves: _QueryLeaves, gcols, unions, lanes):
    """Select and gather for all segments in ONE foreign call
    (seglib.cpp `cube_select_gather`), which runs with the interpreter
    lock released: the number of lock-dropping calls a descent makes no
    longer grows with segments x levels x blocks. None where the query
    or a cube is not of its kind: an OR, a leaf that is no interval
    list, a free leading split dimension (the member scan), a descent
    past _PREFIX_BLOCK_LIMIT, no library. It sees ids and intervals
    only."""
    from pinot_tpu import native
    if native.loaded() is None or leaves.leaves is None:
        return None
    addrs = _cube_addresses(plans)
    if addrs is None:
        return None
    seg_hdr, preds, ivs_flat, gcol_rows, stat_rows = [], [], [], [], []
    est = 0.0
    for si, (seg, cube, levels, frac) in enumerate(plans):
        if not levels:
            return None
        a = addrs[si]
        consumed = {id(lf) for _, lf, _ in levels}
        resid = []
        for lf in leaves.leaves:
            if id(lf) not in consumed:
                ivs = leaves.intervals(lf, si)
                if ivs is None:
                    return None
                resid.append((lf.column, lf, ivs))
        for dim, _, ivs in levels + resid:
            first = len(ivs_flat) // 2
            for iv in ivs:
                ivs_flat += iv
            preds += (a["dim", dim], first, len(ivs_flat) // 2)
        seg_hdr += (cube.n_groups, a["counts"], len(levels), len(resid))
        for c, u in zip(gcols, unions):
            gcol_rows += (a["dim", c], u.lut_addrs[si], u.lut_sizes[si])
        stat_rows += [a[lane] for lane in lanes]
        est += cube.n_groups * frac
    out = native.cube_select_gather(
        (seg_hdr, preds, ivs_flat, gcol_rows, stat_rows), len(gcols),
        len(lanes), max(1024, 2 * int(est)), _PREFIX_BLOCK_LIMIT)
    if out is None:
        return None
    codes, counts, stat_lanes, per_seg = out
    return (list(codes), counts, dict(zip(lanes, stat_lanes)),
            sum(per_seg[0::2]), sum(per_seg[1::2]))


def _segment_identity(s):
    """The segment artifact, not the object: name, rows, CRC."""
    md = getattr(s, "metadata", None)
    return (getattr(s, "segment_name", None), s.num_docs,
            getattr(md, "crc", None))


def _segment_cache_identity(s, col: str):
    """Stable identity for one (segment, column) cache axis.

    id(s) is NOT stable: after a segment unload/reload the interpreter
    can reuse the address for the replacement segment, silently serving
    the OLD union LUT — wrong group-by values with no error. Name +
    num_docs + crc + dictionary fingerprint (cardinality and boundary
    values change whenever the value set changes) pin the entry to the
    segment artifact's contents instead of its transient address."""
    d = s.data_source(col).dictionary
    n = len(d)
    fingerprint = (n, str(d.values[0]), str(d.values[n - 1])) if n else (0,)
    return _segment_identity(s) + (fingerprint,)


# union tables per (segment set, column) and lane addresses per (segment
# set, cubes), under one lock and one bound
_SEGMENT_SET_CACHE: Dict = {}
_SEGMENT_SET_LOCK = threading.Lock()


def _cache_get(key):
    with _SEGMENT_SET_LOCK:
        return _SEGMENT_SET_CACHE.get(key)


def _cache_put(key, value) -> None:
    with _SEGMENT_SET_LOCK:
        if len(_SEGMENT_SET_CACHE) > 256:
            _SEGMENT_SET_CACHE.clear()
        _SEGMENT_SET_CACHE[key] = value


class _UnionTables:
    """One column over a set of segments: the sorted union of their
    dictionaries and the tables between a segment's dictIds and the
    union's.

    values      the union's values; a group code decodes through it
    luts[s]     segment s's local dictId -> union id (int64; None for a
                single segment, whose ids are the union's)
    dictionary  the union as a Dictionary, so that a literal resolves by
                the segments' own rules, once for all of them; None
                where a segment's dictionary is missing or unsorted
    ranks       [S, len(union) + 1]: segment s's values below union id
                u, so that a union id range [a, b) is the local range
                [ranks[s, a], ranks[s, b]) (None for a single segment);
                a memoryview, whose look-ups are Python ints and no
                array calls
    """

    def __init__(self, segments, col: str):
        dicts = [s.data_source(col).dictionary for s in segments]
        if len(dicts) == 1:
            self.values = dicts[0].values
            self.luts = [None]
            self.ranks = None
            self.dictionary = dicts[0]
        else:
            vals = [np.asarray(d.values) for d in dicts]
            self.values = np.unique(np.concatenate(vals))
            self.luts = [np.searchsorted(self.values, v).astype(np.int64)
                         for v in vals]
            self.ranks = self.dictionary = None
            if all(getattr(d, "is_sorted", True) for d in dicts) and \
                    len({d.data_type for d in dicts}) == 1:
                from pinot_tpu.segment.dictionary import Dictionary
                self.dictionary = Dictionary(dicts[0].data_type,
                                             self.values)
                ids = np.arange(len(self.values) + 1)
                self.ranks = memoryview(np.stack(
                    [np.searchsorted(lut, ids) for lut in self.luts]))
        # for the native gather, which reads a lut through its address
        self.lut_addrs = [0 if t is None else t.ctypes.data
                          for t in self.luts]
        self.lut_sizes = [0 if t is None else len(t) for t in self.luts]


def _union_tables(segments, col: str) -> _UnionTables:
    """Cached per (segment identity tuple, column): the union merge and
    its object-array compares run once per segment set, leaving only int
    gathers on the query hot path. A single segment's tables are its own
    dictionary and cost nothing to make."""
    if len(segments) == 1:
        return _UnionTables(segments, col)
    key = (tuple(_segment_cache_identity(s, col) for s in segments), col)
    hit = _cache_get(key)
    if hit is None:
        hit = _UnionTables(segments, col)
        _cache_put(key, hit)
    return hit


def _cube_addresses(plans) -> Optional[List[Dict]]:
    """Per segment, the addresses of its chosen cube's lanes ("counts",
    ("dim", column), (metric, stat kind)) for the native gather; None
    where a lane is not as seglib.cpp reads it (int32 dimensions, int64
    counts, float64 stats, C-contiguous, n_groups long). Cached per
    (segment set, cubes); an entry holds its cubes, so a hit is checked
    by identity and a reloaded segment's cube never reads through stale
    addresses."""
    cubes = [cube for _, cube, _, _ in plans]
    key = tuple(_segment_identity(seg) + (seg.star_trees.index(cube),)
                for seg, cube, _, _ in plans)
    hit = _cache_get(key)
    if hit is not None and all(a is b for a, b in zip(hit[0], cubes)):
        return hit[1]
    addrs = []
    for cube in cubes:
        a = {}
        lanes = [("counts", cube.counts, np.int64)]
        lanes += [(("dim", d), ids, np.int32)
                  for d, ids in cube.dim_ids.items()]
        lanes += [((m, k), arr, np.float64)
                  for m, st in cube.metric_stats.items()
                  for k, arr in st.items()]
        for name, arr, dtype in lanes:
            if arr.dtype != dtype or not arr.flags.c_contiguous or \
                    arr.shape != (cube.n_groups,):
                return None
            a[name] = arr.ctypes.data
        addrs.append(a)
    _cache_put(key, (cubes, addrs))
    return addrs


def _multi_group_by(uniq_vals, codes, counts, stat_rows, functions,
                    blk) -> None:
    """Group-by over UNION-id codes, a lane a group column (int lanes
    only; the object-domain work happened once in _union_tables)."""
    key = np.zeros(len(counts), dtype=np.int64)
    for u, inv in zip(uniq_vals, codes):
        key = key * max(len(u), 1) + inv
    uniq_keys, inverse = np.unique(key, return_inverse=True)
    g = len(uniq_keys)

    value_cols = []
    rem = uniq_keys.copy()
    for u in reversed(uniq_vals):
        value_cols.append(u[rem % max(len(u), 1)])
        rem //= max(len(u), 1)
    value_cols.reverse()

    _fill_group_map(blk, functions, g, inverse, counts, value_cols,
                    lambda f, k: stat_rows[f.column, k])


def _cube_aggregate(counts: np.ndarray, stat_rows, f):
    """Function f over the matched rows' counts and stat lanes."""
    base = f.info.base
    cnt = int(counts.sum())
    if base == "COUNT":
        return cnt
    if cnt == 0:
        return None
    if base == "SUM":
        return float(stat_rows[f.column, "sum"].sum())
    if base == "AVG":
        return (float(stat_rows[f.column, "sum"].sum()), cnt)
    if base == "MIN":
        return float(stat_rows[f.column, "min"].min())
    if base == "MAX":
        return float(stat_rows[f.column, "max"].max())
    if base == "MINMAXRANGE":
        return (float(stat_rows[f.column, "min"].min()),
                float(stat_rows[f.column, "max"].max()))
    raise ValueError(base)


def _fill_group_map(blk: IntermediateResultsBlock, functions, g: int,
                    inverse: np.ndarray, row_counts: np.ndarray,
                    value_cols, stat_rows) -> None:
    """The group-by finisher: scatter matched cube rows into `g` group
    slots and emit the engine's standard intermediate formats (AVG =
    (sum, count), MINMAXRANGE = (min, max)). `stat_rows(f, kind)` yields
    the matched rows' "sum"/"min"/"max" lane for function f."""
    gcounts = np.zeros(g, dtype=np.int64)
    np.add.at(gcounts, inverse, row_counts)
    per_fn: List[List] = []
    for f in functions:
        base = f.info.base
        if base == "COUNT":
            per_fn.append([int(c) for c in gcounts])
            continue
        if base in ("SUM", "AVG"):
            sums = np.zeros(g)
            np.add.at(sums, inverse, stat_rows(f, "sum"))
            if base == "SUM":
                per_fn.append([float(s) for s in sums])
            else:
                per_fn.append([(float(s), int(c))
                               for s, c in zip(sums, gcounts)])
        else:
            mins = np.full(g, np.inf)
            maxs = np.full(g, -np.inf)
            np.minimum.at(mins, inverse, stat_rows(f, "min"))
            np.maximum.at(maxs, inverse, stat_rows(f, "max"))
            if base == "MIN":
                per_fn.append([float(v) for v in mins])
            elif base == "MAX":
                per_fn.append([float(v) for v in maxs])
            else:
                per_fn.append([(float(a), float(b))
                               for a, b in zip(mins, maxs)])
    # tolist() converts np scalars to python at C speed — the per-element
    # _plain/.item() genexpr was the profile's top fixed cost per query
    col_lists = [np.asarray(vc).tolist() for vc in value_cols]
    n_fn = len(functions)
    blk.group_map = {
        key: [per_fn[fi][i] for fi in range(n_fn)]
        for i, key in enumerate(zip(*col_lists))}
