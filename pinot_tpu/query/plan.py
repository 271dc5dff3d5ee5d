"""Per-segment plan maker + execution.

Parity: pinot-core/.../core/plan/maker/InstancePlanMakerImplV2.java — chooses
the per-segment execution strategy:
  - metadata-based COUNT with no filter (InstancePlanMakerImplV2.java:148)
  - dictionary-based MIN/MAX/MINMAXRANGE with no filter (:179-211)
  - inverted-index count fast path (BitmapBasedFilterOperator + count)
  - otherwise: one fused device kernel (filter+project+aggregate/group/select)
and FilterPlanNode.java:51 — converts the FilterQueryTree into a physical
filter, resolving each predicate against the column's dictionary host-side so
the device sees only integer compares / member-vector gathers.

The reference's `num.groups.limit` (100k, InstancePlanMakerImplV2.java:58)
becomes the static group-table bound; queries over it fall back to the host
executor (query/host_exec.py).
"""
from __future__ import annotations

import copy
import dataclasses
import os
import re as _re
import time
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

from pinot_tpu.common import expression as expr_mod
from pinot_tpu.common.datatype import DataType
from pinot_tpu.common.request import (AggregationInfo, BrokerRequest,
                                      FilterOperator, FilterQueryTree)
from pinot_tpu.common.metrics import ServerQueryPhase
from pinot_tpu.obs.profiler import (count_path, mark_group_ladder,
                                    mark_sum_lanes, obs_span,
                                    profiled_device_get, sum_lane_attrs)
from pinot_tpu.ops import kernels
from pinot_tpu.query.aggregation import AggregationFunction, make_functions
from pinot_tpu.query.blocks import ExecutionStats, IntermediateResultsBlock
from pinot_tpu.segment.loader import DataSource, ImmutableSegment

DEFAULT_NUM_GROUPS_LIMIT = 100_000     # parity: num.groups.limit
IN_LIST_MEMBER_THRESHOLD = 16          # small IN → broadcast compare, else
                                       # member-vector gather
MAX_SELECTION_K = 1 << 16


class GroupsLimitExceeded(Exception):
    pass


class UnsupportedOnDevice(Exception):
    """Raised when a query shape needs the host fallback executor."""


# ---------------------------------------------------------------------------
# Filter resolution: FilterQueryTree → (kernel spec, params)
# ---------------------------------------------------------------------------

MATCH_ALL = ("match_all",)
EMPTY = ("empty",)

# -- upsert validDocIds masking ---------------------------------------------
# A segment whose table runs primary-key upserts carries a ValidDocIds
# bitmap (realtime/upsert.py); superseded rows must be masked on EVERY
# result path. On device the mask rides as one more fused filter
# predicate over a pseudo-column lane ("$validDocIds.vdoc", a bool [P]
# runtime operand) so COUNT/SUM/GROUP BY/selection agree bit-for-bit
# with the host oracle without new kernel machinery.

VALID_DOC_COLUMN = "$validDocIds"
VALID_DOC_PRED = ("pred", "vdoc", VALID_DOC_COLUMN, "vdoc", None)


def upsert_mask_active(segment) -> bool:
    """True when the segment has superseded rows to mask. An upsert
    segment with zero invalidations plans mask-free (no lane upload);
    the first invalidation changes the static spec, which just compiles
    one more cached kernel variant."""
    vd = getattr(segment, "valid_doc_ids", None)
    return vd is not None and vd.num_invalid > 0


def has_valid_doc_mask(spec) -> bool:
    if spec == VALID_DOC_PRED:
        return True
    return spec is not None and spec[0] == "and" and \
        VALID_DOC_PRED in spec[1]


def with_valid_doc_mask(spec):
    """AND the validDocIds predicate into a resolved filter spec. The
    predicate consumes no params, so prepending it never perturbs the
    depth-first param order of the original tree."""
    if spec == EMPTY or has_valid_doc_mask(spec):
        return spec
    if spec is None or spec == MATCH_ALL:
        return VALID_DOC_PRED
    return ("and", (VALID_DOC_PRED, spec))


def resolve_filter(tree: Optional[FilterQueryTree], segment: ImmutableSegment,
                   keep_covering: bool = False) -> Tuple[tuple, List]:
    """-> (the filter's static spec, its runtime params). With
    `keep_covering` a range that covers a column's whole dictionary
    stays a `range_ids` predicate beside the other conjuncts of its
    AND (see `_covering_range`); alone it folds to MATCH_ALL as ever."""
    if tree is None:
        return MATCH_ALL, []
    params: List = []
    spec = _resolve(tree, segment, params, keep_covering)
    return spec, params


def _covering_range(node: FilterQueryTree, segment: ImmutableSegment):
    """(spec, params) of a RANGE leaf that `_resolve_leaf` folded to
    MATCH_ALL because its bounds cover the segment's whole dictionary,
    as the predicate it would be with narrower bounds; None for any
    other leaf. A group-by's programs are specialised on the filter's
    STRUCTURE, so `d_year BETWEEN 1992 AND 1998` over a 1992-1998
    dictionary would otherwise make a scout, a histogram rung and the
    group tables of a second filter spec: programs that only the
    literals of the widest band compile (PERF.md section 6, PR 35).
    The bounds are runtime operands: one compare a row."""
    if node.operator != FilterOperator.RANGE or \
            expr_mod.is_expression(node.column):
        return None
    ds = segment.data_source(node.column)
    if not ds.metadata.has_dictionary or not ds.metadata.single_value:
        return None
    lo, hi = ds.dictionary.range_to_id_interval(
        node.lower, node.upper, node.lower_inclusive, node.upper_inclusive)
    return (("pred", "range_ids", node.column, "sv", None),
            [np.int32(lo), np.int32(hi)])


def _resolve(node: FilterQueryTree, segment: ImmutableSegment, params: List,
             keep_covering: bool = False) -> tuple:
    if node.operator in (FilterOperator.AND, FilterOperator.OR):
        is_and = node.operator == FilterOperator.AND
        children = []
        narrowing = False        # a conjunct that is no covering range
        for c in node.children:
            sub_params: List = []
            spec = _resolve(c, segment, sub_params,
                            keep_covering and is_and)
            if spec == EMPTY:
                if is_and:
                    return EMPTY
                continue
            if spec == MATCH_ALL:
                if not is_and:
                    return MATCH_ALL
                kept = _covering_range(c, segment) if keep_covering \
                    else None
                if kept is not None:
                    children.append(kept)
                continue
            narrowing = True
            children.append((spec, sub_params))
        if not narrowing:
            return MATCH_ALL if is_and else EMPTY
        if len(children) == 1:
            params.extend(children[0][1])
            return children[0][0]
        for _, p in children:
            params.extend(p)
        return ("and" if is_and else "or",
                tuple(spec for spec, _ in children))
    return _resolve_leaf(node, segment, params)


def _pred_over_values(node: FilterQueryTree, tv: np.ndarray) -> np.ndarray:
    """Apply a numeric predicate to an array of (transformed) values."""
    op = node.operator
    if op == FilterOperator.IS_NULL:
        return np.zeros(len(tv), dtype=bool)   # transforms never yield null
    if op == FilterOperator.IS_NOT_NULL:
        return np.ones(len(tv), dtype=bool)
    if op == FilterOperator.REGEXP_LIKE:
        pat = _re.compile(node.values[0])
        return np.array([bool(pat.search(str(v))) for v in tv])
    if op == FilterOperator.EQUALITY:
        return tv == float(node.values[0])
    if op == FilterOperator.NOT:
        return tv != float(node.values[0])
    if op == FilterOperator.IN:
        return np.isin(tv, [float(v) for v in node.values])
    if op == FilterOperator.NOT_IN:
        return ~np.isin(tv, [float(v) for v in node.values])
    if op == FilterOperator.RANGE:
        m = np.ones(len(tv), dtype=bool)
        if node.lower is not None:
            lo = float(node.lower)
            m &= (tv >= lo) if node.lower_inclusive else (tv > lo)
        if node.upper is not None:
            hi = float(node.upper)
            m &= (tv <= hi) if node.upper_inclusive else (tv < hi)
        return m
    raise UnsupportedOnDevice(f"expression filter operator {op}")


def _resolve_expr_leaf(node: FilterQueryTree, segment: ImmutableSegment,
                       params: List) -> tuple:
    """Expression filter → member vector over the transformed dictionary.

    TPU-first: the transform is evaluated once over the (cardinality-sized)
    dictionary value table host-side; the doc-scale work stays the plain
    member-gather kernel — the device never sees the expression. Parity:
    ExpressionFilterOperator.java:59 evaluates the transform per projected
    block instead (O(docs) work; here it is O(cardinality)).
    """
    expr = expr_mod.parse_expression(node.column)
    srcs = expr_mod.columns_of(expr)
    if len(srcs) != 1:
        raise UnsupportedOnDevice("multi-column expression filter")
    src = srcs[0]
    ds = segment.data_source(src)
    cm = ds.metadata
    if not (cm.has_dictionary and cm.single_value):
        raise UnsupportedOnDevice(
            f"expression over non-dictionary/MV column {src}")
    vals = np.asarray(ds.dictionary.values)
    tv = np.asarray(expr_mod.evaluate(expr, lambda c: vals),
                    dtype=np.float64)
    card = cm.cardinality
    card_pad = kernels.pow2_bucket(card + 1)
    member = np.zeros(card_pad, dtype=bool)
    member[:card] = _pred_over_values(node, tv)
    if not member.any():
        return EMPTY
    if member[:card].all():
        return MATCH_ALL
    params.append(member)
    return ("pred", "member", src, "sv", card_pad)


def _resolve_leaf(node: FilterQueryTree, segment: ImmutableSegment,
                  params: List) -> tuple:
    if expr_mod.is_expression(node.column):
        return _resolve_expr_leaf(node, segment, params)
    ds = segment.data_source(node.column)
    cm = ds.metadata
    if cm.data_type == DataType.VECTOR:
        # embeddings have no value order or equality semantics a WHERE
        # predicate could use; similarity is the VECTOR_SIMILARITY clause
        raise ValueError(
            f"column '{node.column}' is a VECTOR column — WHERE "
            "predicates over embeddings are not supported")
    op = node.operator

    if not cm.has_dictionary:
        return _resolve_raw_leaf(node, ds, params)

    source = "sv" if cm.single_value else "mv"
    dictionary = ds.dictionary
    card = dictionary.cardinality
    card_pad = kernels.pow2_bucket(card + 1)

    if op == FilterOperator.EQUALITY:
        i = dictionary.index_of(node.values[0])
        if i < 0:
            return EMPTY
        params.append(np.int32(i))
        return ("pred", "eq_id", node.column, source, None)

    if op == FilterOperator.NOT:
        i = dictionary.index_of(node.values[0])
        if i < 0:
            return MATCH_ALL
        if source == "mv":
            # see NOT_IN: member vector keeps padding entries non-matching
            member = np.zeros(card_pad, dtype=bool)
            member[:card] = True
            member[i] = False
            params.append(member)
            return ("pred", "member", node.column, source, card_pad)
        params.append(np.int32(i))
        return ("pred", "neq_id", node.column, source, None)

    if op in (FilterOperator.IN, FilterOperator.NOT_IN):
        ids = [dictionary.index_of(v) for v in node.values]
        ids = sorted({i for i in ids if i >= 0})
        negate = op == FilterOperator.NOT_IN
        if not ids:
            return MATCH_ALL if negate else EMPTY
        if negate and source == "mv":
            # negated MV predicates must go through a member vector: the
            # padded-id compare form would let padding entries (id == card)
            # satisfy the negation and match every doc
            member = np.zeros(card_pad, dtype=bool)
            member[:card] = True
            member[ids] = False
            params.append(member)
            return ("pred", "member", node.column, source, card_pad)
        if len(ids) <= IN_LIST_MEMBER_THRESHOLD:
            k = kernels.pow2_bucket(len(ids), floor=1)
            arr = np.full(k, -1, dtype=np.int32)
            arr[: len(ids)] = ids
            params.append(arr)
            return ("pred", "notin_ids" if negate else "in_ids",
                    node.column, source, k)
        member = np.zeros(card_pad, dtype=bool)
        member[ids] = True
        if negate:
            member = ~member
            member[card:] = False   # padding ids never match
        params.append(member)
        return ("pred", "member", node.column, source, card_pad)

    if op == FilterOperator.RANGE:
        lo, hi = dictionary.range_to_id_interval(
            node.lower, node.upper, node.lower_inclusive,
            node.upper_inclusive)
        if lo >= hi:
            return EMPTY
        if lo == 0 and hi >= card and source == "sv":
            return MATCH_ALL
        params.append(np.int32(lo))
        params.append(np.int32(hi))
        return ("pred", "range_ids", node.column, source, None)

    if op == FilterOperator.REGEXP_LIKE:
        # evaluate over the (small) dictionary host-side → member vector.
        # Parity: RegexpLikePredicateEvaluatorFactory uses Matcher.find()
        # semantics, i.e. pattern found anywhere in the value.
        pattern = _re.compile(node.values[0])
        member = np.zeros(card_pad, dtype=bool)
        for i in range(card):
            if pattern.search(str(dictionary.get(i))):
                member[i] = True
        if not member.any():
            return EMPTY
        params.append(member)
        return ("pred", "member", node.column, source, card_pad)

    if op == FilterOperator.IS_NULL:
        return EMPTY      # no null vector yet: nothing is null
    if op == FilterOperator.IS_NOT_NULL:
        return MATCH_ALL

    raise UnsupportedOnDevice(f"filter operator {op}")


def _resolve_raw_leaf(node: FilterQueryTree, ds: DataSource, params: List
                      ) -> tuple:
    dt = ds.metadata.data_type.np_dtype
    if dt.kind not in "iuf":
        # chunked raw string/bytes columns have no device lane; the host
        # executor evaluates their predicates on the decoded object array
        raise UnsupportedOnDevice(
            f"filter over non-numeric raw column {node.column}")
    op = node.operator
    col = node.column

    def cv(v):
        return dt.type(float(v)) if dt.kind == "f" else dt.type(int(str(v)))

    if op == FilterOperator.EQUALITY:
        params.append(cv(node.values[0]))
        return ("pred", "eq_raw", col, "raw", None)
    if op == FilterOperator.NOT:
        params.append(cv(node.values[0]))
        return ("pred", "neq_raw", col, "raw", None)
    if op in (FilterOperator.IN, FilterOperator.NOT_IN):
        vals = sorted({cv(v) for v in node.values})
        k = kernels.pow2_bucket(len(vals), floor=1)
        arr = np.full(k, vals[0], dtype=dt)
        arr[: len(vals)] = vals
        params.append(arr)
        return ("pred", "notin_raw" if op == FilterOperator.NOT_IN
                else "in_raw", col, "raw", k)
    if op == FilterOperator.RANGE:
        info = np.iinfo(dt) if dt.kind in "iu" else np.finfo(dt)
        lo = cv(node.lower) if node.lower is not None else dt.type(info.min)
        hi = cv(node.upper) if node.upper is not None else dt.type(info.max)
        lo_inc = node.lower_inclusive if node.lower is not None else True
        hi_inc = node.upper_inclusive if node.upper is not None else True
        params.append(lo)
        params.append(hi)
        return ("pred", "range_raw", col, "raw", (lo_inc, hi_inc))
    raise UnsupportedOnDevice(f"raw-column filter operator {op}")


# ---------------------------------------------------------------------------
# Join resolution (stage 2 of the multi-stage engine)
#
# The dim side arrives as a JoinContext (query/stages/join.py) — the
# exchanged, already-dim-filtered key/column arrays. The fact-side probe
# compiles to existing kernel primitives wherever possible:
# - dict-encoded fact key: the per-dictId translation (searchsorted of
#   the dictionary's values against the dim keys, O(cardinality) on
#   host) turns the join MATCH into a plain member-vector predicate and
#   each dim group key into a "jcode" gather table;
# - raw fact key: the dim (key, code) arrays ride as runtime operands
#   and the device builds the sorted probe itself ("join_raw"/"jraw" —
#   lax.sort is the build, searchsorted the probe).
# Either way the match predicate ANDs into the fused filter ahead of
# the upsert vdoc lane, so a dead upserted row can never join.
# ---------------------------------------------------------------------------


def _join_key_source(jctx, segment: ImmutableSegment):
    """→ ("sv"|"raw", DataSource) for the fact key column, with the
    integer-key contract enforced (typed StageCompileError)."""
    from pinot_tpu.query.stages.errors import StageCompileError
    if not segment.has_column(jctx.fact_key):
        raise StageCompileError(
            f"join key column '{jctx.fact_key}' does not exist on the "
            "fact table")
    ds = segment.data_source(jctx.fact_key)
    cm = ds.metadata
    if not cm.single_value or cm.data_type.np_dtype.kind not in "iu":
        raise StageCompileError(
            f"join keys must be single-value INTEGER columns; fact key "
            f"'{jctx.fact_key}' is {cm.data_type.name}"
            f"{'' if cm.single_value else ' (multi-value)'}")
    return ("sv" if cm.has_dictionary else "raw"), ds


def _resolve_join_pred(jctx, segment: ImmutableSegment):
    """(filter spec, params) for the join-match predicate."""
    if jctx.empty:
        return EMPTY, []
    source, ds = _join_key_source(jctx, segment)
    cm = ds.metadata
    if source == "sv":
        member = jctx.member_for(np.asarray(ds.dictionary.values))
        if not member.any():
            return EMPTY, []
        card_pad = kernels.pow2_bucket(cm.cardinality + 1)
        memb = np.zeros(card_pad, dtype=bool)
        memb[: cm.cardinality] = member
        return ("pred", "member", jctx.fact_key, "sv", card_pad), [memb]
    keys = jctx.padded_keys(cm.data_type.np_dtype)
    if keys is None:
        # no dim key is representable in the fact dtype — nothing can
        # match (the raw twin of the all-False member vector above)
        return EMPTY, []
    return ("pred", "join_raw", jctx.fact_key, "raw",
            len(keys)), [keys]


# ---------------------------------------------------------------------------
# Plan construction
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class SegmentPlan:
    segment: ImmutableSegment
    request: BrokerRequest
    # device kernel inputs (None when fast_path_result is set)
    filter_spec: Optional[tuple] = None
    params: Optional[List] = None
    agg_specs: Tuple = ()
    group_spec: Optional[tuple] = None
    select_spec: Optional[tuple] = None
    needed_cols: Tuple[Tuple[str, str], ...] = ()   # (column, lane-kind)
    functions: List[AggregationFunction] = dataclasses.field(
        default_factory=list)
    group_strides: Tuple[int, ...] = ()
    # per group column: None (decode via dictionary) or a transformed value
    # table aligned with the source column's dictIds (expression group-by)
    group_value_tables: Tuple = ()
    select_display: Optional[int] = None   # display cols (rest: order-only)
    fast_path_result: Optional[IntermediateResultsBlock] = None

    def execute(self, stacks=None) -> IntermediateResultsBlock:
        """The plan run alone (`execution.execute_segment_plan`; over
        its stack where `stacks`, a server's `LaneStacks`, holds one)."""
        from pinot_tpu.query import execution
        return execution.execute_segment_plan(self, stacks)


def batch_signature(plan: SegmentPlan) -> Optional[tuple]:
    """The compiled-spec identity under which plans for ONE segment may
    share a batched dispatch, or None when this plan cannot batch.

    This is the ground truth behind the advisory plan_shape_key: two
    plans with equal signatures compile (get_batched_segment_kernel)
    to one executable and differ only in runtime param values. Fast
    paths never reach the device; group specs are excluded because
    drive_group_execution's scout phases are value-dependent per query.
    """
    if plan.fast_path_result is not None or plan.group_spec is not None:
        return None
    return (plan.segment.padded_docs, plan.filter_spec,
            tuple(plan.agg_specs or ()), plan.select_spec,
            tuple(plan.needed_cols))


def preprocess_request(segments, request):
    """Parity: core/plan/maker/BrokerRequestPreProcessor.preProcess —
    rewrite FASTHLL(col) to the derived serialized-HLL column recorded in
    segment metadata (consistency-checked across the segment set).

    Returns the request to plan against: the ORIGINAL when no rewrite
    applies, otherwise a shallow COPY with fresh AggregationInfo entries.
    The shared BrokerRequest is never mutated — with per-segment
    execution parallel (and hybrid sub-requests sharing structure), an
    in-place rewrite would be visible mid-plan to concurrently executing
    in-process servers.
    """
    if not request.aggregations:
        return request
    rewrites: Dict[int, str] = {}
    for idx, agg in enumerate(request.aggregations):
        if agg.function_name.upper() != "FASTHLL":
            continue
        derived = None
        first_name = None
        for i, seg in enumerate(segments):
            md = getattr(seg, "metadata", None)
            d = md.get_derived_column(agg.column, "HLL") \
                if hasattr(md, "get_derived_column") else None
            if i == 0:
                derived, first_name = d, getattr(seg, "segment_name", "?")
            elif d != derived:
                raise RuntimeError(
                    "Found inconsistency HLL derived column name. In "
                    f"segment {first_name}: {derived}; in segment "
                    f"{getattr(seg, 'segment_name', '?')}: {d}")
        if derived is not None:
            rewrites[idx] = derived
    if not rewrites:
        return request
    out = copy.copy(request)
    out.aggregations = [
        AggregationInfo(a.function_name, rewrites[i]) if i in rewrites
        else a
        for i, a in enumerate(request.aggregations)]
    return out


class InstancePlanMaker:
    """Builds a SegmentPlan per segment for a BrokerRequest.

    Parity: InstancePlanMakerImplV2.makeInnerSegmentPlan
    (InstancePlanMakerImplV2.java:97).
    """

    def __init__(self, num_groups_limit: int = DEFAULT_NUM_GROUPS_LIMIT,
                 allow_group_compaction: bool = True):
        self.num_groups_limit = num_groups_limit
        self.allow_group_compaction = allow_group_compaction

    def make_segment_plan(self, segment: ImmutableSegment,
                          request: BrokerRequest) -> SegmentPlan:
        if getattr(segment, "is_mutable", False):
            # consuming segments have arrival-order (unsorted) dictionaries,
            # which breaks the sorted-id-interval device predicates — they
            # take the host executor until committed
            raise UnsupportedOnDevice("mutable segment")
        plan = SegmentPlan(segment=segment, request=request)
        if request.is_aggregation:
            plan.functions = make_functions(request.aggregations)

        # stage-2 join context (query/stages/join.py attaches it to the
        # server-local request copy): the probe fuses into the filter,
        # so every whole-segment shortcut below is off — they would
        # count unjoined rows
        jctx = getattr(request, "_join_ctx", None)

        # upsert masking disables every whole-segment shortcut below:
        # metadata counts, star-tree cubes and inverted-index counts all
        # include superseded rows
        masked = upsert_mask_active(segment)
        no_fast = masked or jctx is not None

        # fast path: no filter, metadata/dictionary-answerable aggregations
        if request.is_aggregation and not request.is_group_by and \
                request.filter is None and not no_fast and \
                self._try_metadata_fast_path(plan, segment, request):
            return plan

        # star-tree: a covering pre-aggregated cube answers the query in
        # O(groups) host work (core/startree/ parity; startree/executor.py).
        # This hook serves the sharded path (which plans directly); the
        # sequential path already checked in ServerQueryExecutor.
        if request.is_aggregation and not request.is_selection and \
                not no_fast and \
                getattr(segment, "star_trees", None):
            from pinot_tpu.startree.executor import try_star_tree_execute
            blk = try_star_tree_execute(segment, request)
            if blk is not None:
                plan.fast_path_result = blk
                return plan

        # a group-by's programs follow the filter's structure: keep it
        # the same for every literal of a query template
        filter_spec, params = resolve_filter(
            request.filter, segment, keep_covering=request.is_group_by)

        if jctx is not None and filter_spec != EMPTY:
            # the join-match predicate ANDs in FIRST (its params precede
            # the original tree's in depth-first order)
            jspec, jparams = _resolve_join_pred(jctx, segment)
            if jspec == EMPTY:
                filter_spec = EMPTY
            elif jspec != MATCH_ALL:
                params = jparams + params
                filter_spec = jspec if filter_spec == MATCH_ALL else \
                    ("and", (jspec, filter_spec))

        if filter_spec == EMPTY:
            plan.fast_path_result = _empty_block(plan, segment)
            return plan

        # fast path: COUNT(*) on a pure match-all filter
        if filter_spec == MATCH_ALL and request.is_aggregation and \
                not no_fast and not request.is_group_by and \
                all(f.info.base == "COUNT" and not f.info.is_mv
                    for f in plan.functions):
            blk = IntermediateResultsBlock(
                agg_intermediates=[segment.num_docs for _ in plan.functions])
            _fill_stats(blk, segment, segment.num_docs, 0, 0)
            plan.fast_path_result = blk
            return plan

        # fast path: COUNT(*) + single EQ/IN leaf answered by inverted index
        if request.is_aggregation and not no_fast and \
                not request.is_group_by and \
                all(f.info.base == "COUNT" and not f.info.is_mv
                    for f in plan.functions):
            cnt = self._try_inverted_count(segment, filter_spec, params)
            if cnt is not None:
                blk = IntermediateResultsBlock(
                    agg_intermediates=[cnt for _ in plan.functions])
                _fill_stats(blk, segment, cnt, 0, 0)
                plan.fast_path_result = blk
                return plan

        if masked:
            filter_spec = with_valid_doc_mask(filter_spec)
        plan.filter_spec = filter_spec
        plan.params = params

        needed: Dict[Tuple[str, str], None] = {}
        _collect_filter_cols(filter_spec, needed)

        if request.is_group_by:
            self._plan_group_by(plan, segment, request, needed)
        elif request.is_aggregation:
            plan.agg_specs = tuple(
                _agg_device_spec(f, segment, needed) for f in plan.functions)
        if request.vector is not None:
            self._plan_vector(plan, segment, request, needed)
        elif request.is_selection:
            self._plan_selection(plan, segment, request, needed)

        plan.needed_cols = tuple(needed.keys())
        return plan

    # -- helpers -----------------------------------------------------------
    def _try_metadata_fast_path(self, plan: SegmentPlan,
                                segment: ImmutableSegment,
                                request: BrokerRequest) -> bool:
        inters: List = []
        for f in plan.functions:
            base = f.info.base
            if base == "COUNT" and not f.info.is_mv:
                inters.append(segment.num_docs)
                continue
            if base in ("MIN", "MAX", "MINMAXRANGE") and \
                    segment.has_column(f.column):
                cm = segment.data_source(f.column).metadata
                if cm.has_dictionary and cm.single_value and \
                        cm.data_type.is_numeric:
                    mn, mx = float(cm.min_value), float(cm.max_value)
                    inters.append(mn if base == "MIN" else
                                  mx if base == "MAX" else (mn, mx))
                    continue
            return False
        blk = IntermediateResultsBlock(agg_intermediates=inters)
        _fill_stats(blk, segment, segment.num_docs, 0, 0)
        plan.fast_path_result = blk
        return True

    def _try_inverted_count(self, segment: ImmutableSegment, spec: tuple,
                            params: List) -> Optional[int]:
        if spec[0] != "pred":
            return None
        _, kind, col, source, extra = spec
        if source != "sv":
            return None
        ds = segment.data_source(col)
        if ds.inverted_index is not None:
            if kind == "eq_id":
                return ds.inverted_index.count(int(params[0]))
            if kind == "in_ids":
                ids = [int(i) for i in np.asarray(params[0]) if i >= 0]
                return sum(ds.inverted_index.count(i) for i in ids)
            if kind == "range_ids":
                return ds.inverted_index.count_range(int(params[0]),
                                                     int(params[1]))
        if ds.sorted_ranges is not None:
            r = ds.sorted_ranges
            if kind == "eq_id":
                s, e = r[int(params[0])]
                return int(e - s)
            if kind == "range_ids":
                lo, hi = int(params[0]), int(params[1])
                return int(r[lo:hi, 1].sum() - r[lo:hi, 0].sum())
        return None

    def _plan_group_by(self, plan: SegmentPlan, segment: ImmutableSegment,
                       request: BrokerRequest, needed: Dict) -> None:
        gcols = []
        value_tables = []
        cards = []
        jctx = getattr(request, "_join_ctx", None)
        for c in request.group_by.columns:
            if jctx is not None and request.join is not None and \
                    request.join.qualifies(c):
                # dim-side group key: the fact key lane group-codes
                # through the join translation (jcode gather table for
                # dict keys; device-probed jraw for raw keys); decode
                # goes through the dim value table like an expression key
                dcol = request.join.unqualify(c)
                codes, uniq = jctx.group_coding(dcol)
                source, ds = _join_key_source(jctx, segment)
                n = len(uniq)
                if source == "sv":
                    cm = ds.metadata
                    card_pad = kernels.pow2_bucket(cm.cardinality + 1)
                    plan.params.append(jctx.code_table_for(
                        np.asarray(ds.dictionary.values), dcol, card_pad))
                    gcols.append((jctx.fact_key, "jcode", 0, n))
                    needed[(jctx.fact_key, "ids")] = None
                else:
                    keys_p, codes_p = jctx.padded_key_codes(
                        dcol, ds.metadata.data_type.np_dtype)
                    plan.params.append(keys_p)
                    plan.params.append(codes_p)
                    gcols.append((jctx.fact_key, "jraw", 0, n))
                    needed[(jctx.fact_key, "raw")] = None
                value_tables.append(uniq)
                cards.append(n)
                continue
            if expr_mod.is_expression(c):
                # expression group key: group in the SOURCE column's id
                # domain on device; decode through the transformed value
                # table host-side (collapsing collisions there) — the
                # kernel is identical to a plain group-by
                expr = expr_mod.parse_expression(c)
                srcs = expr_mod.columns_of(expr)
                if len(srcs) != 1:
                    raise UnsupportedOnDevice(
                        "multi-column expression group key")
                src = srcs[0]
                ds = segment.data_source(src)
                vi = expr_mod.valuein_parts(expr)   # raises on malformed
                if vi is not None:
                    # valuein(mvcol, lits...): an MV group key restricted
                    # to the allowed value set — the kernel's MV row
                    # expansion masks disallowed entries via a member
                    # vector riding as a RUNTIME operand (one executable
                    # per template, any literal set)
                    cm = ds.metadata
                    if not cm.has_dictionary or cm.single_value:
                        raise UnsupportedOnDevice(
                            "valuein group key needs a dict MV column")
                    lits = vi[1]
                    card_pad = kernels.pow2_bucket(cm.cardinality + 1)
                    member = np.zeros(card_pad, dtype=bool)
                    ids = ds.dictionary.index_of_many(lits)
                    member[ids[ids >= 0]] = True
                    plan.params.append(member)
                    gcols.append((src, "mvin", 0, cm.cardinality))
                    value_tables.append(None)
                    cards.append(cm.cardinality)
                    needed[(src, "mv")] = None
                    continue
                if not ds.metadata.has_dictionary or \
                        not ds.metadata.single_value:
                    raise UnsupportedOnDevice(
                        f"expression group key over non-dict/MV column {src}")
                vals = np.asarray(ds.dictionary.values)
                tv = np.asarray(expr_mod.evaluate(expr, lambda _: vals))
                gcols.append((src, "ids", 0, ds.metadata.cardinality))
                value_tables.append(tv)
                cards.append(ds.metadata.cardinality)
                needed[(src, "ids")] = None
                continue
            ds = segment.data_source(c)
            cm = ds.metadata
            if cm.has_dictionary and cm.single_value:
                gcols.append((c, "ids", 0, cm.cardinality))
                value_tables.append(None)
                cards.append(cm.cardinality)
                needed[(c, "ids")] = None
                continue
            if cm.has_dictionary and not cm.single_value:
                # MV group key: the kernel expands the row space to one
                # row per (doc, entry) cross-combination before the
                # group machinery (kernels._expand_mv_group — reference
                # parity: DefaultGroupByExecutor.aggregateGroupByMV)
                gcols.append((c, "mvids", 0, cm.cardinality))
                value_tables.append(None)
                cards.append(cm.cardinality)
                needed[(c, "mv")] = None
                continue
            if not cm.has_dictionary and cm.single_value and \
                    cm.data_type.np_dtype.kind in "iu" and \
                    cm.min_value is not None and \
                    -2**31 <= int(cm.min_value) and int(cm.max_value) < 2**31:
                # no-dictionary integer group key: bin by (value - min) —
                # metadata min/max bound the id range (int32-safe: device
                # lanes are int32 when x64 is off); the groups-limit check
                # below rejects ranges too wide for the group table
                span = int(cm.max_value) - int(cm.min_value) + 1
                gcols.append((c, "rawoff", int(cm.min_value), span))
                value_tables.append(None)
                cards.append(span)
                needed[(c, "raw")] = None
                continue
            raise UnsupportedOnDevice(
                f"group-by on non-dictionary/MV column {c}")
        plan.group_value_tables = tuple(value_tables)
        g = int(np.prod(cards, dtype=np.int64))
        # per-query override (parity: the reference's numGroupsLimit query
        # option, InstancePlanMakerImplV2.java:58 + QueryOptionKey)
        limit = self.num_groups_limit
        opt = request.query_options.options.get("numGroupsLimit")
        if opt is not None:
            limit = int(opt)
        if g > limit:
            raise GroupsLimitExceeded(
                f"{g} potential groups > limit {limit}")
        strides = mixed_radix_strides(cards)
        g_pad = kernels.pow2_bucket(g)
        # sort-compaction for filtered group-bys (see kernels.py): start at
        # ~1.5% of the segment; the executor escalates via the overflow flag
        kmax = 0
        if self.allow_group_compaction and plan.filter_spec is not None \
                and plan.filter_spec != MATCH_ALL:
            kmax = initial_group_kmax(segment.padded_docs)
        agg_specs = tuple(
            _agg_device_spec(f, segment, needed, for_group=True, g_pad=g_pad,
                             compact=bool(kmax))
            for f in plan.functions)
        plan.group_spec = (tuple(gcols), strides, g_pad, agg_specs, kmax)
        plan.group_strides = strides

    def _plan_vector(self, plan: SegmentPlan, segment: ImmutableSegment,
                     request: BrokerRequest, needed: Dict) -> None:
        """Ranked vector selection: filtered batched top-k over the
        packed embedding block. The WHERE filter (and the upsert vdoc
        lane) is already fused into plan.filter_spec, so predicate
        pruning narrows the candidate mask BEFORE scores rank — a dead
        upserted row can never reach the top-k."""
        v = request.vector
        ds = segment.data_source(v.column)
        cm = ds.metadata
        if cm.data_type != DataType.VECTOR:
            raise ValueError(
                f"VECTOR_SIMILARITY over non-VECTOR column '{v.column}'")
        dim = cm.vector_dimension
        q_raw = np.asarray(v.query, dtype=np.float32)
        if q_raw.shape != (dim,):
            raise ValueError(
                f"query vector has {q_raw.shape[0] if q_raw.ndim == 1 else '?'}"
                f" dimensions; column '{v.column}' stores {dim}")
        if v.k <= 0:
            raise ValueError(f"VECTOR_SIMILARITY k must be positive, "
                             f"got {v.k}")
        metric = v.metric.lower()
        if metric == "mips":
            metric = "dot"
        if metric not in ("cosine", "dot"):
            raise ValueError(f"unknown similarity metric '{v.metric}' "
                             "(COSINE | DOT | MIPS)")
        gather = []
        for c in request.selection.columns if request.selection else []:
            cds = segment.data_source(c)
            ccm = cds.metadata
            if ccm.data_type == DataType.VECTOR:
                raise UnsupportedOnDevice(
                    f"selection of VECTOR column {c} (host path)")
            if not ccm.has_dictionary:
                if ccm.data_type.np_dtype.kind not in "iuf":
                    raise UnsupportedOnDevice(
                        f"selection over non-numeric raw column {c}")
                gather.append((c, "raw"))
                needed[(c, "raw")] = None
            elif ccm.single_value:
                gather.append((c, "sv"))
                needed[(c, "ids")] = None
            else:
                gather.append((c, "mv"))
                needed[(c, "mv")] = None
        dim_pad = kernels.pow2_bucket(max(dim, 1), floor=1)
        q = np.zeros(dim_pad, np.float32)
        q[:dim] = q_raw
        q_norm = np_vec_tree_norm(q)
        if metric == "cosine" and not q_norm > 0:
            raise ValueError("COSINE similarity needs a non-zero, finite "
                             "query vector")
        nprobe = int(getattr(v, "nprobe", 0) or 0)
        if nprobe > 0:
            cents = getattr(ds, "ivf_centroids", None)
            if cents is not None and \
                    getattr(ds, "ivf_assignments", None) is not None:
                from pinot_tpu.index import ivf as ivf_mod
                # clamp so lax.top_k never exceeds the padded codebook lane
                nprobe_eff = min(nprobe,
                                 ivf_mod.pad_centroids(cents.shape[0]))
                pred = ("pred", "ivf_probe", v.column, "ivf",
                        (nprobe_eff, metric))
                plan.filter_spec = pred if plan.filter_spec == MATCH_ALL \
                    else ("and", (pred, plan.filter_spec))
                # probe operands precede all other filter params: the pred
                # is the first AND child in depth-first evaluation order
                plan.params = [q, np.float32(q_norm)] + plan.params
                for lane in ("ivfa", "ivfc", "ivfv"):
                    needed[(v.column, lane)] = None
                count_path("ivfProbe")
            else:
                # nprobe requested but this segment has no built index:
                # exact scan keeps results correct (ANN is best-effort)
                count_path("ivfExactFallback")
        k = min(kernels.pow2_bucket(v.k, floor=1), segment.padded_docs)
        plan.select_spec = ("vector", k, ((v.column, metric, dim_pad),),
                            tuple(gather))
        plan.select_display = None
        needed[(v.column, "vec")] = None
        # runtime operands AFTER the filter params (depth-first order)
        plan.params.append(q)
        plan.params.append(np.float32(q_norm))

    def _plan_selection(self, plan: SegmentPlan, segment: ImmutableSegment,
                        request: BrokerRequest, needed: Dict) -> None:
        sel = request.selection
        cols = selection_columns(segment, request)
        plan.select_display = len(cols)
        # ORDER BY columns outside the display list ride along at the end
        # of each row so cross-segment merges can re-sort; the reducer
        # trims them via selection_display_cols
        extras = [ob.column for ob in (sel.order_by or [])
                  if ob.column not in cols]
        gather = []
        for c in cols + extras:
            ds = segment.data_source(c)
            if ds.metadata.data_type == DataType.VECTOR:
                # embedding rows have no device gather lane; the host
                # executor decodes them as per-row float lists
                raise UnsupportedOnDevice(
                    f"selection over VECTOR column {c}")
            if not ds.metadata.has_dictionary:
                if ds.metadata.data_type.np_dtype.kind not in "iuf":
                    # chunked raw string/bytes: object arrays have no
                    # device lane — the whole selection goes host-side
                    raise UnsupportedOnDevice(
                        f"selection over non-numeric raw column {c}")
                gather.append((c, "raw"))
                needed[(c, "raw")] = None
            elif ds.metadata.single_value:
                gather.append((c, "sv"))
                needed[(c, "ids")] = None
            else:
                gather.append((c, "mv"))
                needed[(c, "mv")] = None
        k = sel.offset + sel.size
        if k > MAX_SELECTION_K:
            raise UnsupportedOnDevice(f"selection k={k} too large")
        k = min(kernels.pow2_bucket(k, floor=1), segment.padded_docs)
        if not sel.order_by:
            plan.select_spec = ("limit", k, (), tuple(gather))
            return
        order = []
        packed_bits = 0
        all_dict = True
        single_lane_raw = False
        for ob in sel.order_by:
            ds = segment.data_source(ob.column)
            cm = ds.metadata
            if cm.has_dictionary and cm.single_value:
                # sorted dictionary ⇒ id order == value order: dictIds are
                # exact order keys for ANY dict column (incl. float/string)
                card_pad = cm.cardinality + 1
                packed_bits += int(np.ceil(np.log2(max(card_pad, 2))))
                order.append((ob.column, ob.ascending, card_pad, "sv"))
                needed[(ob.column, "ids")] = None
                continue
            if not cm.has_dictionary and cm.single_value and \
                    cm.data_type.is_numeric:
                all_dict = False
                # the device lane keeps int32/f32 width; wider types only
                # exist device-side under x64 (CPU) where hi/lo keys apply
                single_lane_raw = cm.data_type.np_dtype.itemsize <= 4
                order.append((ob.column, ob.ascending, 0, "raw"))
                needed[(ob.column, "raw")] = None
                continue
            raise UnsupportedOnDevice(
                f"order-by on MV/non-numeric-raw column {ob.column}")
        if all_dict and packed_bits <= 30:
            # fast path: one packed int32 key + top_k
            plan.select_spec = ("order", k, tuple(order), tuple(gather))
        elif len(order) == 1 and single_lane_raw:
            # fast path: single raw int32/f32 key, monotone map + top_k
            plan.select_spec = ("ordertk", k, tuple(order), tuple(gather))
        else:
            # general path: per-column int32 key lanes, full device sort —
            # covers >31-bit dict packings, raw columns, and mixes
            plan.select_spec = ("ordermk", k, tuple(order), tuple(gather))


def np_vec_tree_norm(q: np.ndarray) -> np.float32:
    """f32 balanced-tree norm of a (pow2-padded) query vector.

    Delegates to kernels.vec_tree_sum on a NUMPY operand (the helper is
    pure slicing + adds, backend-agnostic), so the engine has exactly
    ONE tree implementation: the q_norm operand the device divides by
    is the same contract the kernel applies to row norms. The host
    oracle (host_exec) keeps its independent twin by policy."""
    qf = np.asarray(q, np.float32)
    return np.float32(np.sqrt(kernels.vec_tree_sum(qf * qf)))


def mixed_radix_strides(cards) -> tuple:
    """Strides for the mixed-radix group key (last column fastest)."""
    strides = []
    acc = 1
    for c in reversed(list(cards)):
        strides.append(acc)
        acc *= c
    return tuple(reversed(strides))


def initial_group_kmax(padded: int) -> int:
    # ~0.8% selectivity tolerance per 8192-row block (r=64) — the MXU
    # block-compaction makes a rerun cheap, so start small and escalate
    return min(kernels.pow2_bucket(max(padded // 128, 1024)), padded)


def set_group_kmax(group_spec: tuple, padded: int) -> tuple:
    """Re-derive kmax for a different run-time padded size (a plan built
    against a small template segment but executed over bigger lanes)."""
    gcols, strides, g_pad, agg_specs, kmax = group_spec
    if not kmax:
        return group_spec
    return (gcols, strides, g_pad, agg_specs, initial_group_kmax(padded))


def escalate_group_kmax(group_spec: tuple, padded: int):
    """Next rung of the compaction ladder: four times the slots a block,
    kept a power of two; None when already at full size. The kernel's
    slot count is r = ceil(kmax / blocks), so a kmax rounded up by
    itself gives an odd r where the block count is no power of two
    (3052 blocks of a 6.25M-row segment: r 32 -> 172), and the
    compaction's one-hot matmul at such a width takes the TPU compiler
    31-34 s with 7 part planes (PERF.md section 6, PR 37) where r 128
    takes 2.7: longer than a query's deadline on a cache that lacks
    the program."""
    gcols, strides, g_pad, agg_specs, kmax = group_spec
    if not kmax or kmax >= padded:
        return None
    t = max(padded // kernels.CBLOCK, 1)
    r = kernels.pow2_bucket(-(-kmax // t) * 4)
    return (gcols, strides, g_pad, agg_specs, min(t * r, padded))


def group_layout(group_spec, padded: int) -> str:
    """The table layout `ops/kernels.py` `_group_outputs` takes for
    this spec over `padded` rows, by the names the kernels' own cases
    have (`contract_cases`: group_dense, group_scatter, group_compacted,
    group_ranked) and "sorted" for `_group_outputs_compacted_sorted`.
    A name for spans and meters; nothing is chosen by it. (An MV
    group-by's expansion is not followed.)"""
    _gcols, _strides, g_pad, _agg_specs, kmax = group_spec
    if not kmax:
        return "dense" if g_pad <= kernels.DENSE_G_LIMIT and \
            padded <= kernels.DENSE_ROWS_LIMIT else "scatter"
    t = max(padded // kernels.CBLOCK, 1)
    if min(max(-(-kmax // t), 8), kernels.CBLOCK) > 256:
        return "sorted"
    return "ranked" if g_pad > kernels.DENSE_G_LIMIT else "compacted"


RANK_HIST_CARD_LIMIT = int(os.environ.get(
    "PINOT_TPU_RANK_HIST_CARD", "512"))    # hist scout + rank remap only
#                              when every group dim's card_pad fits this
#                              budget: both the scout histogram and the
#                              kernel's one-hot rank contraction are
#                              O(rows * card_pad). 0 disables the rung.
DENSE_RANK_HIST_CARD = int(os.environ.get(
    "PINOT_TPU_DENSE_RANK_HIST_CARD", "128"))  # within the DENSE regime the
#                              hist rung fires only when every dim's hist
#                              is VPU-cheap (card_pad <= 128 takes the
#                              fused compare+reduce histogram — ~10ms-class
#                              at 100M rows, vs ~230ms for a 1024-bin
#                              matmul histogram)
DENSE_RANK_HIST_G = int(os.environ.get(
    "PINOT_TPU_DENSE_RANK_HIST_G", "2048"))    # ...and the span key space
#                              exceeds this: below it the lane-concat
#                              dense kernel is already a single MXU pass
#                              (passes = ceil(n_lanes * g/128 / 128)), so
#                              shrinking g buys nothing


def adaptive_phase_a_specs(group_spec) -> Optional[tuple]:
    """Scout agg specs (masked MIN+MAX of each group column's dictIds)
    for the adaptive two-phase group-by, or None when the plan isn't
    eligible (no filter to narrow the key space, or non-dictionary
    keys). Min/max are streaming-rate tree reductions — the scout costs
    about one filter evaluation. (The HISTOGRAM scout for the densifying
    rank remap is a separate, conditional second rung —
    adaptive_hist_specs — because a wide-card histogram at full row
    scale costs ~5x the min/max scout; measured 229ms vs ~10ms for the
    1024-bin p_brand1 hist at 100M rows on v5e.)"""
    if group_spec is None or not group_spec[4]:
        return None
    specs = []
    for (c, gkind, _off, card) in group_spec[0]:
        if gkind != "ids":
            return None
        card_pad = kernels.pow2_bucket(card + 1)
        specs.append(("min", c, "sv", ("ids", card_pad)))
        specs.append(("max", c, "sv", ("ids", card_pad)))
    return tuple(specs)


def adaptive_hist_specs(group_spec, bounds) -> Optional[tuple]:
    """Conditional second scout rung: matched-id histograms, from which
    the host derives each dim's exact PRESENT id set for the densifying
    rank remap (parity intent: DictionaryBasedGroupKeyGenerator's
    map-based generators serve exactly this sparse-key regime — e.g.
    SSB q3.1's 'the 5 Asian nations in a 25-nation sorted dictionary').

    The rung dispatches in two regimes:
    - RANKED ESCAPE (span space > DENSE_G_LIMIT, dims fit
      RANK_HIST_CARD_LIMIT): densifying is the one layout change the
      offset spans can't buy — escaping the ranked sort layout.
    - DENSE SHRINK (span space > DENSE_RANK_HIST_G, every dim's
      card_pad <= DENSE_RANK_HIST_CARD): the lane-concat int8 dense
      kernel's cost scales with ceil(n_lanes * g/128 / 128) MXU
      passes, so collapsing e.g. q3.1's 32*32*8 offset-span space to
      the 8*8*8 present space (the 5 Asian nations scattered in a
      25-nation sorted dictionary) drops 3 row-stream passes to 1;
      the <=128-bin histograms are fused compare+reduce (~10ms-class
      at 100M rows), well under the pass saved. (The round-2 per-lane
      kernel was g-independent — 394ms at g=8192 vs 398ms at g=512 —
      which is why this regime was previously gated off.)
    Returns hist agg specs or None."""
    if not RANK_HIST_CARD_LIMIT:
        return None
    spans, cards = [], []
    for (c, _gkind, _off, card), (lo, hi) in zip(group_spec[0], bounds):
        card_pad = kernels.pow2_bucket(card + 1)
        if card_pad > RANK_HIST_CARD_LIMIT:
            return None
        cards.append(card_pad)
        spans.append(kernels.pow2_bucket(max(hi - lo + 1, 1), floor=1))
    g_span = int(np.prod(spans, dtype=np.int64))
    if kernels.pow2_bucket(g_span) <= kernels.DENSE_G_LIMIT:
        if not DENSE_RANK_HIST_CARD or g_span <= DENSE_RANK_HIST_G or \
                any(cp > DENSE_RANK_HIST_CARD for cp in cards):
            return None
    return tuple(("hist", c, "sv",
                  ("hist", kernels.pow2_bucket(card + 1)))
                 for (c, _gkind, _off, card) in group_spec[0])


def _adaptive_kmax(matched: int, padded: int, total_docs: int,
                   g_pad: int) -> int:
    """Compaction capacity from measured selectivity (per-2048-row-block
    Poisson mean plus tail headroom). NOTE: r (and hence kmax) is
    pow2-bucketed from the phase-A matched count, so literal stability
    holds only within a selectivity bucket — literals of the same
    template whose match rates land in different pow2 buckets (or cross
    the dense-flip threshold) still compile fresh variants."""
    t = max(padded // kernels.CBLOCK, 1)
    mu = matched * kernels.CBLOCK / max(total_docs, 1)
    r = kernels.pow2_bucket(max(16, int(2 * mu + 8)))
    if r > 128 and g_pad <= kernels.DENSE_G_LIMIT:
        # barely-selective filter: the block-compaction einsum degrades
        # past r=128 while the dense path's one-pass one-hot table keeps
        # a flat per-element rate. Measured on v5e for BOTH value kinds
        # (PR 36, scripts/dense_table_cost.py: one 6.25M-row segment,
        # g=512, 4% kept, whole programs): compact r=256 12.5 ms and
        # r=128 11.2 ms; dense with a float/raw value lane + count
        # 6.5 ms, dense with 4 part lanes + count 6.8 ms
        return 0
    return min(t * r, padded)


def adaptive_phase_b_spec(group_spec, scout, matched: int, padded: int,
                          total_docs: int):
    """Derive the remapped group spec from the phase-A scout.

    `scout` = per-gcol ("bounds", lo, hi) — matched dictId range for the
    OFFSET remap — or ("present", ids) — exact matched id set for the
    DENSIFYING RANK remap, used when its pow2 bucket is strictly smaller
    than the span's (scattered actives, e.g. the five Asian nations in a
    25-nation sorted dictionary, make spans 4-8x wider than the active
    set; parity intent: DictionaryBasedGroupKeyGenerator's map-based
    generators handle exactly this sparse-key regime).  Offsets and rank
    vectors are RUNTIME operands — one compiled executable serves every
    literal of the same query template (spans/present-counts bucket to
    the same widths).
    Returns (kernel_spec, finish_spec, extra_params, empty): the kernel
    spec carries placeholder remaps (static, hashable jit key); the
    finish spec carries the real offsets / present-id arrays for
    host-side group decode. The compaction capacity kmax is sized from
    the scout's matched count (per-2048-row-block Poisson mean plus tail
    headroom; the kernel's overflow flag still escalates on skew).
    """
    gcols, _strides, _g_pad, agg_specs, _kmax = group_spec
    dims = []                    # (span, n_rank | None, payload)
    for c, dim in zip(gcols, scout):
        if dim[0] == "present":
            present = dim[1]
            if len(present) == 0:
                return None, None, (), True
            span = kernels.pow2_bucket(
                int(present[-1]) - int(present[0]) + 1, floor=1)
            n = kernels.pow2_bucket(len(present), floor=1)
            dims.append((span, n if n < span else None, present))
        else:
            lo, hi = dim[1], dim[2]
            if hi < lo:
                return None, None, (), True
            span = kernels.pow2_bucket(hi - lo + 1, floor=1)
            dims.append((span, None, (lo, hi)))
    # The rank remap's one-hot contraction is O(rows); "present" scouts
    # only exist when drive_group_execution judged the hist rung worth
    # its cost (ranked-layout escape), so here any pow2 shrink of the
    # key space takes the rank remap.
    g_span = int(np.prod([d[0] for d in dims], dtype=np.int64))
    g_rank = int(np.prod([d[1] if d[1] is not None else d[0]
                          for d in dims], dtype=np.int64))
    use_rank = kernels.pow2_bucket(g_rank) < kernels.pow2_bucket(g_span)
    kernel_gcols, finish_gcols, spans, extra = [], [], [], []
    for c, (span, n, payload) in zip(gcols, dims):
        card_pad = kernels.pow2_bucket(c[3] + 1)
        if use_rank and n is not None:
            present = payload
            rank = np.zeros(card_pad, np.int32)
            rank[present] = np.arange(len(present), dtype=np.int32)
            kernel_gcols.append((c[0], "idrank", 0, n))
            finish_gcols.append((c[0], "idrank", present, n))
            spans.append(n)
            extra.append(rank)
            continue
        if isinstance(payload, tuple):
            lo, hi = payload
        else:                        # present set, contiguous enough
            lo, hi = int(payload[0]), int(payload[-1])
        kernel_gcols.append((c[0], "idoff", 0, span))
        finish_gcols.append((c[0], "idoff", lo, span))
        spans.append(span)
        extra.append(np.int32(lo))
    g = int(np.prod(spans, dtype=np.int64))
    kernel_gcols = tuple(kernel_gcols)
    finish_gcols = tuple(finish_gcols)
    strides = mixed_radix_strides(spans)
    g_pad = kernels.pow2_bucket(g)
    # compaction capacity from measured selectivity.  NOTE: r (and hence
    # kmax) is pow2-bucketed from the phase-A matched count, so literal
    # stability holds only within a selectivity bucket — literals of the
    # same template whose match rates land in different pow2 buckets (or
    # cross the dense-flip threshold below) still compile fresh variants.
    kmax = _adaptive_kmax(matched, padded, total_docs, g_pad)
    kernel_spec = (kernel_gcols, strides, g_pad, agg_specs, kmax)
    finish_spec = (finish_gcols, strides, g_pad, agg_specs, kmax)
    return kernel_spec, finish_spec, tuple(extra), False


#: a ladder's rungs in the order a walk takes them, each with the span
#: its launches and its one pull run under (a scan without a group-by
#: is one rung and has none)
LADDER_PHASES = (("scan", None),
                 ("scout", ServerQueryPhase.GROUP_SCOUT),
                 ("hist", ServerQueryPhase.GROUP_HIST),
                 ("table", ServerQueryPhase.GROUP_TABLE))


class SegmentLadder:
    """One segment's place on its ladder of device programs: what to
    launch next (`program`), given what was pulled (`accept`). The walk
    that drives it (`walk_ladders`) owns the launches and the pulls, so
    one ladder, a sequential walk and a query's eight segments are this
    one piece of logic driven with 1 or N ladders.

    `run(agg_specs, group_spec, extra_params)`, a `program()`,
    dispatches the kernel over the segment alone and returns DEVICE
    outs (extra_params are appended after the filter operands). `segment` is what the plan was made against (its
    `int_part_info` names the table's part lanes on a traced
    `groupTable`). A plan without a `group_spec` is the one rung
    "scan": its `agg_specs` program. Filtered dictionary-keyed group-bys
    take the ADAPTIVE path:

    - "scout" (phase A): masked min/max of each group column's dictIds +
      the matched count — streaming tree reductions, about one filter
      evaluation.
    - "hist" (phase A2, the conditional rung, adaptive_hist_specs):
      matched-id histograms → exact present sets for the densifying rank
      remap, dispatched only when the span key space would need the
      ranked sort layout (> DENSE_G_LIMIT).
    - "table" (phase B): group tables over the REMAPPED key space
      (product of the scout's active spans — or bucketed PRESENT counts
      where the rank remap applies), with MXU block-compaction sized
      from the measured selectivity. Small remapped spaces take the
      dense one-hot layout (device psum combine); big ones the ranked
      layout. A table that reports `group.overflow` stays on this rung
      with four times the slots (`escalate_group_kmax`).

    No sorts or row-scale scatters anywhere on the hot path — those are
    TPU's slow primitives. The one row-scale gather is the idrank
    remap's rank-vector lookup (kernels._group_key), paid only when the
    hist rung proves it collapses the key space below the offset span.
    Non-eligible plans go straight to the compacted table with the kmax
    escalation rung.

    `place` (a `query/execution.py` `StackedPlace`, or None) is the
    ladder's row in its table's stack: the walk launches the next
    programs of ladders whose places share a key as ONE program
    (`place.launch`), and a ladder without one by `run`.

    `phase` is the rung to launch next, None once the ladder is done
    (or `refused`, see `walk_ladders`); then `outs` holds the HOST outs
    to finish from and `finish_spec` the group spec to decode them with
    (None for a scan, and where the filter matched nothing: `outs` then
    still carries the stats). Every
    group-by ladder marks the ladder's meters once
    (`mark_group_ladder`: with its table, or where the filter matched
    nothing) and, with its table, the sum lanes' (the table is where a
    group-by sums): what a segment took, not what it costs."""

    __slots__ = ("run", "group_spec", "padded", "total_docs", "segment",
                 "place", "phase", "outs", "finish_spec", "refused",
                 "hists", "runs", "_specs", "_kspec", "_fspec", "_extra",
                 "_scout", "_matched")

    def __init__(self, run, agg_specs, group_spec, padded: int,
                 total_docs: int, segment, place=None):
        self.run, self.group_spec, self.padded = run, group_spec, padded
        self.total_docs, self.segment = total_docs, segment
        self.place = place
        self.outs = self.finish_spec = self.refused = None
        self.hists = self.runs = 0
        self._fspec, self._extra, self._scout = None, (), None
        if group_spec is None:
            self.phase, self._specs = "scan", agg_specs
            return
        self._specs = adaptive_phase_a_specs(group_spec) \
            if padded <= kernels.DENSE_ROWS_LIMIT else None
        self.phase = "table" if self._specs is None else "scout"
        self._kspec = group_spec

    def program(self) -> tuple:
        """This rung's program: `(agg_specs, group_spec, extra_params)`,
        what `run` takes."""
        if self.phase == "table":
            return (), self._kspec, self._extra
        return self._specs, None, ()

    def accept(self, outs) -> None:
        """The HOST outs of the rung's program: on to the next rung
        (the per-bound int() reads are host numpy, not device pulls)."""
        if self.phase == "scan":
            self._done(outs, None)
        elif self.phase == "scout":
            self._scout = outs
            bounds = [(int(outs[f"agg{2 * i}.min"]),
                       int(outs[f"agg{2 * i + 1}.max"]))
                      for i in range(len(self._specs) // 2)]
            self._matched = int(outs["stats.num_docs_matched"])
            self._specs = adaptive_hist_specs(self.group_spec, bounds) \
                if self._matched > 0 else None
            if self._specs is not None:
                self.phase = "hist"
            else:
                self._plan_table([("bounds", lo, hi) for lo, hi in bounds])
        elif self.phase == "hist":
            self.hists = 1
            self._plan_table([
                ("present", np.nonzero(np.asarray(outs[f"agg{i}"])[: c[3]])[0])
                for i, c in enumerate(self.group_spec[0])])
        else:
            self.runs += 1
            if int(outs.get("group.overflow", 0)) > 0:
                self._kspec = escalate_group_kmax(self._kspec, self.padded)
                assert self._kspec is not None, \
                    "overflow at full kmax is impossible"
                return
            mark_group_ladder(int(self.scouted), self.hists, self.runs,
                              group_layout(self._kspec, self.padded))
            mark_sum_lanes(self._kspec[3])
            # the finish spec carries the real offsets / present-id
            # arrays, and the kmax the ladder ended on
            self._done(outs, self._kspec if self._fspec is None
                       else self._fspec[:4] + (self._kspec[4],))

    @property
    def scouted(self) -> bool:
        return self._scout is not None

    def table_attrs(self) -> dict:
        """What a traced `groupTable` says of this ladder's table."""
        return {"layout": group_layout(self._kspec, self.padded),
                "g": self._kspec[2], "runs": self.runs}

    def _plan_table(self, scout) -> None:
        kspec, fspec, extra, empty = adaptive_phase_b_spec(
            self.group_spec, scout, self._matched, self.padded,
            self.total_docs)
        if empty:
            mark_group_ladder(1, self.hists, 0, None)
            self._done(self._scout, None)
        else:
            self.phase = "table"
            self._kspec, self._fspec, self._extra = kspec, fspec, extra

    def _done(self, outs, finish_spec) -> None:
        self.phase, self.outs, self.finish_spec = None, outs, finish_spec


def walk_ladders(ladders, deadline: Optional[float] = None,
                 keep_refusals: bool = False) -> None:
    """Drive `ladders` to their ends in phases: every ladder on a rung
    launches its program WITHOUT waiting, then ONE explicit batched
    `jax.device_get` brings the rung's outputs home for all of them
    (tpulint host-sync: never per-scalar, and since PR 38 never a
    program either), so the device has a query's programs queued while
    the host is between a pull and the next launches. A kmax re-run
    repeats the table rung for the ladders that overflowed alone.

    Each group-by rung runs under ONE span for all its ladders
    (`groupScout`, `groupHist`, `groupTable`; `attrs.segments`, and on
    the table `layout`, `g` and `runs` a ladder, `scouted` and the
    first's `partLanes` / `valueLanes`) around their `kernelLaunch`es
    (the callers' `run`) and the rung's `kernelDispatch`;
    `outputRelease` is the drop of the rung's device outputs (not free:
    1.9 ms a segment on the CPU rehearsal), under a span and not
    wherever the temporaries would have died; `rungAccept`
    (`attrs.segments`) is the ladders' host step after each pull (the
    pulled outputs sorted to their ladders, the scout's bounds, the
    histogram rung's specs, the table's plan, the kmax check), one a
    pull.

    Ladders with a stacked `place` whose next programs share its key
    are ONE launch (`place.launch`), one entry of the pull an output
    with a row a ladder, split on the host; any other ladder is a
    launch of its own (`run`). A kmax re-run is such a group of the
    ladders that overflowed, with their new table spec.

    `deadline` (time.monotonic()) is checked between rungs: ladders not
    done by then are left where they stand (`phase` not None). With
    `keep_refusals` a ladder whose launch refuses with
    `UnsupportedOnDevice` or `GroupsLimitExceeded` leaves the walk with
    the exception as its `refused` (the caller's host twin answers it);
    without, the refusal propagates."""
    refused = (GroupsLimitExceeded, UnsupportedOnDevice) \
        if keep_refusals else ()
    for phase, span_name in LADDER_PHASES:
        rung = at = [l for l in ladders if l.phase == phase]
        if not at:
            continue
        if deadline is not None and time.monotonic() >= deadline:
            return
        with obs_span(span_name, segments=len(rung)) as span:
            while at:
                # ONE flat dict a pull, "<launch>/<output>": what
                # `jax.device_get` is handed stays a dict of arrays
                launched, pulled, rows = {}, {}, {}
                for n, group in enumerate(_launch_groups(at, refused)):
                    stacked = group[0][2] is not None
                    try:
                        if stacked:
                            outs = at[group[0][0]].place.launch(
                                [(at[i].place, program)
                                 for i, program, _key in group])
                        else:
                            (i, program, _key), = group
                            outs = at[i].run(*program)
                        launched.update((f"{n}/{name}", out)
                                        for name, out in outs.items())
                        rows[n] = ([i for i, _p, _k in group], stacked)
                        pulled.update((i, {}) for i in rows[n][0])
                    except refused as exc:
                        for i, _p, _k in group:
                            at[i].phase, at[i].refused = None, exc
                if not pulled:
                    break
                outs = profiled_device_get(launched, programs=len(rows))
                with obs_span(ServerQueryPhase.OUTPUT_RELEASE):
                    del launched
                with obs_span(ServerQueryPhase.RUNG_ACCEPT,
                              segments=len(pulled)):
                    for key, out in outs.items():
                        n, _, name = key.partition("/")
                        group, stacked = rows[int(n)]
                        if stacked:
                            for r, i in enumerate(group):
                                pulled[i][name] = out[r, ...]
                        else:
                            pulled[group[0]][name] = out
                    for i, ladder_outs in pulled.items():
                        at[i].accept(ladder_outs)
                at = [l for l in at if l.phase == phase]
        if span is not None and phase == "table":
            tables = [l.table_attrs() for l in rung
                      if l.finish_spec is not None]
            span["attrs"] = {
                "segments": len(rung),
                **{k: [t[k] for t in tables]
                   for k in ("layout", "g", "runs")},
                "scouted": rung[0].scouted,
                **sum_lane_attrs(rung[0]._kspec[3], rung[0].segment)}


def _launch_groups(ladders, refused=()) -> list:
    """`ladders`' next launches: [[(index, program, key)]], one list a
    launch. Ladders whose stacked places give one key are one launch;
    a ladder without a place, or whose program has no key, is one of
    its own (key None). A ladder whose params refuse (`refused`) while
    its key is read leaves the walk as a refused launch does."""
    groups = {}
    for i, ladder in enumerate(ladders):
        program = ladder.program()
        try:
            key = ladder.place.key(program) if ladder.place is not None \
                else None
        except refused as exc:
            ladder.phase, ladder.refused = None, exc
            continue
        groups.setdefault(("alone", i) if key is None else key,
                          []).append((i, program, key))
    return list(groups.values())


def drive_group_execution(run, group_spec, padded: int, total_docs: int,
                          segment):
    """ONE segment's group-by ladder walked to its end (`SegmentLadder`,
    `walk_ladders` with one ladder: a pull a rung).
    Returns (outs, group_spec_for_finish); None finish spec means the
    filter matched nothing (outs still carries the stats)."""
    ladder = SegmentLadder(run, (), group_spec, padded, total_docs, segment)
    walk_ladders([ladder])
    return ladder.outs, ladder.finish_spec


def _agg_device_spec(f: AggregationFunction, segment: ImmutableSegment,
                     needed: Dict, for_group: bool = False,
                     g_pad: int = 0, compact: bool = False) -> tuple:
    base = f.info.base
    if base == "COUNT" and not f.info.is_mv:
        return ("count", "*", "none", None)
    col = f.column
    if expr_mod.is_expression(col):
        # expression aggregation argument: the device produces a plain
        # dictId histogram over the SOURCE column; the host finisher
        # evaluates the transform over the dictionary value table and
        # computes SUM/AVG/MIN/MAX/PERCENTILE/DISTINCTCOUNT from
        # (histogram, transformed values) — exact, O(cardinality) transform
        # work, zero doc-scale expression evaluation
        if f.info.is_mv:
            raise UnsupportedOnDevice("MV expression aggregation")
        if for_group:
            raise UnsupportedOnDevice(
                "expression metric inside group-by (host path)")
        srcs = expr_mod.columns_of(col)
        if len(srcs) != 1:
            raise UnsupportedOnDevice("multi-column expression aggregation")
        src = srcs[0]
        cm = segment.data_source(src).metadata
        if not (cm.has_dictionary and cm.single_value):
            raise UnsupportedOnDevice(
                f"expression over non-dictionary/MV column {src}")
        card_pad = kernels.pow2_bucket(cm.cardinality + 1)
        needed[(src, "ids")] = None
        return ("hist", src, "sv", ("hist", card_pad))
    ds = segment.data_source(col)
    cm = ds.metadata
    if cm.data_type == DataType.VECTOR:
        raise ValueError(
            f"aggregation {base} over VECTOR column '{col}' is not "
            "supported (use VECTOR_SIMILARITY for ranking)")
    fname = {
        "COUNT": "countmv" if f.info.is_mv else "count",
        "SUM": "sum", "MIN": "min", "MAX": "max", "AVG": "avg",
        "MINMAXRANGE": "minmaxrange",
        "DISTINCTCOUNT": "distinctcount",
        "DISTINCTCOUNTHLL": "distinctcount", "FASTHLL": "distinctcount",
        "DISTINCTCOUNTRAWHLL": "distinctcount",
        "PERCENTILE": "percentile", "PERCENTILEEST": "percentile",
        "PERCENTILETDIGEST": "percentile",
    }[base]
    if not cm.has_dictionary:
        if fname in ("percentile", "distinctcount"):
            # raw columns have no dictId histogram: percentile can't merge
            # exactly across segments and distinctcount needs the value set —
            # both take the host fallback path
            raise UnsupportedOnDevice(f"{fname} over no-dictionary column")
        needed[(col, "raw")] = None
        if for_group and fname in ("sum", "avg") and \
                (compact or (segment.padded_docs <= kernels.DENSE_ROWS_LIMIT
                             and g_pad <= kernels.DENSE_G_LIMIT)):
            return (fname, col, "raw", ("csums",))
        return (fname, col, "raw", None)
    card_pad = kernels.pow2_bucket(cm.cardinality + 1)
    if cm.single_value:
        # Strategy selection (see kernels.py "TPU reduction strategy"):
        # integer dict SUM/AVG reads bit-sliced part lanes (exact, no
        # scatter/gather); float dict SUM/AVG reads a decoded value lane;
        # DISTINCTCOUNT/PERCENTILE take the histogram (one-hot matmul);
        # MIN/MAX reduce dictIds. Group-by uses the dense one-hot MXU paths
        # when the group table and segment size allow, else scatter.
        is_int_dict = cm.data_type.np_dtype.kind in "iu"
        dense_ok = segment.padded_docs <= kernels.DENSE_ROWS_LIMIT and \
            g_pad <= kernels.DENSE_G_LIMIT
        if for_group:
            if fname in ("distinctcount", "percentile"):
                # the group kernel has no per-group histogram path; these
                # take the host executor (set/sketch intermediates)
                raise UnsupportedOnDevice(
                    f"group-by with {fname} aggregation")
            if fname in ("sum", "avg"):
                if (dense_ok or compact) and is_int_dict:
                    needed[(col, "parts")] = None
                    return (fname, col, "sv", ("psums", card_pad))
                if dense_ok or compact:
                    needed[(col, "vlane")] = None
                    return (fname, col, "sv", ("csums", card_pad))
                needed[(col, "ids")] = None
                needed[(col, "vals")] = None
                return (fname, col, "sv", ("vals", card_pad))
            needed[(col, "ids")] = None
            return (fname, col, "sv", ("ids", card_pad))
        if base in ("DISTINCTCOUNTHLL", "DISTINCTCOUNTRAWHLL") and \
                not f.info.is_mv:
            # device HLL sketch registers: the dictId histogram's
            # present set scatter-maxes the per-dictId (register index,
            # rank) tables — register-identical to the host
            # HyperLogLog.from_values by construction (shared hashing,
            # sketches.hll_tables), merged by elementwise max across
            # segments/shards/servers. FASTHLL keeps the histogram path
            # (its derived-column rewrite unions serialized sketches).
            from pinot_tpu.common.sketches import DEFAULT_LOG2M
            needed[(col, "ids")] = None
            needed[(col, "hllidx")] = None
            needed[(col, "hllrank")] = None
            return ("hll", col, "sv", ("hll", card_pad,
                                       1 << DEFAULT_LOG2M))
        if fname in ("sum", "avg"):
            if is_int_dict:
                needed[(col, "parts")] = None
                return (fname, col, "sv", ("parts", card_pad))
            # float dictionaries: the MXU histogram + host f64 dot stays
            # EXACT on device-f32 TPUs; the f32 value-lane sum is only for
            # cardinalities past the one-hot matmul cap
            if card_pad <= kernels.DENSE_CARD_LIMIT:
                needed[(col, "ids")] = None
                return (fname, col, "sv", ("hist", card_pad))
            needed[(col, "vlane")] = None
            return (fname, col, "sv", ("vlane", card_pad))
        if fname in ("distinctcount", "percentile"):
            needed[(col, "ids")] = None
            return (fname, col, "sv", ("hist", card_pad))
        needed[(col, "ids")] = None
        return (fname, col, "sv", ("ids", card_pad))
    needed[(col, "mv")] = None
    if for_group:
        raise UnsupportedOnDevice("group-by over MV metric")
    return (fname, col, "mv", (card_pad, cm.cardinality))


def _collect_filter_cols(spec: tuple, needed: Dict) -> None:
    if spec[0] in ("and", "or"):
        for c in spec[1]:
            _collect_filter_cols(c, needed)
    elif spec[0] == "pred":
        _, kind, col, source, _ = spec
        if source == "ivf":
            # three lanes: assignments + padded codebook + validity
            for lane in ("ivfa", "ivfc", "ivfv"):
                needed[(col, lane)] = None
            return
        needed[(col, {"sv": "ids", "mv": "mv", "raw": "raw",
                      "vdoc": "vdoc"}[source])] = None


def selection_columns(segment: ImmutableSegment, request: BrokerRequest
                      ) -> List[str]:
    """Expand SELECT * to the segment's physical columns."""
    cols = request.selection.columns
    if cols == ["*"]:
        return [c for c in segment.column_names if not c.startswith("$")]
    return list(cols)


def _empty_block(plan: SegmentPlan, segment: ImmutableSegment
                 ) -> IntermediateResultsBlock:
    blk = IntermediateResultsBlock()
    if plan.request.is_group_by:
        blk.group_map = {}
    elif plan.request.is_aggregation:
        blk.agg_intermediates = [None for _ in plan.functions]
    if plan.request.vector is not None:
        from pinot_tpu.common.request import VECTOR_RESULT_COLUMNS
        blk.selection_rows = []
        blk.selection_columns = list(plan.request.selection.columns) + \
            list(VECTOR_RESULT_COLUMNS)
    elif plan.request.is_selection:
        blk.selection_rows = []
        blk.selection_columns = selection_columns(segment, plan.request)
    _fill_stats(blk, segment, 0, 0, 0)
    return blk


def _fill_stats(blk: IntermediateResultsBlock, segment: ImmutableSegment,
                docs_scanned: int, entries_filter: int, entries_post: int
                ) -> None:
    blk.stats = ExecutionStats(
        num_docs_scanned=docs_scanned,
        num_entries_scanned_in_filter=entries_filter,
        num_entries_scanned_post_filter=entries_post,
        num_segments_processed=1,
        num_segments_matched=1 if docs_scanned else 0,
        total_docs=segment.num_docs)
