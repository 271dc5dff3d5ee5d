"""Below-the-AST contracts: jaxpr kernel checks + the serde wire schema.

Two gates that no token-level rule can enforce:

**Kernel contracts** — every registered kernel case
(`ops.kernels.contract_cases()`) is traced with `jax.make_jaxpr` over
abstract operands at each shape bucket, and the *jaxpr itself* is
checked:

- no host callbacks anywhere in the (recursively walked) jaxpr — a
  `pure_callback`/`io_callback`/`debug_callback` on the per-segment
  path would serialize every dispatch through the host;
- dtype invariants: under 32-bit mode no output aval is 64-bit (a
  64-bit intermediate would mean the kernel silently relies on
  narrowing); doc-count/docid outputs are int32 exactly;
- retrace/cache-key stability: the spec tuples must be hashable,
  `build_segment_kernel` must return the SAME object for equal specs
  (lru_cache identity — the plan-cache requirement), and re-tracing
  must produce a byte-identical jaxpr (no trace-time nondeterminism
  keying fresh executables).

**Wire schema** — the version-skew surface (InstanceRequest JSON keys,
BrokerRequest tree, BrokerResponse keys, DataTable v1/v2 tags, object
serde tags) is derived from the LIVE code by serializing fully- and
minimally-populated exemplars, and compared against the committed
`wire-schema.json`. Removing or retyping an optional key breaks rolling
upgrades silently — here it fails the gate with a field-level diff.
Intentional changes regenerate the snapshot with
`python -m pinot_tpu.analysis --write-wire-schema`.
"""
from __future__ import annotations

import json
import os
from typing import Dict, List, Tuple

WIRE_SCHEMA_FILE = "wire-schema.json"


# ---------------------------------------------------------------------------
# Kernel contracts
# ---------------------------------------------------------------------------


def _materialize(cols_spec: Dict, params_spec: Tuple, padded: int):
    """Concrete zero-filled operands for one contract case at one shape
    bucket (tracing never executes them; zeros keep it allocation-cheap)."""
    import numpy as np

    def build(dtype, shape):
        shape = tuple(padded if s == "P" else s for s in shape)
        return np.zeros(shape, dtype=np.dtype(dtype))

    cols = {k: build(dt, shp) for k, (dt, shp) in cols_spec.items()}
    params = tuple(build(dt, shp) for dt, shp in params_spec)
    return cols, params


def _walk_jaxpr_eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            inner = getattr(v, "jaxpr", None)
            if inner is not None:
                yield from _walk_jaxpr_eqns(inner)
            if isinstance(v, (list, tuple)):
                for vv in v:
                    inner = getattr(vv, "jaxpr", None)
                    if inner is not None:
                        yield from _walk_jaxpr_eqns(inner)


def find_callbacks(closed_jaxpr) -> List[str]:
    """Primitive names smelling of host callbacks in a traced jaxpr."""
    hits = []
    for eqn in _walk_jaxpr_eqns(closed_jaxpr.jaxpr):
        name = eqn.primitive.name
        if "callback" in name or name in ("outside_call", "host_call"):
            hits.append(name)
    return hits


#: output-key prefixes whose avals must be exactly int32 (docids/counts)
_I32_OUTPUT_PREFIXES = ("stats.", "sel.docids", "sel.count",
                        "group.count")


def check_kernel_contracts(buckets=None) -> List[str]:
    """Violation strings ([] = every registered kernel passes)."""
    import jax
    import numpy as np

    from pinot_tpu.ops import kernels

    x64 = bool(jax.config.jax_enable_x64)
    buckets = tuple(buckets or kernels.CONTRACT_SHAPE_BUCKETS)
    violations: List[str] = []
    for (name, filt, aggs, group, select, cols_spec,
         params_spec) in kernels.contract_cases():
        # cache-key stability: equal spec tuples must be hashable and
        # hit the SAME cached builder (one compiled executable per
        # static signature — the plan-cache requirement)
        try:
            k1 = kernels.build_segment_kernel(buckets[0], filt, aggs,
                                              group, select)
            k2 = kernels.build_segment_kernel(buckets[0], filt, aggs,
                                              group, select)
        except TypeError as e:
            violations.append(f"{name}: spec not hashable — jit cache "
                              f"can never hit: {e}")
            continue
        if k1 is not k2:
            violations.append(f"{name}: build_segment_kernel missed its "
                              "cache on an equal spec — cache key "
                              "unstable, every dispatch would recompile")
        for padded in buckets:
            kernel = kernels.build_segment_kernel(padded, filt, aggs,
                                                  group, select)
            cols, params = _materialize(cols_spec, params_spec, padded)
            num_docs = np.int32(padded - 3)
            try:
                closed = jax.make_jaxpr(kernel)(cols, params, num_docs)
                closed2 = jax.make_jaxpr(kernel)(cols, params, num_docs)
            except Exception as e:  # noqa: BLE001 — a trace failure IS
                violations.append(    # the finding, not an analysis bug
                    f"{name}@P={padded}: kernel does not trace "
                    f"abstractly: {type(e).__name__}: {e}")
                continue
            cbs = find_callbacks(closed)
            if cbs:
                violations.append(
                    f"{name}@P={padded}: host callback primitive(s) "
                    f"{sorted(set(cbs))} inside the kernel jaxpr")
            if str(closed) != str(closed2):
                violations.append(
                    f"{name}@P={padded}: re-trace produced a different "
                    "jaxpr — trace-time nondeterminism will key fresh "
                    "executables per dispatch")
            # dtype invariants on the output avals, keyed by out name
            shapes = jax.eval_shape(kernel, cols, params, num_docs)
            for key, sds in sorted(shapes.items()):
                dt = np.dtype(sds.dtype)
                if not x64 and dt.itemsize == 8 and dt.kind in "iuf":
                    violations.append(
                        f"{name}@P={padded}: output `{key}` is {dt} "
                        "under 32-bit mode — the kernel silently relies "
                        "on x64 narrowing")
                if key.startswith(_I32_OUTPUT_PREFIXES):
                    # 32-bit mode (the TPU reality): exactly int32.
                    # x64 mode (CPU host-parity tests): widths follow
                    # the mode, but counts/docids must stay integral.
                    if not x64 and dt != np.dtype("int32"):
                        violations.append(
                            f"{name}@P={padded}: output `{key}` must "
                            f"be int32 (docid/count contract), got {dt}")
                    elif x64 and dt.kind not in "iu":
                        violations.append(
                            f"{name}@P={padded}: output `{key}` must "
                            f"be integral (docid/count contract), "
                            f"got {dt}")
    violations.extend(_check_extra_kernels(buckets, x64))
    violations.extend(_check_batched_kernels(buckets, x64))
    return violations


def _check_batched_kernels(buckets, x64: bool) -> List[str]:
    """Trace the cross-query batched dispatch (`get_batched_segment_
    kernel`: vmap over the params axis, cols and num_docs shared)
    through the same jaxpr gates at each batch occupancy. The batched
    kernel must inherit every per-member invariant — no callbacks, no
    64-bit leaks, int32 docids/counts — with a leading batch axis on
    every output, or batching would change results member-by-member."""
    import jax
    import numpy as np

    from pinot_tpu.ops import kernels

    violations: List[str] = []
    for (name, filt, aggs, group, select, cols_spec,
         params_spec) in kernels.batched_contract_cases():
        name = f"batched:{name}"
        try:
            k1 = kernels.get_batched_segment_kernel(buckets[0], filt,
                                                    aggs, select)
            k2 = kernels.get_batched_segment_kernel(buckets[0], filt,
                                                    aggs, select)
        except TypeError as e:
            violations.append(f"{name}: spec not hashable — jit cache "
                              f"can never hit: {e}")
            continue
        if k1 is not k2:
            violations.append(f"{name}: get_batched_segment_kernel "
                              "missed its cache on an equal spec — "
                              "every batch would recompile")
        for padded in buckets:
            kernel = kernels.get_batched_segment_kernel(padded, filt,
                                                        aggs, select)
            cols, params = _materialize(cols_spec, params_spec, padded)
            num_docs = np.int32(padded - 3)
            for bsz in kernels.BATCH_CONTRACT_SIZES:
                stacked = tuple(np.stack([p] * bsz) for p in params)
                tag = f"{name}@P={padded},B={bsz}"
                try:
                    closed = jax.make_jaxpr(kernel)(cols, stacked,
                                                    num_docs)
                    closed2 = jax.make_jaxpr(kernel)(cols, stacked,
                                                     num_docs)
                except Exception as e:  # noqa: BLE001 — the finding
                    violations.append(
                        f"{tag}: batched kernel does not trace "
                        f"abstractly: {type(e).__name__}: {e}")
                    continue
                cbs = find_callbacks(closed)
                if cbs:
                    violations.append(
                        f"{tag}: host callback primitive(s) "
                        f"{sorted(set(cbs))} inside the batched jaxpr")
                if str(closed) != str(closed2):
                    violations.append(
                        f"{tag}: re-trace produced a different jaxpr — "
                        "trace-time nondeterminism")
                shapes = jax.eval_shape(kernel, cols, stacked, num_docs)
                for key, sds in sorted(shapes.items()):
                    dt = np.dtype(sds.dtype)
                    if not sds.shape or sds.shape[0] != bsz:
                        violations.append(
                            f"{tag}: output `{key}` shape {sds.shape} "
                            f"lacks the leading batch axis of {bsz} — "
                            "fan-back would mix members")
                    if not x64 and dt.itemsize == 8 and dt.kind in "iuf":
                        violations.append(
                            f"{tag}: output `{key}` is {dt} under "
                            "32-bit mode")
                    if key.startswith(_I32_OUTPUT_PREFIXES):
                        if not x64 and dt != np.dtype("int32"):
                            violations.append(
                                f"{tag}: output `{key}` must be int32 "
                                f"(docid/count contract), got {dt}")
                        elif x64 and dt.kind not in "iu":
                            violations.append(
                                f"{tag}: output `{key}` must be "
                                f"integral (docid/count contract), "
                                f"got {dt}")
    return violations


def _materialize_tree(spec, padded: int):
    """Materialize a pytree of (dtype, shape) leaves (extra kernel
    cases): tuples whose first element is a string are leaves, dicts
    stay dicts; a dim "P" is the padded doc count, "P/<k>" a k-th of it
    (a stack's folded lanes)."""
    import numpy as np
    if isinstance(spec, dict):
        return {k: _materialize_tree(v, padded) for k, v in spec.items()}
    if isinstance(spec, tuple) and len(spec) == 2 and \
            isinstance(spec[0], str):
        dtype, shape = spec
        shape = tuple(padded // int(s[2:] or 1)
                      if isinstance(s, str) and s.startswith("P") else s
                      for s in shape)
        return np.zeros(shape, dtype=np.dtype(dtype))
    return tuple(_materialize_tree(s, padded) for s in spec)


def fill_static(args, padded: int):
    """An extra case's static args with every "P", nested ones too (a
    stacked case's lane shapes), the padded doc count."""
    if isinstance(args, tuple):
        return tuple(fill_static(a, padded) for a in args)
    return padded if args == "P" else args


def _check_extra_kernels(buckets, x64: bool) -> List[str]:
    """Trace the non-segment-plan kernel families (window stage-2 —
    kernels.extra_contract_cases) through the same jaxpr gates."""
    import jax
    import numpy as np

    from pinot_tpu.ops import kernels

    violations: List[str] = []
    for name, builder, static_args, arg_specs in \
            kernels.extra_contract_cases():
        for padded in buckets:
            args = fill_static(static_args, padded)
            try:
                k1 = builder(*args)
                k2 = builder(*args)
            except TypeError as e:
                violations.append(f"{name}: builder args not hashable — "
                                  f"jit cache can never hit: {e}")
                break
            if k1 is not k2:
                violations.append(
                    f"{name}@P={padded}: builder missed its cache on "
                    "equal args — every dispatch would recompile")
            operands = _materialize_tree(arg_specs, padded)
            try:
                closed = jax.make_jaxpr(k1)(*operands)
                closed2 = jax.make_jaxpr(k1)(*operands)
            except Exception as e:  # noqa: BLE001 — the finding itself
                violations.append(
                    f"{name}@P={padded}: kernel does not trace "
                    f"abstractly: {type(e).__name__}: {e}")
                continue
            cbs = find_callbacks(closed)
            if cbs:
                violations.append(
                    f"{name}@P={padded}: host callback primitive(s) "
                    f"{sorted(set(cbs))} inside the kernel jaxpr")
            if str(closed) != str(closed2):
                violations.append(
                    f"{name}@P={padded}: re-trace produced a different "
                    "jaxpr — trace-time nondeterminism")
            shapes = jax.eval_shape(k1, *operands)
            for key, sds in sorted(shapes.items()):
                dt = np.dtype(sds.dtype)
                if not x64 and dt.itemsize == 8 and dt.kind in "iuf":
                    violations.append(
                        f"{name}@P={padded}: output `{key}` is {dt} "
                        "under 32-bit mode")
                if key.startswith("win.") and not x64 and \
                        dt != np.dtype("int32"):
                    violations.append(
                        f"{name}@P={padded}: output `{key}` must be "
                        f"int32 (window contract), got {dt}")
    return violations


def compile_kernel_surface() -> dict:
    """Lower, COMPILE for the installed backend and execute once every
    registered kernel case (`contract_cases` + `extra_contract_cases`)
    at each shape bucket — the step past the jaxpr gates above, which
    only trace: it answers "does the backend this process runs on
    accept every kernel family the repo ships".

    Takes the device (call it from a process that may own it). Returns
    {"device": device_report(), "cases": [{"name", "padded",
    "compileS", "error"?}], "failed": n}; a failing case carries the
    compiler's own words.
    """
    import time

    import jax
    import numpy as np

    from pinot_tpu.ops import kernels
    from pinot_tpu.utils.device import (configure_compile_cache,
                                        device_report)

    configure_compile_cache()
    buckets = kernels.CONTRACT_SHAPE_BUCKETS
    todo = []
    for (name, filt, aggs, group, select, cols_spec,
         params_spec) in kernels.contract_cases():
        for padded in buckets:
            kernel = kernels.build_segment_kernel(padded, filt, aggs,
                                                  group, select)
            cols, params = _materialize(cols_spec, params_spec, padded)
            todo.append((name, padded, kernel,
                         (cols, params, np.int32(padded - 3))))
    for name, builder, static_args, arg_specs in \
            kernels.extra_contract_cases():
        for padded in buckets:
            args = fill_static(static_args, padded)
            todo.append((name, padded, builder(*args),
                         _materialize_tree(arg_specs, padded)))

    # one jit per DISTINCT kernel, compiled once each — a function, not
    # a loop body, so the retrace rule's jit-in-a-loop shape stays a
    # finding everywhere it is one
    def compile_and_run(name, padded, kernel, operands) -> dict:
        entry = {"name": name, "padded": padded}
        t0 = time.perf_counter()
        try:
            compiled = jax.jit(kernel).lower(*operands).compile()
            entry["compileS"] = round(time.perf_counter() - t0, 3)
            jax.block_until_ready(compiled(*operands))
        except Exception as e:  # noqa: BLE001 — the compiler's verdict
            entry["error"] = f"{type(e).__name__}: {e}"   # IS the result
        return entry

    cases = [compile_and_run(*case) for case in todo]
    return {"device": device_report(), "cases": cases,
            "failed": sum(1 for c in cases if "error" in c)}


# ---------------------------------------------------------------------------
# Wire schema
# ---------------------------------------------------------------------------


def _shape_of(v, depth: int = 0):
    """A JSON value → stable type-shape descriptor (recursive, bounded)."""
    if isinstance(v, dict):
        if depth > 6:
            return "object"
        return {k: _shape_of(v[k], depth + 1) for k in sorted(v)}
    if isinstance(v, list):
        return [_shape_of(v[0], depth + 1)] if v else []
    if isinstance(v, bool):
        return "bool"
    if isinstance(v, int):
        return "int"
    if isinstance(v, float):
        return "float"
    if v is None:
        return "null"
    return "str"


def _exemplar_request():
    from pinot_tpu.common.request import (AggregationInfo, BrokerRequest,
                                          FilterOperator, FilterQueryTree,
                                          GroupBy, HavingNode, JoinSpec,
                                          QueryOptions, Selection,
                                          SelectionSort, VectorSimilarity,
                                          WindowSpec)
    filt = FilterQueryTree(
        operator=FilterOperator.AND,
        children=[
            FilterQueryTree(operator=FilterOperator.EQUALITY, column="c",
                            values=["v"]),
            FilterQueryTree(operator=FilterOperator.RANGE, column="t",
                            lower="1", upper="2", lower_inclusive=True,
                            upper_inclusive=False)])
    having = HavingNode(operator=FilterOperator.RANGE,
                        agg=AggregationInfo("SUM", "m"), lower="0",
                        upper="9")
    return BrokerRequest(
        table_name="T_OFFLINE", filter=filt,
        aggregations=[AggregationInfo("SUM", "m")],
        group_by=GroupBy(["g"], top_n=5),
        selection=Selection(columns=["a"],
                            order_by=[SelectionSort("a", False)],
                            offset=1, size=7),
        vector=VectorSimilarity(column="e", query=[1.0, 0.0], k=3,
                                metric="COSINE", nprobe=4),
        join=JoinSpec(dim_table="d", fact_key="k", dim_key="pk",
                      dim_filter=FilterQueryTree(
                          operator=FilterOperator.EQUALITY, column="a",
                          values=["v"]),
                      dim_columns=["b"]),
        windows=[WindowSpec(function="SUM", column="m",
                            partition_by=["g"],
                            order_by=[SelectionSort("t", True)])],
        having=having,
        query_options=QueryOptions(trace=True, timeout_ms=1000,
                                   debug_options={"k": "v"},
                                   options={"o": "1"}),
        limit=7)


def wire_schema() -> dict:
    """The full wire surface, derived from the live code."""
    from pinot_tpu.common import datatable as dtmod
    from pinot_tpu.common import serde
    from pinot_tpu.common.request import InstanceRequest
    from pinot_tpu.common.response import (AggregationResult,
                                           BrokerResponse,
                                           SelectionResults)
    from pinot_tpu.common.sketches import HyperLogLog, TDigest

    req = _exemplar_request()
    # InstanceRequest: minimal vs fully-populated key sets → the
    # required/optional split IS the version-skew contract
    minimal = json.loads(serde.instance_request_to_bytes(
        InstanceRequest(request_id=1, query=req)))
    full = json.loads(serde.instance_request_to_bytes(
        InstanceRequest(request_id=1, query=req, search_segments=["s"],
                        enable_trace=True, broker_id="b",
                        deadline_budget_ms=10.0, trace_id="t",
                        parent_span_id="p", workload="w", hedge=True,
                        publish_exchange={"id": "x1.0",
                                          "keyColumn": "pk"},
                        exchange_sources=[{
                            "server": "s", "xkey": "k", "host": "h",
                            "port": 1, "id": "x1.0", "rows": 1,
                            "partitions": [0],
                            "partitionFunction": "Modulo",
                            "numPartitions": 2}])))
    resp = BrokerResponse(
        aggregation_results=[
            AggregationResult(function="sum(m)", value=1.0),
            AggregationResult(function="sum(m)", group_by_columns=["g"],
                              group_by_result=[{"group": ["x"],
                                                "value": "1"}])],
        selection_results=SelectionResults(columns=["a"], results=[[1]]),
        exceptions=[{"errorCode": 0, "message": "m"}],
        num_consuming_segments_queried=1,
        trace_info={"broker": []}, trace_tree={"spanId": "r"},
        profile_info={"paths": {"scan": 1}})

    # object serde: tag byte per exemplar python type
    object_tags = {}
    for label, value in [
            ("null", None), ("bool", True), ("int64", 1),
            ("bigint", 1 << 80), ("float64", 1.5), ("str", "s"),
            ("bytes", b"b"), ("tuple", (1,)), ("list", [1]),
            ("set", {1}), ("dict", {"k": 1}),
            ("hll", HyperLogLog()), ("tdigest", TDigest())]:
        object_tags[label] = serde.obj_to_bytes(value)[:1].decode("latin1")

    return {
        "version": 1,
        "comment": ("serde wire surface snapshot; regenerate "
                    "INTENTIONALLY with `python -m pinot_tpu.analysis "
                    "--write-wire-schema` and review the diff as a "
                    "version-skew compatibility change"),
        "instanceRequest": {
            "required": sorted(minimal),
            "optional": sorted(set(full) - set(minimal)),
            "shape": _shape_of(full),
        },
        "brokerResponse": _shape_of(resp.to_json()),
        "dataTable": {
            "versions": sorted([dtmod._LEGACY_VERSION,
                                dtmod._V2_VERSION, dtmod.VERSION]),
            "defaultVersion": dtmod.VERSION,
            "columnTags": sorted(t.decode("latin1") for t in (
                dtmod._COL_I64, dtmod._COL_F64, dtmod._COL_STR,
                dtmod._COL_OBJ)),
            "structuredMetadataKeys": sorted([
                dtmod.MISSING_SEGMENTS_KEY, dtmod.SERVER_BUSY_KEY,
                dtmod.RETRY_AFTER_MS_KEY, dtmod.RESULT_CACHE_HIT_KEY,
                dtmod.STAGE_ERROR_KEY]),
        },
        "objectSerde": object_tags,
        # exchange plane (multi-stage stage-1 blocks, server↔server):
        # the frame magic + fetch-op JSON keys, and the ack/source
        # metadata keys the broker round-trips into stage-2 requests
        "exchangeFrame": _exchange_frame_schema(),
    }


def _exchange_frame_schema() -> dict:
    from pinot_tpu.query.stages import exchange
    frame = exchange.fetch_frame("x1.0")
    msg = json.loads(frame[4:].decode("utf-8"))
    return {
        "magic": exchange.XCHG_MAGIC.decode("latin1"),
        "fetchKeys": sorted(msg),
        "ackMetadataKeys": sorted([
            "exchangeId", "exchangeKey", "exchangeRows",
            "exchangePartitions", "partitionFunction", "numPartitions"]),
        "sourceKeys": sorted([
            "server", "xkey", "host", "port", "id", "rows",
            "partitions", "partitionFunction", "numPartitions"]),
    }


def write_wire_schema(path: str = WIRE_SCHEMA_FILE) -> dict:
    schema = wire_schema()
    with open(path, "w") as fh:
        json.dump(schema, fh, indent=1, sort_keys=True)
        fh.write("\n")
    return schema


def _diff(committed, fresh, at: str, out: List[str]) -> None:
    if isinstance(committed, dict) and isinstance(fresh, dict):
        for k in sorted(set(committed) | set(fresh)):
            loc = f"{at}.{k}" if at else k
            if k not in fresh:
                out.append(f"removed: {loc} (was {committed[k]!r}) — "
                           "breaks payloads from version-skewed peers")
            elif k not in committed:
                out.append(f"added: {loc} = {fresh[k]!r} — new optional "
                           "surface; regenerate the snapshot if "
                           "intentional")
            else:
                _diff(committed[k], fresh[k], loc, out)
        return
    if committed != fresh:
        out.append(f"changed: {at}: {committed!r} → {fresh!r}")


def check_wire_schema(path: str = WIRE_SCHEMA_FILE) -> List[str]:
    """Field-level diffs between the committed snapshot and the live
    wire surface ([] = round-trips unchanged)."""
    if not os.path.exists(path):
        return [f"missing committed snapshot {path} — generate it with "
                "--write-wire-schema and commit it"]
    with open(path) as fh:
        committed = json.load(fh)
    fresh = wire_schema()
    out: List[str] = []
    _diff(committed, fresh, "", out)
    return out
