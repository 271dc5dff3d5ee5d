"""What a stacked program costs against the segment programs it replaces.

The scans' walk launches ONE program over the members of a table's stack
of lanes (`ops/kernels.py` `get_stacked_segment_kernel`: one segment's
closure looped over the members) where it launched a program a segment
(`get_segment_kernel`). This script compiles both for the same specs
over synthetic lanes of one no-cube segment (`dense_table_cost.py`'s
`make_lanes`: q3.1's filter, key and dtypes, --seed), eight segments
stacked for the stacked one, and reports per spec:

  compile_s, temp_bytes, hlo_bytes   for the segment program and the
                                     stacked one (ahead-of-time compile,
                                     `memory_analysis()`, the optimized
                                     HLO's text): one body, so the
                                     stacked one should be within 1.5x
                                     and its temp bytes those of one
                                     member (no member's lanes copied)
  ms_8_programs, ms_stacked          on a device: eight segment
                                     programs queued back to back, then
                                     one wait; one stacked launch of
                                     eight members, then one wait (the
                                     median of --reps)

Specs: q3.1's dense table at g 2048 over 4 part lanes (the widest
program a no-cube cell runs), its compacted twin at r 128 over the raw
lane, and the scout's min/max scan.

It runs the device the process is given; --describe compiles for a
DESCRIBED v5e chip and runs nothing (no chip needed).

    python scripts/stacked_program_cost.py [--rows N] [--describe]
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
for p in (REPO, HERE):
    if p not in sys.path:
        sys.path.insert(0, p)

import dense_table_cost as dtc        # noqa: E402

STACK = 8


def specs(K, padded: int) -> dict:
    """name -> (agg_specs, group_spec): the rungs compared."""
    dtc.set_g_pad(2048)
    t = padded // K.CBLOCK
    scout = tuple((f, c, "sv", ("ids", 32)) for c in ("c_nation",
                                                      "s_nation")
                  for f in ("min", "max"))
    return {"table_dense_psums_g2048": ((), dtc.group_spec(
                dtc.PSUMS + dtc.COUNT, 0)),
            "table_compact_r128_csums": ((), dtc.group_spec(
                dtc.CSUMS, t * 128)),
            "scout_minmax": (scout, None)}


def stacked_shape(lane) -> tuple:
    """A lane's shape in a stack of STACK (`segment/loader.py`
    `stack_lanes`)."""
    from pinot_tpu.ops.kernels import STACK_FOLD
    if lane.ndim == 1 and lane.shape[0] % STACK_FOLD == 0:
        return (STACK, lane.shape[0] // STACK_FOLD, STACK_FOLD)
    return (STACK, *lane.shape)


def stacked_operands(cols, params, num_docs: int, stacked_slots):
    """The stack of STACK copies of one segment's lanes and its params,
    as `run_stacked_segment_kernel` lays them out."""
    import numpy as np
    lanes = {k: np.stack([v] * STACK).reshape(stacked_shape(v))
             for k, v in cols.items()}
    ints = [int(p) for p, s in zip(params, stacked_slots) if s is not None]
    packed = np.array([[i, num_docs, *ints] for i in range(STACK)],
                      np.int32)
    leaves = tuple(np.stack([np.asarray(p)] * STACK)
                   for p, s in zip(params, stacked_slots) if s is None)
    return lanes, packed, leaves, np.int32(STACK)


def compile_row(fn, operands, shapes, describe):
    import jax
    t0 = time.perf_counter()
    compiled = jax.jit(fn).lower(*(shapes if describe else operands)) \
        .compile()
    row = {"compile_s": round(time.perf_counter() - t0, 3),
           "temp_bytes": int(getattr(compiled.memory_analysis(),
                                     "temp_size_in_bytes", -1)),
           "hlo_bytes": len(compiled.as_text())}
    return compiled, row


def timed(run, reps: int) -> float:
    import jax
    jax.block_until_ready(run())
    ms = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(run())
        ms.append((time.perf_counter() - t0) * 1e3)
    return round(statistics.median(ms), 3)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", type=int, default=dtc.ROWS,
                    help="padded rows of a segment (a multiple of 8192)")
    ap.add_argument("--seed", type=int, default=2147485041)
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--describe", action="store_true",
                    help="compile for a described v5e chip; run nothing")
    args = ap.parse_args(argv)
    if args.describe:
        os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import numpy as np
    from pinot_tpu.ops import kernels as K
    if jax.config.jax_enable_x64:
        raise SystemExit("the deployed mode is x32: unset JAX_ENABLE_X64")
    padded, num_docs = args.rows, args.rows - args.rows // 12_500
    cols, params = dtc.make_lanes(padded, args.seed)
    slots = tuple(K.packed_scalar_dtype(p) for p in params)
    sharding = None
    if args.describe:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding
        jax.config.update("jax_enable_compilation_cache", False)
        sharding = SingleDeviceSharding(topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices[0])
        device = "described v5e (nothing runs)"
    else:
        device = f"{jax.devices()[0].platform} {jax.devices()[0].device_kind}"

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), np.dtype(dtype),
                                    sharding=sharding)

    one = (cols, params, np.int32(num_docs))
    one_shapes = jax.tree_util.tree_map(
        lambda a: sds(np.shape(a), np.asarray(a).dtype), one)
    n_ints = sum(s is not None for s in slots)
    stacked_shapes = (
        {k: sds(stacked_shape(v), v.dtype) for k, v in cols.items()},
        sds((STACK, 2 + n_ints), np.int32),
        tuple(sds((STACK, *np.shape(p)), np.asarray(p).dtype)
              for p, s in zip(params, slots) if s is None),
        sds((), np.int32))
    dev_one = dev_stacked = None
    if not args.describe:
        dev_one = jax.device_put(one)
        dev_stacked = jax.device_put(
            stacked_operands(cols, params, num_docs, slots))
    result = {"rows": padded, "stack": STACK, "seed": args.seed,
              "device": device, "programs": {}}
    for name, (aggs, group) in specs(K, padded).items():
        seg_fn = K.build_segment_kernel(padded, dtc.FILTER, aggs, group,
                                        None)
        stk_fn = K.build_stacked_segment_kernel(
            padded, dtc.FILTER, aggs, group, None, K.batch_bucket(STACK),
            tuple(sorted((k, v.shape) for k, v in cols.items())), slots)
        seg_c, seg_row = compile_row(seg_fn, dev_one, one_shapes,
                                     args.describe)
        stk_c, stk_row = compile_row(stk_fn, dev_stacked, stacked_shapes,
                                     args.describe)
        row = {"segment": seg_row, "stacked": stk_row,
               "compile_ratio": round(stk_row["compile_s"]
                                      / max(seg_row["compile_s"], 1e-9), 3)}
        if not args.describe:
            row["ms_8_programs"] = timed(
                lambda: [seg_c(*dev_one) for _ in range(STACK)], args.reps)
            row["ms_stacked"] = timed(lambda: stk_c(*dev_stacked),
                                      args.reps)
            a = jax.device_get(seg_c(*dev_one))
            b = jax.device_get(stk_c(*dev_stacked))
            row["members_equal"] = all(
                np.array_equal(np.asarray(b[k])[m], np.asarray(a[k]))
                for k in a for m in range(STACK))
        result["programs"][name] = row
        print(name, json.dumps(row), file=sys.stderr, flush=True)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
