"""What one dense group table of q3.1 costs on the device, form by form.

One segment of the no-cube configuration as q3.1's table program sees
it: synthetic lanes of the benchmark's dtypes and cardinalities drawn
from --seed (int8 dictIds of `c_region`/`s_region` (5), `c_nation`/
`s_nation` (25, a region's five nations scattered in the sorted
dictionary), `d_year` (7); `lo_revenue` a raw INT lane; `lo_supplycost`
as four 7-bit part lanes), q3.1's filter (a year range and two region
equalities: 4% of the rows kept), its remapped key (two `idrank` dims of
8 and an `idoff` dim of 8, `g_pad` 512) and its one aggregation,
`SUM(lo_revenue)` by strategy `csums`.

Each program is compiled ahead of time (seconds, and the temp bytes of
`compiled.memory_analysis()`), run --warm times, then timed --reps
times to `block_until_ready`; the median is the row's `ms`:

  kernel.*   the checkout's OWN whole-plan program (`ops/kernels.py`
             `build_segment_kernel`, what a server launches): the dense
             csums table (kmax 0), the compacted table at r = 256 and
             r = 128, the dense psums twin (4 part lanes + count), the
             count-only dense table;
  form.*     the table alone under one shared preamble (the kernel's own
             filter and key): `scan2` the two 763-step scans every
             checkout before PR 36 ran (count, then float sums at
             Precision.HIGHEST; kept here as the yardstick), `split3`
             the one-pass form (float lanes as three exact bf16 pieces
             beside the count: `kernels._dense_group_sums`), `f32_highest` the same batched einsum in
             float32 at Precision.HIGHEST with the count as a lane,
             `split3_scan7` / `f32_highest_scan7` the batched forms
             inside a scan of 7 fat steps, `psums` the part-lane twin
             (`psums_scan7`: in 7 fat steps), `preamble` filter and key
             alone.

Every form's sums are compared with numpy's float64 `np.add.at` (the
widest relative gap of a group, `rel_err`; counts must be equal).

It runs the device the process is given: on the chip's host the TPU;
with JAX_PLATFORMS=cpu and --rows 57344 a rehearsal (never a device
metric). --describe compiles every program for a DESCRIBED v5e chip
without running anything (compile seconds and temp bytes only: no chip
needed). It runs on any checkout (copy it into a parent's tree).

    python scripts/dense_table_cost.py [--rows N] [--seed S] [--describe]
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

ROWS = 6_250_496            # a segment of the cell, padded: 763 x 8192
G_PAD = 512
NATIONS, REGIONS, YEARS = 25, 5, 7
ASIA = 2                    # a region's dictId


def make_lanes(rows: int, seed: int):
    """{lane key: numpy array} and q3.1's runtime operands."""
    import numpy as np
    rng = np.random.default_rng([seed, 36])
    # nation n lies in region n % 5: a region's nations are scattered
    # over the sorted dictionary, which is what sends q3.1 to the
    # histogram rung and the rank remap
    cols = {}
    for side in ("c", "s"):
        nation = rng.integers(0, NATIONS, rows).astype(np.int8)
        cols[f"{side}_nation.ids"] = nation
        cols[f"{side}_region.ids"] = (nation % REGIONS).astype(np.int8)
    cols["d_year.ids"] = rng.integers(0, YEARS, rows).astype(np.int8)
    # quantity x price x (100 - discount) / 100, in cents: up to 24 bits
    cols["lo_revenue.raw"] = (
        rng.integers(1, 51, rows) * rng.integers(90_000, 200_000, rows)
        * rng.integers(90, 101, rows) // 100).astype(np.int32)
    cost = rng.integers(54_000, 120_000, rows)
    cols["lo_supplycost.parts"] = np.stack(
        [(cost >> (7 * p)) & 0x7F for p in range(4)]).astype(np.int8)
    rank = np.zeros(32, np.int32)
    present = np.arange(ASIA, NATIONS, REGIONS)
    rank[present] = np.arange(len(present))
    params = (np.int32(0), np.int32(YEARS), np.int32(ASIA), np.int32(ASIA),
              rank, rank, np.int32(0))
    return cols, params


FILTER = ("and", (("pred", "range_ids", "d_year", "sv", None),
                  ("pred", "eq_id", "c_region", "sv", None),
                  ("pred", "eq_id", "s_region", "sv", None)))
GCOLS = (("c_nation", "idrank", 0, 8), ("s_nation", "idrank", 0, 8),
         ("d_year", "idoff", 0, 8))
STRIDES = (64, 8, 1)


def set_g_pad(g_pad: int) -> None:
    """Another remapped key space: 2048 is two `idrank` dims of 16 (a
    literal tuple whose nations' present counts bucket to 16)."""
    global G_PAD, GCOLS, STRIDES
    n = {512: 8, 2048: 16}[g_pad]
    G_PAD, STRIDES = g_pad, (n * 8, 8, 1)
    GCOLS = (("c_nation", "idrank", 0, n), ("s_nation", "idrank", 0, n),
             ("d_year", "idoff", 0, 8))
CSUMS = (("sum", "lo_revenue", "raw", ("csums",)),)
PSUMS = (("sum", "lo_supplycost", "sv", ("psums", 131072)),)
COUNT = (("count", "*", "sv", None),)


def group_spec(aggs, kmax: int):
    return (GCOLS, STRIDES, G_PAD, aggs, kmax)


# ---------------------------------------------------------------------------
# The table alone, form by form: (vals f32 [P], parts int8 [4, P], key
# int32 [P], mask bool [P]) -> (count int32 [g], sums f32 [g])
# ---------------------------------------------------------------------------


def form_scan2(K, vals, parts, key, mask):
    """Two 763-step scans: every checkout's dense csums table before
    PR 36 (`_dense_group_count` + `_dense_group_float_sums`, copied)."""
    import jax
    import jax.numpy as jnp
    count = K._mxu_histogram(key, mask, G_PAD)
    b = K._tile_rows(G_PAD, key.shape[0])
    contrib = jnp.where(mask, vals, 0)
    gp = K._radix_pad(G_PAD)

    def body(carry, tb):
        k, c = tb
        oh_hi, oh_lo = K._radix_onehots(k, gp, jnp.float32)
        return carry + K._radix_group_sum(oh_hi, oh_lo, c, G_PAD,
                                          jnp.float32), None

    sums, _ = jax.lax.scan(body, jnp.zeros(G_PAD, jnp.float32),
                           (key.reshape(-1, b), contrib.reshape(-1, b)))
    return count, sums


def form_split3(K, vals, parts, key, mask):
    """The one-pass form: `kernels._dense_group_sums` (a checkout from
    before PR 36 has none: its row says so)."""
    _psums, csums, count = K._dense_group_sums((), [vals], key, mask, G_PAD,
                                               with_count=True)
    return count, csums[0]


def form_f32_highest(K, vals, parts, key, mask):
    """The batched einsum in float32 at Precision.HIGHEST, the count as
    one more lane (exact below 2^24 matches a group)."""
    import jax
    import jax.numpy as jnp
    t = key.shape[0] // K.BLOCK
    oh_hi, oh_lo = K._radix_onehots(key.reshape(t, K.BLOCK),
                                    K._radix_pad(G_PAD), jnp.float32)
    ohm = oh_hi * mask.astype(jnp.float32).reshape(t, K.BLOCK)[:, :, None]
    a = jnp.concatenate(
        [ohm, ohm * vals.reshape(t, K.BLOCK)[:, :, None]], axis=2)
    s = jnp.einsum("tbx,tbc->txc", a, oh_lo,
                   preferred_element_type=jnp.float32,
                   precision=jax.lax.Precision.HIGHEST)
    s = s.sum(axis=0).reshape(2, -1)[:, :G_PAD]
    return s[0].astype(jnp.int32), s[1]


def scan7(form):
    """`form` over 7 fat steps (763 = 7 x 109 blocks): a seventh of the
    temp memory, a hundredth of the two scans' steps."""
    def stepped(K, vals, parts, key, mask):
        import jax
        import jax.numpy as jnp
        steps = 7 if (key.shape[0] // K.BLOCK) % 7 == 0 else 1
        xs = (vals.reshape(steps, -1),
              parts.reshape(parts.shape[0], steps, -1).swapaxes(0, 1),
              key.reshape(steps, -1), mask.reshape(steps, -1))

        def body(carry, tb):
            return jax.tree_util.tree_map(jnp.add, carry,
                                          form(K, *tb)), None

        init = jax.tree_util.tree_map(
            lambda a: jnp.zeros(a.shape, a.dtype),
            jax.eval_shape(lambda *tb: form(K, *tb), *(x[0] for x in xs)))
        return jax.lax.scan(body, init, xs)[0]
    return stepped


def form_psums(K, vals, parts, key, mask):
    """The part-lane twin: 4 part lanes + count (exact int32)."""
    lanes = [parts[p] for p in range(parts.shape[0])]
    own = getattr(K, "_dense_group_sums", None)
    if own is not None:
        psums, _csums, count = own(lanes, (), key, mask, G_PAD,
                                   with_count=True)
    else:
        psums, count = K._dense_group_part_sums(lanes, key, mask, G_PAD,
                                                with_count=True)
    return count, psums


def form_preamble(K, vals, parts, key, mask):
    return mask.sum(), key.sum()


FORMS = {"preamble": form_preamble, "scan2": form_scan2,
         "split3_scan7": scan7(form_split3),
         "f32_highest_scan7": scan7(form_f32_highest),
         "psums_scan7": scan7(form_psums), "split3": form_split3,
         "f32_highest": form_f32_highest, "psums": form_psums}


def form_program(K, name: str):
    """The kernel's own filter and key, then the form."""
    import jax.numpy as jnp

    def program(cols, params, num_docs):
        plist = list(params)
        valid = jnp.arange(cols["d_year.ids"].shape[0],
                           dtype=jnp.int32) < num_docs
        mask = K._eval_filter(FILTER, cols, plist, valid) & valid
        key = K._group_key(GCOLS, STRIDES, G_PAD, cols, plist)
        return FORMS[name](K, cols["lo_revenue.raw"].astype(jnp.float32),
                           cols["lo_supplycost.parts"], key, mask)
    return program


def kernel_programs(K, padded: int):
    t = padded // K.CBLOCK
    return {f"kernel.{name}": K.build_segment_kernel(
        padded, FILTER, (), group_spec(aggs, kmax), None)
        for name, aggs, kmax in (
            ("dense_count", COUNT, 0), ("compact_r128", CSUMS, t * 128),
            ("compact_r256", CSUMS, t * 256), ("dense_csums", CSUMS, 0),
            ("dense_psums", PSUMS, 0))}


def reference(cols, params, num_docs: int):
    """float64 sums and counts of the kept rows, group by group."""
    import numpy as np
    rank = params[4]
    keep = (cols["c_region.ids"] == ASIA) & (cols["s_region.ids"] == ASIA)
    keep[num_docs:] = False
    key = (rank[cols["c_nation.ids"]] * STRIDES[0]
           + rank[cols["s_nation.ids"]] * 8 + cols["d_year.ids"])[keep]
    sums, count = np.zeros(G_PAD), np.zeros(G_PAD, np.int64)
    np.add.at(sums, key, cols["lo_revenue.raw"][keep].astype(np.float64))
    np.add.at(count, key, 1)
    return count, sums, float(keep.mean())


def measure(fn, args, shapes, warm: int, reps: int, describe):
    import jax
    t0 = time.perf_counter()
    compiled = jax.jit(fn).lower(*(shapes if describe else args)).compile()
    row = {"compile_s": round(time.perf_counter() - t0, 2)}
    mem = compiled.memory_analysis()
    row["temp_bytes"] = int(getattr(mem, "temp_size_in_bytes", -1))
    if describe:
        return row, None
    for _ in range(warm):
        out = jax.block_until_ready(compiled(*args))
    ms = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(compiled(*args))
        ms.append((time.perf_counter() - t0) * 1e3)
    row["ms"] = round(statistics.median(ms), 3)
    row["ms_min"] = round(min(ms), 3)
    return row, out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", type=int, default=ROWS,
                    help="padded rows of the segment (a multiple of 8192)")
    ap.add_argument("--seed", type=int, default=2147485036)
    ap.add_argument("--g-pad", type=int, default=G_PAD, choices=(512, 2048))
    ap.add_argument("--warm", type=int, default=3)
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--only", default="", help="comma-separated row names")
    ap.add_argument("--describe", action="store_true",
                    help="compile for a described v5e chip; run nothing")
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)

    set_g_pad(args.g_pad)
    if args.describe:
        os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    import numpy as np
    from pinot_tpu.ops import kernels as K
    if jax.config.jax_enable_x64:
        raise SystemExit("the deployed mode is x32: unset JAX_ENABLE_X64")
    padded, num_docs = args.rows, args.rows - args.rows // 12_500
    cols, params = make_lanes(padded, args.seed)
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
    operands = (cols, params, np.int32(num_docs))
    shapes = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), np.asarray(a).dtype,
                                       sharding=sharding), operands)
    dev_args = None if args.describe else jax.device_put(operands)
    count_ref, sums_ref, kept = reference(cols, params, num_docs)

    programs = {f"form.{n}": form_program(K, n) for n in FORMS}
    programs.update(kernel_programs(K, padded))
    only = [s for s in args.only.split(",") if s]
    result = {"rows": padded, "num_docs": num_docs, "seed": args.seed,
              "g_pad": G_PAD, "kept_share": round(kept, 5),
              "device": device, "reps": args.reps,
              "one_pass_in_kernels": hasattr(K, "_dense_group_sums"),
              "programs": {}}
    for name, fn in programs.items():
        if only and name not in only:
            continue
        try:
            row, out = measure(fn, dev_args, shapes, args.warm, args.reps,
                               args.describe)
        except Exception as e:            # a form the compiler refuses
            row, out = {"error": f"{type(e).__name__}: {e}"[:400]}, None
        if out is not None and name not in ("form.preamble",
                                            "kernel.dense_count"):
            if name.startswith("kernel."):
                count = out.get("group.count")
                sums = out.get("gagg0.csums", out.get("gagg0.vsum"))
            else:
                count, sums = out
            if "psums" in name:
                sums = None
            if "compact" not in name:     # a compacted table may overflow
                row["count_equal"] = bool(
                    (np.asarray(count)[:G_PAD] == count_ref).all())
            if sums is not None and "compact" not in name:
                gap = np.abs(np.asarray(sums, np.float64) - sums_ref)
                row["rel_err"] = float(
                    (gap / np.maximum(sums_ref, 1)).max())
        result["programs"][name] = row
        print(name, json.dumps(row), file=sys.stderr, flush=True)
    text = json.dumps(result)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w") as fh:
            fh.write(text + "\n")
    print(text)
    return 0


if __name__ == "__main__":
    sys.exit(main())
