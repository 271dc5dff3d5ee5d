"""Per-segment query kernels: filter masks, aggregations, group-by, selection.

This is the TPU replacement for the reference's operator tree
(pinot-core/.../core/operator/ — SURVEY.md §2.2 "primary TPU kernel surface").
Where the Java engine pulls 10k-doc blocks through virtual-call iterators
(DocIdSetOperator → ProjectionOperator → AggregationOperator), we compile the
whole per-segment plan into ONE jitted function over padded, HBM-resident
dictId lanes:

- Filter tree → vectorized boolean mask expression. Predicates are resolved
  host-side into the dictId domain (sorted dictionaries make ranges contiguous
  id intervals), so EQ/RANGE/IN become integer compares on int32 lanes and
  arbitrary dictionary predicates (REGEXP_LIKE, big IN lists) become a
  member-vector gather. Replaces BitmapBasedFilterOperator /
  ScanBasedFilterOperator / SortedInvertedIndexBasedFilterOperator and the
  And/OrDocIdIterator hot loops with pure VPU work.
- Aggregations → masked reductions. SUM/AVG/DISTINCTCOUNT go through a dictId
  histogram (int32 scatter-add) so the device only ever computes exact integer
  counts; the final f64 dot with dictionary values happens host-side. MIN/MAX
  reduce dictIds directly (dictionaries are sorted ⇒ id order == value order).
  Replaces AggregationOperator / DictionaryBasedAggregationOperator.
- Group-by → mixed-radix dictId keys (same math as
  DictionaryBasedGroupKeyGenerator.java:204 `groupId = groupId*card + dictId`)
  aggregated WITHOUT row-scale sorts/scatters/gathers: MXU block stream-
  compaction of matched rows + one-hot matmul group tables (dense layout
  for small key spaces, rank-addressed for wide ones), driven by an
  adaptive two-phase executor (plan.drive_group_execution). Replaces
  DefaultGroupByExecutor + CombineGroupByOperator.
- Selection → jnp.nonzero(size=k) for limit queries, lax.top_k over packed
  order keys for ORDER BY. Replaces SelectionOperator's PriorityQueue.

Kernel specs are hashable tuples of static structure (shapes pow2-bucketed);
predicate constants are dynamic operands — so one compiled executable serves
every query with the same shape, the plan-cache requirement called out in
SURVEY.md §7 "hard parts".
"""
from __future__ import annotations

import functools
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from pinot_tpu import compat

INT32_MAX = np.int32(2**31 - 1)


def pow2_bucket(n: int, floor: int = 8) -> int:
    """Round up to a power of two (shape bucketing for jit-cache reuse)."""
    n = max(n, floor)
    return 1 << int(np.ceil(np.log2(n)))


def sum_dtype():
    """Accumulator dtype for value sums: f64 under x64 (CPU tests), else f32."""
    return jnp.float64 if jax.config.jax_enable_x64 else jnp.float32


# ---------------------------------------------------------------------------
# Filter spec evaluation
#
# spec grammar (hashable tuples):
#   ("and", (child, ...)) | ("or", (child, ...))
#   ("match_all",) | ("empty",)
#   ("pred", kind, col, source, extra)
#     kind ∈ {eq_id, neq_id, in_ids, notin_ids, range_ids, member,
#             eq_raw, neq_raw, in_raw, notin_raw, range_raw}
#     source ∈ {sv, mv, raw}
#     extra: kind-specific static data (bucketed value count, inclusivity)
#   ("pred", "ivf_probe", col, "ivf", (nprobe, metric)) — ANN coarse
#     filter over THREE lanes ({col}.ivfa assignments, {col}.ivfc padded
#     centroids, {col}.ivfv centroid validity); consumes the query
#     vector + norm as params and keeps only rows whose coarse cell is
#     in the on-device top-nprobe probe list
# params: flat tuple of jnp arrays consumed in depth-first pred order.
# ---------------------------------------------------------------------------


def _eval_pred(kind: str, source: str, extra, lane, params: List):
    """lane: int32 [P] (sv ids), int32 [P, W] (mv ids), or raw values [P]."""
    if kind == "eq_id" or kind == "eq_raw":
        v = params.pop(0)
        m = lane == v
    elif kind == "neq_id" or kind == "neq_raw":
        v = params.pop(0)
        m = lane != v
    elif kind == "in_ids" or kind == "in_raw":
        vals = params.pop(0)  # [k]
        m = (lane[..., None] == vals).any(-1)
    elif kind == "notin_ids" or kind == "notin_raw":
        vals = params.pop(0)
        m = ~((lane[..., None] == vals).any(-1))
    elif kind == "range_ids":
        lo, hi = params.pop(0), params.pop(0)  # half-open id interval
        m = (lane >= lo) & (lane < hi)
    elif kind == "range_raw":
        lo, hi = params.pop(0), params.pop(0)
        lo_inc, hi_inc = extra
        ml = (lane >= lo) if lo_inc else (lane > lo)
        mh = (lane <= hi) if hi_inc else (lane < hi)
        m = ml & mh
    elif kind == "member":
        member = params.pop(0)  # bool [card_pad]
        # int32 index: a narrow (int8) id lane cannot address a member
        # table whose size exceeds its own dtype range (jax normalizes
        # the axis size into the INDEX dtype)
        m = member[jnp.clip(lane.astype(jnp.int32), 0,
                            member.shape[0] - 1)]
    elif kind == "vdoc":
        # upsert validDocIds mask: the lane IS the per-doc liveness bool
        # (runtime operand — one compiled executable serves any bitmap);
        # fused into the filter mask so aggregation/group/selection all
        # see only live rows
        m = lane
    elif kind == "join_raw":
        # raw-key inner-join probe: the dim side's key array arrives as
        # a RUNTIME operand (padded by repeating its max key, so padding
        # slots are duplicates of a real key and can never create or
        # destroy a match); the probe structure is BUILT ON DEVICE —
        # lax.sort is the hash-build, searchsorted the probe — so one
        # compiled executable serves every dim table of the same
        # pow2-bucketed size
        keys = params.pop(0)                       # [Dp] fact-key dtype
        sk = jax.lax.sort(keys)
        pos = jnp.clip(jnp.searchsorted(sk, lane), 0, sk.shape[0] - 1)
        m = sk[pos] == lane
    else:
        raise ValueError(f"unknown predicate kind {kind}")
    if source == "mv":
        # Pinot MV semantics: doc matches if ANY entry matches; padding
        # entries carry id == cardinality which only member-vectors could
        # accidentally hit — member vectors are padded False there.
        m = m.any(-1)
    return m


def ivf_select_probes(centroids, cvalid, q, q_norm, metric: str,
                      nprobe: int):
    """Top-nprobe coarse-cell selection for the IVF filter lane.

    centroids: f32 [C_pad, dim_pad] zero-padded codebook; cvalid: bool
    [C_pad] liveness (padding rows and dead cells False — a runtime
    lane, NOT a count param, so sharded execution can share one plan
    across segments with different live counts). Scoring reuses the
    query-metric machinery (same balanced tree, same monotone keys) so
    the numpy twin in index/ivf.py is bit-identical; lax.top_k breaks
    score ties toward the LOWER centroid id, like everywhere else.
    Returns (probe_ids i32 [nprobe], probe_ok bool [nprobe])."""
    cscore = _vector_scores(centroids, q, q_norm, metric)
    ckey = jnp.maximum(_monotone_int32_keys(cscore, True)[0], -INT32_MAX)
    scored = jnp.where(cvalid, ckey, -INT32_MAX - 1)
    _, probe = jax.lax.top_k(scored, nprobe)
    probe_ok = jnp.arange(nprobe, dtype=jnp.int32) < \
        cvalid.sum(dtype=jnp.int32)
    return probe.astype(jnp.int32), probe_ok


def _eval_ivf_probe(extra, assign, centroids, cvalid, params: List):
    """rows whose assigned coarse cell is probed. assign: narrow int [P]
    (padding rows carry the never-live sentinel num_centroids). The
    membership test is the in_ids compare form — [P, nprobe] broadcast
    compare + any — which fuses instead of gathering at row scale."""
    nprobe, metric = extra
    q = params.pop(0)               # f32 [dim_pad] query vector
    q_norm = params.pop(0)          # f32 scalar (tree-norm of q)
    probe, probe_ok = ivf_select_probes(centroids, cvalid, q, q_norm,
                                        metric, nprobe)
    m = (assign.astype(jnp.int32)[..., None] == probe) & probe_ok
    return m.any(-1)


def _eval_filter(spec, cols: Dict[str, jnp.ndarray], params: List, valid):
    op = spec[0]
    if op == "match_all":
        return valid
    if op == "empty":
        return jnp.zeros_like(valid)
    if op in ("and", "or"):
        masks = [_eval_filter(c, cols, params, valid) for c in spec[1]]
        out = masks[0]
        for m in masks[1:]:
            out = (out & m) if op == "and" else (out | m)
        return out
    if op == "pred":
        _, kind, col, source, extra = spec
        if source == "ivf":
            # three-lane predicate (assignments + codebook + validity)
            return _eval_ivf_probe(extra, cols[f"{col}.ivfa"],
                                   cols[f"{col}.ivfc"],
                                   cols[f"{col}.ivfv"], params)
        key = {"sv": f"{col}.ids", "mv": f"{col}.mv", "raw": f"{col}.raw",
               "vdoc": f"{col}.vdoc"}[source]
        return _eval_pred(kind, source, extra, cols[key], params)
    raise ValueError(f"unknown filter node {op}")


# ---------------------------------------------------------------------------
# TPU reduction strategy
#
# Scatter/gather run at ~150M rows/s on TPU (serialized updates) while tree
# reductions and MXU matmuls run at memory/matmul bandwidth (20-200x faster,
# measured on v5e). So the hot aggregation paths NEVER scatter or gather:
#
# - SUM/AVG over integer dictionary columns reads precomputed bit-sliced
#   "part lanes" (int8 [n_parts, P], 7 bits of the offset value per lane,
#   built once at segment load) and does masked tree reductions per part.
#   Per 8192-block a part sum is <= 127*8192 < 2^20, so int32 block partials
#   are exact; the final f64/int64 combine (<< 7k shifts + min_value*count)
#   happens host-side. Exact at any scale without f64 on device.
# - GROUP-BY SUM/AVG one-hot-encodes the mixed-radix group key per block and
#   matmuls [B, G]^T @ [B, n_parts] on the MXU with f32 accumulation (block
#   sums < 2^24 => exact), accumulating int32 across blocks.
# - Histograms (DISTINCTCOUNT/PERCENTILE) are one-hot matmuls too.
# - MIN/MAX reduce dictIds directly (sorted dict => id order == value order);
#   group-by min/max uses blocked masked min over a [B, G] compare tile.
# Scatter remains only as the fallback for huge group tables / cardinalities.
# ---------------------------------------------------------------------------

BLOCK = 8192                 # row block: must divide padded segment length
CBLOCK = 2048                # MXU stream-compaction block (B=2048/r=16 won
#                              the measured race against B=8192 variants)
DENSE_G_LIMIT = 32768        # one-hot matmul group-table cap
DENSE_ROWS_LIMIT = 1 << 24   # carry-accum int32 bound (127 * 2^24 < 2^31)
DENSE_CARD_LIMIT = 32768     # one-hot matmul histogram cap


def _tile_rows(g: int, n: Optional[int] = None) -> int:
    """Row-tile size for [B, G] one-hot tiles.

    B*G <= 2^24 keeps a bf16 tile within ~32MB of VMEM; B is a multiple
    of BLOCK up to 8*BLOCK when the table is narrow (fewer, fatter scan
    steps — per-step loop overhead dominates small-G histograms
    otherwise), constrained to divide n when given.
    """
    cap = max((1 << 24) // max(g, 1), 1 << 9)
    b = 1 << max(9, min(16, int(np.log2(cap))))
    b = min(b, 8 * BLOCK)
    if n is not None:
        while b > BLOCK and (n % b or b > n):
            b //= 2
        b = min(b, n)
    return b


def _part_sums(part_lanes, mask):
    """Masked exact sums of 7-bit part lanes.

    part_lanes: [n_parts, P] int8 array (or a list of [P] lanes, stacked
    cheaply as inputs); returns int32 [T1, n_parts] chunk partials.

    ONE reduce op over ONE elementwise producer — never a stack/concat
    of per-lane sibling reduces. Measured (round 5, v5e, 100M rows):
    XLA does not multi-output-fuse sibling reductions even into a
    single concatenated output, so the per-lane form materialized the
    int32 where() contribs at row scale — 3.4GB accessed vs 0.8GB, the
    whole 4.9ms-vs-0.8ms q1.x gap. The [n_parts, T, BLOCK] reduce keeps
    the mask + parts in one fused loop at HBM-bandwidth rate.
    """
    if isinstance(part_lanes, (list, tuple)):
        part_lanes = jnp.stack(part_lanes)            # input-side stack
    contrib = jnp.where(mask[None, :], part_lanes, 0).astype(jnp.int32)
    n_l = part_lanes.shape[0]
    p = part_lanes.shape[-1]
    if 127 * p < 2**31:
        # FULL reduce to [n_parts]: the only shape XLA's fast reduce
        # emitter takes at bandwidth. ANY output keeping a block axis —
        # [T1, L] chunked, [L, T] partials, either orientation —
        # measured 5.0ms vs 0.79ms at 100M rows. Exact: 7-bit lanes
        # bound the int32 sum by 127 * padded < 2^31 (padded <= 16.9M
        # rows per segment — every sharded stack shard qualifies).
        return contrib.reshape(n_l, -1).sum(axis=-1, dtype=jnp.int32), True
    # oversized single segment: exactness first — [n_parts, T] block
    # partials (< 2^20 each), host combines in int64
    return contrib.reshape(n_l, -1, BLOCK).sum(
        axis=-1, dtype=jnp.int32), False


def _chunked_float_sum(vals, mask):
    """Masked float sum -> [T] per-block partials (f64 under x64).

    Like _part_sums, the partials are the OUTPUT — a second on-device
    reduce stage broke the single-reduce fusion (measured 6x) — and the
    host's f64 sum over T values is both exact-enough and cheaper than
    the old two-stage f32 ladder."""
    acc = sum_dtype()
    contrib = jnp.where(mask, vals.astype(acc), 0)
    return contrib.reshape(-1, BLOCK).sum(axis=1, dtype=acc)


import os as _os
RADIX_G = int(_os.environ.get("PINOT_TPU_RADIX_G", "512"))
# row-scale accumulations (full-segment dense tables / histograms) factor
# above RADIX_G; the COMPACTED slot tables process ~100x fewer rows, so
# the direct [K, g] one-hot stays cheap much longer and radix's per-row
# lo-products only pay off for wide tables (measured: direct wins at 513
# slots by 1.5x, radix wins at 8193 by 1.2x on v5e)
SLOT_RADIX_G = int(_os.environ.get("PINOT_TPU_SLOT_RADIX_G", "8192"))
SLOT_CHUNK = 1 << 17   # slot-table chunk: 127 * 2^17 < 2^24 (f32-exact)
#                  ^ above this, one-hots are factored hi x lo: VPU
                   # compares per row drop from g to g/128 + 128, and the
                   # wide accumulation happens on the MXU instead
RADIX_LO = 128     # lane width: lo one-hot fills exactly one vreg lane dim

#: precision for contractions whose VALUE side is f32. At its default
#: precision the TPU rounds f32 operands to bf16 before the MXU, which
#: voids every "0/1 one-hot times a value is exact" argument below; the
#: CPU backend, where the parity tests run, never rounds. Measured on a
#: v5e (PR 21): a one-contributor _block_compact move of two float lanes
#: came back 3.7e-3 off, float group sums 6e-4..2e-3 off; with this
#: precision both are f32-exact (tests/test_on_device.py pins it). A
#: single float lane hid it: XLA lowers that degenerate contraction to a
#: reduce, not an MXU pass. bf16 x bf16 sites need no argument — their
#: operands are already exact in bf16.
_EXACT_F32 = jax.lax.Precision.HIGHEST


def _cmp_onehot(idx, width: int, dtype):
    """one_hot(idx, width) via a NARROW-dtype compare.

    jax.nn.one_hot builds an s32 iota + s32 broadcast before the
    compare; on this XLA those materialize at FULL [rows, width] s32
    scale (measured: 1.6GB apiece inside one compacted q2.1 kernel —
    HLO dump, round 3), which made every one-hot-fed dot
    HBM-bandwidth-bound. Comparing in int8 (width <= 128) / int16
    shrinks the materialized intermediates 4x. idx must already be in
    [0, width): callers clip group keys to the padded table.
    """
    it = jnp.arange(width,
                    dtype=jnp.int8 if width <= 128 else jnp.int16)
    return (idx[..., None].astype(it.dtype) == it).astype(dtype)


def _radix_onehots(idx, g_pad: int, dtype):
    """idx -> (oh_hi [k, g_pad/128], oh_lo [k, 128]) with
    one_hot(idx, g_pad)[k, g] == oh_hi[k, g//128] * oh_lo[k, g%128].

    The factored product is exact in any float dtype (entries are 0/1),
    so S = hi^T @ (lo * v) accumulates the same sums as the direct
    one-hot matmul at 1/40th the VPU comparison work for g ~ 8k.
    """
    g1 = g_pad // RADIX_LO
    oh_hi = _cmp_onehot(idx // RADIX_LO, g1, dtype)
    oh_lo = _cmp_onehot(idx % RADIX_LO, RADIX_LO, dtype)
    return oh_hi, oh_lo


def _radix_pad(g: int) -> int:
    return -(-g // RADIX_LO) * RADIX_LO


def _radix_group_sum(oh_hi, oh_lo, v, g: int, acc):
    """hi^T @ (lo * v) -> [g] per-group sums of v, in `acc` dtype.

    The factored one-hot accumulation (see _radix_onehots): exact
    whenever v's values are exact in the one-hot dtype and the per-call
    accumulation stays within `acc`'s integer range — each call site
    carries its own bound. Counts are the v == mask special case
    (sum m * hi * lo == (hi weighted by m)^T lo)."""
    return jnp.matmul(oh_hi.T, oh_lo * v[:, None],
                      preferred_element_type=acc,
                      precision=_EXACT_F32).reshape(-1)[:g]


def _mxu_histogram(ids, mask, card_pad: int):
    """One-hot histogram: int32 [card_pad], exact.

    Three regimes (all exact — counts are sums of 0/1, every per-call
    f32 accumulation cell <= b < 2^24):
    - card_pad <= 128: fused compare+reduce on the VPU. The [b, card]
      compare tile fuses into the sum (reduces fuse with producers on
      TPU) so NOTHING row-scale materializes — this is what makes the
      adaptive hist scout ~10ms-class at 100M rows.
    - card_pad < RADIX_G: bf16 one-hot matmul.
    - else: hi/lo-factored bf16 one-hots, the MASK folded into the
      NARROW hi factor (counts = (hi*m)^T @ lo, the 2-D histogram) —
      one MXU row-stream pass. (bf16, not s8: s8 dots measured ~1.4x
      slower on this XLA/v5e stack.)"""
    if card_pad <= RADIX_LO:
        # batched (scan-free) fused compare+reduce: per-block partials
        # then an int32 tree-sum — no carry chain to serialize
        t = ids.shape[0] // BLOCK
        hit = (ids.reshape(t, BLOCK)[:, :, None] ==
               jnp.arange(card_pad, dtype=ids.dtype)) & \
            mask.reshape(t, BLOCK)[:, :, None]
        return hit.sum(axis=1, dtype=jnp.int32).sum(axis=0)

    b = _tile_rows(card_pad, ids.shape[0])
    ids_b = ids.reshape(-1, b)
    mask_b = mask.astype(jnp.bfloat16).reshape(-1, b)
    radix = card_pad >= RADIX_G
    gp = _radix_pad(card_pad)

    def body(carry, tb):
        i, m = tb
        if radix:
            oh_hi, oh_lo = _radix_onehots(i, gp, jnp.bfloat16)
            h = jnp.matmul((oh_hi * m[:, None]).T, oh_lo,
                           preferred_element_type=jnp.float32
                           ).reshape(-1)[:card_pad].astype(jnp.int32)
        else:
            onehot = _cmp_onehot(i, card_pad, jnp.bfloat16)
            h = jnp.matmul(m[None, :], onehot,
                           preferred_element_type=jnp.float32
                           )[0].astype(jnp.int32)               # <= b
        return carry + h, None

    out, _ = jax.lax.scan(body, jnp.zeros(card_pad, jnp.int32),
                          (ids_b, mask_b))
    return out


def _bf16_pieces(v):
    """float32 lane -> three bf16 lanes whose float32 sum is v, EXACTLY:
    each piece is the top 8 significand bits of what is left (8 + 8 + 8
    cover float32's 24), cut out by a bit mask so that no compiler pass
    can read the cut as a removable f32 -> bf16 -> f32 round trip. A 0/1
    one-hot times a piece is exact on the MXU at its DEFAULT precision,
    so a float lane rides the bf16 operand instead of paying the six
    passes of Precision.HIGHEST for an M of a few rows."""
    pieces = []
    for _ in range(3):
        top = jax.lax.bitcast_convert_type(
            jax.lax.bitcast_convert_type(v, jnp.uint32)
            & np.uint32(0xFFFF0000), jnp.float32)
        pieces.append(top.astype(jnp.bfloat16))
        v = v - top
    return pieces


def _onehot_lane_sums(lanes, n_int: int, key, mask, g_pad: int, mm):
    """Masked per-group sums of row lanes in ONE pass over the rows:
    (int32 [n_int, g_pad], float [len(lanes) - n_int, g_pad]).

    lanes: [P] arrays of dtype `mm` (bf16, or f64 under x64), `None` for
    the group COUNT (the mask itself: it shares the one-hot build, which
    dominates at dense SSB shapes). The first n_int lanes hold integers
    of at most 7 bits: their per-block f32 cells (<= 127 * 8192 < 2^24)
    and the int32 sum across blocks (127 * DENSE_ROWS_LIMIT < 2^31) are
    exact. The others are float lanes, accumulated in f32 (f64).

    BATCHED per-block partials, no lax.scan, whenever the operand widths
    allow. Measured on the v5e dense floor (q3.1 big-synth, 100M rows,
    round 3; PR 36 at 6.25M rows: scripts/dense_table_cost.py):
    - a scan carry SERIALIZES the per-step dots: 164ms scan vs 98ms
      batched at g=512 (and ~10x the compile time);
    - s8 x s8 -> s32 dots are a SLOW path on this XLA stack (227ms vs
      161ms bf16);
    - per-lane dots paid one full MXU row stream PER LANE (the
      g-independent ~390ms round-2 floor); folding every lane into the
      narrow hi factor and concatenating into one operand lets ALL
      lanes share one stream.
    The mask multiplies into the one-hot ONCE (ohm), so the lanes need
    no row-scale where() prep for it. Wide tables (n_l * g1 > 128, e.g.
    un-remapped g=8192 with 6 lanes) break the batched einsum's compile
    (the concat operand stops fusing), so they fall back to a scan with
    a per-step concat dot (f32-exact at b <= 2^17; _tile_rows caps b at
    2^16) - the adaptive hist rung exists to remap those into the
    batched regime."""
    n_l, n = len(lanes), key.shape[0]
    facc = jnp.float64 if mm == jnp.float64 else jnp.float32
    # g_pad == RADIX_G stays DIRECT here: the 512-wide one-hot fuses into
    # the dot and nothing row-scale materializes, where the radix form
    # writes its [t, B, g1] hi-folded operand a lane (PR 36, 6.25M rows,
    # g 512: float + count 6.5 ms / 14 MB temp against 6.9 ms / 152 MB,
    # 4 part lanes + count 6.8 ms / 151 MB against 8.3 ms / 458 MB)
    radix = g_pad > RADIX_G
    gp = _radix_pad(g_pad) if radix else g_pad
    g1 = gp // RADIX_LO
    batched = not radix or n_l * g1 <= RADIX_LO
    b = BLOCK if batched else _tile_rows(max(n_l * g1 // 2, RADIX_LO), n)

    def cells(k, m, cs):
        """[.., b] key, mask and lanes -> [.., n_l * gp] per-block sums."""
        cs = iter(cs)
        if radix:
            oh_hi, oh_lo = _radix_onehots(k, gp, mm)
            ohm = oh_hi * m[..., None]                      # [.., b, g1]
            a = jnp.concatenate(
                [ohm if l is None else ohm * next(cs)[..., None]
                 for l in lanes], axis=-1)                  # [.., b, n_l*g1]
            s = jnp.einsum("...bx,...bc->...xc", a, oh_lo,
                           preferred_element_type=facc)
        else:
            st = jnp.stack([m if l is None else m * next(cs)
                            for l in lanes], axis=-2)       # [.., n_l, b]
            s = jnp.einsum("...lb,...bg->...lg", st,
                           _cmp_onehot(k, g_pad, mm),
                           preferred_element_type=facc)
        return s.reshape(s.shape[:-2] + (n_l * gp,))

    xs = (key.reshape(-1, b), mask.astype(mm).reshape(-1, b),
          tuple(l.reshape(-1, b) for l in lanes if l is not None))
    if batched:      # exact int32 / float tree-sums across the blocks
        s = cells(*xs)
        out = (s[:, :n_int * gp].astype(jnp.int32).sum(axis=0),
               s[:, n_int * gp:].sum(axis=0))
    else:
        def body(carry, tb):
            s = cells(*tb)
            return (carry[0] + s[:n_int * gp].astype(jnp.int32),
                    carry[1] + s[n_int * gp:]), None

        out, _ = jax.lax.scan(
            body, (jnp.zeros(n_int * gp, jnp.int32),
                   jnp.zeros((n_l - n_int) * gp, facc)), xs)
    return tuple(o.reshape(-1, gp)[:, :g_pad] for o in out)


def _dense_group_sums(part_lanes, val_lanes, key, mask, g_pad: int,
                      with_count: bool = False):
    """Every summed lane of a dense group table, and its count, in one
    pass: (int32 [n_parts, g_pad] exact sums of the 7-bit part lanes,
    sum_dtype() [n_vals, g_pad] sums of the float / raw value lanes,
    int32 [g_pad] match counts or None).

    Under x64 (the CPU parity tests) the value lanes stay f64 through
    their own contraction; on the f32 device path each rides the part
    lanes' bf16 operand as three exact pieces (_bf16_pieces), summed in
    f32: products exact, accumulation f32, as Precision.HIGHEST gave."""
    acc = sum_dtype()
    ints = [l.astype(jnp.bfloat16) for l in part_lanes] + \
        [None] * with_count
    vals = [jnp.where(mask, v.astype(acc), 0) for v in val_lanes]
    if acc == jnp.float64:
        isum = _onehot_lane_sums(ints, len(ints), key, mask, g_pad,
                                 jnp.bfloat16)[0] if ints else None
        vsum = _onehot_lane_sums(vals, 0, key, mask, g_pad,
                                 acc)[1] if vals else None
    else:
        pieces = [p for v in vals for p in _bf16_pieces(v)]
        isum, psum = _onehot_lane_sums(ints + pieces, len(ints), key, mask,
                                       g_pad, jnp.bfloat16)
        vsum = psum.reshape(len(vals), 3, g_pad).sum(axis=1)
    n_p = len(part_lanes)
    return (None if isum is None else isum[:n_p], vsum,
            isum[n_p] if with_count else None)


def _dense_group_extreme(ids_or_vals, key, mask, g_pad: int, sentinel,
                         is_min: bool):
    """Blocked masked min/max per group over a [b, G] compare tile."""
    b = _tile_rows(g_pad, key.shape[0])
    v_b = ids_or_vals.reshape(-1, b)
    key_b = key.reshape(-1, b)
    mask_b = mask.reshape(-1, b)
    groups = jnp.arange(g_pad, dtype=jnp.int32)
    init = jnp.full(g_pad, sentinel, ids_or_vals.dtype)

    def body(carry, tb):
        k, v, m = tb
        hit = (k[:, None] == groups[None, :]) & m[:, None]
        tile = jnp.where(hit, v[:, None], sentinel)
        ext = tile.min(axis=0) if is_min else tile.max(axis=0)
        return (jnp.minimum(carry, ext) if is_min
                else jnp.maximum(carry, ext)), None

    out, _ = jax.lax.scan(body, init, (key_b, v_b, mask_b))
    return out


# ---------------------------------------------------------------------------
# Aggregation spec evaluation (no group-by)
#
# agg spec: (fname, col, source, extra)
#   fname ∈ {count, sum, min, max, avg, minmaxrange, distinctcount,
#            sumhist, percentile}
# extra encodes the planner-chosen strategy (see plan._agg_device_spec):
#   sv: ("parts", n_parts) | ("vlane",) | ("hist", card_pad)
#       | ("ids", card_pad)
# Emitted outputs are "device partials" — host code (query/execution)
# finishes them exactly (int64 shift-combine, f64 histogram ⋅ dictionary
# dot, id → value decode).
# ---------------------------------------------------------------------------


def _histogram(cols, col: str, card_pad: int, mask):
    ids = cols[f"{col}.ids"]
    if card_pad <= DENSE_CARD_LIMIT:
        return _mxu_histogram(ids, mask, card_pad)
    return jnp.zeros(card_pad, jnp.int32).at[ids].add(mask.astype(jnp.int32))


def _is_parts_agg(spec) -> bool:
    fname, _col, source, extra = spec
    return fname in ("sum", "avg") and source == "sv" and \
        isinstance(extra, tuple) and extra[0] == "parts"


def _agg_outputs(agg_specs: Tuple, cols, mask, num_docs):
    outs = {}
    hists: Dict[Tuple[str, int], jnp.ndarray] = {}
    # ALL part-lane sums ride ONE reduce over ONE concatenated [L, P]
    # operand (see _part_sums: sibling reduces don't fuse on this XLA —
    # q4.x's two SUM columns would otherwise pay the materialized-contrib
    # tax twice)
    parts_aggs = [(i, spec) for i, spec in enumerate(agg_specs)
                  if _is_parts_agg(spec)]
    if parts_aggs:
        arrs = [cols[f"{spec[1]}.parts"] for _i, spec in parts_aggs]
        combined = arrs[0] if len(arrs) == 1 else jnp.concatenate(arrs, 0)
        sums, reduced = _part_sums(combined, mask)   # [L] | [L, T]
        key = "parts" if reduced else "partsT"
        off = 0
        for i, spec in parts_aggs:
            n_p = cols[f"{spec[1]}.parts"].shape[0]
            outs[f"agg{i}.{key}"] = sums[off: off + n_p]
            outs[f"agg{i}.count"] = mask.sum(dtype=jnp.int32)
            off += n_p
    for i, spec in enumerate(agg_specs):
        fname, col, source, extra = spec
        if _is_parts_agg(spec):
            continue                     # emitted by the fused pass above
        if fname == "count":
            outs[f"agg{i}"] = mask.sum(dtype=jnp.int32)
        elif fname in ("sum", "avg") and source == "sv" and \
                isinstance(extra, tuple) and extra[0] == "vlane":
            # float dictionary values: decoded value lane, chunked f32/f64
            outs[f"agg{i}.vsum"] = _chunked_float_sum(cols[f"{col}.vlane"],
                                                      mask)
            outs[f"agg{i}.count"] = mask.sum(dtype=jnp.int32)
        elif fname in ("sum", "avg", "distinctcount", "percentile",
                       "hist") and source == "sv":
            card_pad = extra[1] if isinstance(extra, tuple) else extra
            hk = (col, card_pad)
            if hk not in hists:
                hists[hk] = _histogram(cols, col, card_pad, mask)
            # percentile: host walks the value-count CDF; distinctcount:
            # host needs the value set anyway for cross-segment merge
            outs[f"agg{i}"] = hists[hk]
        elif fname == "hll" and source == "sv":
            # HLL sketch registers ON DEVICE: the dictId histogram's
            # present set drives an O(cardinality) scatter-max of the
            # precomputed per-dictId (register index, rank) tables
            # (hashes shared with the host HyperLogLog twin through
            # sketches.hll_tables) into the [m] register array.
            # Registers merge ASSOCIATIVELY (elementwise max) across
            # segments, shards and servers — rank 0 is the merge
            # identity, so masked/padding ids contribute nothing.
            card_pad, m = extra[1], extra[2]
            hk = (col, card_pad)
            if hk not in hists:
                hists[hk] = _histogram(cols, col, card_pad, mask)
            idx = cols[f"{col}.hllidx"]
            rank = cols[f"{col}.hllrank"]
            present = hists[hk] > 0
            outs[f"agg{i}.hll"] = jnp.zeros(m, jnp.int32).at[idx].max(
                jnp.where(present, rank, 0))
        elif source == "mv":
            card_pad, card = extra
            ids = cols[f"{col}.mv"]
            entry_mask = mask[:, None] & (ids < card)  # drop padding entries
            if fname in ("sum", "avg", "percentile", "distinctcount",
                         "countmv"):
                hk = (col, card_pad, "mv")
                if hk not in hists:
                    hists[hk] = jnp.zeros(card_pad, jnp.int32).at[
                        ids.reshape(-1)].add(
                            entry_mask.reshape(-1).astype(jnp.int32))
                if fname == "countmv":
                    outs[f"agg{i}"] = hists[hk][:card].sum(dtype=jnp.int32)
                else:
                    outs[f"agg{i}"] = hists[hk]
            elif fname in ("min", "max", "minmaxrange"):
                if fname in ("min", "minmaxrange"):
                    outs[f"agg{i}.min"] = jnp.where(entry_mask, ids,
                                                    card_pad).min()
                if fname in ("max", "minmaxrange"):
                    outs[f"agg{i}.max"] = jnp.where(entry_mask, ids, -1).max()
            else:
                raise ValueError(f"unsupported MV aggregation {fname}")
        elif fname in ("min", "max", "minmaxrange") and source == "sv":
            card_pad = extra[1] if isinstance(extra, tuple) else extra
            ids = cols[f"{col}.ids"].astype(jnp.int32)
            if fname in ("min", "minmaxrange"):
                outs[f"agg{i}.min"] = jnp.where(mask, ids, card_pad).min()
            if fname in ("max", "minmaxrange"):
                outs[f"agg{i}.max"] = jnp.where(mask, ids, -1).max()
        elif fname in ("sum", "avg", "min", "max", "minmaxrange") and \
                source == "raw":
            vals = cols[f"{col}.raw"]
            if fname in ("sum", "avg"):
                outs[f"agg{i}.vsum"] = _chunked_float_sum(vals, mask)
                outs[f"agg{i}.count"] = mask.sum(dtype=jnp.int32)
            if fname in ("min", "minmaxrange"):
                outs[f"agg{i}.min"] = jnp.where(mask, vals,
                                                jnp.inf).min()
            if fname in ("max", "minmaxrange"):
                outs[f"agg{i}.max"] = jnp.where(mask, vals,
                                                -jnp.inf).max()
        else:
            raise ValueError(f"unsupported aggregation spec {spec}")
    return outs


# ---------------------------------------------------------------------------
# Group-by
#
# group spec: (cols=((name, kind, off, card), ...), strides=(s1,...), g_pad,
#              aggs=(agg specs), kmax)
# Keys are mixed-radix over dictIds; table arrays are pow2-padded.
#
# kmax > 0 selects the SORT-COMPACTED path for filtered group-bys: sort
# (masked key, iota) so matched rows form a prefix, slice kmax rows, and
# aggregate only those. Measured on v5e this beats both the all-rows one-hot
# matmul (selective filters pay row×G work for nothing) and the all-rows
# scatter (~150M rows/s serialized) by 4-10x at SSB shapes. When more than
# kmax rows match, the kernel raises the `group.overflow` flag and the
# executor re-runs with an escalated kmax (plan.escalate_group_kmax).
# ---------------------------------------------------------------------------


def _group_key(gcols, strides, g_pad, cols, params=None):
    key = None
    for (c, gkind, off, _card), s in zip(gcols, strides):
        if gkind == "rawoff":
            # no-dictionary integer group key: bin by (value - min), the
            # on-the-fly analogue of a dictId (metadata min/max bound the
            # range; the planner verified it fits the group table)
            lane = cols[f"{c}.raw"]
            ids = (lane - lane.dtype.type(off)).astype(jnp.int32)
        elif gkind == "idoff":
            # adaptive dense remap (plan.drive_group_execution): the
            # filter's phase-A scout bounded this column's active dictIds
            # to [off, off+span); re-base so the group table covers only
            # the active subspace. The offset is a RUNTIME operand (and
            # spans are pow2-bucketed by the planner) so one compiled
            # executable serves every literal of the same query template.
            off_op = params.pop(0)
            ids = cols[f"{c}.ids"].astype(jnp.int32) - off_op
        elif gkind == "idrank":
            # adaptive DENSIFYING remap: the scout's per-dim histogram
            # found the PRESENT dictIds (scattered ids — e.g. the five
            # Asian nations in a sorted nation dictionary — make
            # offset spans 4-8x wider than the actual active set); the
            # rank vector (runtime operand, [card_pad] int32) maps
            # id -> rank-among-present, collapsing the key space to the
            # bucketed present counts. Evaluated as a ONE-HOT MATMUL,
            # never a row-scale gather (measured: rank[ids] gathers at
            # ~90M rows/s on v5e — 1.1s/dim at 100M rows — vs ~15ms for
            # the [rows, card_pad<=512] one-hot contraction; exact: the
            # one-hot is 0/1 and ranks < 512 are exact in f32).
            # Unmatched rows map to garbage ranks; their contributions
            # are masked everywhere.
            rank = params.pop(0)
            lane = cols[f"{c}.ids"].astype(jnp.int32)
            oh = _cmp_onehot(lane, rank.shape[0], jnp.bfloat16)
            ids = jnp.matmul(oh, rank.astype(jnp.float32)[:, None],
                             preferred_element_type=jnp.float32
                             )[:, 0].astype(jnp.int32)
        elif gkind == "jcode":
            # dict-keyed join group code: the per-dictId fact-key →
            # dim-group-code translation table (runtime operand,
            # [card_pad] int32, built host-side in O(cardinality) by the
            # join planner). A GATHER, not the idrank one-hot matmul:
            # join translate tables span the FACT key's cardinality
            # (thousands to millions), where an O(rows·card) contraction
            # loses to the O(rows) gather. Unmatched dictIds carry code
            # 0 — masked by the fused join-match predicate everywhere.
            code = params.pop(0)
            lane = cols[f"{c}.ids"].astype(jnp.int32)
            ids = code[jnp.clip(lane, 0, code.shape[0] - 1)]
        elif gkind == "jraw":
            # raw-keyed join group code: device-built sorted probe over
            # the dim (key, code) pair — the group-side twin of the
            # join_raw predicate (XLA CSE shares the sort/searchsorted
            # between them). Padding repeats (max key, its code), so
            # probe hits in the padding run resolve to the right code.
            keys = params.pop(0)                   # [Dp] fact-key dtype
            codes = params.pop(0)                  # [Dp] int32
            sk, sc = jax.lax.sort((keys, codes), num_keys=1)
            lane = cols[f"{c}.raw"]
            pos = jnp.clip(jnp.searchsorted(sk, lane), 0, sk.shape[0] - 1)
            ids = sc[pos]
        else:
            ids = cols[f"{c}.ids"].astype(jnp.int32)
        term = ids * np.int32(s)
        key = term if key is None else key + term
    return jnp.clip(key, 0, g_pad - 1)


PLANE_BITS = 7     # compaction planes carry 7-bit values: <= 127 keeps
#                    every plane s8-exact, so the whole compact pipeline
#                    (block compaction + slot tables) runs s8 x s8 -> s32
#                    on the MXU — 2x the bf16 rate, no f32 2^24 bound


def _planes_for(maxval: int) -> int:
    """7-bit planes needed to carry values in [0, maxval]."""
    b = 1
    while (1 << (PLANE_BITS * b)) <= maxval:
        b += 1
    return b


def _block_compact(mask, int_lanes, f32_lanes, r: int):
    """MXU stream compaction: matched rows of each 8192-row block move to
    r per-block output slots via a fused one-hot matmul (no sorts, no
    row-scale scatters/gathers — random HBM access is the slow primitive
    on TPU, matmul is the fast one). Each (block, slot) output cell has
    exactly ONE contributing row, so the f32 accumulation is exact.

    int_lanes: list of [n] integer lanes with values in [0, 127]
    (7-bit planes — s8-exact; any int dtype). f32_lanes: list of [n]
    float lanes, moved in sum_dtype() (f64 under x64 for host parity,
    f32 on device).
    Returns (ints [K, Pi], floats [K, Pf], valid [K], overflow) with
    K = (n // CBLOCK) * r. Rows past r in an overflowing block are
    dropped; `overflow` flags it and the executor escalates kmax.
    """
    n = mask.shape[0]
    t = n // CBLOCK
    mb = mask.reshape(t, CBLOCK)
    # int16 positions/iota: the [t, B, r] one-hot's compare operands
    # materialize at row scale (HLO-measured GBs in s32), so narrow
    # dtypes are the compact path's bandwidth lever (CBLOCK <= 2^15)
    pos = jnp.cumsum(mb.astype(jnp.int16), axis=1) - 1
    cnt = mb.sum(axis=1, dtype=jnp.int32)
    overflow = (cnt > r).any().astype(jnp.int32)
    oh = (pos[:, :, None] == jnp.arange(r, dtype=jnp.int16)) & \
        mb[:, :, None]                                    # [t, B, r]
    ints = None
    if int_lanes:
        # bf16 x bf16 -> f32: exact (one contributor per output cell,
        # values <= 127). s8 x s8 -> s32 measured ~1.4x SLOWER on this
        # XLA/v5e stack — this einsum IS the compact path's row-scale
        # floor (one full row stream), so its dtype is the hot choice.
        lb = jnp.stack([v.reshape(t, CBLOCK).astype(jnp.bfloat16)
                        for v in int_lanes], axis=-1)
        ints = jnp.einsum("tbr,tbl->trl", oh.astype(jnp.bfloat16), lb,
                          preferred_element_type=jnp.float32
                          ).reshape(t * r, len(int_lanes)).astype(jnp.int32)
    floats = None
    if f32_lanes:
        facc = sum_dtype()
        lf = jnp.stack([v.reshape(t, CBLOCK).astype(facc)
                        for v in f32_lanes], axis=-1)
        floats = jnp.einsum("tbr,tbl->trl", oh.astype(facc), lf,
                            preferred_element_type=facc,
                            precision=_EXACT_F32
                            ).reshape(t * r, len(f32_lanes))
    valid = (jnp.arange(r, dtype=jnp.int32)[None, :] <
             jnp.minimum(cnt, r)[:, None]).reshape(t * r)
    return ints, floats, valid, overflow


def _slot_sum_tables(gslot, t_slots: int, int_vals, f32_vals, count_mask):
    """Per-group sums/counts via chunked one-hot matmuls.

    gslot [K] in [0, t_slots] (t_slots = drop slot). Int lanes carry
    7-bit values (<= 127, _planes_for planes / metric parts): chunks
    of <= SLOT_CHUNK = 2^17 rows keep every bf16-product cell sum
    exact in the f32 accumulator (127 * 2^17 < 2^24; the round-2
    2^16 chunk at K ~ 3M meant 48 scan steps x ~0.7ms fixed overhead
    — the measured ~35ms slot-table floor — so the bound is taken to
    its max); chunks combine in int32 (127 * K < 2^31 for K < 2^24 —
    callers route bigger K through DENSE_ROWS_LIMIT macro-chunking).
    bf16 x bf16 -> f32 dots are deliberate: s8 dots measured ~1.4x
    SLOWER on this XLA/v5e stack, and the one-hot operands here must
    stay un-materialized producer fusions (ranked layouts reach
    t_slots ~ millions — a concatenated/stacked operand would
    materialize at [chunk, t_slots/128] scale and cannot compile).
    Returns (int_tables [Li, t_slots] int32, f32_tables [Lf, t_slots],
    counts [t_slots] int32); any of the value args may be None.
    """
    k = gslot.shape[0]
    n_iv = 0 if int_vals is None else int_vals.shape[1]
    n_l = n_iv + (1 if count_mask is not None else 0)   # dispatched lanes
    gp = _radix_pad(t_slots + 1)
    g1 = gp // RADIX_LO
    if n_l and (t_slots + 1 < RADIX_G or n_l * g1 <= RADIX_LO):
        # NARROW tables (the dense/offset-remapped layouts) route the
        # int lanes + count through the BATCHED dense kernel — at
        # compacted caps of ~3M rows the chunked scan below costs ~24
        # sequential steps x ~0.7ms fixed overhead, the dominant term
        # of q2.1-class compacted group-bys (measured round 3)
        kp = -(-k // BLOCK) * BLOCK
        gs_p = jnp.pad(gslot, (0, kp - k), constant_values=t_slots)
        lanes = [jnp.pad(int_vals[:, p], (0, kp - k))
                 for p in range(n_iv)]
        if count_mask is not None:
            # the count mask rides as one more 0/1 VALUE lane (counts
            # are independent of the int sums — masking the sums by it
            # would break the contract), with an all-true row mask;
            # invalid rows land in the drop slot, which is sliced off
            lanes.append(jnp.pad(count_mask, (0, kp - k)).astype(jnp.int8))
        out = _dense_group_sums(lanes, (), gs_p, jnp.ones(kp, bool),
                                t_slots + 1)[0]
        tf = None
        if f32_vals is not None:
            tf = _slot_sum_tables(gslot, t_slots, None, f32_vals, None)[1]
        return (None if int_vals is None else out[:n_iv, :t_slots],
                tf,
                None if count_mask is None else out[n_iv, :t_slots])
    ch = min(k, SLOT_CHUNK)
    nch = -(-k // ch)
    pad = nch * ch - k
    gs = jnp.pad(gslot, (0, pad), constant_values=t_slots).reshape(nch, ch)
    acc = sum_dtype()

    iv = None if int_vals is None else jnp.pad(
        int_vals, ((0, pad), (0, 0))).reshape(nch, ch, -1)
    fv = None if f32_vals is None else jnp.pad(
        f32_vals, ((0, pad), (0, 0))).reshape(nch, ch, -1)
    cm = None if count_mask is None else jnp.pad(
        count_mask, (0, pad)).reshape(nch, ch)

    radix = (t_slots + 1) > SLOT_RADIX_G
    gp = _radix_pad(t_slots + 1)

    def body(carry, xs):
        ci, cf, cc = carry
        g = xs[0]
        j = 1
        if radix:
            # factored accumulation: per value lane, one [k, 128]
            # elementwise product + one MXU matmul replaces the [k, g]
            # one-hot build (the VPU cost that dominated group-by at
            # g ~ 8k; see _radix_onehots)
            oh_hi, oh_lo = _radix_onehots(g, gp, jnp.bfloat16)
            if iv is not None:
                v = xs[j].astype(jnp.bfloat16)
                ci = ci + jnp.stack([
                    _radix_group_sum(oh_hi, oh_lo, v[:, p], t_slots + 1,
                                     jnp.float32)
                    for p in range(v.shape[1])]).astype(jnp.int32)
                j += 1
            if fv is not None:
                hi_a, lo_a = oh_hi.astype(acc), oh_lo.astype(acc)
                v = xs[j].astype(acc)
                cf = cf + jnp.stack([
                    _radix_group_sum(hi_a, lo_a, v[:, p], t_slots + 1, acc)
                    for p in range(v.shape[1])])
                j += 1
            if cm is not None:
                m = xs[j].astype(jnp.bfloat16)
                cc = cc + _radix_group_sum(
                    oh_hi, oh_lo, m, t_slots + 1,
                    jnp.float32).astype(jnp.int32)
            return (ci, cf, cc), None
        oh2 = g[:, None] == jnp.arange(t_slots + 1, dtype=jnp.int32)
        if iv is not None:
            ci = ci + jnp.einsum(
                "kg,kl->lg", oh2.astype(jnp.bfloat16),
                xs[j].astype(jnp.bfloat16),
                preferred_element_type=jnp.float32).astype(jnp.int32)
            j += 1
        if fv is not None:
            cf = cf + jnp.einsum(
                "kg,kl->lg", oh2.astype(acc), xs[j].astype(acc),
                preferred_element_type=acc, precision=_EXACT_F32)
            j += 1
        if cm is not None:
            cc = cc + jnp.einsum(
                "kg,k->g", oh2.astype(jnp.bfloat16),
                xs[j].astype(jnp.bfloat16),
                preferred_element_type=jnp.float32).astype(jnp.int32)
        return (ci, cf, cc), None

    init = (
        jnp.zeros((iv.shape[2] if iv is not None else 0, t_slots + 1),
                  jnp.int32),
        jnp.zeros((fv.shape[2] if fv is not None else 0, t_slots + 1), acc),
        jnp.zeros(t_slots + 1, jnp.int32))
    xs = (gs,) + tuple(x for x in (iv, fv, cm) if x is not None)
    (ti, tf, tc), _ = jax.lax.scan(body, init, xs)
    return (None if int_vals is None else ti[:, :t_slots],
            None if f32_vals is None else tf[:, :t_slots],
            None if count_mask is None else tc[:t_slots])


def _group_outputs_compacted_sorted(group_spec, cols, mask, num_docs,
                                    params=None):
    """Terminal fallback for barely-selective compacted group-bys
    (r > 256): full-segment sort compaction + scatters into dense
    [g_pad] tables. Slower than the MXU path but its memory/compute is
    bounded at any escalation rung, where the one-hot einsums would
    build O(rows * r) / O(cap * slots) intermediates."""
    gcols, strides, g_pad, agg_specs, kmax = group_spec
    key = _group_key(gcols, strides, g_pad, cols, params)
    n = mask.shape[0]
    mk = jnp.where(mask, key, jnp.int32(g_pad))      # invalid rows sort last
    iota = jnp.arange(n, dtype=jnp.int32)
    sk, si = jax.lax.sort((mk, iota), num_keys=1)
    k_c, si_c = sk[:kmax], si[:kmax]
    vm = k_c < g_pad
    matched = mask.sum(dtype=jnp.int32)
    outs = {"group.overflow": (matched > kmax).astype(jnp.int32),
            "group.count": jnp.zeros(g_pad + 1, jnp.int32).at[k_c].add(
                vm.astype(jnp.int32))[:g_pad]}
    acc = sum_dtype()
    for i, spec in enumerate(agg_specs):
        fname, col, source, extra = spec
        if fname == "count":
            continue
        strategy = extra[0] if isinstance(extra, tuple) else "vals"
        if fname in ("sum", "avg"):
            if strategy == "psums":
                # part lanes gathered at the compacted rows, int32
                # scatter per part; kmax past DENSE_ROWS_LIMIT is chunked
                # into a leading axis the host recombines in int64
                pv = cols[f"{col}.parts"][:, si_c].astype(jnp.int32)
                pv = jnp.where(vm[None, :], pv, 0)
                n_parts = pv.shape[0]
                if kmax > DENSE_ROWS_LIMIT:
                    n_ch = -(-kmax // DENSE_ROWS_LIMIT)
                    pad = n_ch * DENSE_ROWS_LIMIT - kmax
                    kc = jnp.pad(k_c, (0, pad), constant_values=g_pad
                                 ).reshape(n_ch, -1)
                    pc = jnp.pad(pv, ((0, 0), (0, pad))
                                 ).reshape(n_parts, n_ch, -1)
                    outs[f"gagg{i}.cpsums"] = jax.vmap(
                        lambda k, p: jnp.zeros(
                            (n_parts, g_pad + 1),
                            jnp.int32).at[:, k].add(p)[:, :g_pad],
                        in_axes=(0, 1))(kc, pc)
                else:
                    outs[f"gagg{i}.cpsums"] = jnp.zeros(
                        (n_parts, g_pad + 1),
                        jnp.int32).at[:, k_c].add(pv)[:, :g_pad]
            else:
                lane = cols[f"{col}.vlane" if source == "sv"
                            else f"{col}.raw"]
                lv = jnp.where(vm, lane[si_c].astype(acc), 0)
                outs[f"gagg{i}.sum"] = jnp.zeros(
                    g_pad + 1, acc).at[k_c].add(lv)[:g_pad]
        elif fname in ("min", "max", "minmaxrange"):
            if source == "sv":
                card_pad = extra[1]
                idv = cols[f"{col}.ids"][si_c].astype(jnp.int32)
                if fname in ("min", "minmaxrange"):
                    outs[f"gagg{i}.min"] = jnp.full(
                        g_pad + 1, card_pad, jnp.int32).at[k_c].min(
                        jnp.where(vm, idv, card_pad))[:g_pad]
                if fname in ("max", "minmaxrange"):
                    outs[f"gagg{i}.max"] = jnp.full(
                        g_pad + 1, -1, jnp.int32).at[k_c].max(
                        jnp.where(vm, idv, -1))[:g_pad]
            else:
                vv = cols[f"{col}.raw"][si_c].astype(acc)
                if fname in ("min", "minmaxrange"):
                    outs[f"gagg{i}.min"] = jnp.full(
                        g_pad + 1, jnp.inf, acc).at[k_c].min(
                        jnp.where(vm, vv, jnp.inf))[:g_pad]
                if fname in ("max", "minmaxrange"):
                    outs[f"gagg{i}.max"] = jnp.full(
                        g_pad + 1, -jnp.inf, acc).at[k_c].max(
                        jnp.where(vm, vv, -jnp.inf))[:g_pad]
        else:
            raise ValueError(f"unsupported group-by aggregation {fname}")
    return outs


def _group_outputs_compacted(group_spec, cols, mask, num_docs,
                             params=None):
    """Filtered group-by over MXU-compacted matched rows.

    Every needed lane (mixed-radix key bytes, int8 metric parts, float
    value lanes, dictIds for extrema) is block-compacted by _block_compact
    in ONE fused one-hot matmul, then aggregated into group tables by a
    second one-hot matmul (_slot_sum_tables). Measured ~500x faster than
    the sort- or scatter-based alternatives at SSB shapes on v5e: the
    only row-scale work is elementwise + matmul. Two table layouts:

    - g_pad <= DENSE_G_LIMIT: dense [g_pad] tables addressed by key
      (shared key space → device psum combine across segments).
    - g_pad >  DENSE_G_LIMIT ("ranked"): sort the compacted keys (k-scale
      only), rank-dedup, tables addressed by group RANK + a parallel
      `group.rkeys` lane. Bounded by matched rows, not by the key
      cross-product; host merges per-segment rank spaces by key (the
      CombineGroupByOperator merge, done columnar in numpy).
    """
    gcols, strides, g_pad, agg_specs, kmax = group_spec
    n = mask.shape[0]
    t = n // CBLOCK
    r = min(max(-(-kmax // t), 8), CBLOCK)
    if r > 256:
        # barely-selective escalation rung: the one-hot compaction would
        # cost O(rows * r) — the bounded sort+scatter fallback wins there
        return _group_outputs_compacted_sorted(group_spec, cols, mask,
                                               num_docs, params)
    key = _group_key(gcols, strides, g_pad, cols, params)

    # lane registry: key byte planes + per-agg value planes
    n_kb = _planes_for(g_pad - 1)
    int_lanes = [((key >> (PLANE_BITS * b)) & 0x7F) for b in range(n_kb)]
    f32_lanes = []
    int_slots: Dict[int, Tuple[int, int]] = {}   # agg i → (start, n_planes)
    f32_slots: Dict[int, int] = {}
    id_slots: Dict[int, Tuple[int, int]] = {}    # agg i → ids byte planes
    for i, spec in enumerate(agg_specs):
        fname, col, source, extra = spec
        if fname == "count":
            continue
        strategy = extra[0] if isinstance(extra, tuple) else "vals"
        if fname in ("sum", "avg"):
            if strategy == "psums":
                pl = cols[f"{col}.parts"]
                plist = [pl[p] for p in range(pl.shape[0])]
                int_slots[i] = (len(int_lanes), len(plist))
                int_lanes.extend(plist)   # 7-bit values: bf16-exact
            else:
                lane = cols[f"{col}.vlane" if source == "sv"
                            else f"{col}.raw"]
                f32_slots[i] = len(f32_lanes)
                f32_lanes.append(lane.astype(jnp.float32))
        elif fname in ("min", "max", "minmaxrange"):
            if source == "sv":
                card_pad = extra[1]
                ids = cols[f"{col}.ids"].astype(jnp.int32)
                nb = _planes_for(card_pad - 1)
                id_slots[i] = (len(int_lanes), nb)
                for b in range(nb):
                    int_lanes.append((ids >> (PLANE_BITS * b)) & 0x7F)
            else:
                f32_slots[i] = len(f32_lanes)
                f32_lanes.append(cols[f"{col}.raw"].astype(jnp.float32))
        else:
            raise ValueError(f"unsupported group-by aggregation {fname}")

    ci, cf, valid, overflow = _block_compact(mask, int_lanes, f32_lanes, r)
    cap = t * r
    outs = {"group.overflow": overflow}

    def _reassemble(start, nb):
        v = ci[:, start].astype(jnp.int32)
        for b in range(1, nb):
            v = v + (ci[:, start + b].astype(jnp.int32) << (PLANE_BITS * b))
        return v

    k_c = jnp.where(valid, _reassemble(0, n_kb), jnp.int32(g_pad))
    acc = sum_dtype()

    ranked = g_pad > DENSE_G_LIMIT
    if ranked:
        # sort only the compacted keys (cap-scale), rank-dedup
        sk, order = jax.lax.sort((k_c, jnp.arange(cap, dtype=jnp.int32)),
                                 num_keys=1)
        vs = sk < g_pad
        if ci is not None:
            ci = ci[order]
        if cf is not None:
            cf = cf[order]
        valid = vs
        newg = vs & jnp.concatenate([vs[:1], sk[1:] != sk[:-1]])
        gslot = jnp.where(vs, jnp.cumsum(newg.astype(jnp.int32)) - 1, cap)
        t_slots = cap
        outs["group.rkeys"] = jnp.full(
            cap + 1, g_pad, jnp.int32).at[
            jnp.where(newg, gslot, cap)].set(sk)[:cap]
        sum_key, min_key, max_key, psums_key = ("rsum", "rmin", "rmax",
                                                "rpsums")
    else:
        gslot = jnp.where(valid, k_c, g_pad)
        t_slots = g_pad
        sum_key, min_key, max_key, psums_key = ("sum", "min", "max",
                                                "cpsums")

    # the int value columns actually summed (metric parts)
    part_cols = []
    for i, (start, np_) in int_slots.items():
        part_cols.extend(range(start, start + np_))
    iv = ci[:, part_cols] if part_cols else None
    if iv is not None:
        iv = jnp.where(valid[:, None], iv, 0)
    fvals = cf if f32_slots else None
    if fvals is not None:
        fvals = jnp.where(valid[:, None], fvals, 0)
    if iv is not None and cap > DENSE_ROWS_LIMIT:
        # int32 accumulation bound (127 * 2^24 < 2^31): emit per-macro-
        # chunk tables; the host recombines chunks exactly in int64
        n_mc = -(-cap // DENSE_ROWS_LIMIT)
        ti = jnp.stack([
            _slot_sum_tables(
                gslot[c * DENSE_ROWS_LIMIT: (c + 1) * DENSE_ROWS_LIMIT],
                t_slots,
                iv[c * DENSE_ROWS_LIMIT: (c + 1) * DENSE_ROWS_LIMIT],
                None, None)[0]
            for c in range(n_mc)])                      # [C, L, t_slots]
        _, tf, tc = _slot_sum_tables(gslot, t_slots, None, fvals,
                                     valid)
    else:
        ti, tf, tc = _slot_sum_tables(gslot, t_slots, iv, fvals,
                                      valid)
    if ranked:
        outs["group.rcount"] = tc
    else:
        outs["group.count"] = tc

    # map table rows back to per-agg outputs
    pci = 0
    for i, spec in enumerate(agg_specs):
        fname, col, source, extra = spec
        if fname == "count":
            continue
        strategy = extra[0] if isinstance(extra, tuple) else "vals"
        if fname in ("sum", "avg"):
            if strategy == "psums":
                _, np_ = int_slots[i]
                outs[f"gagg{i}.{psums_key}"] = (
                    ti[:, pci: pci + np_] if ti.ndim == 3
                    else ti[pci: pci + np_])
                pci += np_
            else:
                outs[f"gagg{i}.{sum_key}"] = tf[f32_slots[i]]
        elif fname in ("min", "max", "minmaxrange"):
            if source == "sv":
                card_pad = extra[1]
                start, nb = id_slots[i]
                idv = _reassemble(start, nb)
                if fname in ("min", "minmaxrange"):
                    outs[f"gagg{i}.{min_key}"] = jnp.full(
                        t_slots + 1, card_pad, jnp.int32).at[gslot].min(
                        jnp.where(valid, idv, card_pad))[:t_slots]
                if fname in ("max", "minmaxrange"):
                    outs[f"gagg{i}.{max_key}"] = jnp.full(
                        t_slots + 1, -1, jnp.int32).at[gslot].max(
                        jnp.where(valid, idv, -1))[:t_slots]
            else:
                vv = cf[:, f32_slots[i]].astype(acc)
                if fname in ("min", "minmaxrange"):
                    outs[f"gagg{i}.{min_key}"] = jnp.full(
                        t_slots + 1, jnp.inf, acc).at[gslot].min(
                        jnp.where(valid, vv, jnp.inf))[:t_slots]
                if fname in ("max", "minmaxrange"):
                    outs[f"gagg{i}.{max_key}"] = jnp.full(
                        t_slots + 1, -jnp.inf, acc).at[gslot].max(
                        jnp.where(valid, vv, -jnp.inf))[:t_slots]
    return outs


def _expand_mv_group(group_spec, cols, mask, params=None):
    """Row-space expansion for MV group keys: one row per (doc, entry)
    cross-combination across all MV key columns (reference parity:
    DefaultGroupByExecutor.aggregateGroupByMV — a doc contributes once
    per value combination, and its metrics repeat per combination).

    Returns (group_spec', cols', mask') with every "mvids"/"mvin" gcol
    rewritten to a flattened "ids" lane over rows*W rows (W = product
    of the MV columns' padded entry widths, static from lane shapes);
    padding entries (id == cardinality) mask their rows out, and "mvin"
    dims (valuein group keys) additionally mask entries outside their
    allowed-value member vector — a RUNTIME operand popped from
    `params` in gcol order. Only row-scale lanes the group machinery
    reads are expanded; dictionary value tables pass through. W
    multiplies the row count, so this is reserved for MV group-bys
    (never on the SSB hot path)."""
    gcols, strides, g_pad, agg_specs, kmax = group_spec
    n = mask.shape[0]
    # widths/entry indexes are keyed per GCOL POSITION, not per column
    # name: two group keys over the same MV column (e.g. GROUP BY col,
    # valuein(col, ...)) must each contribute an independent axis of
    # the entry cross-product — the reference expands each key position
    # sequentially (DefaultGroupByExecutor.aggregateGroupByMV), so a
    # name-keyed expansion would produce diagonal (same-entry) pairs
    # only and diverge from the host executor (round-2 advisor finding)
    widths = [(gi, c, cols[f"{c}.mv"].shape[-1])
              for gi, (c, gkind, _o, _card) in enumerate(gcols)
              if gkind in ("mvids", "mvin")]
    total_w = int(np.prod([w for _gi, _c, w in widths], dtype=np.int64))
    # mixed-radix decomposition of the cross index over the mv widths
    entry_idx, stride = {}, 1
    for gi, _c, w in widths:
        entry_idx[gi] = (np.arange(total_w) // stride) % w
        stride *= w

    def rep1(lane):                       # [n] -> [n * total_w]
        return jnp.broadcast_to(lane[:, None],
                                (n, total_w)).reshape(-1)

    cols2, mask2, gcols2 = {}, rep1(mask), []
    for gi, (c, gkind, off, card) in enumerate(gcols):
        if gkind in ("mvids", "mvin"):
            flat = cols[f"{c}.mv"][:, entry_idx[gi]].reshape(-1)
            # alias the expanded lane per position so a repeated column
            # keeps its per-position entry axis
            alias = f"{c}#g{gi}"
            cols2[f"{alias}.ids"] = flat
            mask2 = mask2 & (flat < card)
            if gkind == "mvin":
                member = params.pop(0)     # bool [card_pad], pad False
                mask2 = mask2 & member[
                    jnp.clip(flat, 0, member.shape[0] - 1)]
            gcols2.append((alias, "ids", off, card))
        else:
            gcols2.append((c, gkind, off, card))
    for key, lane in cols.items():
        if key in cols2:
            continue
        if key.endswith(".mv"):
            w = lane.shape[-1]
            cols2[key] = jnp.broadcast_to(
                lane[:, None, :], (n, total_w, w)).reshape(-1, w)
        elif key.endswith(".parts"):      # [n_parts, n]
            cols2[key] = jnp.broadcast_to(
                lane[:, :, None],
                lane.shape + (total_w,)).reshape(lane.shape[0], -1)
        elif key.endswith(".vals"):       # dictionary value table
            cols2[key] = lane
        else:                             # .ids / .raw / .vlane: [n]
            cols2[key] = rep1(lane)
    # compaction capacity scales with the expansion (the escalation
    # ladder still covers skew/overflow)
    kmax2 = min(kmax * total_w, n * total_w) if kmax else 0
    spec2 = (tuple(gcols2), strides, g_pad, agg_specs, kmax2)
    return spec2, cols2, mask2


def _group_outputs(group_spec, cols, mask, num_docs, params=None):
    if any(g[1] in ("mvids", "mvin") for g in group_spec[0]):
        group_spec, cols, mask = _expand_mv_group(group_spec, cols, mask,
                                                  params)
    gcols, strides, g_pad, agg_specs, kmax = group_spec
    if kmax:
        return _group_outputs_compacted(group_spec, cols, mask, num_docs,
                                        params)
    key = _group_key(gcols, strides, g_pad, cols, params)
    dense = g_pad <= DENSE_G_LIMIT and mask.shape[0] <= DENSE_ROWS_LIMIT
    # every summed lane (7-bit parts of the psums aggregations, float or
    # raw value lanes of the csums ones) and the group count share ONE
    # pass: the one-hot builds dominate at dense shapes
    by_strategy = {s: [(i, spec) for i, spec in enumerate(agg_specs)
                       if spec[0] in ("sum", "avg") and
                       isinstance(spec[3], tuple) and spec[3][0] == s]
                   for s in ("psums", "csums")}
    psums_specs = by_strategy["psums"] if dense else []
    outs = {}
    if psums_specs or by_strategy["csums"]:
        parts = [cols[f"{spec[1]}.parts"] for _i, spec in psums_specs]
        psums, csums, count = _dense_group_sums(
            [pl[p] for pl in parts for p in range(pl.shape[0])],
            [cols[f"{spec[1]}.vlane" if spec[2] == "sv"
                  else f"{spec[1]}.raw"]
             for _i, spec in by_strategy["csums"]],
            key, mask, g_pad, with_count=dense)
        start = 0
        for (i, _spec), pl in zip(psums_specs, parts):
            outs[f"gagg{i}.psums"] = psums[start:start + pl.shape[0]]
            start += pl.shape[0]
        for j, (i, _spec) in enumerate(by_strategy["csums"]):
            outs[f"gagg{i}.csums"] = csums[j]
    else:
        count = _mxu_histogram(key, mask, g_pad) if dense else None
    outs["group.count"] = count if dense else jnp.zeros(
        g_pad, jnp.int32).at[key].add(mask.astype(jnp.int32))
    acc = sum_dtype()
    for i, spec in enumerate(agg_specs):
        fname, col, source, extra = spec
        if fname == "count":
            continue  # shares group.count
        strategy = extra[0] if isinstance(extra, tuple) else "vals"
        if fname in ("sum", "avg"):
            if strategy == "psums":
                if not dense:
                    # scatter fallback keyed per part lane
                    outs[f"gagg{i}.psums"] = jnp.stack([
                        jnp.zeros(g_pad, jnp.int32).at[key].add(
                            jnp.where(mask, cols[f"{col}.parts"][p]
                                      .astype(jnp.int32), 0))
                        for p in range(cols[f"{col}.parts"].shape[0])])
                # dense: already emitted by the fused pass above
            elif strategy != "csums":  # scatter fallback (huge tables)
                if source == "sv":
                    vals = cols[f"{col}.vals"][cols[f"{col}.ids"]]
                else:
                    vals = cols[f"{col}.raw"]
                contrib = jnp.where(mask, vals.astype(acc), 0)
                outs[f"gagg{i}.sum"] = jnp.zeros(g_pad, acc).at[key].add(
                    contrib)
        if fname in ("min", "max", "minmaxrange"):
            if source == "sv":
                card_pad = extra[1]
                ids = cols[f"{col}.ids"].astype(jnp.int32)
                if fname in ("min", "minmaxrange"):
                    outs[f"gagg{i}.min"] = (
                        _dense_group_extreme(ids, key, mask, g_pad,
                                             np.int32(card_pad), True)
                        if dense else jnp.full(g_pad, card_pad, jnp.int32)
                        .at[key].min(jnp.where(mask, ids, card_pad)))
                if fname in ("max", "minmaxrange"):
                    outs[f"gagg{i}.max"] = (
                        _dense_group_extreme(ids, key, mask, g_pad,
                                             np.int32(-1), False)
                        if dense else jnp.full(g_pad, -1, jnp.int32)
                        .at[key].max(jnp.where(mask, ids, -1)))
            else:
                vals = cols[f"{col}.raw"].astype(acc)
                if fname in ("min", "minmaxrange"):
                    outs[f"gagg{i}.min"] = (
                        _dense_group_extreme(vals, key, mask, g_pad,
                                             acc(np.inf), True)
                        if dense else jnp.full(g_pad, jnp.inf, acc)
                        .at[key].min(jnp.where(mask, vals, jnp.inf)))
                if fname in ("max", "minmaxrange"):
                    outs[f"gagg{i}.max"] = (
                        _dense_group_extreme(vals, key, mask, g_pad,
                                             acc(-np.inf), False)
                        if dense else jnp.full(g_pad, -jnp.inf, acc)
                        .at[key].max(jnp.where(mask, vals, -jnp.inf)))
        if fname not in ("sum", "avg", "min", "max", "minmaxrange"):
            raise ValueError(f"unsupported group-by aggregation {fname}")
    return outs


# ---------------------------------------------------------------------------
# Selection
#
# select spec: (kind, k, order=((col, asc, card_pad, source), ...),
#               gather_cols=((col, source), ...))
#   kind ∈ {"limit",     # no order: first-k matched docids
#           "order",     # all-dict keys packed into one int32 → top_k
#           "ordertk",   # single raw int32/f32 key → monotone-map + top_k
#           "ordermk",   # general multi-key → lax.sort (no packing limit)
#           "vector"}    # batched similarity scores → top_k (order slot
#                        #   carries ((col, metric, dim_pad),); runtime
#                        #   params: query vector f32 [dim_pad] + its
#                        #   f32 norm)
# ---------------------------------------------------------------------------


def vec_tree_sum(x):
    """Balanced pairwise sum over the LAST axis (pow2 width).

    This is the vector subsystem's exactness contract: every backend —
    numpy host oracle, XLA CPU, XLA TPU, per-shard sharded lanes — runs
    the SAME log2(D) sequence of elementwise IEEE f32 adds, so scores
    are bit-identical across all of them by construction. A matmul
    would hit the MXU but leaves the accumulation order (and therefore
    the low bits) implementation-defined; for a [P, 128] @ [128]
    matvec the MXU is row-starved anyway, while this form fuses into
    one VPU row stream at HBM bandwidth. Zero padding lanes are exact
    no-ops (x + 0.0 == x for every x the guards let through).
    """
    while x.shape[-1] > 1:
        x = x[..., 0::2] + x[..., 1::2]
    return x[..., 0]


def _vector_scores(mat, q, q_norm, metric: str):
    """Per-row similarity scores, float32 [P].

    mat: f32 [P, dim_pad] embedding block; q: f32 [dim_pad] query
    (zero-padded); q_norm: f32 scalar — the query's tree-norm, computed
    host-side by the planner with the same balanced tree (only read by
    the cosine metric; the planner rejects zero query vectors there).
    Rows with zero norm score -inf under cosine (they can never rank
    above any real match, exactly like the host twin).
    """
    dot = vec_tree_sum(mat * q[None, :])
    if metric == "cosine":
        denom = jnp.sqrt(vec_tree_sum(mat * mat)) * q_norm
        return jnp.where(denom > 0, dot / denom,
                         jnp.float32(-jnp.inf)).astype(jnp.float32)
    return dot.astype(jnp.float32)


def _monotone_int32_keys(lane, asc: bool) -> list:
    """Numeric lane → 1-2 int32 lanes whose lexicographic order equals the
    value order, exactly (IEEE-754 bit tricks; int64/f64 split hi/lo).
    Descending order is per-lane bitwise NOT (x ↦ -x-1 reverses int32 order
    and distributes over the hi/lo concatenation)."""
    dt = lane.dtype
    if dt in (jnp.int8, jnp.int16, jnp.int32):
        keys = [lane.astype(jnp.int32)]
    elif dt == jnp.float32:
        b = jax.lax.bitcast_convert_type(lane, jnp.int32)
        keys = [b ^ ((b >> 31) & jnp.int32(0x7FFFFFFF))]
    elif dt == jnp.int64:
        # wide_i64: these branches only trace for 64-bit lanes (x64 on
        # — the CPU/host-parity path); the helper asserts that instead
        # of silently narrowing to int32 the way jnp.int64(...) would
        hi = (lane >> 32).astype(jnp.int32)
        lo = ((lane & compat.wide_i64(0xFFFFFFFF)) -
              compat.wide_i64(0x80000000)).astype(jnp.int32)
        keys = [hi, lo]
    elif dt == jnp.float64:
        b = jax.lax.bitcast_convert_type(lane, jnp.int64)
        m = b ^ ((b >> 63) & compat.wide_i64(0x7FFFFFFFFFFFFFFF))
        hi = (m >> 32).astype(jnp.int32)
        lo = ((m & compat.wide_i64(0xFFFFFFFF)) -
              compat.wide_i64(0x80000000)).astype(jnp.int32)
        keys = [hi, lo]
    else:
        raise ValueError(f"unsupported order-by lane dtype {dt}")
    return keys if asc else [~k for k in keys]


def _selection_outputs(select_spec, cols, mask, params=None):
    kind, k, order, gather_cols = select_spec
    extra_outs = {}
    if kind == "vector":
        # batched top-k similarity: scores → monotone int32 keys →
        # lax.top_k (XLA top_k breaks ties toward the LOWER index, so
        # equal scores rank docid-ascending — the host twin's contract)
        (col, metric, _dim_pad), = order
        q = params.pop(0)                   # f32 [dim_pad] query vector
        q_norm = params.pop(0)              # f32 scalar (tree-norm of q)
        score = _vector_scores(cols[f"{col}.vec"], q, q_norm, metric)
        key = _monotone_int32_keys(score, True)[0]
        # reserve INT32_MIN for the masked-row sentinel (cost: the two
        # lowest real keys — NaN-pattern scores our guards never emit —
        # collapse into one rank)
        key = jnp.maximum(key, -INT32_MAX)
        scored = jnp.where(mask, key, -INT32_MAX - 1)
        _, docids = jax.lax.top_k(scored, k)
        n_valid = mask.sum(dtype=jnp.int32)
        valid_k = jnp.arange(k, dtype=jnp.int32) < n_valid
        docids = jnp.where(valid_k, docids, -1)
        extra_outs["sel.scores"] = jnp.where(
            valid_k, score[jnp.maximum(docids, 0)], jnp.float32(0))
    elif kind == "limit":
        docids = jnp.nonzero(mask, size=k, fill_value=-1)[0]
    elif kind == "order":
        # pack dict order columns into one int32 key (planner guarantees
        # the radix product fits in 31 bits, else it emits "ordermk")
        key = jnp.zeros(mask.shape[0], jnp.int32)
        for col, asc, card_pad, source in order:
            ids = cols[f"{col}.ids"]
            term = ids if asc else (np.int32(card_pad - 1) - ids)
            key = key * np.int32(card_pad) + term
        key = jnp.where(mask, key, INT32_MAX)
        neg_vals, docids = jax.lax.top_k(-key, k)
        docids = jnp.where(neg_vals == -INT32_MAX, -1, docids)
    elif kind == "ordertk":
        # single raw int32/f32 order column: monotone int32 key + top_k
        (col, asc, _card_pad, _source), = order
        key = _monotone_int32_keys(cols[f"{col}.raw"], asc)[0]
        # reserve INT32_MAX for the masked-row sentinel so no valid row can
        # tie it and get dropped (cost: values whose keys are INT32_MAX and
        # INT32_MAX-1 — int 2^31-1 vs 2^31-2, or two NaN bit patterns —
        # become order-tied with each other)
        key = jnp.minimum(key, INT32_MAX - 1)
        # top_k is descending; ~key descending == key ascending
        scored = jnp.where(mask, ~key, -INT32_MAX - 1)
        _, docids = jax.lax.top_k(scored, k)
        n_valid = mask.sum(dtype=jnp.int32)
        docids = jnp.where(jnp.arange(k, dtype=jnp.int32) < n_valid,
                           docids, -1)
    else:  # ordermk: general multi-key device sort
        keys = []
        for col, asc, card_pad, source in order:
            if source == "sv":
                ids = cols[f"{col}.ids"].astype(jnp.int32)
                keys.append(ids if asc else ~ids)
            else:
                keys.extend(_monotone_int32_keys(cols[f"{col}.raw"], asc))
        flag = jnp.where(mask, jnp.int32(0), jnp.int32(1))
        iota = jnp.arange(mask.shape[0], dtype=jnp.int32)
        res = jax.lax.sort((flag, *keys, iota), num_keys=1 + len(keys))
        docids = jnp.where(res[0][:k] == 0, res[-1][:k], -1)
    out = {"sel.docids": docids.astype(jnp.int32),
           "sel.count": mask.sum(dtype=jnp.int32)}
    out.update(extra_outs)
    safe = jnp.maximum(docids, 0)
    for col, source in gather_cols:
        lane = {"sv": f"{col}.ids", "raw": f"{col}.raw",
                "mv": f"{col}.mv"}[source]
        out[f"sel.{col}"] = cols[lane][safe]
    return out


# ---------------------------------------------------------------------------
# Window kernel (stage 2 of the multi-stage engine, query/stages/window.py)
#
# Operates on ONE exchanged row block (every server's stage-1 scan,
# concatenated in deterministic source order): lax.sort by (validity,
# partition code, window-order keys, input index) puts each window
# partition contiguous with a deterministic total order — the input
# index tie-break makes the sort equal to the host oracle's stable
# np.lexsort — then ROW_NUMBER is an iota rebased at partition starts
# and SUM(...) OVER is jnp.cumsum rebased the same way. All int32: the
# one accumulation every backend (numpy, XLA CPU, XLA TPU) reproduces
# bit-identically, with the executor rejecting inputs whose running
# sums could wrap (the window exactness contract, docs/QUERYENGINE.md).
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=128)
def build_window_kernel(n_pad: int, n_order: int, n_sums: int):
    """Unjitted window kernel: fn(part, orders, sums, num_rows) → outs.

    part: int32 [n_pad] partition codes; orders: tuple of n_order int32
    monotone order-key lanes; sums: tuple of n_sums int32 value lanes;
    num_rows: int32 valid prefix. Outputs (all [n_pad], valid prefix
    num_rows): "win.perm" input row index in window order, "win.rn"
    1-based row number within its partition, "win.sum<j>" running sums.
    """

    def kernel(part, orders, sums, num_rows):
        iota = jnp.arange(n_pad, dtype=jnp.int32)
        invalid = (iota >= num_rows).astype(jnp.int32)
        ops = (invalid, part) + tuple(orders) + (iota,) + tuple(sums)
        res = jax.lax.sort(ops, num_keys=3 + n_order)
        sp = res[1]
        perm = res[2 + n_order]
        svals = res[3 + n_order:]
        new = jnp.concatenate([jnp.ones(1, bool), sp[1:] != sp[:-1]])
        starts = jax.lax.cummax(jnp.where(new, iota, 0), axis=0)
        # all lanes arrive int32 by the window contract, so differences
        # and cumsum stay int32 with no narrowing casts (the executor's
        # host-side bound check guarantees no wrap)
        outs = {"win.perm": perm,
                "win.rn": iota - starts + jnp.int32(1)}
        for j, v in enumerate(svals):
            cs = jnp.cumsum(v, dtype=jnp.int32)
            base = cs[starts] - v[starts]
            outs[f"win.sum{j}"] = cs - base
        return outs

    return kernel


def named_kernel(fn, family: str):
    """Name a kernel closure by its FAMILY before it is jitted, so the
    device trace's `XLA Modules` read `jit_pinot_<family>(<fingerprint>)`
    instead of `jit_kernel(...)`. Never a literal or a shape: the
    fingerprint XLA appends tells programs apart. The name is part of
    the HLO module and so of the persistent compile cache's key."""
    fn.__name__ = fn.__qualname__ = f"pinot_{family}"
    return fn


@functools.lru_cache(maxsize=128)
def get_window_kernel(n_pad: int, n_order: int, n_sums: int):
    return jax.jit(named_kernel(build_window_kernel(n_pad, n_order, n_sums),
                                "window"))


def run_window_kernel(part, orders, sums, num_rows):
    fn = get_window_kernel(int(part.shape[0]), len(orders), len(sums))
    return fn(part, tuple(orders), tuple(sums), jnp.int32(num_rows))


# ---------------------------------------------------------------------------
# Kernel assembly + jit cache
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=1024)
def build_segment_kernel(padded: int, filter_spec, agg_specs, group_spec,
                         select_spec):
    """Unjitted whole-plan kernel closure (vmap/shard_map composable)."""

    def kernel(cols: Dict[str, jnp.ndarray], params: Tuple, num_docs):
        valid = jnp.arange(padded, dtype=jnp.int32) < num_docs
        plist = list(params)
        mask = _eval_filter(filter_spec, cols, plist, valid) & valid
        outs = {"stats.num_docs_matched": mask.sum(dtype=jnp.int32)}
        if group_spec is not None:
            outs.update(_group_outputs(group_spec, cols, mask, num_docs,
                                       plist))
        elif agg_specs:
            outs.update(_agg_outputs(agg_specs, cols, mask, num_docs))
        if select_spec is not None:
            # runtime selection operands (the vector query + its norm)
            # follow the filter/group params in depth-first plan order
            outs.update(_selection_outputs(select_spec, cols, mask,
                                           plist))
        return outs

    return named_kernel(kernel, scan_family(group_spec, select_spec))


def scan_family(group_spec, select_spec) -> str:
    """`scan_group`, `scan_select` or `scan_agg`: which of the three
    output stages the whole-plan kernel was built with."""
    if group_spec is not None:
        return "scan_group"
    return "scan_select" if select_spec is not None else "scan_agg"


@functools.lru_cache(maxsize=1024)
def get_segment_kernel(padded: int, filter_spec, agg_specs, group_spec,
                       select_spec):
    """Compile (once per static signature) the whole per-segment plan."""
    return jax.jit(build_segment_kernel(padded, filter_spec, agg_specs,
                                        group_spec, select_spec))


def run_segment_kernel(padded: int, filter_spec, agg_specs, group_spec,
                       select_spec, cols, params, num_docs):
    fn = get_segment_kernel(padded, filter_spec, tuple(agg_specs or ()),
                            group_spec, select_spec)
    return fn(cols, tuple(params), jnp.int32(num_docs))


# ---------------------------------------------------------------------------
# Cross-query batched dispatch: one kernel execution serves N queries
# that share a compiled spec and differ only in runtime literal
# operands. The column lanes are per-segment data shared across the
# batch (in_axes=None — uploaded once, read by every lane of the vmap);
# each param leaf gains a leading query axis. Group specs are excluded:
# adaptive group execution (query/groupby.py) drives value-dependent
# scout phases per query, so stacking its operands would fuse control
# flow that must stay per-member.
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=256)
def get_batched_segment_kernel(padded: int, filter_spec, agg_specs,
                               select_spec):
    """jit(vmap) of the SAME unjitted closure the sequential path
    compiles — batched and per-query dispatch trace one program, which
    is what makes batched-vs-sequential bit-parity a structural
    property rather than a numerical accident for the integer paths."""
    base = build_segment_kernel(padded, filter_spec, agg_specs, None,
                                select_spec)
    return jax.jit(named_kernel(
        jax.vmap(base, in_axes=(None, 0, None)),
        f"{scan_family(None, select_spec)}_batched"))


def stack_param_leaves(params_list):
    """[(p0, p1, ...)] per member → one tuple of [B, ...] leaves.

    Spec equality implies leaf-shape equality (widths are padded from
    the spec); a mismatch here means the caller grouped plans whose
    specs diverged and is a bug, surfaced as ValueError before any
    device work."""
    n = len(params_list[0])
    for ps in params_list:
        if len(ps) != n:
            raise ValueError("batched plans disagree on param arity")
    return tuple(
        jnp.stack([jnp.asarray(ps[i]) for ps in params_list])
        for i in range(n))


def batch_bucket(n: int) -> int:
    """Next power of two ≥ n (min 2): the batch axis is padded to a
    bucket before jit sees it, exactly like the doc-count padding —
    jit specializes on the leading dim, so raw occupancies would
    compile one XLA program PER DISTINCT BATCH SIZE under load (a
    compile storm that inverts the whole point of coalescing).
    Bucketing bounds the compile surface at log2(max occupancy)
    programs per spec."""
    b = 2
    while b < n:
        b <<= 1
    return b


def run_segment_kernel_batched(padded: int, filter_spec, agg_specs,
                               select_spec, cols, params_list, num_docs):
    """One dispatch for N same-spec queries; every output gains a
    leading query axis the caller slices per member (padded bucket
    lanes beyond N are never read). Callers handle the param-free case
    themselves (one unbatched dispatch shared by all members — vmap
    cannot infer a batch size from an empty pytree)."""
    fn = get_batched_segment_kernel(padded, filter_spec,
                                    tuple(agg_specs or ()), select_spec)
    members = [tuple(ps) for ps in params_list]
    # pad to the bucket by repeating the last member: dead lanes cost
    # only vmapped compute, never a fresh compile
    members.extend([members[-1]] * (batch_bucket(len(members))
                                    - len(members)))
    stacked = stack_param_leaves(members)
    return fn(cols, stacked, jnp.int32(num_docs))


# ---------------------------------------------------------------------------
# Kernel contract registry (consumed by analysis/contracts.py --deep)
#
# Every kernel family the planner can emit is registered here as a
# representative (spec, operand-layout) case; the deep analysis tier
# traces each one with jax.make_jaxpr across the shape-bucket grid and
# asserts the jaxpr-level contract: no host callbacks, no 64-bit avals
# under 32-bit mode (silent narrowing), stable retrace (identical jaxpr
# on re-trace, lru_cache hit on equal specs). Adding a kernel path to
# the planner without registering a case here is a review-visible gap:
# the case list IS the kernel surface the gate certifies.
# ---------------------------------------------------------------------------

#: operand layout legend — cols: {lane key: (dtype, shape)}; "P" is the
#: padded doc count, filled per shape bucket. params: depth-first pred /
#: group runtime operands as (dtype, shape).
CONTRACT_SHAPE_BUCKETS = (8192, 16384)


def contract_cases():
    """[(name, filter_spec, agg_specs, group_spec, select_spec, cols,
    params)] — the registered kernel surface."""
    P = "P"
    i8, i16, i32, f32, bl = "int8", "int16", "int32", "float32", "bool"
    cases = []

    def case(name, filt, aggs, group, select, cols, params=()):
        cases.append((name, filt, tuple(aggs), group, select,
                      dict(cols), tuple(params)))

    # scan-only counts
    case("count_match_all", ("match_all",), [("count", "*", "sv", None)],
         None, None, {})
    # the full predicate mix (sv ids, mv any-match, raw ranges, member
    # vectors, upsert vdoc liveness lane)
    case("filter_pred_mix",
         ("and", (
             ("pred", "eq_id", "d0", "sv", None),
             ("or", (("pred", "range_ids", "d1", "sv", None),
                     ("pred", "member", "d2", "sv", 64),
                     ("pred", "notin_ids", "d1", "sv", None))),
             ("pred", "in_ids", "m0", "mv", None),
             ("pred", "range_raw", "r0", "raw", (True, False)),
             ("pred", "vdoc", "$validDocIds", "vdoc", None))),
         [("count", "*", "sv", None)], None, None,
         {"d0.ids": (i32, (P,)), "d1.ids": (i32, (P,)),
          "d2.ids": (i32, (P,)), "m0.mv": (i32, (P, 4)),
          "r0.raw": (f32, (P,)), "$validDocIds.vdoc": (bl, (P,))},
         [(i32, ()), (i32, ()), (i32, ()), (bl, (64,)), (i32, (4,)),
          (i32, (8,)), (f32, ()), (f32, ())])
    # exact integer sums via bit-sliced part lanes (the q1.x hot path)
    case("agg_part_sums", ("match_all",),
         [("sum", "m0", "sv", ("parts", 2)),
          ("avg", "m1", "sv", ("parts", 3)),
          ("count", "*", "sv", None)],
         None, None,
         {"m0.parts": (i8, (2, P)), "m1.parts": (i8, (3, P))})
    # float sums, id extrema, histograms, decoded value lanes
    case("agg_float_hist",
         ("pred", "eq_id", "d0", "sv", None),
         [("sum", "r0", "raw", None), ("min", "r0", "raw", None),
          ("max", "d0", "sv", ("ids", 64)),
          ("distinctcount", "d0", "sv", ("hist", 64)),
          ("sum", "v0", "sv", ("vlane",))],
         None, None,
         {"d0.ids": (i32, (P,)), "r0.raw": (f32, (P,)),
          "v0.vlane": (f32, (P,))},
         [(i32, ())])
    # multi-value aggregation family
    case("agg_mv", ("match_all",),
         [("sum", "m0", "mv", (64, 50)),
          ("min", "m0", "mv", (64, 50)),
          ("countmv", "m0", "mv", (64, 50))],
         None, None, {"m0.mv": (i32, (P, 4))})
    # dense group-by: fused psums + count + id extrema
    case("group_dense",
         ("pred", "range_ids", "d0", "sv", None),
         [],
         ((("d0", "ids", 0, 8), ("d1", "ids", 0, 8)), (8, 1), 64,
          (("sum", "m0", "sv", ("psums", 2)),
           ("count", "*", "sv", None),
           ("min", "d0", "sv", ("ids", 8))), 0),
         None,
         {"d0.ids": (i32, (P,)), "d1.ids": (i32, (P,)),
          "m0.parts": (i8, (2, P))},
         [(i32, ()), (i32, ())])
    # dense group-by over a value lane: a raw float sum and the count
    # in the one pass (the no-cube q3.1 table)
    case("group_dense_csums",
         ("pred", "eq_id", "d0", "sv", None), [],
         ((("d0", "ids", 0, 8), ("d1", "ids", 0, 64)), (64, 1), 512,
          (("sum", "r0", "raw", ("csums",)),
           ("count", "*", "sv", None)), 0),
         None,
         {"d0.ids": (i32, (P,)), "d1.ids": (i32, (P,)),
          "r0.raw": (f32, (P,))},
         [(i32, ())])
    # scatter-fallback group-by (huge key space) + dict-decode sums
    case("group_scatter", ("match_all",), [],
         ((("d0", "ids", 0, 512),), (1,), 2 * DENSE_G_LIMIT,
          (("sum", "v0", "sv", ("vals",)),
           ("max", "r0", "raw", None)), 0),
         None,
         {"d0.ids": (i32, (P,)), "v0.ids": (i32, (P,)),
          "v0.vals": (f32, (512,)), "r0.raw": (f32, (P,))})
    # MXU-compacted filtered group-by (kmax > 0), dense tables
    case("group_compacted",
         ("pred", "eq_id", "d0", "sv", None), [],
         ((("d0", "ids", 0, 8), ("d1", "ids", 0, 8)), (8, 1), 64,
          (("sum", "m0", "sv", ("psums", 2)),
           ("min", "d0", "sv", ("ids", 8)),
           ("sum", "v0", "sv", ("vlane",))), 1024),
         None,
         {"d0.ids": (i32, (P,)), "d1.ids": (i32, (P,)),
          "m0.parts": (i8, (2, P)), "v0.vlane": (f32, (P,))},
         [(i32, ())])
    # rank-addressed compacted tables (g_pad above the dense limit)
    case("group_ranked", ("pred", "eq_id", "d0", "sv", None), [],
         ((("d0", "ids", 0, 70000),), (1,), 131072,
          (("sum", "m0", "sv", ("psums", 2)),), 1024),
         None,
         {"d0.ids": (i32, (P,)), "m0.parts": (i8, (2, P))},
         [(i32, ())])
    # adaptive remap group kinds consume runtime operands
    case("group_adaptive", ("match_all",), [],
         ((("d0", "idoff", 0, 8), ("d1", "idrank", 0, 8)), (8, 1), 64,
          (("count", "*", "sv", None),), 0),
         None,
         {"d0.ids": (i32, (P,)), "d1.ids": (i32, (P,))},
         [(i32, ()), (i32, (8,))])
    # selection kernels: limit, packed order, monotone top-k, multi-key
    case("select_limit", ("match_all",), [], None,
         ("limit", 16, (), (("d0", "sv"), ("r0", "raw"))),
         {"d0.ids": (i32, (P,)), "r0.raw": (f32, (P,))})
    case("select_order", ("match_all",), [], None,
         ("order", 16, (("d0", True, 8, "sv"), ("d1", False, 8, "sv")),
          (("d0", "sv"),)),
         {"d0.ids": (i32, (P,)), "d1.ids": (i32, (P,))})
    case("select_ordertk", ("match_all",), [], None,
         ("ordertk", 16, (("r0", True, 0, "raw"),), ()),
         {"r0.raw": (f32, (P,))})
    case("select_ordermk", ("match_all",), [], None,
         ("ordermk", 16, (("d0", True, 8, "sv"), ("r0", False, 0, "raw")),
          (("r0", "raw"),)),
         {"d0.ids": (i32, (P,)), "r0.raw": (f32, (P,))})
    # batched vector similarity top-k: MIPS/dot over the packed [P, dim]
    # embedding block, with a gather column riding along
    case("select_vector_dot", ("match_all",), [], None,
         ("vector", 16, (("e0", "dot", 128),), (("d0", "sv"),)),
         {"e0.vec": (f32, (P, 128)), "d0.ids": (i32, (P,))},
         [(f32, (128,)), (f32, ())])
    # cosine, fused with a filter predicate AND the upsert vdoc lane —
    # the "dead upserted rows can never rank" path
    case("select_vector_cosine_filtered",
         ("and", (("pred", "eq_id", "d0", "sv", None),
                  ("pred", "vdoc", "$validDocIds", "vdoc", None))),
         [], None,
         ("vector", 16, (("e0", "cosine", 128),), ()),
         {"e0.vec": (f32, (P, 128)), "d0.ids": (i32, (P,)),
          "$validDocIds.vdoc": (bl, (P,))},
         [(i32, ()), (f32, (128,)), (f32, ())])
    # IVF-indexed vector top-k: the ANN coarse-probe pred (assignment +
    # codebook + validity lanes, probe list selected ON DEVICE) fused
    # with the upsert vdoc lane ahead of the exact scoring tree — the
    # "score only probed, live rows" path. Params: probe q + norm
    # (filter, depth-first first), then the selection's q + norm.
    case("select_vector_ivf_probed",
         ("and", (("pred", "ivf_probe", "e0", "ivf", (8, "cosine")),
                  ("pred", "vdoc", "$validDocIds", "vdoc", None))),
         [], None,
         ("vector", 16, (("e0", "cosine", 128),), ()),
         {"e0.vec": (f32, (P, 128)), "e0.ivfa": (i16, (P,)),
          "e0.ivfc": (f32, (64, 128)), "e0.ivfv": (bl, (64,)),
          "$validDocIds.vdoc": (bl, (P,))},
         [(f32, (128,)), (f32, ()), (f32, (128,)), (f32, ())])
    # inner-join probe fused into the filter, dict-keyed fact side: the
    # host-translated member vector is the join-match predicate, the
    # jcode gather the dim group code — composed with the upsert vdoc
    # lane so dead upserted rows never reach a join side
    case("join_dict_group",
         ("and", (("pred", "member", "k0", "sv", 64),
                  ("pred", "vdoc", "$validDocIds", "vdoc", None))),
         [],
         ((("k0", "jcode", 0, 8), ("d0", "ids", 0, 8)), (8, 1), 64,
          (("sum", "m0", "sv", ("psums", 2)),
           ("count", "*", "sv", None)), 0),
         None,
         {"k0.ids": (i32, (P,)), "d0.ids": (i32, (P,)),
          "m0.parts": (i8, (2, P)), "$validDocIds.vdoc": (bl, (P,))},
         [(bl, (64,)), (i32, (64,))])
    # raw-keyed fact side: the dim key/code tables ride as runtime
    # operands and the probe structure is BUILT ON DEVICE (lax.sort +
    # searchsorted) — join_raw pred + jraw group code share the build
    case("join_raw_probe",
         ("pred", "join_raw", "k0", "raw", 128),
         [],
         ((("k0", "jraw", 0, 8),), (1,), 8,
          (("count", "*", "sv", None),), 0),
         None,
         {"k0.raw": (i32, (P,))},
         [(i32, (128,)), (i32, (128,)), (i32, (128,))])
    # DISTINCTCOUNTHLL device registers: histogram-present scatter-max
    # of the per-dictId (register index, rank) tables → [m] int32
    # registers that merge associatively (max) on every combine path
    case("agg_hll",
         ("pred", "eq_id", "d0", "sv", None),
         [("hll", "v0", "sv", ("hll", 64, 4096)),
          ("count", "*", "sv", None)],
         None, None,
         {"d0.ids": (i32, (P,)), "v0.ids": (i32, (P,)),
          "v0.hllidx": (i32, (64,)), "v0.hllrank": (i32, (64,))},
         [(i32, ())])
    return cases


#: leading-query-axis sizes the deep tier traces batched cases at —
#: pow2 only, because batch_bucket pads every occupancy to a pow2
#: before jit ever sees the leading dim
BATCH_CONTRACT_SIZES = (2, 4)


def batched_contract_cases():
    """The registered cases the dispatch coalescer can stack, traced by
    the deep tier through get_batched_segment_kernel at each
    BATCH_CONTRACT_SIZES occupancy: group-by cases are excluded (the
    coalescer never batches them — adaptive group execution is
    value-dependent per query) and so are param-free cases (they share
    one unbatched dispatch instead of a vmap)."""
    return [(name, filt, aggs, group, select, cols, params)
            for (name, filt, aggs, group, select, cols, params)
            in contract_cases()
            if group is None and params]


def extra_contract_cases():
    """Non-segment-plan kernel families, traced by the same deep-tier
    gate (analysis/contracts.py): [(name, builder, static_args,
    arg_specs)]. builder(*static_args) must return the unjitted kernel
    (lru-cached — the gate asserts cache identity like
    build_segment_kernel's); arg_specs is a pytree of (dtype, shape)
    leaves mirroring the kernel's positional args, with "P" filled per
    shape bucket in both static_args and shapes."""
    from pinot_tpu.ops import ivf_kernels  # lazy: avoids import cycle
    P = "P"
    i32, f32, bl = "int32", "float32", "bool"
    return [
        ("window_rank", build_window_kernel, (P, 2, 0),
         ((i32, (P,)), ((i32, (P,)), (i32, (P,))), (), (i32, ()))),
        ("window_rank_sum", build_window_kernel, (P, 1, 2),
         ((i32, (P,)), ((i32, (P,)),),
          ((i32, (P,)), (i32, (P,))), (i32, ()))),
        # IVF codebook lifecycle: Lloyd's train step, assign-only (the
        # sample-then-assign sweep), and standalone probe-select
        ("ivf_train_step", ivf_kernels.build_ivf_train_kernel,
         (P, 64, 128),
         ((f32, (P, 128)), (f32, (64, 128)), (i32, ()), (i32, ()))),
        ("ivf_assign", ivf_kernels.build_ivf_assign_kernel,
         (P, 64, 128),
         ((f32, (P, 128)), (f32, (64, 128)), (i32, ()), (i32, ()))),
        ("ivf_probe_select", ivf_kernels.build_ivf_probe_kernel,
         (64, 128, 8, "cosine"),
         ((f32, (64, 128)), (bl, (64,)), (f32, (128,)), (f32, ()))),
    ]
