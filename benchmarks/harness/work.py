"""The least work a query asks of the chip, and the chip's peaks.

`lane_bytes` is the bytes a scan of a query's referenced columns has to
read from HBM whatever implements it: one padded forward lane a
referenced column a segment, at the width the table's loader stores it
on the device (`segment/loader.py`):

- a column in a predicate or a group-by: its dictionary ids, at the
  narrowest signed width that holds the cardinality (`min_id_dtype`);
- a summed integer column behind a dictionary: its values less the
  segment's least one, cut into 7-bit slices of one byte each
  (`int_part_info_for`), so ceil(bits(most - least) / 7) bytes a row;
- a summed column without dictionary (the configuration's
  `no_dictionary_columns`): its raw lane, 4 bytes a row in x32.

It is HBM-bound by construction: a scan does a compare and an add a
row, far under the chip's FLOP/s peak at these bytes. A later index
that prunes rows reads fewer bytes than this and would put the roofline
share over 100%: the work then needs restating in a `benchmark` PR.
"""
from __future__ import annotations

import json
import os

from . import tables

#: rows a lane is padded to a multiple of (`ops/kernels.py` BLOCK)
PAD_BLOCK = 8192
PART_BITS = 7
RAW_LANE_BYTES = 4          # x32: every raw numeric lane is 32 bits wide


def padded_rows(n: int) -> int:
    return max(PAD_BLOCK, -(-n // PAD_BLOCK) * PAD_BLOCK)


def part_bytes(least: int, most: int) -> int:
    return -(-max(1, (most - least).bit_length()) // PART_BITS)


def lane_widths(shape_spec: dict, pools, value_range: dict,
                raw_columns=()) -> dict:
    """{lane: bytes a row} of the lanes one query reads in a segment
    whose value columns span `value_range` ({column: (least, most)})."""
    out = {}
    for c in [w["col"] for w in shape_spec["where"]] + \
            list(shape_spec["group_by"]):
        if c not in pools:
            raise KeyError(f"{c}: a predicate or group-by over a value "
                           "column has no stated lane width yet")
        out[c] = tables.id_dtype(len(pools[c])).itemsize
    for c in shape_spec["aggregates"]:
        if c in raw_columns:
            out[f"{c}.raw"] = RAW_LANE_BYTES
            continue
        least, most = value_range[c] if c in value_range else \
            (int(pools[c][0]), int(pools[c][-1]))
        out[f"{c}.parts"] = part_bytes(least, most)
    return out


def lane_bytes(shape_specs, pools, value_ranges, rows: int,
               segments: int, raw_columns=()) -> int:
    """Bytes of the union of the lanes that `shape_specs` (one query's
    shape, or several) reference, over all segments."""
    total = 0
    for (lo, hi), value_range in zip(tables.segment_bounds(rows, segments),
                                     value_ranges):
        widths = {}
        for spec in shape_specs:
            widths.update(lane_widths(spec, pools, value_range,
                                      raw_columns))
        total += padded_rows(hi - lo) * sum(widths.values())
    return total


def peaks(bench_dir: str, device_kind: str) -> dict:
    with open(os.path.join(bench_dir, "peaks.json")) as fh:
        table = json.load(fh)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       "benchmarks/peaks.json")
    return table[device_kind]
