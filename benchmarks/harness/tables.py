"""A table as the reference reads it, made by a generator found by name.

A generator (`benchmarks/generators/<name>.py`, named by the
configuration's `generator`) gives `COLUMN_TYPES`, `METRIC_COLUMNS`,
`VALUE_COLUMNS`, `dimensions(seed, rows)` (with the sorted value
`pools` of the columns drawn as ids) and `make_segment(dims, n, seed,
segment)` -> (id lanes, value lanes). Numpy only.
"""
from __future__ import annotations

import importlib
from typing import List, Tuple

import numpy as np


def load_generator(name: str):
    return importlib.import_module(f"generators.{name}")


def id_dtype(max_value: int) -> np.dtype:
    """Narrowest signed dtype for ids in [0, max_value]: the width the
    table stores an id lane at (`segment/loader.py` `min_id_dtype`)."""
    return np.dtype(np.int8 if max_value <= 127 else
                    np.int16 if max_value <= 32767 else np.int32)


def segment_bounds(rows: int, segments: int) -> List[Tuple[int, int]]:
    per = rows // segments
    return [(i * per, (i + 1) * per if i < segments - 1 else rows)
            for i in range(segments)]


class Table:
    """The pools and every segment's lanes: [(ids, values), ...]."""

    def __init__(self, pools, segments):
        self.pools = pools
        self.segments = segments

    @property
    def rows(self) -> int:
        return sum(len(next(iter(ids.values())))
                   for ids, _values in self.segments)

    def value_ranges(self) -> List[dict]:
        """For each segment, {value column: (least, most)}."""
        return [{c: (int(v.min()), int(v.max())) for c, v in values.items()}
                for _ids, values in self.segments]


def make_table(generator, rows: int, segments: int, seed: int,
               pool=None) -> Table:
    """Every segment's lanes, generated on `pool` (a thread pool) if
    given: numpy's generators release the interpreter lock."""
    dims = generator.dimensions(seed, rows)
    jobs = [(dims, hi - lo, seed, i)
            for i, (lo, hi) in enumerate(segment_bounds(rows, segments))]
    run = pool.map if pool is not None else map
    return Table(dims["pools"],
                 list(run(lambda j: generator.make_segment(*j), jobs)))
