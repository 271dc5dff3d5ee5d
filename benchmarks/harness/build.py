"""Segments through the program's public SegmentCreator, side by side.

The parent hands every segment to a worker process (spawned, so no
thread or JAX state is inherited); a worker draws its segment's rows
from the seed (the configuration's generator) and builds the segment
directory. Workers are held to the CPU backend: building needs no chip,
and the chip belongs to the server.
"""
from __future__ import annotations

import os
import sys

from . import tables


def table_objects(config: dict):
    """(Schema, TableConfig) of the configuration, as program objects."""
    from pinot_tpu.common.datatype import DataType
    from pinot_tpu.common.schema import Schema, dimension, metric
    from pinot_tpu.common.table_config import IndexingConfig, TableConfig
    gen = tables.load_generator(config["generator"])
    fields = [(metric if col in gen.METRIC_COLUMNS else dimension)(
        col, DataType[typ]) for col, typ in gen.COLUMN_TYPES.items()]
    table = TableConfig(config["table"], indexing_config=IndexingConfig(
        no_dictionary_columns=list(config.get("no_dictionary_columns")
                                   or []),
        star_tree_configs=list(config.get("star_tree_configs") or [])))
    return Schema(config["table"], fields), table


def _init_worker(checkout: str, bench_dir: str) -> None:
    os.environ["JAX_PLATFORMS"] = "cpu"
    for p in (checkout, bench_dir):
        if p not in sys.path:
            sys.path.insert(0, p)


def build_segment(job) -> str:
    config, seed, index, n, out_dir = job
    from pinot_tpu.segment.creator import (DictionaryEncodedColumn,
                                           SegmentCreator)
    gen = tables.load_generator(config["generator"])
    dims = gen.dimensions(seed, config["rows"])
    ids, values = gen.make_segment(dims, n, seed, index)
    # a value lane goes in as it is (the creator builds the segment's
    # dictionary from it), an id lane with its pool
    cols = {c: values[c] if c in values
            else DictionaryEncodedColumn(dims["pools"][c], ids[c])
            for c in gen.COLUMN_TYPES}
    schema, table = table_objects(config)
    name = f"ssb_{index}"
    path = os.path.join(out_dir, name)
    SegmentCreator(schema, table, segment_name=name).build(cols, path)
    return path


def build_all(config: dict, seed: int, out_dir: str, checkout: str,
              workers: int):
    """-> segment directories, in segment order."""
    import multiprocessing
    os.makedirs(out_dir, exist_ok=True)
    jobs = [(config, seed, i, hi - lo, out_dir) for i, (lo, hi) in
            enumerate(tables.segment_bounds(config["rows"],
                                            config["segments"]))]
    ctx = multiprocessing.get_context("spawn")
    with ctx.Pool(min(workers, len(jobs)), initializer=_init_worker,
                  initargs=(checkout, os.path.dirname(os.path.dirname(
                      os.path.abspath(__file__))))) as pool:
        return pool.map(build_segment, jobs, chunksize=1)
