"""Shared test fixtures: a deterministic multi-type table + segment builder.

Mirrors the reference's Avro-fixture approach
(pinot-core/src/test/.../queries/*QueriesTest building real segments from
fixtures) with a seeded random table generator.
"""
from __future__ import annotations

import numpy as np

from pinot_tpu.common.datatype import DataType
from pinot_tpu.common.schema import (Schema, TimeUnit, dimension, metric,
                                     time_field)
from pinot_tpu.common.table_config import IndexingConfig, TableConfig
from pinot_tpu.segment.creator import SegmentCreator
from pinot_tpu.segment.loader import ImmutableSegmentLoader

TEAMS = ["ANA", "BAL", "BOS", "CHA", "CLE", "DET", "HOU", "KCA", "LAA",
         "MIN", "NYA", "OAK", "SEA", "TBA", "TEX", "TOR"]
LEAGUES = ["AL", "NL"]
POSITIONS = ["P", "C", "1B", "2B", "3B", "SS", "LF", "CF", "RF", "DH"]


def make_schema() -> Schema:
    return Schema("baseballStats", [
        dimension("teamID", DataType.STRING),
        dimension("league", DataType.STRING),
        dimension("playerName", DataType.STRING),
        dimension("position", DataType.STRING, single_value=False),
        metric("runs", DataType.INT),
        metric("hits", DataType.LONG),
        metric("average", DataType.DOUBLE),
        metric("salary", DataType.FLOAT),
        time_field("yearID", DataType.INT, TimeUnit.DAYS),
    ])


def make_table_config(**kw) -> TableConfig:
    idx = IndexingConfig(
        inverted_index_columns=kw.pop("inverted", ["teamID", "league"]),
        bloom_filter_columns=kw.pop("bloom", ["teamID"]),
        no_dictionary_columns=kw.pop("no_dict", ["salary"]))
    return TableConfig("baseballStats", indexing_config=idx, **kw)


def make_columns(n: int, seed: int = 0) -> dict:
    rng = np.random.default_rng(seed)
    return {
        "teamID": np.array(rng.choice(TEAMS, n), dtype=object),
        "league": np.array(rng.choice(LEAGUES, n), dtype=object),
        "playerName": np.array(
            [f"player_{i % 997:03d}" for i in rng.integers(0, 997, n)],
            dtype=object),
        "position": [list(rng.choice(POSITIONS, rng.integers(1, 4),
                                     replace=False)) for _ in range(n)],
        "runs": rng.integers(0, 150, n).astype(np.int32),
        "hits": rng.integers(0, 250, n).astype(np.int64),
        "average": np.round(rng.random(n), 3),
        "salary": (rng.random(n).astype(np.float32) * 1e6).round(2),
        "yearID": rng.integers(1990, 2020, n).astype(np.int32),
    }


def build_segment(tmpdir: str, n: int = 10_000, seed: int = 0,
                  name: str | None = None):
    cols = make_columns(n, seed)
    creator = SegmentCreator(make_schema(), make_table_config(),
                             segment_name=name)
    creator.build(cols, tmpdir)
    return ImmutableSegmentLoader.load(tmpdir), cols


def make_shared_columns(n: int, seed: int = 0) -> dict:
    """Columns whose first rows enumerate each value pool, so every segment
    built from them has IDENTICAL dictionaries — the shared-dictionary
    layout the mesh-sharded executor combines in the dictId domain."""
    assert n >= 1024, "need n >= 1024 to cover the value pools"
    rng = np.random.default_rng(seed)

    def pick(pool, dtype=None):
        k = len(pool)
        idx = np.concatenate([np.arange(k), rng.integers(0, k, n - k)])
        arr = np.asarray(pool)[idx]
        return arr.astype(dtype) if dtype is not None else \
            np.array(arr, dtype=object)

    players = [f"player_{i:03d}" for i in range(997)]
    avg_grid = np.round(np.arange(256) / 256.0, 4)
    positions = [[POSITIONS[i % len(POSITIONS)]] if i < len(POSITIONS)
                 else list(rng.choice(POSITIONS, rng.integers(1, 4),
                                      replace=False))
                 for i in range(n)]
    return {
        "teamID": pick(TEAMS),
        "league": pick(LEAGUES),
        "playerName": pick(players),
        "position": positions,
        "runs": pick(np.arange(150), np.int32),
        "hits": pick(np.arange(250), np.int64),
        "average": pick(avg_grid, np.float64),
        "salary": (rng.random(n).astype(np.float32) * 1e6).round(2),
        "yearID": pick(np.arange(1990, 2020), np.int32),
    }


def build_shared_segments(base: str, n_segs: int = 8, n: int = 2048,
                          seed: int = 0):
    """n_segs segments with identical dictionaries + concatenated raw cols."""
    import os
    segs, all_cols = [], []
    for i in range(n_segs):
        d = os.path.join(base, f"seg{i}")
        os.makedirs(d, exist_ok=True)
        cols = make_shared_columns(n, seed + i)
        creator = SegmentCreator(make_schema(), make_table_config(),
                                 segment_name=f"shared_{i}")
        creator.build(cols, d)
        segs.append(ImmutableSegmentLoader.load(d))
        all_cols.append(cols)
    merged = {k: (np.concatenate([c[k] for c in all_cols])
                  if isinstance(all_cols[0][k], np.ndarray)
                  else sum((c[k] for c in all_cols), []))
              for k in all_cols[0]}
    return segs, merged


def spy_dead_rows(monkeypatch) -> list:
    """Spy on the walk's stacked launches (`ops/kernels.py`
    `run_stacked_segment_kernel`): the list returned gains, a launch,
    the bytes of its outputs' rows that no member ran. A stacked
    program's every output has a row for each of its stack's bucket, so
    what a walk pulls is its members' rows and these."""
    from pinot_tpu.ops import kernels
    real, dead = kernels.run_stacked_segment_kernel, []

    def spy(*args):
        outs = real(*args)
        n_stack, members = args[-2], args[-1]
        bucket = kernels.batch_bucket(n_stack)
        dead.append(sum(int(v.nbytes) // bucket * (bucket - len(members))
                        for v in outs.values()))
        return outs
    monkeypatch.setattr(kernels, "run_stacked_segment_kernel", spy)
    return dead
