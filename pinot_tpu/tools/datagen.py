"""Synthetic data generators: in-memory segments for benchmarks & dryruns.

Parity: the reference's data-generation tooling
(pinot-tools/.../tools/data/DataGenerator.java and the SSB/TPC-H style
pinot-druid-benchmark harness, SURVEY.md §6). Builds ImmutableSegment objects
directly from arrays — no file round-trip — so 100M-row benchmark tables
materialize in seconds. All segments of a table share one global dictionary
per column (the layout the mesh-sharded executor combines in the dictId
domain).
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from pinot_tpu.common.datatype import DataType
from pinot_tpu.segment.dictionary import Dictionary
from pinot_tpu.segment.loader import DataSource, ImmutableSegment
from pinot_tpu.segment.metadata import ColumnMetadata, SegmentMetadata


def _bits_for(card: int) -> int:
    return max(1, int(np.ceil(np.log2(max(card, 2)))))


def make_segment_from_arrays(
        name: str, table: str,
        dict_cols: Dict[str, Tuple[DataType, np.ndarray, np.ndarray]],
        raw_cols: Optional[Dict[str, Tuple[DataType, np.ndarray]]] = None,
        ) -> ImmutableSegment:
    """Build a queryable in-memory segment.

    dict_cols: col → (data_type, sorted_unique_values, dict_ids[int32])
    raw_cols:  col → (data_type, values)  (no-dictionary columns)
    """
    raw_cols = raw_cols or {}
    num_docs = None
    columns: Dict[str, ColumnMetadata] = {}
    sources: Dict[str, DataSource] = {}

    for col, (dt, values, ids) in dict_cols.items():
        ids = np.ascontiguousarray(ids, dtype=np.int32)
        if num_docs is None:
            num_docs = len(ids)
        assert len(ids) == num_docs, f"column {col} length mismatch"
        card = len(values)
        cm = ColumnMetadata(
            name=col, data_type=dt, cardinality=card,
            bits_per_element=_bits_for(card), single_value=True,
            sorted=bool(np.all(ids[1:] >= ids[:-1])) if len(ids) else True,
            has_dictionary=True,
            min_value=values[0] if card else None,
            max_value=values[-1] if card else None,
            total_number_of_entries=num_docs)
        ds = DataSource(cm, None)
        ds.dictionary = Dictionary(dt, values)
        ds.dict_ids = ids
        columns[col] = cm
        sources[col] = ds

    for col, (dt, vals) in raw_cols.items():
        vals = np.ascontiguousarray(vals)
        if num_docs is None:
            num_docs = len(vals)
        assert len(vals) == num_docs, f"column {col} length mismatch"
        cm = ColumnMetadata(
            name=col, data_type=dt, cardinality=num_docs,
            bits_per_element=vals.dtype.itemsize * 8, single_value=True,
            sorted=False, has_dictionary=False,
            min_value=vals.min() if num_docs else None,
            max_value=vals.max() if num_docs else None,
            total_number_of_entries=num_docs)
        ds = DataSource(cm, None)
        ds.raw_values = vals
        columns[col] = cm
        sources[col] = ds

    meta = SegmentMetadata(segment_name=name, table_name=table,
                           total_docs=int(num_docs), columns=columns)
    seg = ImmutableSegment(meta, sources)
    for ds in sources.values():
        ds._segment = seg
    return seg


# ---------------------------------------------------------------------------
# SSB star-schema table, denormalized (flat lineorder) — the layout the
# Star Schema Benchmark Q1.1–Q4.3 queries run against, and the shape the
# reference's contrib/pinot-druid-benchmark flattens TPC-H into.
# ---------------------------------------------------------------------------

SSB_REGIONS = ["AFRICA", "AMERICA", "ASIA", "EUROPE", "MIDDLE EAST"]
SSB_NATIONS = ["ALGERIA", "ARGENTINA", "BRAZIL", "CANADA", "CHINA", "EGYPT",
               "ETHIOPIA", "FRANCE", "GERMANY", "INDIA", "INDONESIA", "IRAN",
               "IRAQ", "JAPAN", "JORDAN", "KENYA", "MOROCCO", "MOZAMBIQUE",
               "PERU", "ROMANIA", "RUSSIA", "SAUDI ARABIA", "UNITED KINGDOM",
               "UNITED STATES", "VIETNAM"]
# TPC-H nation → region (SSB inherits it)
SSB_NATION_REGION = {
    "ALGERIA": "AFRICA", "ETHIOPIA": "AFRICA", "KENYA": "AFRICA",
    "MOROCCO": "AFRICA", "MOZAMBIQUE": "AFRICA",
    "ARGENTINA": "AMERICA", "BRAZIL": "AMERICA", "CANADA": "AMERICA",
    "PERU": "AMERICA", "UNITED STATES": "AMERICA",
    "CHINA": "ASIA", "INDIA": "ASIA", "INDONESIA": "ASIA", "JAPAN": "ASIA",
    "VIETNAM": "ASIA",
    "FRANCE": "EUROPE", "GERMANY": "EUROPE", "ROMANIA": "EUROPE",
    "RUSSIA": "EUROPE", "UNITED KINGDOM": "EUROPE",
    "EGYPT": "MIDDLE EAST", "IRAN": "MIDDLE EAST", "IRAQ": "MIDDLE EAST",
    "JORDAN": "MIDDLE EAST", "SAUDI ARABIA": "MIDDLE EAST",
}
_MONTHS = ["Jan", "Feb", "Mar", "Apr", "May", "Jun", "Jul", "Aug", "Sep",
           "Oct", "Nov", "Dec"]


SSB_TYPES = {
    "lo_quantity": DataType.INT, "lo_discount": DataType.INT,
    "lo_revenue": DataType.LONG, "lo_supplycost": DataType.DOUBLE,
    "d_year": DataType.INT, "d_yearmonthnum": DataType.INT,
    "d_yearmonth": DataType.STRING, "d_weeknuminyear": DataType.INT,
    "c_region": DataType.STRING, "c_nation": DataType.STRING,
    "c_city": DataType.STRING,
    "s_region": DataType.STRING, "s_nation": DataType.STRING,
    "s_city": DataType.STRING,
    "p_mfgr": DataType.STRING, "p_category": DataType.STRING,
    "p_brand1": DataType.STRING,
}
SSB_RAW_COLS = {"lo_supplycost"}


def _city_pool() -> np.ndarray:
    """250 cities: nation name truncated to 9 chars + digit (SSB layout,
    e.g. 'UNITED KI1'). Nations sorted + fixed-width suffix ⇒ the pool is
    lexicographically sorted and city_id == nation_id * 10 + digit."""
    nations = sorted(SSB_NATIONS)
    return np.array([n[:9] + str(d) for n in nations for d in range(10)],
                    dtype=object)


def ssb_pools(seed: int = 0) -> Dict[str, np.ndarray]:
    """Sorted global value pools (== the shared dictionaries)."""
    rng = np.random.default_rng(seed + 10_007)
    revenue = np.unique((rng.integers(100, 10_000, 8192) * 100)
                        .astype(np.int64))
    ymn = np.array(sorted(y * 100 + m for y in range(1992, 1999)
                          for m in range(1, 13)), dtype=np.int64)
    yearmonth = np.array(sorted(f"{_MONTHS[m]}{y}" for y in range(1992, 1999)
                                for m in range(12)), dtype=object)
    nations = np.array(sorted(SSB_NATIONS), dtype=object)
    return {
        "lo_quantity": np.arange(1, 51, dtype=np.int64),
        "lo_discount": np.arange(0, 11, dtype=np.int64),
        "lo_revenue": revenue,
        "d_year": np.arange(1992, 1999, dtype=np.int64),
        "d_yearmonthnum": ymn,
        "d_yearmonth": yearmonth,
        "d_weeknuminyear": np.arange(1, 54, dtype=np.int64),
        "c_region": np.array(sorted(SSB_REGIONS), dtype=object),
        "c_nation": nations,
        "c_city": _city_pool(),
        "s_region": np.array(sorted(SSB_REGIONS), dtype=object),
        "s_nation": nations,
        "s_city": _city_pool(),
        "p_mfgr": np.array([f"MFGR#{m}" for m in range(1, 6)], dtype=object),
        "p_category": np.array([f"MFGR#{m}{c}" for m in range(1, 6)
                                for c in range(1, 6)], dtype=object),
        "p_brand1": np.array([f"MFGR#{m}{c}{b:02d}" for m in range(1, 6)
                              for c in range(1, 6)
                              for b in range(1, 41)], dtype=object),
    }


def ssb_derivation_tables(pools) -> Dict[str, np.ndarray]:
    """Id-domain derivation maps for the correlated dimensions."""
    nations = pools["c_nation"]
    regions = list(pools["c_region"])
    nation_region = np.array(
        [regions.index(SSB_NATION_REGION[n]) for n in nations],
        dtype=np.int32)
    # ymn id (chronological) → d_yearmonth id (lexicographically sorted pool)
    ym_sorted = list(pools["d_yearmonth"])
    ymn_to_ym = np.array(
        [ym_sorted.index(f"{_MONTHS[(int(v) % 100) - 1]}{int(v) // 100}")
         for v in pools["d_yearmonthnum"]], dtype=np.int32)
    return {"nation_region": nation_region, "ymn_to_ym": ymn_to_ym}


def make_ssb_ids(total_rows: int, seed: int = 0
                 ) -> Tuple[Dict[str, np.ndarray], np.ndarray]:
    """Correlated id-domain SSB table: (ids per column, raw supplycost).

    Base draws are uniform; city→nation→region, ymn→year/yearmonth and
    brand→category→mfgr are derived exactly like the star schema's
    functional dependencies."""
    rng = np.random.default_rng(seed)
    pools = ssb_pools(seed)
    maps = ssb_derivation_tables(pools)
    n = total_rows

    def narrow(arr):
        # minimal id dtype: keeps a 100M-row table host-resident
        from pinot_tpu.segment.loader import min_id_dtype
        m = int(arr.max()) if len(arr) else 0
        return arr.astype(min_id_dtype(m))

    ids: Dict[str, np.ndarray] = {}
    ids["lo_quantity"] = narrow(rng.integers(0, 50, n))
    ids["lo_discount"] = narrow(rng.integers(0, 11, n))
    ids["lo_revenue"] = narrow(
        rng.integers(0, len(pools["lo_revenue"]), n))
    ymn = narrow(rng.integers(0, 84, n))
    ids["d_yearmonthnum"] = ymn
    ids["d_year"] = narrow(ymn // 12)
    ids["d_yearmonth"] = narrow(maps["ymn_to_ym"][ymn])
    ids["d_weeknuminyear"] = narrow(rng.integers(0, 53, n))
    for side in ("c", "s"):
        city = narrow(rng.integers(0, 250, n))
        nation = narrow(city // 10)
        ids[f"{side}_city"] = city
        ids[f"{side}_nation"] = nation
        ids[f"{side}_region"] = narrow(maps["nation_region"][nation])
    brand = narrow(rng.integers(0, 1000, n))
    ids["p_brand1"] = brand
    ids["p_category"] = narrow(brand // 40)
    ids["p_mfgr"] = narrow(brand // 200)
    supplycost = (rng.random(n) * 1e5).round(2)
    return ids, supplycost


def ssb_schema():
    """Schema for the flat lineorder table (creator/loader path)."""
    from pinot_tpu.common.schema import (Schema, dimension, metric)
    fields = []
    for col, dt in SSB_TYPES.items():
        if col.startswith("lo_"):
            fields.append(metric(col, dt))
        else:
            fields.append(dimension(col, dt))
    return Schema("lineorder", fields)


# Star-tree cube configs for the SSB query classes (parity: the reference
# benchmark's star-tree segment variant, contrib/pinot-druid-benchmark
# config/; functional dependencies — city→nation→region, brand→category→
# mfgr — keep the actual group counts far below the dimension product).
# Split orders put each query class's FILTER dims first: cube rows are
# sorted by split order, so the executor's prefix descent narrows to
# contiguous blocks by binary search (the classic split-order guidance —
# most-filtered dimensions first).
SSB_STAR_TREE_CONFIGS = [
    {"dimensionsSplitOrder": ["s_region", "p_brand1", "d_year",
                              "p_category"],
     "metrics": ["lo_revenue"]},                      # Q2.2-2.3
    # Q2.1's EQ pair (s_region, p_category) leads its own cube so the
    # prefix descent lands on tens of rows instead of a region-block
    # residual scan (the chooser ranks by prefix depth, so Q2.2/2.3
    # keep the brand1-leading cube above)
    {"dimensionsSplitOrder": ["s_region", "p_category", "p_brand1",
                              "d_year"],
     "metrics": ["lo_revenue"]},                      # Q2.1
    {"dimensionsSplitOrder": ["c_region", "s_region", "c_nation",
                              "s_nation", "d_year"],
     "metrics": ["lo_revenue"]},                      # Q3.1
    {"dimensionsSplitOrder": ["c_nation", "s_nation", "c_city", "s_city",
                              "d_year"],
     "metrics": ["lo_revenue"]},                      # Q3.2
    {"dimensionsSplitOrder": ["c_city", "s_city", "d_year"],
     "metrics": ["lo_revenue"]},                      # Q3.3
    {"dimensionsSplitOrder": ["c_region", "s_region", "p_mfgr", "d_year",
                              "c_nation"],
     "metrics": ["lo_revenue", "lo_supplycost"]},     # Q4.1
    {"dimensionsSplitOrder": ["c_region", "s_region", "p_mfgr", "d_year",
                              "s_nation", "p_category"],
     "metrics": ["lo_revenue", "lo_supplycost"]},     # Q4.2
    # Q3.4/Q4.3: cubes whose row counts approach the segment's — useless
    # for scans, but the exact-prefix descents (c_city IN / region+
    # nation+category EQ) touch only tens of rows; maxSize raised past
    # the default cap because the scan-payoff heuristic doesn't apply
    {"dimensionsSplitOrder": ["c_city", "s_city", "d_yearmonth",
                              "d_year"],
     "metrics": ["lo_revenue"], "maxSize": 8_000_000},        # Q3.4
    {"dimensionsSplitOrder": ["c_region", "s_nation", "p_category",
                              "d_year", "s_city", "p_brand1"],
     "metrics": ["lo_revenue", "lo_supplycost"],
     "maxSize": 12_000_000},                                  # Q4.3
]


def ssb_table_config(star_tree: bool = False):
    from pinot_tpu.common.table_config import IndexingConfig, TableConfig
    return TableConfig("lineorder", indexing_config=IndexingConfig(
        no_dictionary_columns=sorted(SSB_RAW_COLS),
        star_tree_configs=list(SSB_STAR_TREE_CONFIGS) if star_tree
        else []))


def build_ssb_segment_dirs(base_dir: str, total_rows: int,
                           num_segments: int, seed: int = 0,
                           log=None, star_tree: bool = False,
                           shared_dictionaries: bool = False
                           ) -> Tuple[List[str], Dict, np.ndarray]:
    """Full storage path: rows → SegmentCreator → segment dirs on disk.

    Each segment builds its OWN dictionaries from its own rows — exactly
    what the reference's per-segment SegmentDictionaryCreator produces —
    and the sharded executor's stack-time union remap handles the
    differing id domains. `shared_dictionaries=True` restores the old
    engineered full-domain dictionaries (kept for A/B comparisons).
    Returns (segment_dirs, ids, supplycost) — ids feed the numpy oracle."""
    import os

    from pinot_tpu.segment.creator import SegmentCreator

    pools = ssb_pools(seed)
    ids, supplycost = make_ssb_ids(total_rows, seed)
    schema = ssb_schema()
    config = ssb_table_config(star_tree=star_tree)
    per = total_rows // num_segments
    dirs = []
    fixed = {c: pools[c] for c in SSB_TYPES if c not in SSB_RAW_COLS} \
        if shared_dictionaries else None
    for i in range(num_segments):
        lo = i * per
        hi = (i + 1) * per if i < num_segments - 1 else total_rows
        from pinot_tpu.segment.creator import DictionaryEncodedColumn
        cols = {}
        for c in SSB_TYPES:
            if c in SSB_RAW_COLS:
                cols[c] = supplycost[lo:hi]
            else:
                # dictionary-encoded columnar input: the creator still
                # builds a PER-SEGMENT dictionary of only this slice's
                # present values (byte-identical segments to the decoded
                # path) without hashing row-scale strings
                cols[c] = DictionaryEncodedColumn(pools[c], ids[c][lo:hi])
        d = os.path.join(base_dir, f"ssb_{i}")
        SegmentCreator(schema, config, segment_name=f"ssb_{i}",
                       fixed_dictionaries=fixed).build(cols, d)
        dirs.append(d)
        if log:
            log(f"datagen: built segment {i + 1}/{num_segments} "
                f"({hi - lo} rows) via SegmentCreator")
    return dirs, ids, supplycost


# ---------------------------------------------------------------------------
# Star-schema JOIN tables: a `part` dim table × a `lineorderj` fact table
# (the normalized shape the multi-stage join engine serves — the flat SSB
# table above is the 2019-era denormalized workaround).
# ---------------------------------------------------------------------------


def part_dim_schema():
    from pinot_tpu.common.schema import Schema, dimension
    return Schema("part", [
        dimension("p_partkey", DataType.INT),
        dimension("p_mfgr", DataType.STRING),
        dimension("p_category", DataType.STRING),
        dimension("p_brand1", DataType.STRING),
    ])


def fact_join_schema():
    from pinot_tpu.common.schema import Schema, dimension, metric
    return Schema("lineorderj", [
        dimension("lo_partkey", DataType.INT),
        dimension("d_year", DataType.INT),
        metric("lo_quantity", DataType.INT),
        metric("lo_revenue", DataType.LONG),
    ])


def join_table_configs(num_partitions: int = 0):
    """(fact config, dim config); `num_partitions` > 0 partitions BOTH
    tables on their join keys (Modulo) — the co-partitioned dispatch
    shape."""
    from pinot_tpu.common.table_config import IndexingConfig, TableConfig
    part_cfg = {"functionName": "Modulo",
                "numPartitions": num_partitions}
    fact_idx = IndexingConfig(
        segment_partition_config={"lo_partkey": dict(part_cfg)}
        if num_partitions else {})
    dim_idx = IndexingConfig(
        segment_partition_config={"p_partkey": dict(part_cfg)}
        if num_partitions else {})
    return (TableConfig("lineorderj", indexing_config=fact_idx),
            TableConfig("part", indexing_config=dim_idx))


def make_join_rows(fact_rows: int, dim_rows: int = 800, seed: int = 0,
                   miss_rate: float = 0.1) -> Tuple[Dict, Dict]:
    """(dim columns, fact columns) as plain arrays (oracle-friendly).

    Dim keys are a NON-CONTIGUOUS sorted sample (probes must not
    degenerate to offsets) with SSB-style brand→category→mfgr
    functional dependencies; `miss_rate` of fact keys reference no dim
    row (inner-join drops them).
    """
    rng = np.random.default_rng(seed + 40_009)
    keys = np.sort(rng.choice(np.arange(1, dim_rows * 7, dtype=np.int64),
                              size=dim_rows, replace=False))
    brand_id = rng.integers(0, 1000, dim_rows)
    dim = {
        "p_partkey": keys.astype(np.int32),
        "p_brand1": np.array(
            [f"MFGR#{b // 200 + 1}{(b // 40) % 5 + 1}{b % 40 + 1:02d}"
             for b in brand_id], dtype=object),
        "p_category": np.array(
            [f"MFGR#{b // 200 + 1}{(b // 40) % 5 + 1}" for b in brand_id],
            dtype=object),
        "p_mfgr": np.array([f"MFGR#{b // 200 + 1}" for b in brand_id],
                           dtype=object),
    }
    n = fact_rows
    fact_key = keys[rng.integers(0, dim_rows, n)].astype(np.int64)
    miss = rng.random(n) < miss_rate
    # miss keys: values guaranteed absent from the dim key set
    fact_key[miss] = -fact_key[miss] - 1
    fact = {
        "lo_partkey": fact_key.astype(np.int32),
        "d_year": rng.integers(1992, 1999, n).astype(np.int32),
        "lo_quantity": rng.integers(1, 51, n).astype(np.int32),
        "lo_revenue": (rng.integers(100, 10_000, n) * 100).astype(
            np.int64),
    }
    return dim, fact


def build_join_table_dirs(base_dir: str, fact_rows: int,
                          num_fact_segments: int, dim_rows: int = 800,
                          num_dim_segments: int = 1, seed: int = 0,
                          num_partitions: int = 0
                          ) -> Tuple[List[str], List[str], Dict, Dict]:
    """Segment dirs for the join tables via the real storage path.

    With `num_partitions` > 0, rows are partition-aligned: each segment
    holds exactly one Modulo partition's rows (per-segment partition
    metadata becomes discriminating, the co-partitioned exchange shape).
    Returns (fact_dirs, dim_dirs, dim columns, fact columns).
    """
    import os

    from pinot_tpu.segment.creator import SegmentCreator

    dim, fact = make_join_rows(fact_rows, dim_rows, seed)
    fact_cfg, dim_cfg = join_table_configs(num_partitions)

    def build(schema, cfg, cols, key_col, n_segs, prefix):
        n = len(cols[key_col])
        if num_partitions:
            pids = np.abs(cols[key_col].astype(np.int64)) % num_partitions
            slices = [np.nonzero(pids == p)[0]
                      for p in range(num_partitions)]
        else:
            per = -(-n // n_segs)
            slices = [np.arange(i * per, min((i + 1) * per, n))
                      for i in range(n_segs)]
        dirs = []
        for i, rows in enumerate(slices):
            if not len(rows):
                continue
            d = os.path.join(base_dir, f"{prefix}_{i}")
            sub = {c: (v[rows] if isinstance(v, np.ndarray)
                       else [v[j] for j in rows])
                   for c, v in cols.items()}
            SegmentCreator(schema, cfg,
                           segment_name=f"{prefix}_{i}").build(sub, d)
            dirs.append(d)
        return dirs

    fact_dirs = build(fact_join_schema(), fact_cfg, fact, "lo_partkey",
                      num_fact_segments, "factj")
    dim_dirs = build(part_dim_schema(), dim_cfg, dim, "p_partkey",
                     num_dim_segments, "partd")
    return fact_dirs, dim_dirs, dim, fact


def join_oracle(dim: Dict, fact: Dict, dim_filter=None,
                group_cols: Sequence[str] = (),
                agg: str = "sum_revenue") -> Dict:
    """Independent numpy oracle for the join smoke/bench parity gates:
    inner-join fact×dim on the part key, optional dim-side row mask
    (callable dim→bool [D]), group by (qualified) columns, aggregate
    SUM(lo_revenue)+COUNT."""
    keys = dim["p_partkey"].astype(np.int64)
    order = np.argsort(keys, kind="stable")
    skeys = keys[order]
    fk = fact["lo_partkey"].astype(np.int64)
    pos = np.clip(np.searchsorted(skeys, fk), 0, max(len(skeys) - 1, 0))
    hit = skeys[pos] == fk if len(skeys) else np.zeros(len(fk), bool)
    dimrow = order[pos]
    if dim_filter is not None:
        hit = hit & dim_filter(dim)[dimrow]
    rows = np.nonzero(hit)[0]
    out: Dict = {"count": int(len(rows)),
                 "sum_revenue": int(fact["lo_revenue"][rows].sum())}
    if group_cols:
        lanes = []
        for c in group_cols:
            if c.startswith("part."):
                lanes.append(dim[c[5:]][dimrow[rows]])
            else:
                lanes.append(fact[c.split(".", 1)[-1]][rows])
        keyed: Dict[tuple, list] = {}
        for i in range(len(rows)):
            k = tuple(lane[i] for lane in lanes)
            e = keyed.setdefault(k, [0, 0])
            e[0] += int(fact["lo_revenue"][rows[i]])
            e[1] += 1
        out["groups"] = {k: tuple(v) for k, v in keyed.items()}
    return out


class SsbTable:
    """Generated table: segments + id-level host arrays for oracle math.

    Oracle checks run on the int32 id arrays (decode via `pools`) so 100M-row
    tables never materialize 100M python-object string columns host-side.
    """

    def __init__(self, segments, pools, ids, supplycost):
        self.segments = segments
        self.pools = pools            # col → sorted values (the dictionary)
        self.ids = ids                # col → int32 [total_rows]
        self.supplycost = supplycost  # raw float64 [total_rows]

    def id_of(self, col: str, value) -> int:
        i = int(np.searchsorted(self.pools[col], value))
        assert self.pools[col][i] == value
        return i

    def decoded(self, col: str) -> np.ndarray:
        if col == "lo_supplycost":
            return self.supplycost
        return self.pools[col][self.ids[col]]


def make_ssb_device_stack(total_rows: int, num_segments: int, mesh,
                          seed: int = 0):
    """Device-generated stacked SSB lanes for large-scale benchmarking.

    Building a huge synthetic table host-side is slow, so the column lanes
    are synthesized directly in HBM with jax PRNG — same pools/cardinalities/
    distributions as make_ssb_segments, different values. Returns
    (lanes, num_docs_sharded, plan_table) where `lanes` maps
    "col.ids"/"col.parts"/"col.raw" to [S, P] device arrays sharded over the
    mesh's `seg` axis, and `plan_table` is a tiny host SsbTable with the
    same dictionaries for building plans/params.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from pinot_tpu.parallel.sharded import SEG_AXIS
    from pinot_tpu.segment.loader import padded_size

    pools = ssb_pools(seed)
    maps = ssb_derivation_tables(pools)
    per = total_rows // num_segments
    padded = padded_size(per)
    shard = NamedSharding(mesh, P(SEG_AXIS))
    n_dev = mesh.devices.size
    s_total = -(-num_segments // n_dev) * n_dev

    key = jax.random.PRNGKey(seed)
    lanes = {}

    def lane_dtype(card):
        # narrow id lanes, matching the loader's storage-path ladder
        from pinot_tpu.segment.loader import min_id_dtype
        return jnp.dtype(min_id_dtype(card))

    def uniform(card):
        nonlocal key
        key, sub = jax.random.split(key)
        arr = jax.random.randint(sub, (s_total, padded), 0, card,
                                 dtype=jnp.int32)
        return jax.device_put(arr.astype(lane_dtype(card)), shard)

    # base uniforms
    for c in ("lo_quantity", "lo_discount", "lo_revenue",
              "d_weeknuminyear"):
        lanes[f"{c}.ids"] = uniform(len(pools[c]))
    ymn = uniform(84)
    lanes["d_yearmonthnum.ids"] = ymn
    # derived dimensions: the same functional dependencies as the host
    # generator, applied with device gathers over tiny mapping tables
    ym_map = jnp.asarray(maps["ymn_to_ym"].astype(np.int8))
    region_map = jnp.asarray(maps["nation_region"].astype(np.int8))
    derive = jax.jit(lambda f, x: f(x), static_argnums=0,
                     out_shardings=shard)
    lanes["d_year.ids"] = derive(lambda y: (y // 12).astype(jnp.int8), ymn)
    lanes["d_yearmonth.ids"] = derive(lambda y: ym_map[y.astype(jnp.int32)],
                                      ymn)
    for side in ("c", "s"):
        city = uniform(250)
        lanes[f"{side}_city.ids"] = city
        nation = derive(lambda x: (x // 10).astype(jnp.int8), city)
        lanes[f"{side}_nation.ids"] = nation
        lanes[f"{side}_region.ids"] = derive(
            lambda x: region_map[x.astype(jnp.int32)], nation)
    brand = uniform(1000)
    lanes["p_brand1.ids"] = brand
    lanes["p_category.ids"] = derive(lambda b: (b // 40).astype(jnp.int8),
                                     brand)
    lanes["p_mfgr.ids"] = derive(lambda b: (b // 200).astype(jnp.int8),
                                 brand)

    # bit-sliced part lanes for the integer SUM metric (lo_revenue)
    plan_table = make_ssb_segments(max(BLOCK_ROWS, 2 * padded_size(1)),
                                   1, seed=seed)
    ds = plan_table.segments[0].data_source("lo_revenue")
    n_parts, _ = ds.int_part_info()
    vals = np.asarray(ds.dictionary.values, dtype=np.int64)
    off = vals - int(vals[0])
    table = np.stack([(off >> (7 * k)) & 0x7F
                      for k in range(n_parts)]).astype(np.int8)
    table_dev = jnp.asarray(table)
    rev_ids = lanes["lo_revenue.ids"]
    parts = jax.jit(
        lambda ids: jnp.moveaxis(table_dev[:, ids], 1, 0),
        out_shardings=shard)(rev_ids)
    lanes["lo_revenue.parts"] = parts

    key, sub = jax.random.split(key)
    raw = jax.random.uniform(sub, (s_total, padded), jnp.float32) * 1e5
    lanes["lo_supplycost.raw"] = jax.device_put(raw, shard)

    num_docs = np.zeros(s_total, np.int32)
    num_docs[:num_segments] = per
    num_docs_dev = jax.device_put(num_docs, shard)
    return lanes, num_docs_dev, plan_table, padded


BLOCK_ROWS = 16384


def make_ssb_segments(total_rows: int, num_segments: int, seed: int = 0
                      ) -> SsbTable:
    """num_segments equal slices of an SSB table with GLOBAL dictionaries.

    DictIds are generated directly against pre-sorted pools (no
    unique/searchsorted pass over the full table — 100M rows materialize in
    seconds). Same correlated distributions as the creator path
    (build_ssb_segment_dirs), no file round-trip.
    """
    pools = ssb_pools(seed)
    ids, supplycost = make_ssb_ids(total_rows, seed)

    per = total_rows // num_segments
    segments = []
    for i in range(num_segments):
        lo, hi = i * per, (i + 1) * per if i < num_segments - 1 else total_rows
        dict_part = {c: (SSB_TYPES[c], pools[c], ids[c][lo:hi])
                     for c in pools}
        raw_part = {"lo_supplycost": (DataType.DOUBLE, supplycost[lo:hi])}
        segments.append(make_segment_from_arrays(
            f"ssb_{i}", "lineorder", dict_part, raw_part))
    return SsbTable(segments, pools, ids, supplycost)
