"""Immutable segment loader: segment dir → host arrays → HBM device arrays.

Parity: pinot-core/.../indexsegment/immutable/{ImmutableSegmentImpl,
ImmutableSegmentLoader}.java + core/common/DataSource.java. Where the
reference mmaps per-index files into PinotDataBuffer (off-heap memory,
core/segment/memory/PinotDataBuffer.java:54), the TPU build's "native memory"
is HBM: each column's dictId lanes and numeric dictionary are pushed to device
once at load, padded to a lane-friendly block multiple so every query kernel
sees static shapes (SURVEY.md §7 — padded power-of-two blocks instead of mmap).
"""
from __future__ import annotations

import os
import threading
import weakref
from typing import Dict, Optional

import numpy as np

from pinot_tpu.common.datatype import DataType
from pinot_tpu.obs import residency
from pinot_tpu.segment import format as fmt
from pinot_tpu.segment.bloom import BloomFilter
from pinot_tpu.segment.dictionary import Dictionary
from pinot_tpu.segment.fwd import (mv_to_padded, read_mv_fwd, read_raw_fwd,
                                   read_sorted_fwd, read_sv_fwd,
                                   read_vec_fwd)
from pinot_tpu.segment.inverted import InvertedIndexReader
from pinot_tpu.segment.metadata import ColumnMetadata, SegmentMetadata

# Padding block == the kernel row-block so blocked reductions/matmuls tile
# evenly; 8192 = 8 x (8 x 128) VPU tiles.
from pinot_tpu.ops.kernels import BLOCK as PAD_BLOCK  # noqa: E402


def padded_size(n: int, block: int = PAD_BLOCK) -> int:
    return max(block, ((n + block - 1) // block) * block)


def min_id_dtype(max_value: int) -> np.dtype:
    """Smallest signed dtype holding ids in [0, max_value] — the single
    source of truth for id-lane narrowing (~4x less HBM/upload/filter
    bandwidth on low-cardinality columns). Kernels that mix ids with
    card-scale sentinels or bit-ops promote with .astype(int32) at the
    consumption site, sized to exactly these thresholds."""
    return np.dtype(np.int8 if max_value <= 127 else
                    np.int16 if max_value <= 32767 else np.int32)


def pad_dict_values(values: np.ndarray, np_dtype) -> np.ndarray:
    """Dictionary value table padded to the kernels' pow2 cardinality
    bucket; padding repeats the last value (kernels mask it out). The
    single convention shared by per-segment and union-dictionary lanes."""
    from pinot_tpu.ops.kernels import pow2_bucket
    if len(values) == 0:
        values = np.zeros(1, np_dtype)
    card_pad = pow2_bucket(len(values) + 1)
    return np.concatenate(
        [values, np.full(card_pad - len(values), values[-1], values.dtype)])


def vec_dim_pad(dim: int) -> int:
    """Pow2-bucketed vector width: the tree-dot kernels halve the dim
    axis pairwise, and one bucket per pow2 keeps the jit cache small.
    Padding lanes are zero — an exact no-op in every dot/norm sum."""
    from pinot_tpu.ops.kernels import pow2_bucket
    return pow2_bucket(max(dim, 1), floor=1)


def int_part_info_for(values: np.ndarray) -> tuple:
    """(n_parts, min_value) for the 7-bit bit-sliced integer sum encoding
    of a sorted integer dictionary (value = min + sum_k part_k << 7k)."""
    vals = np.asarray(values, dtype=np.int64)
    min_v = int(vals[0]) if len(vals) else 0
    max_off = (int(vals[-1]) - min_v) if len(vals) else 0
    n_parts = -(-max(1, max_off.bit_length()) // 7)
    return (n_parts, min_v)


def segment_host_bytes(seg) -> int:
    """Host-side column footprint of a (loaded or mutable) segment —
    the single accounting used by the server size/debug endpoints and
    the RealtimeProvisioningHelper. Object string arrays report their
    actual encoded payload, not 8-byte pointers."""
    def _arr_bytes(arr) -> int:
        if arr is None or not hasattr(arr, "nbytes"):
            return 0
        if getattr(arr, "dtype", None) is not None and \
                arr.dtype.kind == "O":
            return int(sum(len(str(v).encode("utf-8", "replace"))
                           for v in arr.ravel()))
        return int(arr.nbytes)

    total = 0
    for name in seg.column_names:
        ds = seg.data_source(name)
        # chunked raw columns: account the resident COMPRESSED buffer
        # without triggering the lazy full decode (the size endpoint
        # must not materialize gigabyte object arrays)
        chunks = getattr(ds, "raw_chunks", None)
        raw = getattr(ds, "_raw_values", None) \
            if chunks is not None else getattr(ds, "raw_values", None)
        if chunks is not None and raw is None:
            total += len(chunks._data)
        for arr in (getattr(ds, "dict_ids", None), raw,
                    getattr(ds, "mv_dict_ids", None),
                    getattr(ds, "vec_values", None)):
            total += _arr_bytes(arr)
        vals = getattr(getattr(ds, "dictionary", None), "values", None)
        total += _arr_bytes(vals)
    return total


def hll_tables_padded(values: np.ndarray) -> tuple:
    """(idx, rank) int32 [card_pad] HLL tables for a dictionary, padded
    to the kernels' pow2 cardinality bucket with (0, 0) — rank 0 is the
    register-max identity, so padding ids can never perturb a sketch."""
    from pinot_tpu.common.sketches import hll_tables
    from pinot_tpu.ops.kernels import pow2_bucket
    idx, rank = hll_tables(np.asarray(values))
    card_pad = pow2_bucket(len(idx) + 1)
    out_i = np.zeros(card_pad, np.int32)
    out_r = np.zeros(card_pad, np.int32)
    out_i[: len(idx)] = idx
    out_r[: len(rank)] = rank
    return out_i, out_r


def int_part_table(values: np.ndarray, n_parts: int,
                   min_v: int) -> np.ndarray:
    """[n_parts, card + 1] int8 plane table (last column = all-zero pad
    sentinel for id == cardinality row padding)."""
    off = np.asarray(values, dtype=np.int64) - min_v
    table = np.stack([(off >> (7 * k)) & 0x7F
                      for k in range(n_parts)]).astype(np.int8)
    return np.concatenate([table, np.zeros((n_parts, 1), np.int8)], axis=1)


class DataSource:
    """Column access for operators.

    Parity: core/common/DataSource.java + BlockValSet — exposes dictId forward
    index, dictionary, optional inverted/bloom index and column metadata.
    """

    @property
    def raw_values(self) -> Optional[np.ndarray]:
        if self._raw_values is None and self.raw_chunks is not None:
            with self._lane_lock:
                if self._raw_values is None:
                    self._raw_values = self.raw_chunks.decode_all()
        return self._raw_values

    @raw_values.setter
    def raw_values(self, arr) -> None:
        with self._lane_lock:
            self._raw_values = arr

    def __init__(self, metadata: ColumnMetadata, segment: "ImmutableSegment"):
        self.metadata = metadata
        self._segment = segment
        # one lock for every host-lane writer: lazy raw decode, lazy
        # HLL tables, and the residency tier's release/adopt swaps
        self._lane_lock = threading.Lock()
        self.dictionary: Optional[Dictionary] = None
        # host arrays
        self.dict_ids: Optional[np.ndarray] = None        # int32 [num_docs]
        self._raw_values: Optional[np.ndarray] = None     # no-dict columns
        # chunked raw reader (VarByteChunk parity): set for string/bytes
        # no-dictionary columns; point lookups decompress one chunk,
        # raw_values materializes lazily for scan paths
        self.raw_chunks = None
        self.mv_dict_ids: Optional[np.ndarray] = None     # int32 [docs, width]
        self.vec_values: Optional[np.ndarray] = None      # f32 [docs, dim]
        # IVF ANN index (VECTOR columns with a built index only)
        self.ivf_centroids: Optional[np.ndarray] = None   # f32 [C, dim]
        self.ivf_assignments: Optional[np.ndarray] = None  # i32 [docs]
        self.ivf_meta: Optional[dict] = None
        self.sorted_ranges: Optional[np.ndarray] = None   # [card, 2]
        self.inverted_index: Optional[InvertedIndexReader] = None
        self.bloom_filter: Optional[BloomFilter] = None
        # device arrays (lazy)
        self._dev: Dict[str, object] = {}
        self._dev_finalizer = None           # set on first device upload
        self._part_info: Optional[tuple] = None
        self._hll_tables: Optional[tuple] = None

    # -- device access -----------------------------------------------------
    #: lane kind (the tag of `plan.needed_cols` and of `host_operand`) →
    #: (lane-cache key, residency ledger kind): a new kind is one row
    #: here and its layout in `host_operand`
    LANES = {
        "ids": ("dict_ids", "scan"), "mv": ("mv_dict_ids", "scan"),
        "vals": ("dict_values", "scan"), "raw": ("raw_values", "scan"),
        "parts": ("part_lanes", "scan"), "vlane": ("value_lane", "scan"),
        "vec": ("vec_values", "vector"), "ivfa": ("ivf_assign", "vector"),
        "ivfc": ("ivf_centroids", "vector"),
        "ivfv": ("ivf_valid", "vector"),
        "hllidx": ("hll_idx", "hll"), "hllrank": ("hll_rank", "hll"),
    }

    def device_dict_ids(self):
        """Padded int32 dictIds on device; padding = cardinality (invalid)."""
        return self.device_lane("ids")

    def device_mv_dict_ids(self):
        return self.device_lane("mv")

    def device_dict_values(self):
        """Numeric dictionary values on device (f64/i64 host width preserved
        when x64 is on; jax downcasts otherwise). Padded to the same pow2
        bucket the kernels use for cardinality so compiled executables are
        shared across segments with similar dictionaries; padding slots
        repeat the last value (kernels mask them out)."""
        return self.device_lane("vals")

    def device_raw_values(self):
        return self.device_lane("raw")

    def device_part_lanes(self):
        """Bit-sliced int8 part lanes [n_parts, P] for exact integer sums
        (see kernels.py 'TPU reduction strategy')."""
        return self.device_lane("parts")

    def device_value_lane(self):
        """Decoded dictionary-value lane [P] for float sums."""
        return self.device_lane("vlane")

    def device_vec_values(self):
        """Padded [P, dim_pad] float32 embedding block on device; row
        padding is zeros (masked by the kernel's validity iota), dim
        padding is zeros (an exact no-op in the tree-dot sums)."""
        return self.device_lane("vec")

    def device_ivf_assign(self):
        """Narrow per-row coarse-cell lane [P] (padding rows carry the
        never-probed sentinel id numCentroids)."""
        return self.device_lane("ivfa")

    def device_ivf_centroids(self):
        """Zero-padded codebook [C_pad, dim_pad] f32."""
        return self.device_lane("ivfc")

    def device_ivf_valid(self):
        """Centroid liveness [C_pad] bool (live count rides as a lane,
        not a param, so sharded plans stay shareable)."""
        return self.device_lane("ivfv")

    def device_hll_idx(self):
        """Per-dictId HLL register-index table [card_pad] int32 — built
        once from the dictionary values with the SAME hashing the host
        HyperLogLog uses (sketches.hll_tables), so the device register
        kernel is bit-identical to the host sketch by construction."""
        return self.device_lane("hllidx")

    def device_hll_rank(self):
        """Per-dictId HLL rank table [card_pad] int32 (padding rank 0 =
        the register-max merge identity)."""
        return self.device_lane("hllrank")

    def int_part_info(self) -> tuple:
        """(n_parts, min_value) for the bit-sliced integer sum encoding.

        Values are offset by min_value (so lanes are non-negative) and split
        into 7-bit slices: value = min_value + sum_k part_k << (7k).
        """
        if self._part_info is None:
            with self._lane_lock:
                if self._part_info is None:
                    self._part_info = int_part_info_for(
                        self.dictionary.values)
        return self._part_info

    def host_operand(self, kind: str) -> np.ndarray:
        """Padded host array for a lane kind ('ids'|'vals'|'raw'|'mv') —
        identical layout to the device arrays; used by the sharded executor
        to stack homogeneous segments onto a leading mesh axis."""
        if kind == "ids":
            return self._pad_ids(self.dict_ids)
        if kind == "vals":
            return pad_dict_values(self.dictionary.values,
                                   self.metadata.data_type.np_dtype)
        if kind == "raw":
            arr = self.raw_values
            p = padded_size(len(arr))
            out = np.zeros(p, dtype=arr.dtype)
            out[: len(arr)] = arr
            return out
        if kind == "mv":
            arr = self.mv_dict_ids
            p = padded_size(arr.shape[0])
            out = np.full((p, arr.shape[1]), self.metadata.cardinality,
                          dtype=np.int32)
            out[: arr.shape[0]] = arr
            return out
        if kind == "parts":
            n_parts, min_v = self.int_part_info()
            table = int_part_table(self.dictionary.values, n_parts, min_v)
            return table[:, self.host_operand("ids")]
        if kind == "vlane":
            vals = np.asarray(self.dictionary.values, dtype=np.float64)
            vals = np.concatenate([vals, [0.0]])
            return vals[self.host_operand("ids")]
        if kind == "vec":
            mat = self.vec_values
            p = padded_size(len(mat))
            dp = vec_dim_pad(self.metadata.vector_dimension)
            out = np.zeros((p, dp), dtype=np.float32)
            out[: len(mat), : mat.shape[1]] = mat
            return out
        if kind in ("ivfa", "ivfc", "ivfv"):
            from pinot_tpu.index import ivf
            c = int(self.ivf_centroids.shape[0])
            if kind == "ivfa":
                return ivf.assignment_lane(
                    self.ivf_assignments, c,
                    padded_size(len(self.ivf_assignments)))
            if kind == "ivfc":
                return ivf.centroid_lane(self.ivf_centroids)
            return ivf.validity_lane(self.ivf_assignments, c)
        if kind in ("hllidx", "hllrank"):
            if self._hll_tables is None:
                with self._lane_lock:
                    if self._hll_tables is None:
                        self._hll_tables = hll_tables_padded(
                            self.dictionary.values)
            return self._hll_tables[0 if kind == "hllidx" else 1]
        raise ValueError(kind)

    def _pad_ids(self, ids: np.ndarray) -> np.ndarray:
        p = padded_size(len(ids))
        card = self.metadata.cardinality     # padding id == cardinality
        out = np.full(p, card, dtype=min_id_dtype(card))
        out[: len(ids)] = ids
        return out

    def device_lane(self, kind: str):
        """The device lane of `kind` (a row of `LANES`), from the lane
        cache: a hit is one look-up and does no host work. Only a miss
        builds the padded host operand of `kind` (needed once, for the
        upload; never kept)."""
        key, ledger_kind = self.LANES[kind]
        lane = self._dev.get(key)
        if lane is not None:
            residency.mark_lane_cache(hit=True)
            return lane
        # built OUTSIDE _lane_lock: raw_values, int_part_info and the
        # HLL tables take that same non-reentrant lock. Published under
        # it, first published wins: a losing racer drops its operand
        # and neither uploads nor registers a duplicate
        host_array = self.host_operand(kind)
        residency.mark_lane_cache(hit=False)
        seg = self._segment
        with self._lane_lock:
            if self._dev_finalizer is None:
                # superseded frozen snapshots are freed by GC, not
                # destroy() — the finalizer keeps the ledger truthful
                # on that path too (release_prefix is idempotent)
                self._dev_finalizer = weakref.finalize(
                    self, residency.LEDGER.release_prefix,
                    f"ds:{id(self)}:")
            if key not in self._dev:
                self._dev[key] = residency.ledgered_asarray(
                    host_array,
                    owner=f"ds:{id(self)}:{key}",
                    table=seg.metadata.table_name
                    if seg is not None else "",
                    segment=seg.segment_name
                    if seg is not None else "",
                    kind=ledger_kind)
            return self._dev[key]

    def release_device(self) -> None:
        """Drop every device lane and its ledger entries (segment drop/
        eviction path; re-upload after this re-registers)."""
        self._dev.clear()
        residency.LEDGER.release_prefix(f"ds:{id(self)}:")

    def device_bytes_estimate(self) -> int:
        """Bytes `warm_device` would pin in HBM for this column, from
        metadata alone (no array is materialized or uploaded) — the
        residency manager's admission charge for a not-yet-resident
        segment."""
        from pinot_tpu.ops.kernels import pow2_bucket
        cm = self.metadata
        n = cm.total_number_of_entries
        if self.dict_ids is not None or \
                (cm.has_dictionary and cm.single_value):
            total = padded_size(n) * min_id_dtype(cm.cardinality).itemsize
            if cm.data_type.is_numeric:
                total += pow2_bucket(cm.cardinality + 1) * \
                    cm.data_type.np_dtype.itemsize
            return total
        if self.vec_values is not None:
            rows = len(self.vec_values)
            total = padded_size(rows) * vec_dim_pad(
                cm.vector_dimension) * 4
            if self.ivf_centroids is not None:
                from pinot_tpu.index import ivf
                c = int(self.ivf_centroids.shape[0])
                total += padded_size(rows) * \
                    min_id_dtype(c).itemsize             # assignment lane
                total += ivf.pad_centroids(c) * \
                    (vec_dim_pad(cm.vector_dimension) * 4 + 1)  # cb + valid
            return total
        if self.raw_chunks is not None:
            return 0              # no device lane for chunked raw
        if self.raw_values is not None:
            return padded_size(len(self.raw_values)) * \
                self.raw_values.dtype.itemsize \
                if self.raw_values.dtype.kind != "O" else 0
        if self.mv_dict_ids is not None:
            return padded_size(self.mv_dict_ids.shape[0]) * \
                self.mv_dict_ids.shape[1] * 4
        if cm.has_dictionary and not cm.single_value:
            return padded_size(n) * 4
        return 0

    def release_host(self) -> None:
        """Drop the fat host-side row payloads (forward indexes, raw
        values, embeddings) for the disk residency tier. Dictionaries,
        inverted/bloom indexes and chunked-raw readers stay — they are
        dictionary-scale (or already disk-backed) and the pruner still
        needs them. `adopt_host` restores the dropped arrays from a
        freshly loaded copy of the same artifact."""
        with self._lane_lock:
            self.dict_ids = None
            self._raw_values = None
            self.mv_dict_ids = None
            self.vec_values = None
            self.ivf_assignments = None    # row-scale; codebook stays
            self._hll_tables = None

    def adopt_host(self, fresh: "DataSource") -> None:
        """Rebind host row payloads from a freshly loaded DataSource of
        the same column (disk-tier reload). Object identity of `self`
        is preserved so data-manager refs, sharded caches and in-flight
        plans keyed on the live object stay valid."""
        with self._lane_lock:
            self.dict_ids = fresh.dict_ids
            self._raw_values = fresh._raw_values
            self.mv_dict_ids = fresh.mv_dict_ids
            self.vec_values = fresh.vec_values
            self.ivf_assignments = fresh.ivf_assignments
            if fresh.raw_chunks is not None:
                self.raw_chunks = fresh.raw_chunks


class ImmutableSegment:
    """A loaded, queryable immutable segment.

    Parity: core/indexsegment/immutable/ImmutableSegmentImpl.java.
    """

    def __init__(self, metadata: SegmentMetadata,
                 data_sources: Dict[str, DataSource]):
        self.metadata = metadata
        self._data_sources = data_sources
        for ds in data_sources.values():
            if ds._segment is None:   # loader builds DataSource(cm, None)
                ds._segment = self    # backref names ledger entries
        self.star_trees = []     # pre-aggregated cubes (startree/cube.py)
        # primary-key upsert liveness bitmap (realtime/upsert.py); None
        # for non-upsert tables. Attached by the realtime data manager
        # when the committed segment swaps in / cold-start loads.
        self.valid_doc_ids = None
        self._valid_dev = None   # (bitmap version, padded device lane)
        self._valid_finalizer = None         # set on first vdoc upload

    @property
    def segment_name(self) -> str:
        return self.metadata.segment_name

    @property
    def num_docs(self) -> int:
        return self.metadata.total_docs

    @property
    def padded_docs(self) -> int:
        return padded_size(self.metadata.total_docs)

    @property
    def column_names(self):
        return list(self._data_sources.keys())

    #: parity: core/segment/virtualcolumn/VirtualColumnProviderFactory —
    #: $docId / $segmentName / $hostName are synthesized on first access
    VIRTUAL_COLUMNS = ("$docId", "$segmentName", "$hostName")

    def data_source(self, column: str) -> DataSource:
        try:
            return self._data_sources[column]
        except KeyError:
            if column in self.VIRTUAL_COLUMNS:
                ds = self._make_virtual(column)
                self._data_sources[column] = ds
                return ds
            raise KeyError(f"column '{column}' not in segment "
                           f"'{self.segment_name}'")

    def has_column(self, column: str) -> bool:
        return column in self._data_sources or \
            column in self.VIRTUAL_COLUMNS

    def _make_virtual(self, column: str) -> DataSource:
        from pinot_tpu.common.datatype import DataType
        n = self.num_docs
        if column == "$docId":
            cm = ColumnMetadata(
                name=column, data_type=DataType.INT, cardinality=n,
                bits_per_element=32, has_dictionary=False,
                min_value=0, max_value=max(n - 1, 0),
                total_number_of_entries=n)
            ds = DataSource(cm, self)
            ds.raw_values = np.arange(n, dtype=np.int32)
            return ds
        if column == "$segmentName":
            value = self.segment_name
        else:
            import socket
            value = socket.gethostname()
        cm = ColumnMetadata(
            name=column, data_type=DataType.STRING, cardinality=1,
            bits_per_element=1, sorted=True, has_dictionary=True,
            min_value=value, max_value=value, total_number_of_entries=n)
        ds = DataSource(cm, self)
        ds.dictionary = Dictionary(DataType.STRING,
                                   np.array([value], dtype=object))
        ds.dict_ids = np.zeros(n, dtype=np.int32)
        return ds

    def device_valid_lane(self):
        """Padded bool liveness lane (upsert validDocIds) on device,
        re-uploaded only when the bitmap version changes. Rows past
        num_docs pad False; the kernel ANDs with its row-validity iota
        anyway."""
        vd = self.valid_doc_ids
        ver = vd.version
        cached = self._valid_dev
        if cached is None or cached[0] != ver:
            host = np.zeros(self.padded_docs, dtype=bool)
            host[: self.num_docs] = vd.valid_mask(0, self.num_docs)
            if self._valid_finalizer is None:
                self._valid_finalizer = weakref.finalize(
                    self, residency.LEDGER.release,
                    f"seg:{id(self)}:vdoc")
            lane = residency.ledgered_asarray(
                host, owner=f"seg:{id(self)}:vdoc",
                table=self.metadata.table_name or "",
                segment=self.segment_name, kind="vdoc")
            cached = (ver, lane)
            self._valid_dev = cached  # tpulint: disable=concurrency -- benign racy single-slot cache: concurrent queries at worst duplicate one upload; tuple publish is atomic
        return cached[1]

    def warm_device(self, columns=None) -> None:
        """Eagerly push forward indexes + dictionaries to HBM."""
        for name in (columns or self.column_names):
            ds = self.data_source(name)
            if ds.dict_ids is not None:
                ds.device_dict_ids()
                if ds.metadata.data_type.is_numeric:
                    ds.device_dict_values()
            elif getattr(ds, "vec_values", None) is not None:
                ds.device_vec_values()
            elif ds.raw_chunks is not None:
                pass      # no device lane for string/bytes raw columns
            elif ds.raw_values is not None:
                ds.device_raw_values()
            elif ds.mv_dict_ids is not None:
                ds.device_mv_dict_ids()

    def device_bytes_estimate(self) -> int:
        """Bytes a full `warm_device` (plus the upsert vdoc lane, when
        one exists) would pin in HBM — the residency manager's
        admission charge, computed without touching the device."""
        total = sum(ds.device_bytes_estimate()
                    for ds in self._data_sources.values())
        if self.valid_doc_ids is not None:
            total += self.padded_docs        # bool lane, 1 byte/row
        return total

    def release_device_lanes(self) -> None:
        """Drop every device lane (vdoc included) and the ledger
        entries backing them, keeping host arrays intact — the
        device→host demotion step. Re-access re-uploads lazily."""
        self._valid_dev = None  # tpulint: disable=concurrency -- the residency manager drains query pins before releasing; worst case a racing reader re-uploads one lane
        residency.LEDGER.release(f"seg:{id(self)}:vdoc")
        for ds in self._data_sources.values():
            ds.release_device()

    def release_host_lanes(self, columns) -> None:
        """Drop the named columns' fat host payloads (host→disk
        demotion). Only columns the on-disk artifact can restore may be
        named — the residency manager verifies the artifact first."""
        for name in columns:
            ds = self._data_sources.get(name)
            if ds is not None:
                ds.release_host()

    def rebind_host_lanes(self, fresh: "ImmutableSegment") -> None:
        """Re-populate host payloads from a freshly loaded copy of the
        same artifact (disk-tier reload), preserving this object's
        identity so refcounted managers and caches stay valid."""
        for name, ds in self._data_sources.items():
            src = fresh._data_sources.get(name)
            if src is not None:
                ds.adopt_host(src)

    def destroy(self) -> None:
        self._valid_dev = None  # tpulint: disable=concurrency -- destroy runs after the refcounted release of the last query; worst case a racing reader re-uploads one lane
        self.release_device_lanes()


class ImmutableSegmentLoader:
    """load(segment_dir) → ImmutableSegment.

    Parity: ImmutableSegmentLoader.load (core/indexsegment/immutable/
    ImmutableSegmentLoader.java:50-81): read metadata, build a
    ColumnIndexContainer per column, wire DataSources.
    """

    @staticmethod
    def load(seg_dir: str, schema=None,
             index_loading_config=None) -> ImmutableSegment:
        """`schema`: when given, columns the schema defines but the
        segment predates are synthesized as default-value columns
        (schema evolution). `index_loading_config`: an IndexingConfig —
        inverted indexes it lists are generated at load when missing.
        Parity: core/segment/index/loader/SegmentPreProcessor.
        """
        from pinot_tpu.segment import format as fmt
        seg_dir = fmt.open_dir(seg_dir)      # v1 dir or v3 columns.psf
        meta = SegmentMetadata.load(seg_dir)
        sources: Dict[str, DataSource] = {}
        for name, cm in meta.columns.items():
            ds = DataSource(cm, None)
            if cm.data_type == DataType.VECTOR:
                ds.vec_values = read_vec_fwd(seg_dir, name)
                from pinot_tpu.index import ivf
                index = ivf.load_index(seg_dir, name)
                if index is not None:
                    ds.ivf_centroids = index.centroids
                    ds.ivf_assignments = index.assignments
                    ds.ivf_meta = index.meta
                sources[name] = ds
                continue
            if not cm.has_dictionary:
                from pinot_tpu.segment.rawchunks import (ChunkedRawReader,
                                                         has_raw_chunks)
                if has_raw_chunks(seg_dir, name):
                    ds.raw_chunks = ChunkedRawReader.open(
                        seg_dir, name,
                        is_bytes=cm.data_type == DataType.BYTES)
                else:
                    ds.raw_values = read_raw_fwd(seg_dir, name)
            else:
                ds.dictionary = Dictionary.load(seg_dir, name, cm.data_type)
                if cm.single_value:
                    ds.dict_ids = read_sv_fwd(seg_dir, name,
                                              cm.bits_per_element,
                                              meta.total_docs)
                    if cm.sorted:
                        ds.sorted_ranges = read_sorted_fwd(seg_dir, name)
                else:
                    flat, offs = read_mv_fwd(seg_dir, name)
                    ds.mv_dict_ids = mv_to_padded(flat, offs, cm.cardinality)
                if cm.has_inverted_index:
                    ds.inverted_index = InvertedIndexReader.load(
                        seg_dir, name, meta.total_docs)
                if cm.has_bloom_filter:
                    ds.bloom_filter = BloomFilter.load(seg_dir, name)
            sources[name] = ds
        # -- SegmentPreProcessor parity ---------------------------------
        if index_loading_config is not None:
            from pinot_tpu.segment.inverted import build_inverted_csr
            for name in index_loading_config.inverted_index_columns:
                ds = sources.get(name)
                if ds is None or ds.inverted_index is not None:
                    continue
                card = ds.metadata.cardinality
                if ds.dict_ids is not None:
                    docids, offsets = build_inverted_csr(
                        ds.dict_ids, np.arange(len(ds.dict_ids)), card)
                elif ds.mv_dict_ids is not None:
                    mv = ds.mv_dict_ids
                    flat = mv.reshape(-1)
                    docs = np.repeat(np.arange(mv.shape[0]), mv.shape[1])
                    keep = flat < card       # drop padding entries
                    docids, offsets = build_inverted_csr(
                        flat[keep], docs[keep], card)
                else:
                    continue                 # raw column: no dictIds
                ds.inverted_index = InvertedIndexReader(
                    docids, offsets, meta.total_docs)
                ds.metadata.has_inverted_index = True
        if schema is not None:
            for field in schema.fields:
                if field.name in sources:
                    continue
                # default column: the segment predates this schema field
                sources[field.name] = _default_column(field,
                                                      meta.total_docs)
        seg = ImmutableSegment(meta, sources)
        for ds in sources.values():
            ds._segment = seg
        from pinot_tpu.startree.cube import load_star_trees
        seg.star_trees = load_star_trees(seg_dir)
        return seg


def _default_column(field, num_docs: int) -> DataSource:
    """Constant default-value column (parity: DefaultColumnHandler +
    virtual default column providers)."""
    if field.data_type == DataType.VECTOR:
        # segments predating the vector field serve zero embeddings
        cm = ColumnMetadata(
            name=field.name, data_type=field.data_type,
            cardinality=num_docs, bits_per_element=32,
            has_dictionary=False, total_number_of_entries=num_docs,
            vector_dimension=field.vector_dimension)
        ds = DataSource(cm, None)
        ds.vec_values = np.zeros((num_docs, field.vector_dimension),
                                 np.float32)
        return ds
    default = field.default_null_value
    cm = ColumnMetadata(
        name=field.name, data_type=field.data_type, cardinality=1,
        bits_per_element=1, single_value=field.single_value, sorted=True,
        has_dictionary=True, min_value=default, max_value=default,
        total_number_of_entries=num_docs)
    ds = DataSource(cm, None)
    dtype = object if not field.data_type.is_numeric else \
        field.data_type.np_dtype
    ds.dictionary = Dictionary(field.data_type,
                               np.array([default], dtype=dtype))
    if field.single_value:
        ds.dict_ids = np.zeros(num_docs, dtype=np.int32)
    else:
        ds.mv_dict_ids = np.zeros((num_docs, 1), dtype=np.int32)
    return ds
