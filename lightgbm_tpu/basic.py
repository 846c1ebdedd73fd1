"""Dataset and Booster — the user-facing core API.

Re-design of the reference python-package surface
(/root/reference/python-package/lightgbm/basic.py: Dataset :1744, Booster
:3539) fused with the C++ layers it fronts (src/io/dataset.cpp,
dataset_loader.cpp, metadata.cpp, src/c_api.cpp): there is no C API /
ctypes boundary here — binning is host numpy, training state is JAX arrays
in HBM, and the model is numpy trees (models/tree.py).
"""

from __future__ import annotations

import io
import os
import threading
import warnings
from pathlib import Path
from typing import Any, Dict, List, Optional, Tuple, Union

import numpy as np

from .config import ALIASES, Config, resolve_params
from .metrics import create_metrics
from .objectives import create_objective
from .ops.binning import BinMapper, BinType, MissingType, bin_values, find_bin

__all__ = ["Dataset", "Booster", "LightGBMError", "Sequence"]


class LightGBMError(Exception):
    """Error class (matches the reference package's exception name)."""


def _is_1d(a) -> bool:
    return hasattr(a, "ndim") and a.ndim == 1


def _load_text_file(path: str, cfg: Config
                    ) -> Tuple[np.ndarray, np.ndarray, Optional[np.ndarray],
                               Optional[np.ndarray]]:
    """Parse CSV/TSV/LibSVM into (X, label, weight, group).

    Format auto-detection follows Parser::CreateParser
    (/root/reference/src/io/parser.cpp): sniff the first lines for tabs,
    commas, or 'idx:value' pairs. Companion ``<file>.weight`` /
    ``<file>.query`` files are honored like Metadata::Init
    (src/io/metadata.cpp).
    """
    with open(path, "r") as f:
        first = f.readline().strip()
    header = cfg.header
    sep = None
    if "\t" in first:
        sep = "\t"
    elif "," in first:
        sep = ","
    tokens = first.replace(",", " ").replace("\t", " ").split()
    is_libsvm = any(":" in t for t in tokens[1:])

    label_col = 0
    lc = str(cfg.label_column)
    if lc.startswith("name:"):
        # resolve against the header line (Config::label_column name:
        # form, config.h; DataLoader maps it through the header)
        want = lc[len("name:"):]
        if not header:
            raise LightGBMError(
                "label_column='name:...' requires header=true")
        names = [t.strip() for t in
                 (first.split(sep) if sep else first.split())]
        if want not in names:
            raise LightGBMError(
                f"label column '{want}' not found in header: {names}")
        label_col = names.index(want)
    elif lc != "":
        label_col = int(lc)

    if is_libsvm:
        labels, rows = [], []
        max_idx = -1
        with open(path) as f:
            for line in f:
                parts = line.split()
                if not parts:
                    continue
                labels.append(float(parts[0]))
                row = {}
                for tok in parts[1:]:
                    if ":" not in tok:
                        continue
                    i, v = tok.split(":")
                    i = int(i)
                    row[i] = float(v)
                    max_idx = max(max_idx, i)
                rows.append(row)
        X = np.zeros((len(rows), max_idx + 1))
        for r, row in enumerate(rows):
            for i, v in row.items():
                X[r, i] = v
        y = np.asarray(labels)
    else:
        # native OpenMP parser (src/io/parser.cpp analog); numpy is the
        # no-compiler fallback
        from .utils.native import parse_dense_text
        raw = parse_dense_text(path, bool(header))
        if raw is None:
            raw = np.genfromtxt(path, delimiter=sep,
                                skip_header=1 if header else 0)
        if raw.ndim == 1:
            raw = raw[:, None]
        y = raw[:, label_col].copy()
        X = np.delete(raw, label_col, axis=1)

    weight = None
    group = None
    wfile = path + ".weight"
    if os.path.exists(wfile):
        weight = np.loadtxt(wfile)
    qfile = path + ".query"
    if os.path.exists(qfile):
        group = np.loadtxt(qfile).astype(np.int64)
    return X, y, weight, group


def _two_round_load(path: str, cfg: Config, cat_idx_set,
                    feature_name):
    """Two-round / out-of-core text loading (``two_round=true``;
    dataset_loader.cpp:299,960 LoadFromFile's two-pass path).

    Round 1 streams the file once: counts rows and reservoir-samples up
    to ``bin_construct_sample_cnt`` raw lines; BinMappers are built from
    the sample only (the reference's SampleTextDataFromFile +
    ConstructBinMappersFromTextData). Round 2 streams again in bounded
    chunks, parsing and binning each chunk straight into the
    preallocated u8/u16 matrix — the raw float matrix is NEVER
    materialized, so peak memory is the BINNED matrix (1-2 bytes/value)
    plus one chunk, not 8 bytes/value.

    Returns (bins [n, F_used], mappers, used, full_mappers, n, F,
    label, weight, group).
    """
    from .ops.binning import BinType, bin_values, find_bin

    with open(path, "r") as f:
        first = f.readline().strip()
    sep = "\t" if "\t" in first else ("," if "," in first else None)
    tokens = first.replace(",", " ").replace("\t", " ").split()
    if any(":" in t for t in tokens[1:]):
        return None  # libsvm rows are ragged; eager loader handles them
    header = bool(cfg.header)
    label_col = 0
    lc = str(cfg.label_column)
    if lc.startswith("name:"):
        # resolve against the header HERE rather than deferring to the
        # eager loader: a user sets two_round precisely because the
        # file dwarfs host RAM, so falling back to the full-matrix
        # loader would defeat the mode on exactly its target input.
        # Silently assuming column 0 trained on a feature as the
        # label (ADVICE r4).
        want = lc[len("name:"):]
        if not header:
            raise LightGBMError(
                "label_column='name:...' requires header=true")
        names = [t.strip() for t in
                 (first.split(sep) if sep else first.split())]
        if want not in names:
            raise LightGBMError(
                f"label column '{want}' not found in header: {names}")
        label_col = names.index(want)
    elif lc:
        label_col = int(lc)

    # ---- round 1: count + reservoir sample ----
    rs = np.random.RandomState(cfg.data_random_seed)
    cap = max(int(cfg.bin_construct_sample_cnt), 2)
    sample_lines: List[str] = []
    n = 0
    with open(path, "r") as f:
        if header:
            f.readline()
        for line in f:
            if not line.strip():
                continue
            if n < cap:
                sample_lines.append(line)
            else:
                j = int(rs.randint(0, n + 1))
                if j < cap:
                    sample_lines[j] = line
            n += 1
    if n == 0:
        raise LightGBMError(f"empty data file {path}")

    def parse_lines(lines):
        try:
            # np.loadtxt's C tokenizer: fast and allocation-light (the
            # python-object row lists genfromtxt builds would dominate
            # the loader's peak memory)
            arr = np.loadtxt(lines, delimiter=sep, ndmin=2)
        except ValueError:
            arr = np.genfromtxt(lines, delimiter=sep)
            if arr.ndim == 1:
                arr = arr[None, :] if len(lines) == 1 else arr[:, None]
        return arr

    sample = parse_lines(sample_lines)
    del sample_lines
    F = sample.shape[1] - 1
    Xs = np.delete(sample, label_col, axis=1)
    del sample

    # ---- mappers from the sample only ----
    full_mappers = []
    for j in range(F):
        mb = cfg.max_bin
        if cfg.max_bin_by_feature and j < len(cfg.max_bin_by_feature):
            mb = cfg.max_bin_by_feature[j]
        m = find_bin(
            Xs[:, j], mb,
            min_data_in_bin=cfg.min_data_in_bin,
            bin_type=(BinType.CATEGORICAL if j in cat_idx_set
                      else BinType.NUMERICAL),
            use_missing=cfg.use_missing,
            zero_as_missing=cfg.zero_as_missing)
        full_mappers.append(m)
    del Xs
    used = [j for j, m in enumerate(full_mappers) if not m.is_trivial]
    mappers = [full_mappers[j] for j in used]
    max_bins = max((m.num_bins for m in mappers), default=2)
    bdtype = np.uint8 if max_bins <= 256 else np.uint16

    # ---- round 2: chunked parse -> bin in place ----
    CHUNK = 16384
    bins = np.zeros((n, len(used)), bdtype)
    label = np.zeros(n, np.float64)
    row = 0
    with open(path, "r") as f:
        if header:
            f.readline()
        buf: List[str] = []
        for line in f:
            if not line.strip():
                continue
            buf.append(line)
            if len(buf) == CHUNK:
                arr = parse_lines(buf)
                label[row:row + len(buf)] = arr[:, label_col]
                Xc = np.delete(arr, label_col, axis=1)
                bins[row:row + len(buf)] = bin_values(
                    [Xc[:, j] for j in used], mappers, bdtype)
                row += len(buf)
                buf = []
        if buf:
            arr = parse_lines(buf)
            label[row:row + len(buf)] = arr[:, label_col]
            Xc = np.delete(arr, label_col, axis=1)
            bins[row:row + len(buf)] = bin_values(
                [Xc[:, j] for j in used], mappers, bdtype)
            row += len(buf)
    if row != n:
        raise LightGBMError(
            f"two_round: second pass read {row} rows, first pass {n}")

    weight = None
    group = None
    if os.path.exists(path + ".weight"):
        weight = np.loadtxt(path + ".weight")
    if os.path.exists(path + ".query"):
        group = np.loadtxt(path + ".query").astype(np.int64)
    return (bins, mappers, np.asarray(used, np.int32), full_mappers,
            n, F, label, weight, group)


def _extract_pandas(data, categorical_feature):
    """Pandas ingestion: category dtypes -> integer codes (the
    pandas_categorical path of basic.py _data_from_pandas)."""
    import pandas as pd
    feature_name = [str(c) for c in data.columns]
    cat_cols = []
    pandas_categorical = []
    arrs = []
    for i, col in enumerate(data.columns):
        s = data[col]
        if isinstance(s.dtype, pd.CategoricalDtype):
            cat_cols.append(i)
            pandas_categorical.append(list(s.cat.categories))
            codes = s.cat.codes.to_numpy().astype(np.float64)
            codes[codes < 0] = np.nan
            arrs.append(codes)
        else:
            arrs.append(s.to_numpy(dtype=np.float64, na_value=np.nan))
    X = np.column_stack(arrs) if arrs else np.zeros((len(data), 0))
    if categorical_feature in ("auto", None, ""):
        cat_idx = cat_cols
    else:
        cat_idx = _resolve_cat_indices(categorical_feature, feature_name)
    return X, feature_name, cat_idx, pandas_categorical


def _count_missing_cells(n: int, mappers, nan_cells: np.ndarray) -> None:
    """Counters ``bin_cells`` / ``bin_cells_missing`` of one table binned
    in memory: how much of it sits in a NaN bin. ``nan_cells`` is what
    ``bin_matrix`` counted a column while it binned (no pass is made for
    this); a NaN in a column without a NaN bin reads as zero and is not
    counted."""
    from .obs.registry import registry
    has_bin = np.asarray([m.bin_type == BinType.NUMERICAL
                          and m.missing_type == MissingType.NAN
                          for m in mappers], bool)
    registry.counter("bin_cells").inc(n * len(mappers))
    registry.counter("bin_cells_missing").inc(int(nan_cells[has_bin].sum()))


def _resolve_cat_indices(categorical_feature, feature_name) -> List[int]:
    out = []
    for c in categorical_feature or []:
        if isinstance(c, str):
            if c in feature_name:
                out.append(feature_name.index(c))
            else:
                raise LightGBMError(f"Unknown categorical feature {c}")
        else:
            out.append(int(c))
    return sorted(set(out))


class Sequence:
    """Generic chunked data source (the reference's abstract streaming
    Sequence, python-package/lightgbm/basic.py:903): subclass with
    ``__getitem__`` (row index or slice -> numpy rows), ``__len__``,
    and optionally ``batch_size``. A Sequence (or list of Sequences) is
    a valid ``Dataset(data=...)`` — rows are pulled batch by batch, so
    the raw source never needs to be materialized at once by the
    caller."""

    batch_size = 4096

    def __getitem__(self, idx):  # pragma: no cover - abstract
        raise NotImplementedError("Sequence.__getitem__")

    def __len__(self) -> int:  # pragma: no cover - abstract
        raise NotImplementedError("Sequence.__len__")


def _extract_arrow(data):
    """pyarrow Table / RecordBatch -> [n, F] float64 + column names
    (the reference's Arrow C-data-interface ingest, arrow.h)."""
    import pyarrow as pa

    if isinstance(data, pa.RecordBatch):
        data = pa.Table.from_batches([data])
    if isinstance(data, (pa.ChunkedArray, pa.Array)):
        col = data.combine_chunks() if isinstance(data, pa.ChunkedArray) \
            else data
        return np.asarray(col, dtype=np.float64)[:, None], []
    if not isinstance(data, pa.Table):
        raise LightGBMError(
            f"Unsupported pyarrow input {type(data)}; pass a Table, "
            "RecordBatch or Array")
    cols = []
    for name in data.column_names:
        col = data.column(name)
        np_col = col.to_numpy(zero_copy_only=False)
        cols.append(np.asarray(np_col, dtype=np.float64))
    X = np.column_stack(cols) if cols else np.zeros((data.num_rows, 0))
    return X, list(data.column_names)


class Dataset:
    """Binned training data container (Dataset + Metadata + DatasetLoader
    analog: dataset.h:48-555, dataset_loader.cpp)."""

    _construct_tl = threading.local()

    def __init__(self, data, label=None, reference: Optional["Dataset"] = None,
                 weight=None, group=None, init_score=None,
                 feature_name: Union[str, List[str]] = "auto",
                 categorical_feature: Union[str, List] = "auto",
                 params: Optional[Dict[str, Any]] = None,
                 free_raw_data: bool = True, position=None):
        self.data = data
        self.label = label
        self.reference = reference
        self.weight = weight
        self.group = group
        self.position = position
        self.init_score = init_score
        self.feature_name = feature_name
        self.categorical_feature = categorical_feature
        self.params = resolve_params(params)
        self.free_raw_data = free_raw_data
        self._handle = None  # "constructed" flag
        # constructed state
        self.mappers: List[BinMapper] = []
        self._bins: Optional[np.ndarray] = None       # [n, F_used]
        self._used_features: Optional[np.ndarray] = None
        self._device_bins = None
        self._data_digest: Optional[str] = None
        self._host_bins_freed = False
        self._feature_names: List[str] = []
        self._pandas_categorical = None
        self._n: int = 0
        self._F: int = 0
        self._query_boundaries: Optional[np.ndarray] = None
        self.used_indices = None

    # -- streaming push ingest (LGBM_DatasetInitStreaming /
    # PushRows[WithMetadata] / MarkFinished, c_api.h:177-323): rows and
    # their metadata arrive in arbitrary-order batches into a
    # preallocated host staging area; construction (binning + device
    # upload) happens once at mark_finished ----------------------------
    @classmethod
    def init_streaming(cls, num_rows: int, num_features: int,
                       **dataset_kwargs) -> "Dataset":
        ds = cls(data=np.zeros((0, num_features)), **dataset_kwargs)
        ds.data = np.full((num_rows, num_features), np.nan, np.float64)
        ds._stream_label = np.zeros(num_rows, np.float64)
        ds._stream_weight = None
        ds._stream_filled = np.zeros(num_rows, bool)
        ds._stream_total = num_rows
        return ds

    def push_rows(self, mat, start_row: int = None, label=None,
                  weight=None) -> "Dataset":
        """Append (or place, with ``start_row``) a batch of raw rows;
        the WithMetadata variant is the optional label/weight args."""
        if getattr(self, "_stream_filled", None) is None:
            raise LightGBMError(
                "push_rows requires a Dataset.init_streaming dataset")
        mat = np.atleast_2d(np.asarray(mat, np.float64))
        if start_row is None:
            filled = np.flatnonzero(~self._stream_filled)
            start_row = int(filled[0]) if len(filled) else \
                self._stream_total
        end = start_row + mat.shape[0]
        if end > self._stream_total:
            raise LightGBMError("push_rows beyond the declared num_rows")
        self.data[start_row:end] = mat
        self._stream_filled[start_row:end] = True
        if label is not None:
            self._stream_label[start_row:end] = np.asarray(label).ravel()
        if weight is not None:
            if self._stream_weight is None:
                self._stream_weight = np.ones(self._stream_total,
                                              np.float64)
            self._stream_weight[start_row:end] = \
                np.asarray(weight).ravel()
        return self

    def mark_finished(self) -> "Dataset":
        """All pushes done -> bin and construct (MarkFinished)."""
        if getattr(self, "_stream_filled", None) is None:
            raise LightGBMError(
                "mark_finished requires a Dataset.init_streaming dataset")
        if not self._stream_filled.all():
            missing = int((~self._stream_filled).sum())
            raise LightGBMError(
                f"streaming dataset has {missing} unpushed rows")
        if self.label is None:
            self.label = self._stream_label
        if self.weight is None and self._stream_weight is not None:
            self.weight = self._stream_weight
        self._stream_filled = None
        return self.construct()

    # -- binary serialization (save_binary, dataset.h:692 /
    # dataset_loader.cpp:417 LoadFromBinFile analog: the binned matrix +
    # mappers + metadata round-trip so re-runs skip parsing and binning) --
    _BIN_MAGIC = "lightgbm_tpu.dataset.v1"

    def save_binary(self, filename) -> "Dataset":
        self.construct()
        import json
        meta = {
            "magic": self._BIN_MAGIC,
            "mappers": [m.to_dict() for m in self.mappers],
            "full_mappers": [m.to_dict() if m is not None else None
                             for m in self._full_mappers],
            "feature_names": self._feature_names,
            "F_total": int(self._F_total),
            "cat_idx": sorted(int(c) for c in self._cat_idx),
        }
        arrays = {
            "bins": self._bins,
            "used_features": self._used_features,
            "meta_json": np.frombuffer(
                json.dumps(meta).encode(), dtype=np.uint8),
        }
        if self.label is not None:
            arrays["label"] = np.asarray(self.label, np.float64)
        if self.weight is not None:
            arrays["weight"] = np.asarray(self.weight, np.float64)
        if self._query_boundaries is not None:
            arrays["query_boundaries"] = self._query_boundaries
        if self.init_score is not None:
            arrays["init_score"] = np.asarray(self.init_score, np.float64)
        with open(filename, "wb") as f:
            np.savez(f, **arrays)
        return self

    @staticmethod
    def _is_binary_file(path: str) -> bool:
        """Probe for our npz container: zip magic + the meta_json member.
        A text file that merely starts with 'PK' falls through to the
        text parser."""
        try:
            with open(path, "rb") as f:
                if f.read(4) != b"PK\x03\x04":
                    return False
            with np.load(path, allow_pickle=False) as z:
                return "meta_json" in z.files
        except (OSError, ValueError, KeyError):
            return False

    def _construct_from_binary(self, path: str) -> "Dataset":
        import json
        from .ops.binning import BinMapper
        with np.load(path, allow_pickle=False) as z:
            meta = json.loads(bytes(z["meta_json"]).decode())
            if meta.get("magic") != self._BIN_MAGIC:
                raise LightGBMError(f"{path} is not a lightgbm_tpu "
                                    "binary dataset")
            self._bins = z["bins"]
            self._used_features = z["used_features"].astype(np.int32)
            if "label" in z.files and self.label is None:
                self.label = z["label"]
            if "weight" in z.files and self.weight is None:
                self.weight = z["weight"]
            if "query_boundaries" in z.files:
                self._query_boundaries = z["query_boundaries"]
            if "init_score" in z.files and self.init_score is None:
                self.init_score = z["init_score"]
        self.mappers = [BinMapper.from_dict(d) for d in meta["mappers"]]
        self._feature_names = meta["feature_names"]
        self._F_total = meta["F_total"]
        self._cat_idx = set(meta["cat_idx"])
        self._full_mappers = [None if d is None else BinMapper.from_dict(d)
                              for d in meta["full_mappers"]]
        self._n = self._bins.shape[0]
        self._F = len(self.mappers)

        # a valid set loaded from binary must share the reference's bin
        # mappers (LoadFromBinFile alignment checks, dataset_loader.cpp)
        if self.reference is not None:
            ref = self.reference.construct()
            ref_dicts = [m.to_dict() for m in ref.mappers]
            own_dicts = [m.to_dict() for m in self.mappers]
            if ref_dicts != own_dicts:
                raise LightGBMError(
                    f"Binary dataset {path} was binned differently from "
                    "its reference dataset; rebuild it from text against "
                    "the same training data")

        # metadata supplied by the caller wins over the stored copies and
        # gets the same normalization/validation as the text path
        if self.label is not None:
            self.label = np.asarray(self.label, np.float64).ravel()
            if len(self.label) != self._n:
                raise LightGBMError(
                    f"Length of label ({len(self.label)}) != number of "
                    f"rows ({self._n})")
        if self.weight is not None:
            self.weight = np.asarray(self.weight, np.float64).ravel()
        if self.group is not None:
            g = np.asarray(self.group, np.int64).ravel()
            self._query_boundaries = np.concatenate(
                [[0], np.cumsum(g)]).astype(np.int64)
            if self._query_boundaries[-1] != self._n:
                raise LightGBMError("Sum of group sizes != number of rows")
        if self.init_score is not None:
            self.init_score = np.asarray(self.init_score, np.float64)
        self._handle = True
        if self.free_raw_data:
            self.data = None
        return self

    # -- construction ---------------------------------------------------
    def construct(self) -> "Dataset":
        if self._handle is not None:
            return self
        # time only the OUTERMOST construct: an unconstructed
        # `reference` chain re-enters here and would double-count the
        # inner duration under the same label
        tl = Dataset._construct_tl
        if getattr(tl, "depth", 0):
            return self._construct_impl()
        from .utils.timer import timed
        tl.depth = 1
        try:
            with timed("dataset/construct", job=True):
                return self._construct_impl()
        finally:
            tl.depth = 0

    def _construct_impl(self) -> "Dataset":
        cfg = Config.from_params(self.params)
        data = self.data
        label = self.label
        weight = self.weight
        group = self.group

        cat_idx: List[int] = []
        feature_name = self.feature_name
        if isinstance(data, (str, Path)) and self._is_binary_file(str(data)):
            return self._construct_from_binary(str(data))
        # out-of-core streaming construct (lightgbm_tpu/data/): chunk
        # sources always stream; text/parquet paths stream when
        # ingest_chunk_rows > 0 (docs/DATA.md). The dense float matrix
        # never exists on this path.
        from .data.sources import coerce_chunk_source
        chunk_src = coerce_chunk_source(data, cfg)
        if chunk_src is not None:
            return self._construct_streaming(cfg, chunk_src, label,
                                             weight, group)
        from .utils.timer import timed
        with timed("dataset/construct/load", job=True):
            if isinstance(data, (str, Path)):
                if cfg.two_round and self.reference is None:
                    cat_set = set()
                    cat_ok = True
                    for src in (self.categorical_feature,
                                cfg.categorical_feature):
                        if src in ("auto", "", None):
                            continue
                        if isinstance(src, str):
                            src = [c for c in src.split(",") if c]
                        if isinstance(src, (list, tuple)):
                            try:
                                cat_set |= {int(c) for c in src}
                                continue
                            except (TypeError, ValueError):
                                pass
                        # name-based spec needs the parsed header; the
                        # eager loader resolves it
                        cat_ok = False
                    out = _two_round_load(str(data), cfg, cat_set,
                                          feature_name) if cat_ok else None
                    if out is not None:
                        return self._finish_two_round(cfg, out, label,
                                                      weight, group,
                                                      cat_set)
                X, y, w, q = _load_text_file(str(data), cfg)
                if label is None:
                    label = y
                if weight is None and w is not None:
                    weight = w
                if group is None and q is not None:
                    group = q
            else:
                try:
                    import pandas as pd
                    is_pandas = isinstance(data, pd.DataFrame)
                except ImportError:
                    is_pandas = False
                if is_pandas:
                    X, names, cat_idx, self._pandas_categorical = \
                        _extract_pandas(data, self.categorical_feature)
                    if feature_name == "auto":
                        feature_name = names
                    try:
                        import pandas as pd
                        if isinstance(label, (pd.Series, pd.DataFrame)):
                            label = label.to_numpy().ravel()
                    except ImportError:
                        pass
                elif type(data).__module__.split(".")[0] == "pyarrow":
                    # Arrow ingest (the C-data-interface path of the
                    # reference, include/LightGBM/arrow.h): Tables /
                    # RecordBatches column-by-column, chunked arrays
                    # concatenated; per-column to_numpy is zero-copy for
                    # non-null numeric chunks
                    X, names = _extract_arrow(data)
                    if feature_name == "auto" and names:
                        feature_name = names
                elif hasattr(data, "tocsr") or hasattr(data, "toarray"):
                    X = np.asarray(data.todense(), dtype=np.float64)
                elif isinstance(data, np.ndarray):
                    # float32 is kept WITHOUT a whole-matrix float64 copy:
                    # every consumer (find_bin, bin_values, _raw_numeric)
                    # casts per column, so upcasting here would only
                    # double peak host RSS — at Allstate-bench scale
                    # (2M x 4228) that is the difference between ~44 GB
                    # and OOM. Mirrors the reference accepting float32
                    # buffers (C_API_DTYPE_FLOAT32, c_api.h).
                    X = data if data.dtype == np.float32 \
                        else np.asarray(data, dtype=np.float64)
                    if X.ndim == 1:
                        X = X[:, None]
                elif isinstance(data, (list, tuple)):
                    X = np.asarray(data, dtype=np.float64)
                else:
                    raise LightGBMError(
                        f"Cannot construct Dataset from {type(data)}")

        if label is None:
            raise LightGBMError("Label should not be None")
        y = np.asarray(label, dtype=np.float64).ravel()
        n, F = X.shape
        if len(y) != n:
            raise LightGBMError(
                f"Length of label ({len(y)}) != number of rows ({n})")
        self._n, self._F_total = n, F

        if not isinstance(feature_name, list) or feature_name == "auto":
            feature_name = [f"Column_{i}" for i in range(F)]
        self._feature_names = list(feature_name)

        if not cat_idx and self.categorical_feature not in ("auto", None, ""):
            cat_idx = _resolve_cat_indices(self.categorical_feature,
                                           self._feature_names)
        cat_param = cfg.categorical_feature
        if not cat_idx and cat_param not in ("auto", "", None):
            if isinstance(cat_param, str):
                cat_param = [c for c in cat_param.split(",") if c]
            cat_idx = _resolve_cat_indices(cat_param, self._feature_names)
        self._cat_idx = set(cat_idx)

        # -- binning: reuse the reference dataset's mappers for alignment
        # (LoadFromFileAlignWithOtherDataset, dataset_loader.cpp:299) --
        if self.reference is not None:
            ref = self.reference.construct()
            self.mappers = ref.mappers
            self._used_features = ref._used_features
            self._feature_names = ref._feature_names
            full_mappers = ref._full_mappers
        else:
            with timed("dataset/construct/find_bins", job=True,
                       attrs={"rows": int(n), "features": int(F)}):
                full_mappers = self._find_bins(cfg, X)
            used = [j for j, m in enumerate(full_mappers) if not m.is_trivial]
            self._used_features = np.asarray(used, dtype=np.int32)
            self.mappers = [full_mappers[j] for j in used]
        self._full_mappers = full_mappers

        from .ops.binning import bin_matrix
        with timed("dataset/construct/bin_rows", job=True,
                   attrs={"rows": int(n),
                          "features": len(self.mappers)}):
            nan_cells = np.zeros(len(self.mappers), np.int64)
            self._bins = bin_matrix(X, self._used_features, self.mappers,
                                    nan_cells=nan_cells)
            _count_missing_cells(n, self.mappers, nan_cells)
        self._F = len(self.mappers)
        # linear trees fit on raw numerical values (the reference keeps
        # raw data when linear_tree is set — Dataset raw_data_, dataset.h).
        # Datasets aligned to a reference inherit its retention so valid
        # sets of a linear model can be scored.
        if cfg.linear_tree or (self.reference is not None
                               and self.reference.raw_numeric() is not None):
            self._raw_numeric = (
                np.asarray(X)[:, self._used_features].astype(
                    np.float32, copy=False)
                if len(self._used_features)
                else np.zeros((n, 0), np.float32))
        else:
            self._raw_numeric = None

        self.label = y
        self.weight = None if weight is None else \
            np.asarray(weight, np.float64).ravel()
        if group is not None:
            g = np.asarray(group, np.int64).ravel()
            self._query_boundaries = np.concatenate(
                [[0], np.cumsum(g)]).astype(np.int64)
            if self._query_boundaries[-1] != n:
                raise LightGBMError(
                    "Sum of group sizes != number of rows")
        if self.init_score is not None:
            self.init_score = np.asarray(self.init_score,
                                         np.float64)
        self._handle = True
        if self.free_raw_data:
            self.data = None
        return self

    def _find_bins(self, cfg, X) -> list:
        """The row sample and one ``find_bin`` per column (the eager
        path's bin boundaries; span ``dataset/construct/find_bins``)."""
        n, F = X.shape
        sample_cnt = min(cfg.bin_construct_sample_cnt, n)
        if sample_cnt < n:
            rng = np.random.RandomState(cfg.data_random_seed)
            sample_rows = rng.choice(n, size=sample_cnt, replace=False)
        else:
            sample_rows = slice(None)
        full_mappers = []
        for j in range(F):
            mb = cfg.max_bin
            if cfg.max_bin_by_feature and j < len(cfg.max_bin_by_feature):
                mb = cfg.max_bin_by_feature[j]
            full_mappers.append(find_bin(
                X[sample_rows, j], mb,
                min_data_in_bin=cfg.min_data_in_bin,
                bin_type=(BinType.CATEGORICAL if j in self._cat_idx
                          else BinType.NUMERICAL),
                use_missing=cfg.use_missing,
                zero_as_missing=cfg.zero_as_missing))
        return full_mappers

    def _resolve_streaming_cats(self, cfg, src) -> set:
        """Categorical-feature resolution for chunk sources: integer
        indices always work; names resolve through the source's column
        names (CSV header, Arrow schema) when it has any. Precedence
        matches the eager constructor: the ``categorical_feature``
        argument wins outright, the params spec is only a fallback
        when the argument resolved to nothing."""
        cat_set = set()
        names = src.feature_names()
        for spec in (self.categorical_feature, cfg.categorical_feature):
            if cat_set:
                break
            if spec in ("auto", "", None):
                continue
            if isinstance(spec, str):
                spec = [c for c in spec.split(",") if c]
            for c in spec or []:
                try:
                    cat_set.add(int(c))
                    continue
                except (TypeError, ValueError):
                    pass
                if names and str(c) in names:
                    cat_set.add(names.index(str(c)))
                else:
                    raise LightGBMError(
                        f"categorical feature {c!r} cannot be resolved "
                        "for a chunked source without column names; "
                        "pass integer indices (or a header/Arrow "
                        "schema)")
        return cat_set

    def _construct_streaming(self, cfg, src, label, weight,
                             group) -> "Dataset":
        """Out-of-core construct (lightgbm_tpu/data/, docs/DATA.md):
        two-pass chunk ingestion — sample -> host-synced BinMappers ->
        chunk-by-chunk binning into the preallocated shard. The dense
        float matrix never exists; peak host memory scales with
        ``ingest_chunk_rows x n_features``, not dataset rows."""
        from .data.ingest import dataset_digest, ingest_dataset
        cat_set = self._resolve_streaming_cats(cfg, src)
        ref = None
        if self.reference is not None:
            ref = self.reference.construct()
        # linear trees fit on raw numerical values: pass 2 retains the
        # used-column f32 matrix — the eager path's exact retention
        # cost — instead of refusing the mode (valid sets inherit the
        # reference's retention so they can be scored)
        keep_raw = bool(cfg.linear_tree) or (
            ref is not None and ref.raw_numeric() is not None)
        res = ingest_dataset(src, cfg, cat_set, reference=ref,
                             keep_raw=keep_raw)
        y = res.label
        if label is not None:
            y = np.asarray(label, np.float64).ravel()
        if y is None:
            raise LightGBMError("Label should not be None")
        if len(y) != res.n:
            raise LightGBMError(
                f"Length of label ({len(y)}) != number of rows "
                f"({res.n})")
        if weight is None and res.weight is not None:
            weight = res.weight
        # companion metadata files of a streamed text path (Metadata::
        # Init semantics, like the eager and two-round loaders)
        path = getattr(src, "path", None)
        if path is not None:
            if weight is None and os.path.exists(path + ".weight"):
                weight = np.loadtxt(path + ".weight")
            if group is None and os.path.exists(path + ".query"):
                group = np.loadtxt(path + ".query").astype(np.int64)
        self._n, self._F_total = res.n, res.F
        fn = self.feature_name
        names = src.feature_names()
        if ref is not None:
            self._feature_names = list(ref._feature_names)
            self._cat_idx = set(ref._cat_idx)
        else:
            if isinstance(fn, list) and len(fn) == res.F:
                self._feature_names = list(fn)
            elif names and len(names) == res.F:
                self._feature_names = [str(c) for c in names]
            else:
                self._feature_names = [f"Column_{i}"
                                       for i in range(res.F)]
            self._cat_idx = set(cat_set)
        self.mappers = res.mappers
        self._used_features = res.used
        self._full_mappers = res.full_mappers
        self._bins = res.bins
        self._F = len(res.mappers)
        self._raw_numeric = res.raw
        # checkpoint data fingerprint: accumulated incrementally over
        # the pass-2 label/bin chunks; only an explicit label override
        # forces a recompute of the label leg
        if label is not None or res.digest is None:
            self._data_digest = dataset_digest(y, res.bins)
        else:
            self._data_digest = res.digest
        self._ingest_stats = res.stats
        return self._install_metadata(y, weight, group, res.n)

    def _finish_two_round(self, cfg, out, label, weight, group,
                          cat_set) -> "Dataset":
        """Install the out-of-core loader's pre-binned result (the tail
        of construct() without a raw float matrix ever existing)."""
        (bins, mappers, used, full_mappers, n, F, y, w, q) = out
        if label is not None:
            y = np.asarray(label, np.float64).ravel()
        if weight is None and w is not None:
            weight = w
        if group is None and q is not None:
            group = q
        if len(y) != n:
            raise LightGBMError(
                f"Length of label ({len(y)}) != number of rows ({n})")
        if cfg.linear_tree:
            raise LightGBMError(
                "two_round loading cannot retain raw data for "
                "linear_tree (the reference's two-pass loader has the "
                "same restriction on raw-data consumers)")
        self._n, self._F_total = n, F
        fn = self.feature_name
        if isinstance(fn, list) and len(fn) == F:
            self._feature_names = list(fn)
        else:
            self._feature_names = [f"Column_{i}" for i in range(F)]
        self._cat_idx = set(cat_set)
        self.mappers = mappers
        self._used_features = used
        self._full_mappers = full_mappers
        self._bins = bins
        self._F = len(mappers)
        self._raw_numeric = None
        return self._install_metadata(y, weight, group, n)

    def _install_metadata(self, y, weight, group, n) -> "Dataset":
        """Shared construct() tail: metadata coercion + validation +
        handle flip (used by the eager and two-round paths)."""
        self.label = y
        self.weight = None if weight is None else \
            np.asarray(weight, np.float64).ravel()
        if group is not None:
            g = np.asarray(group, np.int64).ravel()
            self._query_boundaries = np.concatenate(
                [[0], np.cumsum(g)]).astype(np.int64)
            if self._query_boundaries[-1] != n:
                raise LightGBMError(
                    "Sum of group sizes != number of rows")
        if self.init_score is not None:
            self.init_score = np.asarray(self.init_score, np.float64)
        self._handle = True
        if self.free_raw_data:
            self.data = None
        return self

    # -- introspection ---------------------------------------------------
    def num_data(self) -> int:
        self.construct()
        return self._n

    def num_features(self) -> int:
        """Number of *usable* (non-trivial) features."""
        self.construct()
        return self._F

    def num_total_features(self) -> int:
        self.construct()
        return self._F_total

    def num_total_bins(self) -> int:
        self.construct()
        return max((m.num_bins for m in self.mappers), default=2)

    def get_feature_name(self) -> List[str]:
        self.construct()
        return list(self._feature_names)

    def get_label(self):
        return self.label

    def get_weight(self):
        return self.weight

    def get_init_score(self):
        return self.init_score

    def get_group(self):
        if self._query_boundaries is None:
            return None
        return np.diff(self._query_boundaries)

    def get_position(self):
        """Per-row result-list positions for position-debiased LTR
        (Metadata::positions, dataset.h:48-398)."""
        return self.position

    def raw_numeric(self) -> Optional[np.ndarray]:
        """[n, F_used] float32 raw values (NaN preserved) — retained only
        when linear_tree is set (the reference's Dataset raw_data_)."""
        return getattr(self, "_raw_numeric", None)

    def set_position(self, position) -> "Dataset":
        self.position = None if position is None else \
            np.asarray(position).ravel()
        return self

    def query_boundaries(self) -> Optional[np.ndarray]:
        self.construct()
        return self._query_boundaries

    def set_label(self, label) -> "Dataset":
        self.label = np.asarray(label, np.float64).ravel()
        # a streaming construct's precomputed checkpoint fingerprint
        # covered the OLD labels; drop it so the checkpoint layer
        # rehashes the current ones (different-data refusal stays sound)
        self._data_digest = None
        return self

    def set_weight(self, weight) -> "Dataset":
        self.weight = None if weight is None else \
            np.asarray(weight, np.float64).ravel()
        return self

    def set_group(self, group) -> "Dataset":
        g = np.asarray(group, np.int64).ravel()
        self._query_boundaries = np.concatenate(
            [[0], np.cumsum(g)]).astype(np.int64)
        return self

    def set_init_score(self, init_score) -> "Dataset":
        self.init_score = None if init_score is None else \
            np.asarray(init_score, np.float64)
        return self

    def create_valid(self, data, label=None, weight=None, group=None,
                     init_score=None, params=None, position=None) -> "Dataset":
        return Dataset(data, label=label, reference=self, weight=weight,
                       group=group, init_score=init_score,
                       params=params or self.params, position=position)

    # -- reference-parity accessors (python-package basic.py Dataset) ----
    _FIELD_GETTERS = {"label": "get_label", "weight": "get_weight",
                      "init_score": "get_init_score",
                      "position": "get_position", "group": "get_group"}

    def get_field(self, field_name: str):
        """Generic field accessor (Dataset.get_field)."""
        getter = self._FIELD_GETTERS.get(field_name)
        if getter is None:
            raise LightGBMError(f"Unknown field {field_name}")
        return getattr(self, getter)()

    def set_field(self, field_name: str, data) -> "Dataset":
        """Generic field setter (Dataset.set_field)."""
        setter = {"label": self.set_label, "weight": self.set_weight,
                  "init_score": self.set_init_score,
                  "position": self.set_position,
                  "group": self.set_group}.get(field_name)
        if setter is None:
            raise LightGBMError(f"Unknown field {field_name}")
        return setter(data)

    def get_data(self):
        """The raw data this Dataset was built from (row-subset for
        subset Datasets; None once freed via free_raw_data)."""
        if self.data is not None and self.used_indices is not None:
            idx = np.asarray(self.used_indices)
            if hasattr(self.data, "iloc"):
                return self.data.iloc[idx]
            return np.asarray(self.data)[idx]
        return self.data

    def get_ref_chain(self, ref_limit: int = 100):
        """Set of Datasets along the reference chain."""
        chain = set()
        node, hops = self, 0
        while node is not None and hops < ref_limit:
            chain.add(node)
            node = node.reference
            hops += 1
        return chain

    def set_reference(self, reference: "Dataset") -> "Dataset":
        if self._handle is not None:
            raise LightGBMError(
                "Cannot set reference after the Dataset is constructed")
        self.reference = reference
        return self

    def set_feature_name(self, feature_name: List[str]) -> "Dataset":
        if feature_name == "auto":
            # the documented default sentinel: keep current names
            # (python-package Dataset.set_feature_name semantics)
            return self
        if self._handle is not None and feature_name is not None:
            if len(feature_name) != self._F_total:
                raise LightGBMError(
                    f"Expected {self._F_total} feature names, got "
                    f"{len(feature_name)}")
            self._feature_names = [str(f) for f in feature_name]
        else:
            self.feature_name = feature_name
        return self

    def set_categorical_feature(self, categorical_feature) -> "Dataset":
        if self._handle is not None:
            raise LightGBMError(
                "Cannot set categorical feature after the Dataset is "
                "constructed")
        self.categorical_feature = categorical_feature
        return self

    def subset(self, used_indices, params=None) -> "Dataset":
        """Row-subset view constructed against this Dataset's bin
        mappers (Dataset::CopySubrow analog; the cv() fold path)."""
        from .engine import _subset_dataset
        self.construct()
        return _subset_dataset(self, np.asarray(used_indices, np.int64),
                               params or self.params)

    def add_features_from(self, other: "Dataset") -> "Dataset":
        """Stack another constructed Dataset's features onto this one
        (Dataset::AddFeaturesFrom, src/io/dataset.cpp)."""
        self.construct()
        other.construct()
        if other._n != self._n:
            raise LightGBMError(
                "Cannot add features from a Dataset with a different "
                "number of rows")
        self._bins = np.hstack([self._bins, other._bins])
        self.mappers = list(self.mappers) + list(other.mappers)
        self._full_mappers = list(self._full_mappers) \
            + list(other._full_mappers)
        self._used_features = np.concatenate(
            [self._used_features,
             other._used_features + self._F_total]).astype(np.int32)
        self._feature_names = list(self._feature_names) \
            + list(other._feature_names)
        self._F += other._F
        self._F_total += other._F_total
        self._cat_idx = set(self._cat_idx) | {
            c + self._F_total - other._F_total for c in other._cat_idx}
        self._device_bins = None
        self._bundle_info = None
        self._device_raw = None
        if self._raw_numeric is not None \
                and other._raw_numeric is not None:
            self._raw_numeric = np.hstack([self._raw_numeric,
                                           other._raw_numeric])
        else:
            self._raw_numeric = None
        return self

    # -- device views ----------------------------------------------------
    def device_bins(self):
        """[F, n] bin matrix on device (feature-major; HBM-resident)."""
        import jax.numpy as jnp
        self.construct()
        if self._device_bins is None:
            if self._bins is None and getattr(self, "_host_bins_freed",
                                              False):
                raise LightGBMError(
                    "the host binned matrix was freed after device "
                    "placement and no device view was registered "
                    "(shard_residency=device; docs/SHARDING.md)")
            self._device_bins = jnp.asarray(self._bins.T)
        return self._device_bins

    def host_bins(self) -> np.ndarray:
        self.construct()
        if self._bins is None and getattr(self, "_host_bins_freed",
                                          False):
            raise LightGBMError(
                "the host binned matrix was freed after device "
                "placement (shard_residency=device; docs/SHARDING.md) "
                "— construct the Dataset with shard_residency=host if "
                "a host copy is required")
        return self._bins

    def free_host_bins(self) -> None:
        """Release the host binned matrix after device placement
        (shard_residency=device, parallel/placement.py). The checkpoint
        data fingerprint is computed FIRST and cached on the Dataset
        (``_data_digest``) so resume validation keeps working without
        the bins; subsequent ``host_bins()`` calls raise a clear error
        instead of returning None."""
        if self._bins is None:
            return
        if self._data_digest is None and self.label is not None:
            from .data.ingest import dataset_digest
            self._data_digest = dataset_digest(
                np.asarray(self.label, np.float64), self._bins)
        try:
            from .obs.registry import registry
            registry.gauge("host_binned_bytes").set(0.0)
        except Exception:
            pass
        self._bins = None
        self._device_bins = None
        self._bundle_info = None
        self._host_bins_freed = True

    def bundles(self, cfg):
        """Exclusive-feature-bundling info (ops/bundling.py), or None
        when bundling is off / not profitable. Cached per bin matrix
        (subset copies recompute — the shapes differ)."""
        self.construct()
        if not getattr(cfg, "enable_bundle", True):
            return None
        cap = getattr(cfg, "max_cat_to_onehot", 4)
        cached = getattr(self, "_bundle_info", None)
        # cache key includes the one-hot cap: it gates cat-member
        # ELIGIBILITY, so a stale bundle under a different cap would
        # leave wide cat members with zero split candidates
        if cached is not None and \
                cached.bins_bundled.shape[0] == self._n \
                and getattr(self, "_bundle_cat_cap", None) == cap:
            return cached
        if self._bins is None and getattr(self, "_host_bins_freed",
                                          False):
            raise LightGBMError(
                "the host binned matrix was freed after device "
                "placement (shard_residency=device; docs/SHARDING.md) "
                "— bundles cannot be rebuilt; reconstruct the Dataset "
                "to retrain with EFB")
        from .ops.bundling import build_bundles
        self._bundle_info = build_bundles(
            self._bins, self.mappers, max_cat_onehot=cap)
        self._bundle_cat_cap = cap
        return self._bundle_info

    def device_raw(self):
        """[n, F_used] raw float32 values on device (linear trees)."""
        import jax.numpy as jnp
        self.construct()
        if getattr(self, "_device_raw", None) is None:
            rn = self.raw_numeric()
            if rn is None:
                raise LightGBMError(
                    "linear tree evaluation needs raw data; construct the "
                    "Dataset with the linear_tree parameter")
            self._device_raw = jnp.asarray(rn)
        return self._device_raw

    def device_feat_num_bins(self):
        import jax.numpy as jnp
        self.construct()
        return jnp.asarray([m.num_bins for m in self.mappers], jnp.int32)

    def device_feat_nan_bin(self):
        import jax.numpy as jnp
        self.construct()
        # The "missing bin" per feature: rows landing in it are routed by
        # the learned default direction, not the threshold. NaN features
        # keep it as the last bin; zero_as_missing features use the zero
        # bin (which may sit mid-range).
        nb = []
        for m in self.mappers:
            if m.bin_type != BinType.NUMERICAL:
                nb.append(-1)
            elif m.missing_type == MissingType.NAN:
                nb.append(m.num_bins - 1)
            elif m.missing_type == MissingType.ZERO:
                nb.append(m.default_bin)
            else:
                nb.append(-1)
        return jnp.asarray(nb, jnp.int32)

    def device_feat_is_cat(self):
        """[F] bool categorical-feature mask, or None if all numerical."""
        import jax.numpy as jnp
        self.construct()
        arr = np.asarray([m.bin_type == BinType.CATEGORICAL
                          for m in self.mappers], bool)
        return jnp.asarray(arr) if arr.any() else None

    def used_feature_indices(self) -> np.ndarray:
        self.construct()
        return self._used_features

    def usable_feature_mask(self) -> np.ndarray:
        self.construct()
        return np.ones((self._F,), bool)

    def inner_feature_index(self, real_idx: np.ndarray) -> np.ndarray:
        """Map real feature indices to positions in the used-feature set."""
        self.construct()
        lut = np.full((self._F_total,), -1, np.int32)
        lut[self._used_features] = np.arange(self._F, dtype=np.int32)
        return lut[np.asarray(real_idx, np.int64)]

    def thresholds_to_bins(self, real_feat: np.ndarray,
                           thresholds: np.ndarray) -> np.ndarray:
        self.construct()
        inner = self.inner_feature_index(real_feat)
        out = np.zeros(len(thresholds), np.int32)
        for i, (f, t) in enumerate(zip(inner, thresholds)):
            m = self.mappers[f]
            out[i] = int(np.searchsorted(m.upper_bounds, t, side="left"))
        return out

    def monotone_array(self, cfg: Config) -> Optional[np.ndarray]:
        mc = cfg.monotone_constraints
        if not mc:
            return None
        self.construct()
        full = np.zeros((self._F_total,), np.int8)
        full[: len(mc)] = mc
        return full[self._used_features]

    def feature_infos(self) -> List[str]:
        self.construct()
        out = []
        lut = {int(j): m for j, m in zip(self._used_features, self.mappers)}
        for j in range(self._F_total):
            m = lut.get(j)
            if m is None:
                out.append("none")
            elif m.bin_type == BinType.CATEGORICAL:
                out.append(":".join(str(int(c)) for c in m.bin_to_cat))
            else:
                out.append(f"[{m.min_value:g}:{m.max_value:g}]")
        return out


class _EvalResultTuple(tuple):
    pass


class Booster:
    """User-facing booster (basic.py:3539 Booster analog)."""

    def __init__(self, params: Optional[Dict[str, Any]] = None,
                 train_set: Optional[Dataset] = None,
                 model_file: Optional[str] = None,
                 model_str: Optional[str] = None):
        self.best_iteration = -1
        self.best_score: Dict = {}
        self._train_data_name = "training"
        self._attrs: Dict[str, str] = {}
        self.params = params or {}
        self._engine = None
        self._metrics = []
        self._valid_names: List[str] = []
        self.pandas_categorical = None
        self._trees: List = []
        self._cfg: Optional[Config] = None
        self._num_class = 1
        self._feature_names: List[str] = []
        self._feature_infos: List[str] = []
        self._objective_str = "none"
        self._avg_output = False
        self._compiled_forest = None

        if train_set is not None:
            if not isinstance(train_set, Dataset):
                raise TypeError("Training data should be a Dataset instance")
            cfg = Config.from_params(params)
            from .utils.log import scoped_verbosity
            with scoped_verbosity(cfg.verbosity):
                train_set.params = {**resolve_params(train_set.params),
                                    **resolve_params(params)}
                train_set.construct()
                self._cfg = cfg
                objective = create_objective(cfg)
                if objective is not None and hasattr(objective,
                                                     "set_dataset"):
                    objective.set_dataset(train_set)
                from .models.gbdt import GBDTBooster
                self._engine = GBDTBooster(cfg, train_set, objective)
                self._metrics = create_metrics(cfg)
                self._num_class = cfg.num_class
                self._feature_names = train_set.get_feature_name()
                self._feature_infos = train_set.feature_infos()
                self._objective_str = self._objective_repr(cfg)
                self._avg_output = cfg.boosting == "rf"
            self.train_set = train_set
        elif model_file is not None:
            with open(model_file) as f:
                self._load_model_string(f.read())
        elif model_str is not None:
            self._load_model_string(model_str)
        else:
            raise TypeError(
                "At least one of train_set, model_file or model_str "
                "should be not None")

    # -- training --------------------------------------------------------
    @property
    def _models(self) -> List:
        return self._engine.models if self._engine is not None \
            else self._trees

    def _objective_repr(self, cfg: Config) -> str:
        """Objective line of the model text (matches the reference's
        ObjectiveFunction::ToString tokens, e.g. ``binary sigmoid:1``,
        ``multiclassova num_class:3 sigmoid:1``, ``regression sqrt``)."""
        o = cfg.objective
        if o == "binary":
            return f"binary sigmoid:{cfg.sigmoid:g}"
        if o == "multiclass":
            return f"multiclass num_class:{cfg.num_class}"
        if o == "multiclassova":
            return (f"multiclassova num_class:{cfg.num_class} "
                    f"sigmoid:{cfg.sigmoid:g}")
        if o in ("regression", "regression_l2") and cfg.reg_sqrt:
            return "regression sqrt"
        if o == "lambdarank":
            return "lambdarank"
        return o

    def _preload(self, base: "Booster") -> None:
        """Adopt an existing model's trees for continued training
        (init_model semantics, reference engine.py/basic.py).

        The trees are adopted through a model-text round trip rather
        than a deepcopy: a live Booster's trees carry ``threshold_bin``
        indices in the bin space of the dataset they were GROWN
        against, and continued training on FRESH data (the
        warm-start retrain loop, docs/PIPELINE.md) bins this train set
        with its own mappers — stale bin indices would silently
        mis-route rows. Parsed trees carry ``threshold_bin = -1``, so
        the binned traversal maps the real-valued thresholds onto the
        current mappers (``_binned_node_arrays``), exactly like the
        init_model-from-file and checkpoint-restore paths (whose
        byte-exact resume proves the round trip lossless)."""
        parsed = Booster(model_str=base.model_to_string())
        self._engine.preload_models(parsed._trees)
        # continued training adds num_boost_round NEW iterations on
        # top of the adopted ones (reference: init_iteration +
        # num_boost_round); the engine loop needs the offset
        self._engine.init_iteration = int(self._engine.iter_)

    def add_valid(self, data: Dataset, name: str) -> "Booster":
        data.construct()
        self._engine.add_valid(data, name)
        self._valid_names.append(name)
        return self

    def update(self, train_set: Optional[Dataset] = None, fobj=None) -> bool:
        """One boosting iteration; True means training should stop
        (no further splits possible)."""
        if train_set is not None:
            raise LightGBMError(
                "Resetting train_set mid-training is not supported yet")
        if fobj is not None:
            import numpy as _np
            score = self._engine.current_score(0)
            K = self._engine.K
            grad, hess = fobj(score[0] if K == 1 else score,
                              self._engine.train_set)
            return self._engine.train_one_iter(
                _np.asarray(grad), _np.asarray(hess))
        return self._engine.train_one_iter()

    def rollback_one_iter(self) -> "Booster":
        self._engine.rollback_one_iter()
        return self

    def current_iteration(self) -> int:
        return len(self._models) // self.num_model_per_iteration()

    def num_trees(self) -> int:
        return len(self._models)

    def num_model_per_iteration(self) -> int:
        if self._engine is not None:
            return self._engine.K
        return max(1, self._num_class)

    def num_feature(self) -> int:
        if self._engine is not None:
            return self._engine.train_set.num_total_features()
        return len(self._feature_names)

    def feature_name(self) -> List[str]:
        return list(self._feature_names)

    # -- evaluation -------------------------------------------------------
    def eval_train(self, feval=None) -> List[Tuple]:
        return self._eval(0, self._train_data_name, feval)

    def eval_valid(self, feval=None) -> List[Tuple]:
        out = []
        for i, name in enumerate(self._valid_names):
            out.extend(self._eval(i + 1, name, feval))
        return out

    def eval(self, data, name: str, feval=None) -> List[Tuple]:
        if data is self.train_set:
            return self._eval(0, self._train_data_name, feval)
        for i, v in enumerate(self._engine.valid_sets):
            if v.dataset is data:
                return self._eval(i + 1, name, feval)
        raise LightGBMError("Data should be added with add_valid first")

    def _eval(self, data_idx: int, name: str, feval=None) -> List[Tuple]:
        res = self._engine.eval_metrics(self._metrics, data_idx)
        out = [(name, mname, val, self._metric_higher_better(mname))
               for mname, val in res.items()]
        if feval is not None:
            fevals = feval if isinstance(feval, (list, tuple)) else [feval]
            score = self._engine.current_score(data_idx)
            ds = self._engine.train_set if data_idx == 0 else \
                self._engine.valid_sets[data_idx - 1].dataset
            for f in fevals:
                ret = f(score[0] if self._engine.K == 1 else score, ds)
                if isinstance(ret, list):
                    for (mn, v, hb) in ret:
                        out.append((name, mn, v, hb))
                else:
                    mn, v, hb = ret
                    out.append((name, mn, v, hb))
        return out

    def _metric_higher_better(self, mname: str) -> bool:
        for m in self._metrics:
            if m.name == mname:
                return m.higher_better
        return False

    # -- prediction --------------------------------------------------------
    def predict(self, data, start_iteration: int = 0,
                num_iteration: Optional[int] = None,
                raw_score: bool = False, pred_leaf: bool = False,
                pred_contrib: bool = False, validate_features: bool = False,
                **kwargs) -> np.ndarray:
        from .prediction import predict_any
        if num_iteration is None:
            num_iteration = self.best_iteration if self.best_iteration > 0 \
                else -1
        return predict_any(
            self, data, start_iteration, num_iteration,
            raw_score, pred_leaf, pred_contrib,
            pred_early_stop=bool(kwargs.get("pred_early_stop", False)),
            pred_early_stop_freq=int(kwargs.get("pred_early_stop_freq", 10)),
            pred_early_stop_margin=float(
                kwargs.get("pred_early_stop_margin", 10.0)))

    def compile(self, num_iteration: Optional[int] = None,
                start_iteration: int = 0, **kwargs):
        """Compile the forest once into tensorized device arrays
        (serve/compile.py): the returned
        :class:`~lightgbm_tpu.serve.compile.CompiledForest` predicts
        through ONE jitted program with power-of-two row bucketing,
        and subsequent :meth:`predict` calls over the same iteration
        range ride it too — ad-hoc batch sizes stop triggering
        per-shape recompiles. The cached compilation is bypassed
        automatically when the booster trains further or a different
        iteration range is requested. ``kwargs``:
        ``min_bucket`` / ``max_batch_rows`` (powers of two)."""
        if num_iteration is None:
            num_iteration = self.best_iteration \
                if self.best_iteration > 0 else -1
        from .serve.compile import compile_forest
        cf = compile_forest(self, num_iteration=num_iteration,
                            start_iteration=start_iteration, **kwargs)
        self._compiled_forest = cf
        return cf

    # -- model io ----------------------------------------------------------
    def model_to_string(self, num_iteration: Optional[int] = None,
                        start_iteration: int = 0,
                        importance_type: str = "split") -> str:
        from .models.model_io import model_to_string
        return model_to_string(self, num_iteration, start_iteration,
                               importance_type)

    def save_model(self, filename, num_iteration: Optional[int] = None,
                   start_iteration: int = 0,
                   importance_type: str = "split") -> "Booster":
        s = self.model_to_string(num_iteration, start_iteration,
                                 importance_type)
        # crash-safe write (same-directory tmp + os.replace, like the
        # native-lib build and checkpoint snapshots): a killed process
        # never leaves a truncated model file behind
        from .utils.atomic import atomic_write_text
        atomic_write_text(filename, s)
        return self

    def _load_model_string(self, s: str) -> None:
        from .models.model_io import load_model_string
        load_model_string(self, s)

    def dump_model(self, num_iteration: Optional[int] = None,
                   start_iteration: int = 0,
                   importance_type: str = "split") -> Dict:
        from .models.model_io import dump_model_dict
        return dump_model_dict(self, num_iteration, start_iteration,
                               importance_type)

    def feature_importance(self, importance_type: str = "split",
                           iteration: Optional[int] = None) -> np.ndarray:
        nf = self.num_feature()
        imp = np.zeros((nf,), np.float64)
        trees = self._models
        if iteration is not None and iteration > 0:
            trees = trees[: iteration * self.num_model_per_iteration()]
        for t in trees:
            for i in range(t.num_nodes):
                f = int(t.split_feature[i])
                if importance_type == "split":
                    imp[f] += 1
                else:
                    imp[f] += max(0.0, float(t.split_gain[i]))
        if importance_type == "split":
            return imp.astype(np.int64 if True else np.float64)
        return imp

    def trees_to_dataframe(self):
        from .models.model_io import trees_to_dataframe
        return trees_to_dataframe(self)

    # -- misc reference-API methods ---------------------------------------
    # -- reference-parity surface (python-package basic.py Booster) -----
    @classmethod
    def model_from_string(cls, model_str: str) -> "Booster":
        """Load a Booster from a model-format string."""
        return cls(model_str=model_str)

    def attr(self, key: str) -> Optional[str]:
        """Free-form string attribute (Booster::GetAttr analog)."""
        return self._attrs.get(key)

    def set_attr(self, **kwargs) -> "Booster":
        """Set (value) or delete (None) string attributes."""
        for k, v in kwargs.items():
            if v is None:
                self._attrs.pop(k, None)
            else:
                self._attrs[k] = str(v)
        return self

    def lower_bound(self) -> float:
        """Smallest reachable raw score: sum over trees of each tree's
        minimum leaf value (Booster::LowerBoundValue)."""
        return float(sum(np.min(t.leaf_value[: t.num_leaves])
                         for t in self._models) or 0.0)

    def upper_bound(self) -> float:
        """Largest reachable raw score (Booster::UpperBoundValue)."""
        return float(sum(np.max(t.leaf_value[: t.num_leaves])
                         for t in self._models) or 0.0)

    def set_network(self, machines, local_listen_port: int = 12400,
                    listen_time_out: int = 120,
                    num_machines: int = 1) -> "Booster":
        """Wire the multi-controller runtime (LGBM_NetworkInit analog;
        on TPU the 'network' is the jax.distributed world)."""
        from .parallel.distributed import init_distributed
        if num_machines > 1:
            init_distributed(machines=machines if isinstance(machines, str)
                             else ",".join(machines))
        return self

    def free_network(self) -> "Booster":
        """Tear the multi-controller runtime down (LGBM_NetworkFree)."""
        from .parallel.distributed import shutdown_distributed
        shutdown_distributed()
        return self

    def set_train_data_name(self, name: str) -> "Booster":
        self._train_data_name = name
        return self

    def reset_parameter(self, params: Dict[str, Any]) -> "Booster":
        """Re-apply tunable params mid-training (LGBM_BoosterResetParameter;
        learning_rate takes effect on the next iteration)."""
        self.params = {**self.params, **params}
        if self._engine is not None:
            if "learning_rate" in params:
                self._engine._shrinkage = float(params["learning_rate"])
                # the new rate must take effect on the NEXT iteration
                # (reference semantics) — discard any precomputed
                # lookahead still scored at the old rate
                self._engine._abort_scan_window()
            for k in ("bagging_fraction", "bagging_freq",
                      "feature_fraction", "feature_fraction_bynode"):
                if k in params:
                    setattr(self._engine.cfg, k, params[k])
                    # the scan-window programs BAKE the bagging
                    # fractions/freq and key schedules into their
                    # traced bodies (gbdt._get_scan_fn fresh_bag /
                    # _StepCtx), unlike the per-iteration fused fn
                    # whose row weights arrive as operands — drop the
                    # cache (and any precomputed lookahead) so the
                    # next window re-traces with the new cfg
                    self._engine._scan_fns = {}
                    self._engine._abort_scan_window()
            if "feature_fraction_bynode" in params:
                # bynode is baked into the traced grow programs (the
                # per-node key schedule): refresh the static grow
                # config and drop/rebuild every cached program —
                # fused, eager (reads grow_cfg per call), and the
                # distributed grow fn — so all three re-trace with
                # the new setting
                eng = self._engine
                bynode = float(params["feature_fraction_bynode"])
                gcfg = eng.grow_cfg._replace(bynode=bynode)
                if bynode < 1.0 and gcfg.grower != "compact":
                    # same coercion as engine init: per-node column
                    # sampling lives on the compact grower only
                    gcfg = gcfg._replace(grower="compact")
                eng.grow_cfg = gcfg
                eng._fused_fn = None
                eng._scan_fns = {}
                if eng._grow_fn is not None:
                    eng._grow_fn = eng._build_grow_fn()
        return self

    def get_leaf_output(self, tree_id: int, leaf_id: int) -> float:
        return float(self._models[tree_id].leaf_value[leaf_id])

    def set_leaf_output(self, tree_id: int, leaf_id: int,
                        value: float) -> "Booster":
        self._models[tree_id].leaf_value[leaf_id] = value
        return self

    def shuffle_models(self, start_iteration: int = 0,
                       end_iteration: int = -1) -> "Booster":
        """Randomly permute tree order in [start, end) iterations
        (LGBM_BoosterShuffleModels)."""
        models = self._models
        K = self.num_model_per_iteration()
        n_iters = len(models) // K
        end = n_iters if end_iteration < 0 else min(end_iteration, n_iters)
        idx = np.arange(start_iteration, end)
        np.random.shuffle(idx)
        order = list(range(n_iters))
        order[start_iteration:end] = idx.tolist()
        reordered = []
        for it in order:
            reordered.extend(models[it * K: (it + 1) * K])
        models[:] = reordered
        return self

    def get_split_value_histogram(self, feature, bins=None,
                                  xgboost_style: bool = False):
        """Histogram of split thresholds used for a feature
        (basic.py get_split_value_histogram analog)."""
        if isinstance(feature, str):
            fidx = self.feature_name().index(feature)
        else:
            fidx = int(feature)
        values = []
        for t in self._models:
            for i in range(t.num_nodes):
                if int(t.split_feature[i]) == fidx \
                        and not t.is_categorical_node(i):
                    values.append(float(t.threshold[i]))
        hist, bin_edges = np.histogram(values, bins=bins or "auto")
        if xgboost_style:
            import pandas as pd
            ret = np.column_stack((bin_edges[1:], hist))
            ret = ret[ret[:, 1] > 0]
            return pd.DataFrame(ret, columns=["SplitValue", "Count"])
        return hist, bin_edges

    def refit(self, data, label, decay_rate: float = 0.9, weight=None,
              **kwargs) -> "Booster":
        """Refit leaf values on new data keeping tree structures
        (reference basic.py refit -> LGBM_BoosterRefit / GBDT::RefitTree:
        new_leaf = decay*old + (1-decay)*fit, trees processed in boosting
        order so later trees see refreshed scores).

        The warm-start edge of the continuous retrain loop
        (docs/PIPELINE.md): fresh production data is rarely clean, so
        per-tree gradients/hessians and the fitted leaf values run
        through the same non-finite guard as training
        (``nonfinite_policy``: raise | skip_tree — the tree keeps its
        old leaf values | clamp), and the ``refit_nan@T`` chaos kind
        (resilience/faults.py) poisons tree ``T``'s gradients to prove
        it. Guard trips surface as ``refit_nan`` fault events."""
        if not self._models:
            raise LightGBMError("Cannot refit an empty model")
        if any(t.is_linear and t.leaf_coeff and any(
                len(c) for c in t.leaf_coeff) for t in self._models):
            raise LightGBMError(
                "refit is not yet supported for linear trees (the "
                "reference's is_refit CalculateLinear path)")
        new_bst = self.__deepcopy__(None)
        X = np.asarray(data, np.float64)
        y = np.asarray(label, np.float64).ravel()
        w = None if weight is None else np.asarray(weight, np.float64)
        leaves = self.predict(X, pred_leaf=True)  # [n, T]
        if leaves.ndim == 1:
            leaves = leaves[:, None]
        cfg = self._cfg or Config.from_params(self.params)
        from .objectives import create_objective
        obj_cfg = Config.from_params(
            {**self.params, "objective": (self._objective_str or
                                          "regression").split()[0]})
        objective = create_objective(obj_cfg)
        if objective is None:
            raise LightGBMError("Cannot refit without a built-in objective")
        if hasattr(objective, "init_label_weights"):
            objective.init_label_weights(y, w)
        K = self.num_model_per_iteration()
        n = len(y)
        score = np.zeros((K, n), np.float64)
        lam = cfg.lambda_l2
        shrink = cfg.learning_rate
        from .resilience.faults import FaultPlan, append_fault_event
        fault_plan = FaultPlan.from_env()
        policy = cfg.nonfinite_policy
        fault_log: List[Dict] = []
        for ti, tree in enumerate(new_bst._models):
            k = ti % K
            g, h = objective.grad_hess(
                np.asarray(score[0] if K == 1 else score, np.float32),
                np.asarray(y, np.float32),
                None if w is None else np.asarray(w, np.float32))
            g = np.asarray(g, np.float64).reshape(K, n)[k] if K > 1 \
                else np.asarray(g, np.float64).ravel()
            h = np.asarray(h, np.float64).reshape(K, n)[k] if K > 1 \
                else np.asarray(h, np.float64).ravel()
            if fault_plan.take("refit_nan", ti):
                g = np.where(np.arange(n) % 7 == 0, np.nan, g)
            lv = leaves[:, ti]
            L = tree.num_leaves
            sg = np.bincount(lv, weights=g, minlength=L)
            sh = np.bincount(lv, weights=h, minlength=L)
            fit = -sg / (sh + lam)
            fit = fit * shrink
            # non-finite guard (same policy surface as training): bad
            # labels / poisoned gradients in the fresh data must not
            # publish a NaN forest into the serve fleet
            if not np.all(np.isfinite(fit)):
                if policy == "raise":
                    raise LightGBMError(
                        f"refit: non-finite leaf values fitted for "
                        f"tree {ti} (nonfinite_policy=raise)")
                if policy == "skip_tree":
                    append_fault_event(
                        fault_log, "refit_nan", ti, "skip_tree",
                        f"non-finite refit values for tree {ti}; "
                        "keeping its existing leaf values")
                    score[k] += tree.leaf_value[lv]
                    continue
                append_fault_event(
                    fault_log, "refit_nan", ti, "clamp",
                    f"non-finite refit values for tree {ti} clamped")
                fit = np.nan_to_num(fit, nan=0.0,
                                    posinf=1e30, neginf=-1e30)
            tree.leaf_value = decay_rate * tree.leaf_value \
                + (1.0 - decay_rate) * fit
            score[k] += tree.leaf_value[lv]
        new_bst._refit_fault_log = fault_log
        return new_bst

    def free_dataset(self) -> "Booster":
        self.train_set = None
        return self

    def __copy__(self):
        return self.__deepcopy__(None)

    def __deepcopy__(self, memo):
        model_str = self.model_to_string()
        return Booster(model_str=model_str)
