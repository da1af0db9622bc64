"""Dataset: binned training data + metadata, resident on device.

Reference analogs: ``Dataset``/``Metadata`` (include/LightGBM/dataset.h:487,
:48, src/io/dataset.cpp), ``DatasetLoader`` (src/io/dataset_loader.cpp).

TPU-first design: instead of per-feature Bin column objects with col-wise /
row-wise layout heuristics (reference dataset.cpp:619 GetShareStates), the
whole dataset is ONE dense ``[num_rows, num_planes]`` uint8/uint16 device
array of bin indices.  Binning happens host-side in NumPy at construction
from a row sample (reference bin_construct_sample_cnt), then the binned
matrix is pushed to HBM once.

Exclusive Feature Bundling (EFB, reference dataset.cpp FindGroups /
FastFeatureBundling): with ``enable_bundle`` (default true), mutually
exclusive sparse columns share one bin plane — plane bin 0 is the shared
all-default bin and each member owns a contiguous sub-range (bundling.py).
Wide one-hot data then trains with #bundles planes instead of #columns,
which is both the histogram-volume win and what keeps the dense [N, P]
layout viable at 50k+ columns.  Dense data never bundles (eligibility in
bundling.py), so its bin matrix stays byte-identical to the unbundled form.
"""

from __future__ import annotations

import dataclasses
from pathlib import Path
from typing import Any, Dict, List, Optional, Union
from typing import Sequence as TypingSequence

import numpy as np

from .binning import BinMapper
from .config import Config

try:  # pandas is optional
    import pandas as pd  # type: ignore
except Exception:  # pragma: no cover
    pd = None


def _is_1d(a: np.ndarray) -> np.ndarray:
    a = np.asarray(a)
    if a.ndim == 2 and a.shape[1] == 1:
        a = a.ravel()
    if a.ndim != 1:
        raise ValueError(f"expected 1-D array, got shape {a.shape}")
    return a


def _check_label_finite(label: np.ndarray) -> None:
    """Eager NaN/inf label validation (resilience guard rail): a poisoned
    label would otherwise surface many iterations later as NaN gradients
    (or, worse, silently as a degenerate model).  Fail at construction
    with the offending row."""
    bad = ~np.isfinite(label)
    if bad.any():
        first = int(np.argmax(bad))
        raise ValueError(
            f"label contains {int(bad.sum())} non-finite value(s) "
            f"(NaN/inf); first at row {first} "
            f"(value={label[first]!r})"
        )


@dataclasses.dataclass
class Metadata:
    """Per-row metadata (reference: include/LightGBM/dataset.h:48)."""

    label: np.ndarray
    weight: Optional[np.ndarray] = None
    init_score: Optional[np.ndarray] = None
    query_boundaries: Optional[np.ndarray] = None  # [num_queries+1] int32
    position: Optional[np.ndarray] = None

    @property
    def num_data(self) -> int:
        return len(self.label)

    def set_query(self, group_sizes: np.ndarray) -> None:
        group_sizes = _is_1d(group_sizes).astype(np.int64)
        boundaries = np.zeros(len(group_sizes) + 1, dtype=np.int32)
        np.cumsum(group_sizes, out=boundaries[1:])
        if boundaries[-1] != self.num_data:
            raise ValueError(
                f"sum of query sizes ({boundaries[-1]}) != num_data ({self.num_data})"
            )
        self.query_boundaries = boundaries


class Sequence:
    """Generic row-batched data source (reference: basic.py Sequence, the
    out-of-core ingestion ABC). Subclasses implement __getitem__ (row or
    slice -> numpy rows) and __len__; Dataset materializes in
    ``batch_size`` chunks at construction."""

    batch_size = 4096

    def __getitem__(self, idx):  # pragma: no cover - abstract
        raise NotImplementedError("Sub-classes of Sequence must implement __getitem__")

    def __len__(self) -> int:  # pragma: no cover - abstract
        raise NotImplementedError("Sub-classes of Sequence must implement __len__")


def _materialize_sequences(seqs) -> np.ndarray:
    parts = []
    for seq in seqs:
        n = len(seq)
        bs = getattr(seq, "batch_size", None) or 4096
        for start in range(0, n, bs):
            parts.append(np.asarray(seq[slice(start, min(start + bs, n))]))
    return np.concatenate(parts, axis=0)


def _parse_libsvm(lines, path: str) -> Dict[str, Any]:
    """LibSVM text parser (reference: LibSVMParser, src/io/parser.hpp:136):
    ``label [qid:q] idx:val idx:val ...`` -> CSR matrix, never densified."""
    import scipy.sparse as sp

    labels: List[float] = []
    rows: List[int] = []
    cols: List[int] = []
    vals: List[float] = []
    qids: List[int] = []
    r = 0
    for ln in lines:
        parts = ln.split()
        if not parts:
            continue
        labels.append(float(parts[0]))
        for tok in parts[1:]:
            k, v = tok.split(":", 1)
            if k == "qid":
                qids.append(int(v))
                continue
            rows.append(r)
            cols.append(int(k))
            vals.append(float(v))
        r += 1
    ncol = max(cols) + 1 if cols else 1
    csr = sp.csr_matrix(
        (np.asarray(vals), (np.asarray(rows), np.asarray(cols))),
        shape=(r, ncol),
    )
    out: Dict[str, Any] = {"data": csr, "label": np.asarray(labels)}
    if len(qids) == r and r > 0:
        # consecutive qid runs -> group sizes (reference parses qid the same
        # way its query file does)
        q = np.asarray(qids)
        change = np.nonzero(np.diff(q))[0] + 1
        bounds = np.concatenate([[0], change, [r]])
        out["group"] = np.diff(bounds)
    qpath = Path(str(path) + ".query")
    if qpath.exists():
        out["group"] = np.loadtxt(qpath, dtype=np.int64, ndmin=1)
    wpath = Path(str(path) + ".weight")
    if wpath.exists():
        out["weight"] = np.loadtxt(wpath, dtype=np.float64, ndmin=1)
    return out


def _is_arrow(data) -> bool:
    """True for pyarrow Table/RecordBatch (duck-typed so pyarrow stays an
    optional dependency, like the reference's header-only arrow ingestion,
    include/LightGBM/arrow.h)."""
    t = type(data).__module__
    return t.startswith("pyarrow") and hasattr(data, "schema") and hasattr(
        data, "column"
    )


def _arrow_to_numpy(data, category_maps=None):
    """pyarrow Table/RecordBatch -> (float64 matrix with nulls as NaN,
    feature names, categorical column names, category_maps).

    Reference analog: the Arrow C-data ingestion
    (include/LightGBM/arrow.h + c_api LGBM_DatasetCreateFromArrow) — numeric
    and boolean columns bin as floats, dictionary-encoded columns become
    categorical features via integer codes.  Codes are made STABLE across
    tables the way the reference's ``pandas_categorical`` remap is: the
    training call records each column's dictionary values in
    ``category_maps``; later tables (predict) remap their codes through the
    recorded value order, and unseen categories become NaN (routed like
    missing, matching the reference's unseen-category handling)."""
    import pyarrow as pa  # deferred; _is_arrow guaranteed pyarrow is loaded

    if isinstance(data, pa.RecordBatch):
        data = pa.Table.from_batches([data])
    data = data.combine_chunks()
    names = [str(c) for c in data.schema.names]
    record = category_maps is None
    if record:
        category_maps = {}
    cats = []
    cols = []
    for i, field in enumerate(data.schema):
        col = data.column(i)
        name = names[i]
        if pa.types.is_dictionary(field.type):
            cats.append(name)
            cc = col.combine_chunks()
            values = [v.as_py() for v in cc.dictionary]
            codes = cc.indices.to_numpy(zero_copy_only=False).astype(np.float64)
            mask = col.is_null().to_numpy(zero_copy_only=False)
            if record:
                category_maps[name] = values
            else:
                train_vals = category_maps.get(name)
                if train_vals is not None and train_vals != values:
                    # remap this table's codes onto the TRAIN dictionary
                    # order; unseen categories -> NaN (missing)
                    lut = {v: float(j) for j, v in enumerate(train_vals)}
                    remap = np.array(
                        [lut.get(v, np.nan) for v in values] or [np.nan]
                    )
                    # null slots surface as NaN indices: substitute 0 before
                    # indexing (the null mask overwrites them below anyway)
                    safe_idx = np.clip(
                        np.nan_to_num(codes, nan=0.0), 0, len(values) - 1
                    ).astype(np.int64)
                    codes = remap[safe_idx]
            arr = np.where(mask, np.nan, codes)
        elif pa.types.is_boolean(field.type) or pa.types.is_floating(
            field.type
        ) or pa.types.is_integer(field.type):
            arr = col.to_numpy(zero_copy_only=False).astype(np.float64)
        else:
            raise ValueError(
                f"Arrow column {name!r} has unsupported type "
                f"{field.type} (numeric, boolean, or dictionary expected)"
            )
        cols.append(arr)
    mat = (
        np.stack(cols, axis=1)
        if cols
        else np.zeros((data.num_rows, 0), np.float64)
    )
    return mat, names, cats, category_maps


def _is_cat_dtype(dtype) -> bool:
    """Column dtypes that carry non-numeric category values: classic
    object/category plus pandas 2.x (arrow-backed) string dtypes."""
    s = str(dtype)
    return s in ("category", "object", "str") or s.startswith(
        ("string", "large_string")
    )


def _pandas_to_numpy(df, category_maps=None):
    """DataFrame -> (float64 matrix with NaN missing, categorical column
    names, category_maps).

    category/object columns become float codes through a recorded category
    order, exactly like the reference's ``pandas_categorical`` machinery
    (python-package/lightgbm/basic.py ``_data_from_pandas``): the training
    call records each column's category values; later frames (valid sets,
    predict) remap their values through the recorded order and unseen
    categories become NaN (routed like missing)."""
    import pandas as pd  # caller guaranteed pandas is importable

    record = category_maps is None
    if record:
        category_maps = {}
    cats: List[str] = []
    cols = []
    for name in df.columns:
        col = df[name]
        sname = str(name)
        if _is_cat_dtype(col.dtype):
            cats.append(sname)
            cc = col.astype("category")
            if not record and category_maps and sname not in category_maps:
                # this column was NOT categorical at train time — its codes
                # would be this frame's own arbitrary order (reference:
                # "train and valid dataset categorical_feature do not match")
                raise ValueError(
                    f"column {sname!r} is categorical but the train-time "
                    "category record has no entry for it (categorical "
                    "features must match between train and later frames)"
                )
            if record and sname not in category_maps:
                # native python values (np.int64 -> int, …) so the maps
                # survive a JSON model-file round trip without stringifying
                category_maps[sname] = [
                    v.item() if hasattr(v, "item") else v
                    for v in cc.cat.categories
                ]
            train_vals = category_maps.get(sname)
            if train_vals is not None and list(cc.cat.categories) != list(
                train_vals
            ):
                cc = cc.cat.set_categories(train_vals)
            codes = cc.cat.codes.to_numpy().astype(np.float64)
            codes[codes < 0] = np.nan  # pandas NaN / unseen category -> -1
            cols.append(codes)
        else:
            cols.append(col.to_numpy(dtype=np.float64, na_value=np.nan))
    mat = (
        np.column_stack(cols)
        if cols
        else np.zeros((len(df), 0), np.float64)
    )
    return mat, cats, category_maps


def _arrow_column_to_numpy(arr):
    """A pyarrow Array/ChunkedArray — or single-column Table/RecordBatch —
    as a 1-D numpy array (labels/weights)."""
    import pyarrow as pa

    if isinstance(arr, (pa.Table, pa.RecordBatch)):
        if arr.num_columns != 1:
            raise ValueError(
                f"expected a single-column Arrow table for a label/weight, "
                f"got {arr.num_columns} columns"
            )
        arr = arr.column(0)
    return arr.to_numpy(zero_copy_only=False)


def _is_binary_dataset_file(path: str) -> bool:
    """True when ``path`` is a lightgbm_tpu binary dataset (pickle with our
    format marker in the first bytes) — the reference's binary-magic check
    (dataset_loader.cpp LoadFromBinFile)."""
    try:
        with open(path, "rb") as fh:
            head = fh.read(64)
    except OSError:
        return False
    return head[:1] == b"\x80" and b"lightgbm_tpu.dataset.v1" in head


def _label_column_index(config: Config, header_line: Optional[str]) -> int:
    """Resolve label_column to a 0-based index: plain int, ``column=N``,
    or the reference's ``name:<colname>`` form (needs the header line)."""
    if config.label_column in ("", None):
        return 0
    lc = str(config.label_column)
    if lc.startswith("name:"):
        name = lc[len("name:"):]
        if not header_line:
            raise ValueError(
                "label_column='name:...' requires header=true so the column "
                "name can be resolved"
            )
        delim = "\t" if "\t" in header_line else ","
        names = [t.strip() for t in header_line.split(delim)]
        if name not in names:
            raise ValueError(
                f"label_column names {name!r} but the header has {names}"
            )
        return names.index(name)
    return int(lc.split("=")[-1]) if "=" in lc else int(lc)


def _resolve_data_columns(
    spec, header_line: Optional[str], label_col: int, what: str
) -> List[int]:
    """Resolve a weight/group/ignore column spec to RAW file-column indices
    (reference DatasetLoader::SetHeader, src/io/dataset_loader.cpp:111-160):
    integer indices do NOT count the label column; ``name:a,b`` forms need
    ``header=true`` and resolve against the header names."""
    if spec in ("", None):
        return []
    s = str(spec)
    if s.startswith("name:"):
        if not header_line:
            raise ValueError(
                f"{what}='name:...' requires header=true so column names "
                "can be resolved"
            )
        delim = "\t" if "\t" in header_line else ","
        names = [t.strip() for t in header_line.split(delim)]
        out = []
        for nm in s[len("name:"):].split(","):
            nm = nm.strip()
            if nm == "":
                continue
            if nm not in names:
                raise ValueError(f"{what} names {nm!r} but the header has {names}")
            out.append(names.index(nm))
        return out
    out = []
    for tok in s.split(","):
        tok = tok.strip()
        if tok == "":
            continue
        idx = int(tok)
        # "doesn't count the label column": data column i is raw column
        # i when i < label_col, else i + 1
        out.append(idx if idx < label_col else idx + 1)
    return out


def _extract_column_fields(
    arr: np.ndarray, config: Config, header_line: Optional[str], label_col: int
) -> Dict[str, Any]:
    """weight_column / group_column / ignore_column extraction for the dense
    text path (reference dataset_loader.cpp:111-160).  Extracted columns
    REMAIN in the feature matrix but are marked ignored (trivial mappers),
    preserving the reference's original feature numbering in models."""
    out: Dict[str, Any] = {}
    ignore_raw: List[int] = []
    wcols = _resolve_data_columns(
        config.weight_column, header_line, label_col, "weight_column"
    )
    if wcols:
        out["weight"] = arr[:, wcols[0]].astype(np.float64)
        ignore_raw += wcols[:1]
    gcols = _resolve_data_columns(
        config.group_column, header_line, label_col, "group_column"
    )
    if gcols:
        # the group column holds per-row query ids; consecutive runs become
        # query sizes (reference Metadata::SetQueryId)
        q = arr[:, gcols[0]].astype(np.int64)
        change = np.nonzero(np.diff(q))[0] + 1
        bounds = np.concatenate([[0], change, [len(q)]])
        out["group"] = np.diff(bounds)
        ignore_raw += gcols[:1]
    ignore_raw += _resolve_data_columns(
        config.ignore_column, header_line, label_col, "ignore_column"
    )
    if ignore_raw:
        # raw file column -> feature index after the label column is removed
        out["ignore"] = sorted(
            {c - (1 if c > label_col else 0) for c in ignore_raw
             if c != label_col}
        )
    return out


def _attach_sidecars(out: Dict[str, Any], path: str) -> Dict[str, Any]:
    """Load the reference's sidecar files (train.txt.query/.weight/.init)
    next to any text data file (reference Metadata::LoadQueryBoundaries)."""
    qpath = Path(str(path) + ".query")
    if qpath.exists() and "group" not in out:
        # an explicit group_column wins over the sidecar
        out["group"] = np.loadtxt(qpath, dtype=np.int64, ndmin=1)
    wpath = Path(str(path) + ".weight")
    if wpath.exists() and "weight" not in out:
        out["weight"] = np.loadtxt(wpath, dtype=np.float64, ndmin=1)
    ipath = Path(str(path) + ".init")
    if ipath.exists():
        out["init_score"] = np.loadtxt(ipath, dtype=np.float64, ndmin=1)
    ppath = Path(str(path) + ".position")
    if ppath.exists():
        # result positions for unbiased lambdarank (reference
        # Metadata::LoadPositions, src/io/metadata.cpp:663); string
        # position ids map to dense codes like the reference's
        # position_ids_
        raw = [
            ln.strip() for ln in ppath.read_text().splitlines() if ln.strip()
        ]
        try:
            out["position"] = np.asarray([int(v) for v in raw], np.int32)
        except (ValueError, OverflowError):
            ids = sorted(set(raw))
            code = {v: i for i, v in enumerate(ids)}
            out["position"] = np.asarray([code[v] for v in raw], np.int32)
    return out


def _is_libsvm_row(ln: str) -> bool:
    toks = ln.replace(",", " ").split()
    return len(toks) > 1 and ":" in toks[1]


def _load_text_file(path: str, config: Config) -> Dict[str, Any]:
    """Parse a CSV/TSV/LibSVM training file (reference src/io/parser.cpp);
    LibSVM rows load into a CSR matrix (sparse path), dense CSV/TSV into a
    float matrix. Label column defaults to 0 as in the reference CLI."""
    p = Path(path)
    text = p.read_text()
    lines = text.splitlines()
    skip = 1 if config.header else 0
    header_line = lines[0] if (config.header and lines) else None
    if config.parser_config_file:
        # custom parser plugin (Parser::CreateParser's add-on dispatch,
        # src/io/parser.cpp:288): className routes lines through a
        # registered Python parser; the config str persists with the
        # dataset like the reference's parser_config_str_
        from .parser import create_parser, generate_parser_config_str

        pcs = generate_parser_config_str(
            config.parser_config_file, config.header,
            _label_column_index(config, header_line),
        )
        parse_line = create_parser(pcs)
        if parse_line is not None:
            labels, rows = [], []
            max_col = -1
            for ln in lines[skip:]:
                if not ln.strip():
                    continue
                feats, lab = parse_line(ln)
                labels.append(float(lab))
                rows.append(list(feats))
            # decide sparse from ANY row, not the first (a legal label-only
            # row parses to []); mixed outputs normalize to pairs
            sparse = any(r and isinstance(r[0], tuple) for r in rows)
            if sparse:
                rows = [
                    r if (not r or isinstance(r[0], tuple))
                    else list(enumerate(r))
                    for r in rows
                ]
                for r in rows:
                    for ci, _ in r:
                        max_col = max(max_col, int(ci))
            else:
                for r in rows:
                    max_col = max(max_col, len(r) - 1)
            n, f = len(rows), max_col + 1
            if sparse:
                try:
                    import scipy.sparse as sp
                except Exception as exc:  # pragma: no cover
                    raise ValueError(
                        "custom parser returned sparse rows but scipy is "
                        "unavailable"
                    ) from exc
                data_v, indices, indptr = [], [], [0]
                for feats in rows:
                    for ci, v in feats:
                        indices.append(int(ci))
                        data_v.append(float(v))
                    indptr.append(len(indices))
                mat = sp.csr_matrix(
                    (data_v, indices, indptr), shape=(n, f)
                )
                out = {"data": mat, "label": np.asarray(labels)}
            else:
                dense = np.zeros((n, f), np.float64)
                for i, feats in enumerate(rows):
                    dense[i, : len(feats)] = feats
                out = {"data": dense, "label": np.asarray(labels)}
            out["parser_config_str"] = pcs
            return _attach_sidecars(out, path)
    # scan a few rows: a leading label-only line is legal LibSVM (all-zero
    # sample), so one line is not enough to decide the format
    probe = [ln for ln in lines[skip:] if ln.strip()][:20]
    if probe and any(_is_libsvm_row(ln) for ln in probe):
        return _parse_libsvm(lines[skip:], path)
    first = lines[0] if lines else ""
    delim = "\t" if "\t" in first else ("," if "," in first else None)
    arr = np.loadtxt(path, delimiter=delim, skiprows=skip, dtype=np.float64, ndmin=2)
    label_col = _label_column_index(config, header_line)
    label = arr[:, label_col]
    feats = np.delete(arr, label_col, axis=1)
    out: Dict[str, Any] = {"data": feats, "label": label}
    out.update(_extract_column_fields(arr, config, header_line, label_col))
    return _attach_sidecars(out, path)


class Dataset:
    """Binned dataset (reference: Dataset, include/LightGBM/dataset.h:487).

    Lazily constructed like the python-package Dataset (basic.py:1744): raw
    data is held until ``construct()`` bins it (or bins are inherited from a
    reference dataset for validation sets).
    """

    def __init__(
        self,
        data: Union[np.ndarray, str, "pd.DataFrame", None],
        label: Optional[np.ndarray] = None,
        *,
        reference: Optional["Dataset"] = None,
        weight: Optional[np.ndarray] = None,
        group: Optional[np.ndarray] = None,
        init_score: Optional[np.ndarray] = None,
        feature_name: Union[str, TypingSequence[str]] = "auto",
        categorical_feature: Union[str, TypingSequence] = "auto",
        params: Optional[Dict[str, Any]] = None,
        free_raw_data: bool = True,
        position: Optional[np.ndarray] = None,
    ) -> None:
        self.params: Dict[str, Any] = dict(params or {})
        self.config = Config.from_params(self.params)
        self._raw_data = data
        self._label = label
        self._weight = weight
        self._group = group
        self._init_score = init_score
        self._position = position
        self._feature_name = feature_name
        self._categorical_feature = categorical_feature
        self.reference = reference
        self.free_raw_data = free_raw_data

        # filled by construct()
        self._constructed = False
        self.bin_mappers: List[BinMapper] = []
        self.used_features: List[int] = []  # original feature idx per used column
        # EFB plane layout (bundling.py), or None for the identity layout
        # (bins column ci <=> used_features[ci])
        self.bundle_layout = None
        self._ignore_set: set = set()  # ignore_column / weight_column / group_column
        self.bins: Optional[np.ndarray] = None  # [N, num_planes] uint8/uint16
        self.raw: Optional[np.ndarray] = None  # raw values (for linear trees / predict checks)
        self.metadata: Optional[Metadata] = None
        self.feature_names: List[str] = []
        self.num_total_features: int = 0
        self.arrow_categories: Optional[Dict[str, list]] = None
        self.pandas_categorical: Optional[Dict[str, list]] = None
        self._device_cache: Dict[str, Any] = {}

    # ----------------------------------------------------------- properties
    @property
    def num_data(self) -> int:
        self.construct()
        return int(self.bins.shape[0])

    @property
    def num_feature(self) -> int:
        """Number of original (pre-pruning) features, like the reference."""
        self.construct()
        return self.num_total_features

    @property
    def num_used_feature(self) -> int:
        self.construct()
        return int(self.bins.shape[1])

    def num_bins_per_feature(self) -> np.ndarray:
        self.construct()
        return np.array([self.bin_mappers[i].num_bins for i in self.used_features], dtype=np.int32)

    # -------------------------------------------------- plane-space accessors
    # The trainer consumes bins COLUMN-wise; with EFB a column is a bundle
    # plane, without it a used feature (identity).  These return per-column
    # arrays either way (boosting/gbdt.py builds its device operands here).
    @property
    def num_planes(self) -> int:
        self.construct()
        return int(self.bins.shape[1])

    def plane_num_bins(self) -> np.ndarray:
        self.construct()
        if self.bundle_layout is not None:
            return np.asarray(self.bundle_layout.plane_bins, dtype=np.int32)
        return self.num_bins_per_feature()

    def plane_nan_bins(self) -> np.ndarray:
        self.construct()
        if self.bundle_layout is None:
            return np.array(
                [self.bin_mappers[j].nan_bin for j in self.used_features],
                dtype=np.int32,
            )
        # bundle planes never carry a NaN bin (bundling eligibility)
        return np.array(
            [
                self.bin_mappers[feats[0]].nan_bin if len(feats) == 1 else -1
                for feats in self.bundle_layout.planes
            ],
            dtype=np.int32,
        )

    def plane_is_cat(self) -> np.ndarray:
        self.construct()
        if self.bundle_layout is None:
            return np.array(
                [self.bin_mappers[j].is_categorical for j in self.used_features],
                dtype=bool,
            )
        return np.array(
            [
                len(feats) == 1 and self.bin_mappers[feats[0]].is_categorical
                for feats in self.bundle_layout.planes
            ],
            dtype=bool,
        )

    # ------------------------------------------------------------ construct
    def construct(self) -> "Dataset":
        if self._constructed:
            return self
        from .obs.trace import get_tracer

        with get_tracer().span("dataset/construct", "setup", timer=True):
            return self._construct_inner()

    def _construct_inner(self) -> "Dataset":
        from .obs.trace import get_tracer

        data = self._raw_data
        label = self._label
        if isinstance(data, (str, Path)) and _is_binary_dataset_file(str(data)):
            # binary dataset auto-detection (reference: DatasetLoader checks
            # the binary magic before falling back to the text parsers,
            # src/io/dataset_loader.cpp LoadFromBinFile)
            if self.reference is not None:
                raise ValueError(
                    "a binary dataset carries its own bin mappers and "
                    "cannot be re-binned against a reference dataset; "
                    "construct the validation set from the raw data file, "
                    "or save the binary from a Dataset built with "
                    "reference= so its bins already match"
                )
            # explicitly passed per-row fields override the pickled ones
            keep = {
                "label": self._label,
                "weight": self._weight,
                "group": self._group,
                "init_score": self._init_score,
                "position": self._position,
            }
            loaded_ds = Dataset.load_binary(str(data), params=self.params)
            self.__dict__.update(loaded_ds.__dict__)
            self._constructed = True
            for name, val in keep.items():
                if val is not None:
                    self.set_field(name, val)
            return self
        # ---- out-of-core streaming ingest (lightgbm_tpu/ingest): two-pass
        # chunked construction whenever the data is chunk-iterable (the
        # explicit out-of-core API) or ingest_chunk_rows is set.  Bins,
        # bundle layout and the downstream model are byte-identical to the
        # one-shot path; the raw float64 matrix never materializes.
        streamed = self._maybe_construct_streamed(data, label)
        if streamed is not None:
            return streamed
        if data is not None and not isinstance(data, (str, Path)):
            from .ingest.sources import materialize_chunks

            data = materialize_chunks(data)
        if isinstance(data, (str, Path)):
            loaded = _load_text_file(str(data), self.config)
            data = loaded["data"]
            self.parser_config_str = loaded.get("parser_config_str", "")
            self._ignore_set = set(loaded.get("ignore", []))
            if label is None:
                label = loaded.get("label")
            if self._group is None:
                self._group = loaded.get("group")
            if self._weight is None:
                self._weight = loaded.get("weight")
            if self._init_score is None:
                self._init_score = loaded.get("init_score")
            if self._position is None:
                self._position = loaded.get("position")
        if isinstance(data, Sequence):
            data = _materialize_sequences([data])
        elif isinstance(data, list) and data and all(
            isinstance(d, Sequence) for d in data
        ):
            data = _materialize_sequences(data)
        if _is_arrow(data):
            # reuse a reference dataset's dictionaries so valid sets bin
            # categories consistently with the train set
            ref_maps = getattr(
                self.reference, "arrow_categories", None
            ) or getattr(self.reference, "pandas_categorical", None)
            data, names, cats, self.arrow_categories = _arrow_to_numpy(
                data, ref_maps
            )
            if self._feature_name == "auto" and names is not None:
                self._feature_name = names
            if self._categorical_feature == "auto":
                self._categorical_feature = cats
        if label is not None and type(label).__module__.startswith("pyarrow"):
            label = _arrow_column_to_numpy(label)
        if pd is not None and isinstance(data, pd.DataFrame):
            if self._feature_name == "auto":
                self._feature_name = [str(c) for c in data.columns]
            # category/object columns -> stable float codes; valid sets reuse
            # the train set's recorded category order (reference:
            # pandas_categorical in basic.py _data_from_pandas)
            ref_maps = getattr(
                self.reference, "pandas_categorical", None
            ) or getattr(self.reference, "arrow_categories", None)
            data, cats, self.pandas_categorical = _pandas_to_numpy(
                data, ref_maps
            )
            if self._categorical_feature == "auto":
                self._categorical_feature = cats
        if data is None:
            raise ValueError("Dataset has no data")
        sparse_csc = None
        if hasattr(data, "tocsc") and hasattr(data, "nnz"):
            # scipy CSR/CSC (reference: Dataset::CreateFromCSR, c_api.cpp +
            # SparseBin construction, src/io/sparse_bin.hpp): bin directly
            # from the sparse columns — the dense FLOAT matrix is never
            # materialized; only the uint8/16 bin matrix is (zeros fill each
            # feature's zero bin, nonzeros scatter their bins)
            sparse_csc = data.tocsc()
            n, num_features = sparse_csc.shape
        else:
            data = np.asarray(data, dtype=np.float64)
            if data.ndim != 2:
                raise ValueError(f"data must be 2-D, got shape {data.shape}")
            n, num_features = data.shape
        self.num_total_features = num_features

        if label is None:
            raise ValueError("label is required to construct a Dataset")
        label = _is_1d(np.asarray(label, dtype=np.float64))
        if len(label) != n:
            raise ValueError(f"label length {len(label)} != num rows {n}")
        _check_label_finite(label)

        if isinstance(self._feature_name, str):
            self.feature_names = [f"Column_{i}" for i in range(num_features)]
        else:
            self.feature_names = [str(s) for s in self._feature_name]

        cat_idx = self._resolve_categorical(num_features)

        if self.reference is not None:
            ref = self.reference.construct()
            self.bin_mappers = ref.bin_mappers
            self.used_features = ref.used_features
            self.bundle_layout = getattr(ref, "bundle_layout", None)
            self.feature_names = ref.feature_names
            self.num_total_features = ref.num_total_features
            if sparse_csc is not None and sparse_csc.shape[1] < self.num_total_features:
                # a sparse file may simply lack the highest-index features
                # (LibSVM row widths vary); missing columns are all-zero.
                # copy first: tocsc() on a csc_matrix aliases the caller's
                # object and resize() would mutate it
                sparse_csc = sparse_csc.copy()
                sparse_csc.resize(n, self.num_total_features)
        elif sparse_csc is not None:
            with get_tracer().span("dataset/bin_fit", "setup", timer=True):
                self._build_bin_mappers_sparse(sparse_csc, cat_idx)
        else:
            with get_tracer().span("dataset/bin_fit", "setup", timer=True):
                self._build_bin_mappers(data, cat_idx)
        self._sync_mappers_across_processes()

        # ---- EFB (reference dataset.cpp FindGroups): bundle mutually
        # exclusive sparse columns into shared planes BEFORE the footprint
        # check — bundling is exactly what makes sparse-wide data fit the
        # dense plane layout.  Validation sets inherit the reference layout
        # above so planes bin identically.
        if self.reference is None and self.config.enable_bundle \
                and self._bundling_allowed():
            with get_tracer().span("dataset/bundle", "setup", timer=True):
                self.bundle_layout = self._find_bundle_layout(
                    data, sparse_csc, n
                )
        layout = self.bundle_layout
        if layout is not None:
            max_bins = max(layout.plane_bins)
            n_cols = layout.num_planes
        else:
            max_bins = max((m.num_bins for m in self.bin_mappers), default=1)
            n_cols = len(self.used_features)
        dtype = np.uint8 if max_bins <= 256 else np.uint16
        self._check_binned_footprint(n, n_cols, np.dtype(dtype).itemsize)
        if sparse_csc is not None:
            binned = np.zeros((n, n_cols), dtype=dtype)
            for ci, j in enumerate(self.used_features):
                mapper = self.bin_mappers[j]
                sl = slice(sparse_csc.indptr[j], sparse_csc.indptr[j + 1])
                if layout is None:
                    p, bundled = ci, False
                else:
                    p, k = layout.feature_position(j)
                    bundled = layout.is_bundle(p)
                if not bundled:
                    zb = mapper.values_to_bins(np.zeros(1))[0]
                    if zb:
                        binned[:, p] = zb
                    binned[sparse_csc.indices[sl], p] = mapper.values_to_bins(
                        sparse_csc.data[sl]
                    ).astype(dtype)
                else:
                    # bundle member: non-default bins land at start + b - 1;
                    # zeros stay in the shared plane bin 0 (default_bin == 0
                    # is a bundling-eligibility invariant)
                    local = mapper.values_to_bins(sparse_csc.data[sl])
                    layout.pack_sparse_members(
                        binned, p, k, sparse_csc.indices[sl], local
                    )
            self.bins = binned
            if self.config.linear_tree:
                raise ValueError("linear_tree is not supported for sparse input")
            # free_raw_data=False keeps the (row-sliceable) sparse matrix so
            # cv()'s fold slicing works; the dense float is still never built
            self.raw = None if self.free_raw_data else sparse_csc.tocsr()
        else:
            with get_tracer().span("dataset/pack", "setup", timer=True):
                if layout is not None:
                    binned = layout.pack_columns(
                        n,
                        lambda j: self.bin_mappers[j].values_to_bins(
                            data[:, j]
                        ),
                    )
                    self.bins = binned.astype(dtype)
                else:
                    cols = []
                    for j in self.used_features:
                        cols.append(
                            self.bin_mappers[j].values_to_bins(data[:, j])
                        )
                    if cols:
                        binned = np.stack(cols, axis=1)
                    else:
                        binned = np.zeros((n, 0), dtype=np.int32)
                    self.bins = binned.astype(dtype)
            self.raw = (
                data
                if (self.config.linear_tree or not self.free_raw_data)
                else None
            )

        weight = self._weight
        if weight is not None:
            weight = _is_1d(np.asarray(weight, dtype=np.float64))
        init_score = self._init_score
        if init_score is not None:
            init_score = np.asarray(init_score, dtype=np.float64)
        self.metadata = Metadata(label=label, weight=weight, init_score=init_score)
        if self._group is not None:
            self.metadata.set_query(np.asarray(self._group))
        if self._position is not None:
            # per-row result position for unbiased lambdarank
            # (reference Metadata::SetPosition, src/io/metadata.cpp:360)
            pos = np.asarray(self._position)
            if len(pos) != len(label):
                raise ValueError(
                    f"position length {len(pos)} != num_data {len(label)}"
                )
            self.metadata.position = pos

        self._constructed = True
        if self.free_raw_data and not self.config.linear_tree:
            self._raw_data = None
        return self

    def _maybe_construct_streamed(self, data, label) -> Optional["Dataset"]:
        """Route construction through the streaming ingest pipeline, or
        return None for the one-shot path (knob unset, unstreamable
        format, or a mode that needs the raw matrix anyway)."""
        from .ingest.sources import (
            StreamingUnsupported,
            is_chunk_iterable,
            make_chunk_source,
        )

        cfg = self.config
        chunky = is_chunk_iterable(data)
        if data is None or (not chunky and cfg.ingest_chunk_rows <= 0):
            return None
        if hasattr(data, "tocsc") and hasattr(data, "nnz"):
            # sparse input bins column-wise from CSC without ever
            # densifying — already out-of-core in the way that matters
            return None
        if cfg.linear_tree or not self.free_raw_data:
            from .utils.log import log_warning

            log_warning(
                "streaming ingest frees the raw matrix after binning; "
                "linear_tree / free_raw_data=false fall back to one-shot "
                "construction"
            )
            return None
        ref_maps = getattr(
            self.reference, "arrow_categories", None
        ) or getattr(self.reference, "pandas_categorical", None)
        try:
            source = make_chunk_source(data, cfg, ref_maps)
        except StreamingUnsupported:
            return None
        if source is None:
            return None
        return self._construct_streamed(source, label)

    def _construct_streamed(self, source, label) -> "Dataset":
        """Two-pass out-of-core construction (lightgbm_tpu/ingest): pass 1
        draws the one-shot path's exact seeded sample from chunks and fits
        bin mappers + the EFB layout on it; pass 2 streams chunks through
        binning into preallocated packed planes.  Under multi-process
        ``pre_partition`` the sample is assembled GLOBALLY
        (ingest/sharded.py), so every host fits identical mappers from its
        row shard alone."""
        from .ingest.pipeline import stream_pack
        from .ingest.sources import ArrowChunkSource, PandasChunkSource
        from .obs.trace import get_tracer

        cfg = self.config
        n = source.n_rows
        num_features = source.n_cols
        self.num_total_features = num_features
        self.parser_config_str = ""
        self._ignore_set = set(source.ignore_features)
        if isinstance(source, ArrowChunkSource):
            self.arrow_categories = source.category_maps
        elif isinstance(source, PandasChunkSource):
            self.pandas_categorical = source.category_maps
        if self._feature_name == "auto" and getattr(source, "names", None):
            self._feature_name = source.names
        if self._categorical_feature == "auto" and hasattr(source, "cats"):
            self._categorical_feature = source.cats
        if isinstance(self._feature_name, str):
            self.feature_names = [f"Column_{i}" for i in range(num_features)]
        else:
            self.feature_names = [str(s) for s in self._feature_name]
        cat_idx = self._resolve_categorical(num_features)

        sharded = False
        if cfg.pre_partition:
            try:
                import jax

                sharded = jax.process_count() > 1
            except Exception:  # pragma: no cover
                sharded = False

        if self.reference is not None:
            ref = self.reference.construct()
            self.bin_mappers = ref.bin_mappers
            self.used_features = ref.used_features
            self.bundle_layout = getattr(ref, "bundle_layout", None)
            self.feature_names = ref.feature_names
            self.num_total_features = ref.num_total_features
        else:
            with get_tracer().span("dataset/ingest/sample", "setup", timer=True):
                if sharded:
                    from .ingest.sharded import exchange_global_sample

                    # mappers fit from the GLOBAL sample on every host:
                    # no per-rank feature slicing, no mapper allgather,
                    # and EFB layouts agree by construction
                    self._ingest_global_mappers = True
                    _gn, _off, sample = exchange_global_sample(source, cfg)
                else:
                    sample_cnt = min(n, cfg.bin_construct_sample_cnt)
                    if sample_cnt < n:
                        rng = np.random.default_rng(cfg.data_random_seed)
                        rows = np.sort(
                            rng.choice(n, size=sample_cnt, replace=False)
                        )
                    else:
                        rows = np.arange(n, dtype=np.int64)
                    sample = source.sample_rows(rows)
            with get_tracer().span("dataset/ingest/bin_fit", "setup", timer=True):
                self.bin_mappers = []
                self.used_features = []
                for j in range(num_features):
                    self._add_mapper(j, sample[:, j], cat_idx)
            if cfg.enable_bundle and self._bundling_allowed():
                with get_tracer().span("dataset/ingest/bundle", "setup", timer=True):
                    from .bundling import build_layout

                    # nonzero scan over the SAMPLE matrix with the sample
                    # count as the row universe — bit-identical to the
                    # one-shot scan over full data mapped through
                    # sample_rows (bundling.py maps nz to sample positions
                    # and normalizes by the sample count either way)
                    self.bundle_layout = build_layout(
                        self.used_features,
                        self.bin_mappers,
                        lambda j: np.flatnonzero(sample[:, j]),
                        sample.shape[0],
                        sample_rows=None,
                        max_conflict_rate=cfg.max_conflict_rate,
                    )
            del sample

        layout = self.bundle_layout
        if layout is not None:
            max_bins = max(layout.plane_bins)
            n_cols = layout.num_planes
        else:
            max_bins = max(
                (m.num_bins for m in self.bin_mappers), default=1
            )
            n_cols = len(self.used_features)
        dtype = np.uint8 if max_bins <= 256 else np.uint16
        self._check_binned_footprint(n, n_cols, np.dtype(dtype).itemsize)
        with get_tracer().span("dataset/ingest/pack", "setup", timer=True):
            self.bins = stream_pack(
                source, self.bin_mappers, self.used_features, layout,
                dtype, cfg,
            )
        self.raw = None

        fields = source.row_fields()
        if label is None:
            label = fields.get("label")
        if self._group is None:
            self._group = fields.get("group")
        if self._weight is None:
            self._weight = fields.get("weight")
        if self._init_score is None:
            self._init_score = fields.get("init_score")
        if self._position is None:
            self._position = fields.get("position")
        if label is None:
            raise ValueError("label is required to construct a Dataset")
        label = _is_1d(np.asarray(label, dtype=np.float64))
        if len(label) != n:
            raise ValueError(f"label length {len(label)} != num rows {n}")
        _check_label_finite(label)
        weight = self._weight
        if weight is not None:
            weight = _is_1d(np.asarray(weight, dtype=np.float64))
        init_score = self._init_score
        if init_score is not None:
            init_score = np.asarray(init_score, dtype=np.float64)
        self.metadata = Metadata(
            label=label, weight=weight, init_score=init_score
        )
        if self._group is not None:
            self.metadata.set_query(np.asarray(self._group))
        if self._position is not None:
            pos = np.asarray(self._position)
            if len(pos) != len(label):
                raise ValueError(
                    f"position length {len(pos)} != num_data {len(label)}"
                )
            self.metadata.position = pos
        self._constructed = True
        self._raw_data = None
        return self

    def _resolve_categorical(self, num_features: int) -> List[int]:
        cf = self._categorical_feature
        if cf == "auto" or cf is None or cf == "":
            cfg_cf = self.config.categorical_feature
            cf = cfg_cf if cfg_cf not in ("", "auto", None) else []
        if isinstance(cf, str):
            cf = [c for c in cf.split(",") if c != ""]
        out: List[int] = []
        for c in cf:
            if isinstance(c, (int, np.integer)):
                out.append(int(c))
            elif str(c) in self.feature_names:
                out.append(self.feature_names.index(str(c)))
            else:
                out.append(int(str(c).replace("name:", "")) if str(c).isdigit() else -1)
        return [c for c in out if 0 <= c < num_features]

    def _sync_mappers_across_processes(self) -> None:
        """Distributed binning (reference:
        DatasetLoader::ConstructBinMappersFromTextData,
        src/io/dataset_loader.cpp:1079): under ``pre_partition`` each process
        holds only its local rows, so per-process quantile mappers would
        disagree.  Like the reference, each rank keeps the mappers for its
        CONTIGUOUS feature slice (built from local rows) and the slices are
        allgathered so every process ends with identical mappers; binning
        then proceeds locally."""
        if not self.config.pre_partition:
            return
        try:
            import jax

            nproc = jax.process_count()
        except Exception:  # pragma: no cover
            return
        if nproc <= 1:
            return
        from .parallel import allgather_host_exact

        f = len(self.bin_mappers)
        rank = jax.process_index()
        mb_max = max(
            [int(self.config.max_bin), 2]
            + [int(m) for m in self.config.max_bin_by_feature]
        )
        width = 16 + 2 * mb_max
        local = np.zeros((f, width), np.float64)
        per = (f + nproc - 1) // nproc
        lo, hi = rank * per, min(f, (rank + 1) * per)
        for j in range(lo, hi):
            local[j] = self.bin_mappers[j].to_vector(width)
        # bit-exact gather: boundaries are float64 and a lossy f32 roundtrip
        # would bin train rows differently per... identically-wrong on every
        # process, but differently from single-process binning of the same
        # sample (observed: 1e-35 -> 1.00000002e-35)
        gathered = allgather_host_exact(local)  # [nproc, F, W]
        mappers: List[BinMapper] = []
        for j in range(f):
            owner = min(j // per, nproc - 1)
            mappers.append(BinMapper.from_vector(gathered[owner, j]))
        self.bin_mappers = mappers
        self.used_features = [
            j for j in range(f) if not mappers[j].is_trivial
        ]

    def _owned_feature_range(self, f: int):
        """Under pre_partition + multi-process, the contiguous feature slice
        this rank bins (others arrive via the mapper allgather); None when
        every feature is local."""
        if getattr(self, "_ingest_global_mappers", False):
            # streamed sharded ingest fits every mapper from the GLOBAL
            # sample (ingest/sharded.py): no per-rank feature slicing
            return None
        if not self.config.pre_partition:
            return None
        try:
            import jax

            nproc = jax.process_count()
        except Exception:  # pragma: no cover
            return None
        if nproc <= 1:
            return None
        per = (f + nproc - 1) // nproc
        rank = jax.process_index()
        return rank * per, min(f, (rank + 1) * per)

    def _add_mapper(self, j: int, values: np.ndarray, cat_idx: List[int],
                    total_cnt: Optional[int] = None) -> None:
        """Shared per-feature mapper construction for the dense and sparse
        builders (max_bin_by_feature lookup + trivial-feature pruning)."""
        cfg = self.config
        if j in self._ignore_set:
            # ignore_column / weight_column / group_column features stay in
            # the column count (reference keeps original feature numbering)
            # but never train: a trivial mapper drops them from used_features
            self.bin_mappers.append(
                BinMapper(bin_upper_bound=np.array([np.inf]), num_bins=1)
            )
            return
        owned = self._owned_feature_range(self.num_total_features)
        if owned is not None and not (owned[0] <= j < owned[1]):
            # another rank bins this feature; a placeholder keeps indices
            # aligned until _sync_mappers_across_processes replaces it
            self.bin_mappers.append(
                BinMapper(bin_upper_bound=np.array([np.inf]), num_bins=1)
            )
            return
        mb = (
            cfg.max_bin_by_feature[j]
            if j < len(cfg.max_bin_by_feature)
            else cfg.max_bin
        )
        mapper = BinMapper.from_sample(
            values,
            mb,
            is_categorical=j in cat_idx,
            min_data_in_bin=cfg.min_data_in_bin,
            use_missing=cfg.use_missing,
            zero_as_missing=cfg.zero_as_missing,
            total_cnt=total_cnt,
            forced_bounds=self._forced_bin_bounds(j, cat_idx),
        )
        self.bin_mappers.append(mapper)
        if not mapper.is_trivial:
            self.used_features.append(j)

    def _check_binned_footprint(self, n: int, n_cols: int, itemsize: int):
        """Enforce the dense-layout memory ceiling with an actionable error.

        The TPU build stores bins as ONE dense [N, P] matrix (module
        docstring); the check runs AFTER the EFB bundling decision, so the
        column count already reflects the bundled plane count.  A dataset
        still over the ceiling (bundling off, or columns that are not
        mutually exclusive) would materialize hundreds of GB and OOM deep
        inside allocation — fail early and say what to do: exclusive
        one-hot blocks bundle away with enable_bundle=true (or carry the
        same information as ONE integer-coded categorical column,
        categorical_feature= + sorted-subset splits)."""
        import os

        est = n * max(1, n_cols) * itemsize
        ceiling = int(
            os.environ.get("LGBM_TPU_MAX_BINNED_BYTES", 16 << 30)
        )
        if est > ceiling:
            bundled = (
                f" after bundling into {n_cols} planes"
                if self.bundle_layout is not None
                else ""
            )
            raise ValueError(
                f"binned dataset would need {est / (1 << 30):.1f} GiB "
                f"({n} rows x {n_cols} columns{bundled}, dense layout) — "
                f"over the {ceiling / (1 << 30):.1f} GiB ceiling. Enable "
                "EFB feature bundling (enable_bundle=true, on by default) "
                "for mutually-exclusive sparse columns, encode exclusive "
                "one-hot column blocks as a single integer-coded "
                "categorical feature (categorical_feature=...), drop "
                "empty/constant columns, or raise LGBM_TPU_MAX_BINNED_BYTES "
                "if the footprint is intended."
            )

    def _bundling_allowed(self) -> bool:
        """EFB is skipped under multi-process pre_partition feeding: the
        conflict scan sees only local rows, so per-process layouts would
        disagree (the mapper allgather has no layout channel yet)."""
        if getattr(self, "_ingest_global_mappers", False):
            # streamed sharded ingest scans conflicts on the allgathered
            # GLOBAL sample — identical layout on every process
            return True
        if not self.config.pre_partition:
            return True
        try:
            import jax

            return jax.process_count() <= 1
        except Exception:  # pragma: no cover
            return True

    def _find_bundle_layout(self, data, sparse_csc, n: int):
        """Greedy conflict-count bundling over a row sample (reference
        DatasetLoader FindGroups; bundling.py has the algorithm)."""
        from .bundling import build_layout

        cfg = self.config
        if sparse_csc is not None:
            indptr = sparse_csc.indptr
            indices = sparse_csc.indices
            vals = sparse_csc.data

            def nonzeros_of(j):
                sl = slice(indptr[j], indptr[j + 1])
                idx = indices[sl]
                return np.sort(idx[vals[sl] != 0])
        else:

            def nonzeros_of(j):
                return np.flatnonzero(data[:, j])

        sample_cnt = min(n, cfg.bin_construct_sample_cnt)
        sample_rows = None
        if sample_cnt < n:
            rng = np.random.default_rng(cfg.data_random_seed)
            sample_rows = np.sort(
                rng.choice(n, size=sample_cnt, replace=False)
            )
        return build_layout(
            self.used_features,
            self.bin_mappers,
            nonzeros_of,
            n,
            sample_rows=sample_rows,
            max_conflict_rate=cfg.max_conflict_rate,
        )

    def _forced_bin_bounds(self, j: int, cat_idx: List[int]):
        """User-forced bin upper bounds for feature j, or None.

        ``forcedbins_filename`` points at a JSON array of
        ``{"feature": i, "bin_upper_bound": [...]}`` records (reference:
        DatasetLoader::GetForcedBins, src/io/dataset_loader.cpp:1431);
        categorical features ignore their record with a warning, duplicate
        bounds are dropped."""
        path = getattr(self.config, "forcedbins_filename", "")
        if not path:
            return None
        if getattr(self, "_forced_bins_cache", None) is None:
            import json

            from .utils.log import log_warning

            table = {}
            try:
                with open(path) as fh:
                    records = json.load(fh)
                for rec in records:
                    fi = int(rec["feature"])
                    bounds = [float(v) for v in rec.get("bin_upper_bound", [])]
                    # remove consecutive duplicates (reference std::unique)
                    dedup: List[float] = []
                    for b in bounds:
                        if not dedup or b != dedup[-1]:
                            dedup.append(b)
                    table[fi] = dedup
            except (OSError, ValueError, TypeError, KeyError, AttributeError):
                # unreadable OR malformed (bad JSON, wrong shape, missing
                # keys): warn and ignore, as the reference's GetForcedBins
                # does — never crash construct()
                log_warning(f"Could not parse {path}. Will ignore.")
                table = {}
            self._forced_bins_cache = table
        if j not in self._forced_bins_cache:
            return None
        if j in cat_idx:
            from .utils.log import log_warning

            log_warning(
                f"Feature {j} is categorical. Will ignore forced bins for "
                "this feature."
            )
            return None
        return self._forced_bins_cache[j]

    def _build_bin_mappers(self, data: np.ndarray, cat_idx: List[int]) -> None:
        cfg = self.config
        n = data.shape[0]
        sample_cnt = min(n, cfg.bin_construct_sample_cnt)
        if sample_cnt < n:
            rng = np.random.default_rng(cfg.data_random_seed)
            sample_rows = rng.choice(n, size=sample_cnt, replace=False)
            sample = data[np.sort(sample_rows)]
        else:
            sample = data
        self.bin_mappers = []
        self.used_features = []
        for j in range(data.shape[1]):
            self._add_mapper(j, sample[:, j], cat_idx)

    def _build_bin_mappers_sparse(self, csc, cat_idx: List[int]) -> None:
        """Per-column binning from CSC nonzeros; zeros enter as an implied
        count (reference: BinMapper::FindBin's zero_cnt handling,
        src/io/bin.cpp — the sparse loader never expands columns)."""
        cfg = self.config
        n = csc.shape[0]
        self.bin_mappers = []
        self.used_features = []
        # sampling: cap the per-column nonzeros considered, like
        # bin_construct_sample_cnt caps rows for the dense path
        sample_cnt = min(n, cfg.bin_construct_sample_cnt)
        frac = sample_cnt / n
        rng = np.random.default_rng(cfg.data_random_seed)
        for j in range(csc.shape[1]):
            sl = slice(csc.indptr[j], csc.indptr[j + 1])
            vals = np.asarray(csc.data[sl], dtype=np.float64)
            total = n
            if frac < 1.0 and len(vals) > 0:
                keep = rng.random(len(vals)) < frac
                vals = vals[keep]
                # the binomial draw can keep more than sample_cnt * density
                # nonzeros; never let the implied zero count go negative
                total = max(sample_cnt, len(vals))
            if j in cat_idx and total > len(vals):
                # categorical zeros are a real category, not an implied bin
                vals = np.concatenate([vals, np.zeros(total - len(vals))])
            self._add_mapper(j, vals, cat_idx, total_cnt=total)

    # ----------------------------------------------------------- field API
    def set_label(self, label: np.ndarray) -> "Dataset":
        if self._constructed:
            arr = _is_1d(np.asarray(label, dtype=np.float64))
            _check_label_finite(arr)
            self.metadata.label = arr
            self._device_cache.clear()
        else:
            self._label = label
        return self

    def set_weight(self, weight: Optional[np.ndarray]) -> "Dataset":
        if self._constructed:
            self.metadata.weight = (
                None if weight is None else _is_1d(np.asarray(weight, dtype=np.float64))
            )
            self._device_cache.clear()
        else:
            self._weight = weight
        return self

    def set_group(self, group: Optional[np.ndarray]) -> "Dataset":
        if self._constructed:
            if group is not None:
                self.metadata.set_query(np.asarray(group))
        else:
            self._group = group
        return self

    def set_position(self, position: Optional[np.ndarray]) -> "Dataset":
        if position is not None and self._constructed:
            position = np.asarray(position)
            if len(position) != self.num_data:
                raise ValueError(
                    f"position length {len(position)} != num_data {self.num_data}"
                )
        if self._constructed:
            self.metadata.position = position
        else:
            self._position = position
        return self

    def get_data(self):
        """Raw data if retained (reference basic.py get_data; requires
        free_raw_data=False)."""
        self.construct()
        if self.raw is None:
            raise ValueError(
                "raw data was freed; construct the Dataset with "
                "free_raw_data=False to keep it"
            )
        return self.raw

    def get_feature_name(self) -> List[str]:
        self.construct()
        return list(self.feature_names)

    def set_feature_name(self, feature_name) -> "Dataset":
        if feature_name is None or (
            isinstance(feature_name, str) and feature_name == "auto"
        ):
            return self
        names = [str(s) for s in feature_name]
        if self._constructed:
            if len(names) != self.num_total_features:
                raise ValueError(
                    f"{len(names)} feature names for "
                    f"{self.num_total_features} features"
                )
            self.feature_names = names
        else:
            self._feature_name = names
        return self

    def set_categorical_feature(self, categorical_feature) -> "Dataset":
        if self._constructed:
            raise ValueError(
                "cannot change categorical_feature after construction; "
                "create a new Dataset"
            )
        self._categorical_feature = categorical_feature
        return self

    def set_reference(self, reference: "Dataset") -> "Dataset":
        if self._constructed:
            raise ValueError(
                "cannot change reference after construction; create a new Dataset"
            )
        self.reference = reference
        return self

    def get_ref_chain(self, ref_limit: int = 100):
        """Set of datasets reachable via reference links (basic.py)."""
        head = self
        chain = set()
        while head is not None and len(chain) < ref_limit:
            if head in chain:
                break
            chain.add(head)
            head = head.reference
        return chain

    def feature_num_bin(self, feature) -> int:
        """Number of bins for a feature (reference LGBM_DatasetGetFeatureNumBin)."""
        self.construct()
        if isinstance(feature, str):
            feature = self.feature_names.index(feature)
        return int(self.bin_mappers[feature].num_bins)

    def get_position(self):
        self.construct()
        return self.metadata.position

    def add_features_from(self, other: "Dataset") -> "Dataset":
        """Column-concatenate another dataset's features (reference
        LGBM_DatasetAddFeaturesFrom). Both must be constructed and have the
        same row count."""
        self.construct()
        other.construct()
        if self.num_data != other.num_data:
            raise ValueError("datasets must have the same number of rows")
        if self.bundle_layout is not None or other.bundle_layout is not None:
            raise ValueError(
                "add_features_from is not supported on EFB-bundled datasets "
                "(plane columns are not per-feature); construct with "
                "enable_bundle=false to merge"
            )
        base_f = self.num_total_features
        self.bin_mappers = list(self.bin_mappers) + list(other.bin_mappers)
        self.used_features = list(self.used_features) + [
            base_f + j for j in other.used_features
        ]
        self.bins = np.concatenate(
            [
                self.bins.astype(np.uint16),
                other.bins.astype(np.uint16),
            ],
            axis=1,
        )
        if self.bins.max(initial=0) < 256:
            self.bins = self.bins.astype(np.uint8)
        self.feature_names = list(self.feature_names) + list(other.feature_names)
        self.num_total_features = base_f + other.num_total_features
        if self.raw is not None and other.raw is not None:
            if hasattr(self.raw, "toarray") or hasattr(other.raw, "toarray"):
                import scipy.sparse as sp

                self.raw = sp.hstack(
                    [sp.csr_matrix(self.raw), sp.csr_matrix(other.raw)]
                ).tocsr()
            else:
                self.raw = np.concatenate([self.raw, other.raw], axis=1)
        elif self.raw is not None:
            from .utils.log import log_warning

            log_warning(
                "cannot merge raw data: the other dataset freed its raw "
                "data; the merged dataset keeps none (reference warns too)"
            )
            self.raw = None
        self._device_cache.clear()
        return self

    def set_init_score(self, init_score: Optional[np.ndarray]) -> "Dataset":
        if self._constructed:
            self.metadata.init_score = (
                None if init_score is None else np.asarray(init_score, dtype=np.float64)
            )
        else:
            self._init_score = init_score
        return self

    def get_label(self) -> np.ndarray:
        self.construct()
        return self.metadata.label

    def get_weight(self) -> Optional[np.ndarray]:
        self.construct()
        return self.metadata.weight

    def get_group(self) -> Optional[np.ndarray]:
        self.construct()
        qb = self.metadata.query_boundaries
        return None if qb is None else np.diff(qb)

    def get_init_score(self) -> Optional[np.ndarray]:
        self.construct()
        return self.metadata.init_score

    def get_field(self, name: str):
        getters = {
            "label": self.get_label,
            "weight": self.get_weight,
            "group": self.get_group,
            "init_score": self.get_init_score,
            "position": self.get_position,
        }
        if name not in getters:
            raise KeyError(name)
        return getters[name]()

    def set_field(self, name: str, value) -> "Dataset":
        setters = {
            "label": self.set_label,
            "weight": self.set_weight,
            "group": self.set_group,
            "init_score": self.set_init_score,
            "position": self.set_position,
        }
        if name not in setters:
            raise KeyError(name)
        return setters[name](value)

    def create_valid(
        self,
        data,
        label=None,
        weight=None,
        group=None,
        init_score=None,
        params=None,
    ) -> "Dataset":
        return Dataset(
            data,
            label,
            reference=self,
            weight=weight,
            group=group,
            init_score=init_score,
            params=params if params is not None else self.params,
        )

    # ------------------------------------------------------------- binary IO
    def save_binary(self, filename: str) -> "Dataset":
        """Serialize the constructed (binned) dataset (reference:
        Dataset::SaveBinaryFile via save_binary, src/io/dataset_loader.cpp:424).
        Format: npz with bins, metadata and per-feature mapper tables."""
        self.construct()
        import pickle

        with open(filename, "wb") as fh:
            pickle.dump(
                {
                    "format": "lightgbm_tpu.dataset.v1",
                    "bins": self.bins,
                    "used_features": self.used_features,
                    "bundle_layout": self.bundle_layout,
                    "bin_mappers": self.bin_mappers,
                    "feature_names": self.feature_names,
                    "num_total_features": self.num_total_features,
                    "label": self.metadata.label,
                    "weight": self.metadata.weight,
                    "init_score": self.metadata.init_score,
                    "query_boundaries": self.metadata.query_boundaries,
                    "position": getattr(self.metadata, "position", None),
                    "arrow_categories": self.arrow_categories,
                    "pandas_categorical": self.pandas_categorical,
                    # parser_config_str_ persists with the binary dataset
                    # (reference dataset.cpp SaveBinaryFile / :875 load)
                    "parser_config_str": getattr(
                        self, "parser_config_str", ""
                    ),
                    "raw": self.raw,
                },
                fh,
            )
        return self

    @classmethod
    def load_binary(cls, filename: str, params=None) -> "Dataset":
        import pickle

        with open(filename, "rb") as fh:
            blob = pickle.load(fh)
        if blob.get("format") != "lightgbm_tpu.dataset.v1":
            raise ValueError(f"{filename} is not a lightgbm_tpu binary dataset")
        ds = cls.__new__(cls)
        ds.params = dict(params or {})
        ds.config = Config.from_params(ds.params)
        ds._raw_data = None
        ds._label = None
        ds._weight = None
        ds._group = None
        ds._init_score = None
        ds._feature_name = "auto"
        ds._categorical_feature = "auto"
        ds.reference = None
        ds.free_raw_data = True
        ds._constructed = True
        ds.arrow_categories = blob.get("arrow_categories")
        ds.pandas_categorical = blob.get("pandas_categorical")
        ds.parser_config_str = blob.get("parser_config_str", "")
        ds.bin_mappers = blob["bin_mappers"]
        ds.used_features = blob["used_features"]
        ds.bundle_layout = blob.get("bundle_layout")
        ds._ignore_set = set()
        ds.bins = blob["bins"]
        ds.raw = blob.get("raw")
        ds.feature_names = blob["feature_names"]
        ds.num_total_features = blob["num_total_features"]
        ds._position = None
        ds.metadata = Metadata(
            label=blob["label"],
            weight=blob["weight"],
            init_score=blob["init_score"],
            query_boundaries=blob["query_boundaries"],
        )
        ds.metadata.position = blob.get("position")
        ds._device_cache = {}
        return ds

    def subset(self, used_indices, params=None) -> "Dataset":
        """Row subset sharing the bin mappers (reference: Dataset::CopySubrow,
        python basic.py Dataset.subset)."""
        self.construct()
        idx = np.asarray(used_indices, dtype=np.int64)
        ds = Dataset.__new__(Dataset)
        ds.params = dict(params or self.params)
        ds.config = Config.from_params(ds.params)
        ds._raw_data = None
        ds._label = None
        ds._weight = None
        ds._group = None
        ds._init_score = None
        ds._feature_name = "auto"
        ds._categorical_feature = "auto"
        ds.reference = self
        ds.free_raw_data = self.free_raw_data
        ds._constructed = True
        ds.arrow_categories = self.arrow_categories
        ds.pandas_categorical = self.pandas_categorical
        ds.parser_config_str = getattr(self, "parser_config_str", "")
        ds.bin_mappers = self.bin_mappers
        ds.used_features = self.used_features
        ds.bundle_layout = self.bundle_layout
        ds._ignore_set = set()
        ds.bins = self.bins[idx]
        ds.raw = None if self.raw is None else self.raw[idx]
        ds.feature_names = self.feature_names
        ds.num_total_features = self.num_total_features
        md = self.metadata
        ds.metadata = Metadata(
            label=md.label[idx],
            weight=None if md.weight is None else md.weight[idx],
            init_score=None if md.init_score is None else md.init_score[idx],
        )
        ds._device_cache = {}
        return ds

    # -------------------------------------------------------------- device
    def device_bins(self):
        """The binned matrix as a device array (cached)."""
        import jax.numpy as jnp

        self.construct()
        if "bins" not in self._device_cache:
            # keep the narrow host dtype (uint8/uint16): 4x less HBM traffic
            # for every gather in the grower and 4x smaller kernel tiles; the
            # Pallas kernel widens per-tile in VMEM
            from .obs.trace import traced_transfer

            self._device_cache["bins"] = traced_transfer(
                "bins", lambda: jnp.asarray(self.bins)
            )
        return self._device_cache["bins"]

    def device_label(self):
        import jax.numpy as jnp

        self.construct()
        if "label" not in self._device_cache:
            self._device_cache["label"] = jnp.asarray(self.metadata.label, dtype=jnp.float32)
        return self._device_cache["label"]

    def device_weight(self):
        import jax.numpy as jnp

        self.construct()
        if "weight" not in self._device_cache:
            w = self.metadata.weight
            self._device_cache["weight"] = (
                None if w is None else jnp.asarray(w, dtype=jnp.float32)
            )
        return self._device_cache["weight"]
