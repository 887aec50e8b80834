"""Binned training data: the TPU-facing data representation.

The reference stores bins in per-group `Bin` columns with EFB bundling and
sparse/dense specializations (reference src/io/dataset.cpp:265, include/
LightGBM/feature_group.h:37).  TPU-first, the binned matrix is instead ONE
fixed-shape `[n_rows, n_features]` integer array resident in HBM — the analog
of the GPU learner's `Feature4` packing (reference src/treelearner/
gpu_tree_learner.cpp:354-527) — because the histogram kernel consumes all
features of a row block at once via one-hot contractions on the MXU.

`TrainingData` owns:
  * per-feature `BinMapper`s (shared with validation sets, like the reference's
    `CreateValid` alignment, dataset.h:501),
  * the host binned matrix (uint8/uint16) and its device copy,
  * `Metadata` (labels / weights / query boundaries / init scores,
    reference src/io/metadata.cpp).
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence

import os

import numpy as np

from ..config import Config
from .bin_mapper import BinMapper, BinType, MissingType, K_ZERO_THRESHOLD
from .parser import load_text_file


def _is_scipy_sparse(data) -> bool:
    """scipy.sparse matrix/array, detected without importing scipy."""
    return hasattr(data, "tocsc") and hasattr(data, "nnz")


def _parallel_columns(fn, count: int, config: Optional[Config]) -> None:
    """Fan per-column ingest work out on a thread pool — the analog of
    the reference's OpenMP-parallel `ConstructBinMappersFromData`
    (dataset_loader.cpp:696).  numpy's sort / searchsorted release the
    GIL on large arrays, so column work genuinely overlaps.  Output is
    deterministic: every column writes only its own pre-allocated slot,
    and `fn` is pure per column."""
    workers = int(getattr(config, "num_threads", 0) or 0) if config else 0
    if workers <= 0:
        workers = os.cpu_count() or 1
    workers = min(workers, count)
    if workers <= 1 or count <= 1:
        for j in range(count):
            fn(j)
        return
    from concurrent.futures import ThreadPoolExecutor

    with ThreadPoolExecutor(max_workers=workers) as ex:
        # list() drains the iterator so worker exceptions propagate
        list(ex.map(fn, range(count)))


class Metadata:
    """Labels, weights, query boundaries, init scores (reference dataset.h:87)."""

    def __init__(self, num_data: int, label: Optional[np.ndarray] = None,
                 weight: Optional[np.ndarray] = None,
                 group_sizes: Optional[np.ndarray] = None,
                 init_score: Optional[np.ndarray] = None):
        self.num_data = num_data
        self.label = (np.zeros(num_data, dtype=np.float32) if label is None
                      else np.asarray(label, dtype=np.float32))
        self.weight = None if weight is None else np.asarray(weight, dtype=np.float32)
        self.init_score = (None if init_score is None
                           else np.asarray(init_score, dtype=np.float64))
        if group_sizes is not None:
            gs = np.asarray(group_sizes, dtype=np.int64)
            self.query_boundaries = np.concatenate([[0], np.cumsum(gs)]).astype(np.int64)
            if self.query_boundaries[-1] != num_data:
                raise ValueError(
                    f"sum of query sizes ({self.query_boundaries[-1]}) != num_data ({num_data})")
        else:
            self.query_boundaries = None

    @property
    def num_queries(self) -> int:
        return 0 if self.query_boundaries is None else len(self.query_boundaries) - 1

    def query_weights(self) -> Optional[np.ndarray]:
        """Per-query weight = mean of row weights inside the query; None when
        rows are unweighted (reference src/io/metadata.cpp:461-470)."""
        if self.query_boundaries is None or self.weight is None:
            return None
        w = np.asarray(self.weight, np.float64)
        sums = np.add.reduceat(w, self.query_boundaries[:-1])
        return sums / np.diff(self.query_boundaries)

    def set_field(self, name: str, data: Optional[np.ndarray]) -> None:
        if name == "label":
            self.label = np.asarray(data, dtype=np.float32)
        elif name == "weight":
            self.weight = None if data is None else np.asarray(data, dtype=np.float32)
        elif name in ("group", "query"):
            if data is None:
                self.query_boundaries = None
            else:
                gs = np.asarray(data, dtype=np.int64)
                self.query_boundaries = np.concatenate([[0], np.cumsum(gs)]).astype(np.int64)
        elif name == "init_score":
            self.init_score = None if data is None else np.asarray(data, dtype=np.float64)
        else:
            raise ValueError(f"unknown field {name}")

    def get_field(self, name: str) -> Optional[np.ndarray]:
        if name == "label":
            return self.label
        if name == "weight":
            return self.weight
        if name in ("group", "query"):
            return self.query_boundaries
        if name == "init_score":
            return self.init_score
        raise ValueError(f"unknown field {name}")


def _load_forced_bins(config: Config) -> Dict[int, List[float]]:
    """Load forcedbins_filename JSON: [{"feature": i, "bin_upper_bound": [...]}]

    (reference src/io/dataset_loader.cpp:1246 GetForcedBins).
    """
    path = config.forcedbins_filename
    if not path:
        return {}
    import json
    with open(path) as f:
        entries = json.load(f)
    out: Dict[int, List[float]] = {}
    for e in entries:
        out[int(e["feature"])] = [float(x) for x in e["bin_upper_bound"]]
    return out


def _parse_column_spec(spec: str, feature_names: List[str]) -> List[int]:
    """Parse '0,1,2' or 'name:a,b,c' into column indices."""
    if not spec:
        return []
    s = str(spec)
    if s.startswith("name:"):
        names = [x.strip() for x in s[5:].split(",") if x.strip()]
        return [feature_names.index(n) for n in names if n in feature_names]
    return [int(x) for x in s.replace(";", ",").split(",") if x != ""]


class TrainingData:
    """Binned dataset + metadata. The unit the tree learners consume."""

    def __init__(self) -> None:
        self.num_data: int = 0
        self.num_total_features: int = 0
        self.used_feature_idx: List[int] = []     # used col -> original col
        self.mappers: List[BinMapper] = []        # one per ORIGINAL column
        self._bins: Optional[np.ndarray] = None   # [n, num_used] uint8/uint16
        # device-resident [n, num_used]: one array, or ops/binning.RowParts
        # where ingest dealt the rows to a row-sharded learner's chips
        self._ingest_bins = None
        self.metadata: Optional[Metadata] = None
        self.feature_names: List[str] = []
        self.config: Optional[Config] = None
        self.monotone_constraints: Optional[np.ndarray] = None  # per used feature
        self.feature_penalty: Optional[np.ndarray] = None       # per used feature
        self._device_bins = None

    # ------------------------------------------------------------------
    @property
    def bins(self) -> Optional[np.ndarray]:
        """Host binned matrix.  When ingest ran on device the host copy
        materializes LAZILY here, on first access by a host consumer
        (EFB planning, get_data, save_binary, subset) — the device fast
        path never pays for it."""
        if self._bins is None and self._ingest_bins is not None:
            self._bins = np.asarray(self._ingest_bins)
        return self._bins

    @bins.setter
    def bins(self, value: Optional[np.ndarray]) -> None:
        self._bins = value
        self._ingest_bins = None
        self._device_bins = None

    @property
    def has_bins(self) -> bool:
        """True when ANY binned representation exists (host or device).
        Check this instead of `bins is None`: the property fetch would
        force a host materialization of a device-resident matrix."""
        return self._bins is not None or self._ingest_bins is not None

    def device_ingest_bins(self):
        """The device-resident narrow-dtype bin matrix (an array, or a
        RowParts over several chips), or None when the host copy is
        authoritative (host ingest, or a consumer already materialized +
        possibly mutated through the property)."""
        return self._ingest_bins if self._bins is None else None

    def ingest_matrix(self):
        """The device-ingested matrix as ONE device array; row parts are
        gathered onto their first chip (a whole-table consumer's cost)."""
        from ..ops.binning import RowParts

        return RowParts.of(self._ingest_bins).gathered()

    @property
    def num_features(self) -> int:
        return len(self.used_feature_idx)

    @property
    def max_num_bin(self) -> int:
        if not self.used_feature_idx:
            return 1
        return max(self.mappers[i].num_bin for i in self.used_feature_idx)

    def feature_arrays(self) -> Dict[str, np.ndarray]:
        """Per-used-feature static arrays consumed by the device grower."""
        idx = self.used_feature_idx
        num_bin = np.array([self.mappers[i].num_bin for i in idx], dtype=np.int32)
        missing = np.array([int(self.mappers[i].missing_type) for i in idx], dtype=np.int32)
        default_bin = np.array([self.mappers[i].default_bin for i in idx], dtype=np.int32)
        is_categorical = np.array(
            [self.mappers[i].bin_type == BinType.CATEGORICAL for i in idx], dtype=bool)
        mono = (self.monotone_constraints if self.monotone_constraints is not None
                else np.zeros(len(idx), dtype=np.int32))
        penalty = (self.feature_penalty if self.feature_penalty is not None
                   else np.ones(len(idx), dtype=np.float32))
        return {"num_bin": num_bin, "missing_type": missing,
                "default_bin": default_bin, "is_categorical": is_categorical,
                "monotone": mono.astype(np.int32), "penalty": penalty.astype(np.float32)}

    def device_bins(self):
        """Device int32 copy of the binned matrix (cached).  Ingest that
        ran on device just widens in place — no host round trip."""
        import jax.numpy as jnp
        if self._device_bins is None:
            if self._ingest_bins is not None:
                self._device_bins = self.ingest_matrix().astype(jnp.int32)
            else:
                self._device_bins = jnp.asarray(self.bins.astype(np.int32))
        return self._device_bins

    # -- reductions host consumers ask for without forcing the full
    # host matrix (the learner's layout step reads these) -------------
    def column_zero_fraction(self) -> np.ndarray:
        """Per-used-column fraction of rows at bin 0 (the EFB candidate
        gate).  Device-resident matrices reduce on device and fetch only
        the [F] counts; the division happens in f64 on the host either
        way, so the result is bit-identical to `(bins == 0).mean(0)`."""
        dev = self.device_ingest_bins()
        if dev is not None:
            from ..ops.binning import RowParts

            cnt = RowParts.of(dev).column_counts(lambda p: p == 0)
            return cnt.astype(np.float64) / max(self.num_data, 1)
        return (self.bins == 0).mean(axis=0)

    def column_nonzero_counts(self, zero_bins: np.ndarray) -> np.ndarray:
        """Per-used-column count of rows NOT at that column's zero bin
        (the sparse-storage gate).  One vectorized pass — device reduce
        when resident, row-chunked host sweep otherwise (bounds the
        boolean temporary on Bosch-shaped data)."""
        zb = np.asarray(zero_bins)
        dev = self.device_ingest_bins()
        if dev is not None:
            from ..ops.binning import RowParts

            zb32 = zb.astype(np.int32)[None, :]
            return RowParts.of(dev).column_counts(lambda p: p != zb32)
        bins = self.bins
        n = bins.shape[0]
        step = max((1 << 28) // max(bins.shape[1], 1), 1024)
        out = np.zeros(bins.shape[1], np.int64)
        for lo in range(0, n, step):
            out += (bins[lo:lo + step] != zb[None, :]).sum(axis=0)
        return out

    def strided_row_sample(self, quota: int) -> np.ndarray:
        """The deterministic strided row sample `bundling._stride_sample`
        would take, fetched as a host array — a device slice-gather when
        resident, so EFB planning never pulls the full matrix."""
        dev = self.device_ingest_bins()
        if dev is None:
            from .bundling import _stride_sample

            return _stride_sample(self.bins, quota)
        from ..ops.binning import RowParts

        n = self.num_data
        if n > quota:
            return RowParts.of(dev).take(np.arange(0, n, n // quota)[:quota])
        return np.asarray(dev)

    # ------------------------------------------------------------------
    @classmethod
    def from_matrix(cls, X: np.ndarray, label: Optional[np.ndarray] = None,
                    config: Optional[Config] = None,
                    weight: Optional[np.ndarray] = None,
                    group_sizes: Optional[np.ndarray] = None,
                    init_score: Optional[np.ndarray] = None,
                    reference: Optional["TrainingData"] = None,
                    feature_names: Optional[List[str]] = None,
                    categorical_features: Optional[Sequence[int]] = None,
                    forced_bins: Optional[Dict[int, List[float]]] = None,
                    ) -> "TrainingData":
        """Bin a raw float matrix.

        With `reference` given, reuses its BinMappers (validation-set
        alignment, reference dataset.h:501 CreateValid).
        """
        config = config or Config()
        # arm the telemetry policy BEFORE the ingest phases run: the
        # train set constructs ahead of the GBDT driver, and its
        # sketch/binning spans must not be lost to ordering
        from .. import obs

        obs.configure_from_config(config)
        with obs.span("dataset/construct", source="matrix"):
            X = np.asarray(X, dtype=np.float64)
            if X.ndim != 2:
                raise ValueError("X must be 2-D")
            n, nf = X.shape
            self = cls()
            self.config = config
            self.num_data = n
            self.num_total_features = nf
            self.feature_names = (list(feature_names) if feature_names
                                  else [f"Column_{i}" for i in range(nf)])

            from ..utils import timer

            with timer.PHASE("sketch"):
                if reference is not None:
                    self._adopt_reference_mappers(reference)
                else:
                    self._find_mappers_maybe_distributed(
                        X, config, categorical_features or [], forced_bins or {})

            # bin all used columns: device chunk-streamed kernel on the fast
            # path, host per-column numpy otherwise
            with timer.PHASE("binning"):
                dtype = np.uint8 if self.max_num_bin <= 256 else np.uint16
                binner = self._make_device_binner(config, dtype, n)
                if binner is not None:
                    self._ingest_bins = binner.bin_matrix(
                        X, self._row_shard_devices(config))
                    self._bins = None
                else:
                    bins = np.empty((n, self.num_features), dtype=dtype)

                    def _bin_col(j: int) -> None:
                        col = self.used_feature_idx[j]
                        # contiguous column copy: searchsorted on a strided
                        # view costs ~40% more than the 8 MB copy saves
                        bins[:, j] = self.mappers[col].values_to_bins(
                            np.ascontiguousarray(X[:, col])).astype(
                                dtype, copy=False)

                    _parallel_columns(_bin_col, self.num_features, config)
                    self.bins = bins

            self.metadata = Metadata(n, label, weight, group_sizes, init_score)
            self._set_constraints(config)
            return self

    @staticmethod
    def _row_shard_devices(config: Config):
        """The chips a device ingest deals the rows to, or None for one
        matrix on the default device: under tree_learner=data|voting with
        num_machines chips in one process the learner shards rows over
        exactly these (parallel/topology.make_topology takes jax.devices()
        in order), so each shard is binned where it will train.  A learner
        built otherwise still lays out correctly, at a chip-to-chip copy."""
        import jax

        from ..parallel.strategies import resolve_tree_learner

        shards = int(config.num_machines)
        if (resolve_tree_learner(config.tree_learner) in ("data", "voting")
                and 1 < shards <= len(jax.devices())
                and jax.process_count() == 1 and not str(config.machines)):
            return jax.devices()[:shards]
        return None

    def _make_device_binner(self, config: Config, dtype, n_rows: int):
        """A ready DeviceBinner when config routes ingest to the device
        kernel (ops/binning.py), else None.  'auto' requires an
        accelerator default backend AND enough rows to amortize the
        dispatch; huge categorical id spaces fall back to host (the
        kernel's LUT is dense)."""
        from ..config import parse_tristate

        mode = parse_tristate(config.tpu_ingest_device)
        if mode == "false" or self.num_features == 0:
            return None
        if mode == "auto":
            import jax

            if (jax.default_backend() == "cpu"
                    or n_rows < int(config.tpu_ingest_min_rows)):
                return None
        from ..ops.binning import DeviceBinner

        return DeviceBinner.build(self.mappers, self.used_feature_idx,
                                  dtype, int(config.tpu_ingest_chunk_rows))

    @classmethod
    def from_sparse(cls, sp, label: Optional[np.ndarray] = None,
                    config: Optional[Config] = None,
                    weight: Optional[np.ndarray] = None,
                    group_sizes: Optional[np.ndarray] = None,
                    init_score: Optional[np.ndarray] = None,
                    reference: Optional["TrainingData"] = None,
                    feature_names: Optional[List[str]] = None,
                    categorical_features: Optional[Sequence[int]] = None,
                    forced_bins: Optional[Dict[int, List[float]]] = None,
                    ) -> "TrainingData":
        """Bin a scipy CSR/CSC matrix in O(nnz) host memory.

        The reference keeps sparse features delta-encoded end to end
        (src/io/sparse_bin.hpp:73, include/LightGBM/bin.h:472-508); the
        TPU core is a dense `[n, F]` int8/16 matrix (the histogram
        kernel's one-hot contraction wants fixed shape), so the sparse
        path's job is to reach that matrix WITHOUT ever materializing the
        `[n, F]` f64 intermediate: bin finding reads stored values off
        the CSC arrays, and binning fills each column with its zero bin
        then scatters the O(nnz) stored-value bins.
        """
        config = config or Config()
        from .. import obs

        obs.configure_from_config(config)
        with obs.span("dataset/construct", source="sparse"):
            sp = sp.tocsc()
            # non-canonical inputs (duplicate coordinates) must SUM like
            # scipy's own toarray(), not last-write-win in the bin scatter
            sp.sum_duplicates()
            n, nf = sp.shape
            self = cls()
            self.config = config
            self.num_data = n
            self.num_total_features = nf
            self.feature_names = (list(feature_names) if feature_names
                                  else [f"Column_{i}" for i in range(nf)])

            from ..utils import timer

            with timer.PHASE("sketch"):
                if reference is not None:
                    self._adopt_reference_mappers(reference)
                else:
                    # sparse ingest joins the collective bin-finding path
                    # directly: the feature-sharded mapper search slices CSC
                    # columns and samples stored values exactly like the local
                    # find (local_payload -> _find_mappers is sparse-aware)
                    self._find_mappers_maybe_distributed(
                        sp, config, categorical_features or [], forced_bins or {})

            with timer.PHASE("binning"):
                dtype = np.uint8 if self.max_num_bin <= 256 else np.uint16
                bins = np.empty((n, self.num_features), dtype=dtype)
                indptr, indices, data = sp.indptr, sp.indices, sp.data
                for j, col in enumerate(self.used_feature_idx):
                    m = self.mappers[col]
                    lo, hi = int(indptr[col]), int(indptr[col + 1])
                    # implicit zeros take the column's zero-value bin
                    # (default_bin IS value_to_bin(0.0), set at find time;
                    # most_freq_bin semantics fall out of it)
                    colbins = np.full(n, m.default_bin, dtype=dtype)
                    if hi > lo:
                        vals = np.asarray(data[lo:hi], dtype=np.float64)
                        colbins[indices[lo:hi]] = \
                            m.values_to_bins(vals).astype(dtype)
                    bins[:, j] = colbins
                self.bins = bins

            self.metadata = Metadata(n, label, weight, group_sizes, init_score)
            self._set_constraints(config)
            return self

    @classmethod
    def from_file(cls, path: str, config: Optional[Config] = None,
                  reference: Optional["TrainingData"] = None) -> "TrainingData":
        config = config or Config()
        # binary fast path (reference CheckCanLoadFromBin,
        # dataset_loader.cpp:1217 + binary token check): <path>.bin skips
        # parsing and re-binning entirely
        # per-host cache presence may diverge; every host must walk the
        # same (collective) bin-finding path or the group hangs
        from .distributed_binning import (config_wants_distributed,
                                          ensure_distributed)
        from .. import obs

        obs.configure_from_config(config)
        with obs.span("dataset/construct", source="file"):
            ensure_distributed(config)
            skip_cache = config_wants_distributed(config)
            if reference is None and not skip_cache \
                    and os.path.exists(path + ".bin"):
                try:
                    return cls.from_binary(path + ".bin")
                except Exception as exc:
                    from ..utils.log import Log

                    Log.warning(f"ignoring stale binary cache {path}.bin: {exc}")
            if bool(config.two_round):
                try:
                    data = cls._from_file_two_round(path, config, reference)
                    if bool(config.save_binary):
                        data.save_binary(path + ".bin")
                    return data
                except ValueError as exc:  # e.g. libsvm: no streaming reader
                    from ..utils.log import Log

                    Log.warning(f"two_round fell back to one-pass load: {exc}")
            X, y, w, group, init, names = load_text_file(
                path, label_column=config.label_column,
                header=True if config.header else None)
            cat = _parse_column_spec(config.categorical_feature, names)
            data = cls.from_matrix(X, y, config, weight=w, group_sizes=group,
                                   init_score=init, reference=reference,
                                   feature_names=names, categorical_features=cat,
                                   forced_bins=_load_forced_bins(config))
            if bool(config.save_binary):
                data.save_binary(path + ".bin")
            return data

    @classmethod
    def _from_file_two_round(cls, path: str, config: Config,
                             reference: Optional["TrainingData"],
                             chunk_rows: int = 200_000) -> "TrainingData":
        """Two-pass streaming load (reference two_round,
        dataset_loader.cpp:188-216): pass 1 reservoir-samples
        `bin_construct_sample_cnt` rows for bin finding and counts rows;
        pass 2 streams chunks straight into the uint8/16 bin matrix.  The
        raw float matrix is never resident — peak memory drops from
        n*F*8 bytes to n*F*1 plus one chunk."""
        from .parser import TextChunkReader, load_sidecars

        reader = TextChunkReader(path, label_column=config.label_column,
                                 header=True if config.header else None,
                                 chunk_rows=chunk_rows)
        names = reader.feature_names
        sample_cnt = max(int(config.bin_construct_sample_cnt), 2)
        rng = np.random.default_rng(int(config.data_random_seed))

        # ---- pass 1: row count + algorithm-R reservoir over chunks
        # (with a reference the mappers are reused, so only the count,
        # labels, and column width are needed — no sampling) ----
        n = 0
        ncols = 0
        sample: Optional[np.ndarray] = None
        labels_parts: List[np.ndarray] = []
        for Xc, yc in reader.chunks():
            m = len(yc)
            labels_parts.append(yc)
            ncols = Xc.shape[1]
            if reference is None:
                if sample is None:
                    sample = Xc[:sample_cnt].copy()
                elif len(sample) < sample_cnt:
                    # reservoir not yet full: the chunk's LEADING rows are
                    # the next global positions < sample_cnt
                    need = sample_cnt - len(sample)
                    sample = np.vstack([sample, Xc[:need]])
                start = max(n, sample_cnt)
                if start < n + m:
                    pos = np.arange(start, n + m)
                    local = pos - n
                    accept = rng.random(len(pos)) < sample_cnt / (pos + 1.0)
                    slots = rng.integers(0, sample_cnt,
                                         size=int(accept.sum()))
                    sample[slots] = Xc[local[accept]]
            n += m
        if n == 0:
            raise ValueError(f"empty data file {path}")
        label = np.concatenate(labels_parts)

        from ..utils import timer

        self = cls()
        self.config = config
        self.num_data = n
        self.num_total_features = ncols
        self.feature_names = list(names)
        with timer.PHASE("sketch"):
            if reference is not None:
                self._adopt_reference_mappers(reference)
            else:
                cat = _parse_column_spec(config.categorical_feature, names)
                self._find_mappers_maybe_distributed(
                    sample, config, cat or [], _load_forced_bins(config),
                    total_rows=n)

        # ---- pass 2: stream rows into bins (file chunks feed the
        # device kernel directly when ingest is device-routed, so the
        # full host matrix never exists on that path either) ----
        with timer.PHASE("binning"):
            dtype = np.uint8 if self.max_num_bin <= 256 else np.uint16
            binner = self._make_device_binner(config, dtype, n)
            if binner is not None:
                # bin_stream re-chunks across reader blocks, so only the
                # file's final launch pads
                self._ingest_bins = binner.bin_stream(
                    (Xc for Xc, _ in reader.chunks()),
                    self._row_shard_devices(config), n)
                self._bins = None
            else:
                bins = np.empty((n, self.num_features), dtype=dtype)
                row = 0
                for Xc, _ in reader.chunks():
                    m = Xc.shape[0]
                    for j, col in enumerate(self.used_feature_idx):
                        bins[row:row + m, j] = self.mappers[col] \
                            .values_to_bins(Xc[:, col]).astype(dtype)
                    row += m
                self.bins = bins

        weight, group, init_score = load_sidecars(path)
        self.metadata = Metadata(n, label, weight, group, init_score)
        self._set_constraints(config)
        return self

    # ------------------------------------------------------------------
    _BINARY_TOKEN = "lightgbm_tpu.binned.v1"

    def save_binary(self, path: str) -> None:
        """Serialize the binned dataset (reference Dataset::SaveBinaryFile,
        src/io/dataset.cpp:695): bins + mappers + metadata, so reloading
        skips parsing and bin finding."""
        import json

        md = self.metadata
        np.savez_compressed(
            path,
            token=np.frombuffer(self._BINARY_TOKEN.encode(), np.uint8),
            bins=self.bins,
            used_feature_idx=np.asarray(self.used_feature_idx, np.int64),
            num_total_features=np.int64(self.num_total_features),
            mappers=np.frombuffer(json.dumps(
                [m.to_dict() for m in self.mappers]).encode(), np.uint8),
            feature_names=np.frombuffer(
                json.dumps(self.feature_names).encode(), np.uint8),
            label=md.label,
            weight=(md.weight if md.weight is not None
                    else np.zeros(0, np.float32)),
            query_boundaries=(md.query_boundaries
                              if md.query_boundaries is not None
                              else np.zeros(0, np.int64)),
            init_score=(md.init_score if md.init_score is not None
                        else np.zeros(0, np.float64)),
            monotone=(self.monotone_constraints
                      if self.monotone_constraints is not None
                      else np.zeros(0, np.int32)),
            penalty=(self.feature_penalty
                     if self.feature_penalty is not None
                     else np.zeros(0, np.float32)))
        # numpy appends .npz; normalize to the requested name
        if not path.endswith(".npz") and os.path.exists(path + ".npz"):
            os.replace(path + ".npz", path)

    @classmethod
    def from_binary(cls, path: str) -> "TrainingData":
        import json

        from .bin_mapper import BinMapper

        with np.load(path, allow_pickle=False) as z:
            token = bytes(z["token"]).decode()
            if token != cls._BINARY_TOKEN:
                raise ValueError(f"unrecognized binary dataset token "
                                 f"{token!r}")
            self = cls()
            self.bins = z["bins"]
            self.used_feature_idx = [int(i) for i in z["used_feature_idx"]]
            self.num_total_features = int(z["num_total_features"])
            self.mappers = [BinMapper.from_dict(d) for d in
                            json.loads(bytes(z["mappers"]).decode())]
            self.feature_names = json.loads(
                bytes(z["feature_names"]).decode())
            self.num_data = int(self.bins.shape[0])
            md = Metadata(self.num_data, label=z["label"])
            if z["weight"].size:
                md.weight = z["weight"]
            if z["query_boundaries"].size:
                md.query_boundaries = z["query_boundaries"]
            if z["init_score"].size:
                md.init_score = z["init_score"]
            self.metadata = md
            if z["monotone"].size:
                self.monotone_constraints = z["monotone"]
            if z["penalty"].size:
                self.feature_penalty = z["penalty"]
        return self

    # ------------------------------------------------------------------
    def _adopt_reference_mappers(self, reference: "TrainingData") -> None:
        """Share the reference's BinMappers for validation-set alignment
        (reference dataset.h:501 CreateValid)."""
        self.mappers = reference.mappers
        self.used_feature_idx = list(reference.used_feature_idx)
        self.monotone_constraints = reference.monotone_constraints
        self.feature_penalty = reference.feature_penalty
        # eval_for_data on a freed booster (train_data dropped) can no
        # longer compare mapper identity; this flag records that the bins
        # came from SOME reference rather than a fresh find
        self.adopted_reference = True
        if reference.num_total_features != self.num_total_features:
            raise ValueError("validation data feature count mismatch")

    def _note_columns(self) -> None:
        """What the sketch decided, for the telemetry: the used columns by
        missing type, and how many of them took the bin search's
        distinct-value path."""
        from .. import obs

        used = [self.mappers[c] for c in self.used_feature_idx]
        for kind in MissingType:
            obs.REGISTRY.set_gauge(
                "lgbm_dataset_columns",
                sum(m.missing_type == kind for m in used),
                missing=kind.name.lower(),
                help="used columns of the last constructed dataset by "
                     "missing type")
        obs.REGISTRY.set_gauge(
            "lgbm_dataset_distinct_path_columns",
            sum(m.distinct_path for m in used),
            help="used columns of the last constructed dataset whose sample "
                 "held no more distinct values than bins were offered")

    def _find_mappers_maybe_distributed(self, X, config, categorical,
                                        forced_bins,
                                        total_rows: Optional[int] = None
                                        ) -> None:
        """Feature-sharded multi-host bin finding when this process is
        part of a pre-partitioned jax.distributed group (reference
        dataset_loader.cpp:959-1042); plain local find otherwise.

        NO silent fallback once pre_partition requests distribution: a
        host that skipped the collective while its peers entered it would
        deadlock the group, so errors here must be loud."""
        from .distributed_binning import (config_wants_distributed,
                                          ensure_distributed,
                                          find_mappers_multihost)

        ensure_distributed(config)
        if config_wants_distributed(config):
            self.mappers = find_mappers_multihost(
                X, config, categorical, forced_bins,
                local_total_rows=total_rows,
                feature_names=self.feature_names)
            self.used_feature_idx = [i for i, m in enumerate(self.mappers)
                                     if not m.is_trivial]
        else:
            self._find_mappers(X, config, categorical, forced_bins,
                               total_rows=total_rows)
        self._note_columns()

    def _find_mappers(self, X: np.ndarray, config: Config,
                      categorical_features: Sequence[int],
                      forced_bins: Dict[int, List[float]],
                      total_rows: Optional[int] = None,
                      feature_subset: Optional[Sequence[int]] = None
                      ) -> None:
        # total_rows: full dataset size when X is already a sample (the
        # two-round path) — the near-unsplittable filter must scale by
        # sample/total like the reference (dataset_loader.cpp:599-600);
        # the internal subsample below still indexes X's own rows.
        # feature_subset: X's columns' GLOBAL feature ids (distributed
        # feature-sharded bin finding) — per-feature config (ignore,
        # max_bin_by_feature, categorical, forced bins) is keyed globally
        n, nf = X.shape
        full_n = max(int(total_rows), n) if total_rows is not None else n
        sample_cnt = min(n, int(config.bin_construct_sample_cnt))
        if sample_cnt < n:
            rng = np.random.default_rng(int(config.data_random_seed))
            sample_idx = np.sort(rng.choice(n, size=sample_cnt, replace=False))
            Xs = X[sample_idx]
        else:
            Xs = X
        # sparse input: per-column stored values come straight off the
        # CSC arrays — the f64 matrix is never densified (reference
        # sparse-aware sampling, dataset_loader.cpp:959-1042 /
        # src/io/sparse_bin.hpp:73)
        sp_csc = None
        if _is_scipy_sparse(Xs):
            sp_csc = Xs.tocsc()
            # duplicate coordinates sum under densification; match that
            # before reading stored values per column
            sp_csc.sum_duplicates()
        total = Xs.shape[0]

        ignore = set(_parse_column_spec(config.ignore_column, self.feature_names))
        cat_set = set(int(c) for c in categorical_features)
        max_bin_by_feature = list(config.max_bin_by_feature)
        # near-unsplittable feature filter (reference dataset_loader.cpp:599-600)
        filter_cnt = int(float(config.min_data_in_leaf) * total / full_n)

        self.mappers = [BinMapper() for _ in range(nf)]

        def _find_col(col: int) -> None:
            gcol = int(feature_subset[col]) if feature_subset is not None \
                else col
            m = self.mappers[col]
            if gcol in ignore:
                m.num_bin = 1
                m.is_trivial = True
                return
            if sp_csc is not None:
                colv = sp_csc.data[sp_csc.indptr[col]:sp_csc.indptr[col + 1]]
                colv = np.asarray(colv, dtype=np.float64)
            else:
                colv = Xs[:, col]
            # drop (near-)zeros: implied by total_sample_cnt (reference
            # dataset_loader.cpp sparse-aware sampling; stored sparse
            # zeros drop identically to dense explicit zeros)
            nonzero = colv[~((np.abs(colv) <= K_ZERO_THRESHOLD)
                             & ~np.isnan(colv))]
            mb = int(config.max_bin)
            if max_bin_by_feature and gcol < len(max_bin_by_feature):
                mb = int(max_bin_by_feature[gcol])
            m.find_bin(nonzero, total, mb,
                       min_data_in_bin=int(config.min_data_in_bin),
                       min_split_data=filter_cnt,
                       bin_type=(BinType.CATEGORICAL if gcol in cat_set
                                 else BinType.NUMERICAL),
                       use_missing=bool(config.use_missing),
                       zero_as_missing=bool(config.zero_as_missing),
                       forced_bounds=forced_bins.get(gcol))

        # per-column fan-out (reference OpenMP pragma over features,
        # dataset_loader.cpp:696): each column fills only its own
        # pre-constructed mapper, so the result is order-independent
        _parallel_columns(_find_col, nf, config)
        self.used_feature_idx = [c for c in range(nf)
                                 if not self.mappers[c].is_trivial]

    def _set_constraints(self, config: Config) -> None:
        mono = list(config.monotone_constraints)
        if mono:
            self.monotone_constraints = np.array(
                [mono[c] if c < len(mono) else 0 for c in self.used_feature_idx],
                dtype=np.int32)
        contri = list(config.feature_contri)
        if contri:
            self.feature_penalty = np.array(
                [contri[c] if c < len(contri) else 1.0 for c in self.used_feature_idx],
                dtype=np.float32)

    # ------------------------------------------------------------------
    def create_valid(self, X, label: Optional[np.ndarray] = None,
                     **kw) -> "TrainingData":
        factory = (TrainingData.from_sparse if _is_scipy_sparse(X)
                   else TrainingData.from_matrix)
        return factory(X, label, self.config, reference=self, **kw)

    def real_threshold(self, feature: int, bin_threshold: int) -> float:
        """Bin threshold -> raw-value threshold for model serialization.

        Numerical split at bin t means `value <= bin_upper_bound[t]` goes left
        (reference Tree::RealThreshold usage in tree.cpp).
        """
        m = self.mappers[self.used_feature_idx[feature]]
        return m.bin_to_value(bin_threshold)
