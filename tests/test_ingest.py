"""Device-parallel ingest parity suite.

Everything here is a BITWISE contract: the vectorized bin finder must
reproduce the scalar `greedy_find_bin_scalar` boundaries exactly, and
the ops/binning.py device kernel must reproduce scalar
`value_to_bin`/`values_to_bins` exactly — across NaN / zero-as-missing,
every MissingType, categorical unseen values, forced bins, max_bin edge
sizes, the uint8 -> uint16 storage crossover, and sampled-vs-full bin
finding.  A short training run closes the loop: a device-ingested
dataset must grow byte-identical trees.
"""

import json
import warnings

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.config import Config
from lightgbm_tpu.io.bin_mapper import (BinMapper, BinType, MissingType,
                                        greedy_find_bin,
                                        greedy_find_bin_scalar)
from lightgbm_tpu.io.dataset import TrainingData
from lightgbm_tpu.ops.binning import DeviceBinner, sort_keys


def _mixed_matrix(seed=0, n=4000, f=10):
    """Dense matrix exercising every routing corner: NaN, zeros near the
    kZeroThreshold band, a categorical column with unseen-at-predict
    values, constant (trivial) and integer-code columns."""
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, f))
    X[:, 1][rng.random(n) < 0.4] = 0.0
    X[:, 2][rng.random(n) < 0.25] = np.nan
    X[:, 3] = rng.choice([0, 1, 2, 5, 9, 300], size=n)     # categorical
    X[:, 4] = np.round(X[:, 4], 1)                         # heavy ties
    X[:, 5] = 1e-36 * rng.normal(size=n)                   # inside zero band
    X[:, 6] = 7.5                                          # trivial
    X[:, 7] = rng.integers(-3, 40, size=n)                 # negative ints
    return X


class TestVectorizedGreedy:
    def test_fuzz_bit_identical(self):
        rng = np.random.default_rng(42)
        for _ in range(150):
            nd = int(rng.integers(1, 400))
            dv = np.unique(np.sort(rng.normal(size=nd)))
            cnt = rng.integers(0, 25, size=len(dv)).astype(np.int64)
            cnt[int(rng.integers(0, len(dv)))] = int(rng.integers(0, 3000))
            total = int(cnt.sum()) + int(rng.integers(0, 50))
            mb = int(rng.choice([1, 2, 3, 15, 63, 255, 300]))
            mdib = int(rng.choice([0, 1, 3, 10]))
            assert greedy_find_bin(dv, cnt, mb, total, mdib) == \
                greedy_find_bin_scalar(dv.tolist(), cnt.tolist(), mb,
                                       total, mdib)

    def test_single_distinct_value(self):
        assert greedy_find_bin([1.5], [10], 16, 10, 3) == \
            greedy_find_bin_scalar([1.5], [10], 16, 10, 3)

    def test_zero_count_entries(self):
        # interior zero spliced at count 0 (find_bin does this)
        dv, cnt = [-2.0, 0.0, 3.0, 4.0], [5, 0, 5, 5]
        for mb in (2, 3, 16):
            assert greedy_find_bin(dv, cnt, mb, 15, 3) == \
                greedy_find_bin_scalar(dv, cnt, mb, 15, 3)


class TestSortKeys:
    def test_total_order_matches_f64(self):
        rng = np.random.default_rng(1)
        v = np.concatenate([
            rng.normal(size=500) * (10.0 ** rng.integers(-300, 300, 500)
                                    .astype(float)),
            [0.0, -0.0, np.inf, -np.inf, 1e-35, -1e-35, 5e-324, -5e-324,
             1.0, np.nextafter(1.0, 2.0)]])
        k = sort_keys(v)
        order = np.argsort(v, kind="stable")
        assert np.all(np.diff(k[order]) >= 0)
        # equal floats <-> equal keys (incl. -0.0 == +0.0)
        for i in range(len(v)):
            eq_f = v == v[i]
            eq_k = k == k[i]
            assert np.array_equal(eq_f, eq_k)

    def test_nan_sentinel(self):
        k = sort_keys(np.array([np.nan, np.inf, 1.0]))
        assert k[0] == np.iinfo(np.int64).max
        assert k[1] < k[0] and k[2] < k[1]


def _build_mappers(X, cfg=None, categorical=(3,)):
    td = TrainingData()
    td.feature_names = [f"Column_{i}" for i in range(X.shape[1])]
    td._find_mappers(X, cfg or Config({"max_bin": 63}), list(categorical),
                     {})
    return td


class TestRowsDealtToDevices:
    """`bin_matrix(X, devices)`: consecutive row ranges of whole chunks,
    each binned and kept on its device, the bins those of one matrix."""

    @pytest.fixture(scope="class")
    def dealt(self):
        import jax

        X = _mixed_matrix(seed=7)
        td = _build_mappers(X)
        b = DeviceBinner.build(td.mappers, td.used_feature_idx, np.uint8,
                               chunk_rows=256)
        devices = jax.devices()[:4]
        return (np.asarray(b.bin_matrix(X)), b.bin_matrix(X, devices),
                devices)

    def test_every_device_keeps_its_consecutive_whole_chunks(self, dealt):
        whole, parts, devices = dealt
        n = whole.shape[0]
        per = -(-n // (4 * 256)) * 256
        assert [p.shape[0] for p in parts.parts] == [
            min(per, n - k * per) for k in range(4)]
        assert [next(iter(p.devices())) for p in parts.parts] == devices
        assert parts.shape == whole.shape and parts.dtype == whole.dtype

    def test_the_bins_are_those_of_one_matrix(self, dealt):
        whole, parts, _ = dealt
        assert np.array_equal(np.asarray(parts), whole)
        assert np.array_equal(np.asarray(parts.gathered()), whole)

    @pytest.mark.parametrize("lo, hi", [(0, 300), (250, 2600), (1024, 1024),
                                        (2000, 10 ** 9)])
    def test_a_row_range_arrives_on_the_device_asked_for(self, dealt, lo, hi):
        whole, parts, devices = dealt
        pieces = parts.rows(lo, min(hi, whole.shape[0]), devices[2])
        assert all(next(iter(p.devices())) == devices[2] for p in pieces)
        got = (np.concatenate([np.asarray(p) for p in pieces]) if pieces
               else whole[:0])
        assert np.array_equal(got, whole[lo:hi])

    def test_column_counts_and_the_strided_sample(self, dealt):
        whole, parts, _ = dealt
        assert np.array_equal(parts.column_counts(lambda p: p == 0),
                              (whole == 0).sum(axis=0))
        idx = np.arange(0, whole.shape[0], 37)
        assert np.array_equal(parts.take(idx), whole[idx])

    def test_a_plain_matrix_is_the_one_part_case(self, dealt):
        from lightgbm_tpu.ops.binning import RowParts
        import jax.numpy as jnp

        whole = jnp.asarray(dealt[0])
        one = RowParts.of(whole)
        assert one.gathered() is whole and RowParts.of(one) is one


class TestDeviceKernelParity:
    @pytest.mark.parametrize("max_bin", [2, 3, 16, 255, 300])
    def test_mixed_corners(self, max_bin):
        X = _mixed_matrix(seed=max_bin)
        cfg = Config({"max_bin": max_bin})
        td = _build_mappers(X, cfg)
        used = td.used_feature_idx
        dtype = np.uint8 if td.max_num_bin <= 256 else np.uint16
        b = DeviceBinner.build(td.mappers, used, dtype, chunk_rows=512)
        assert b is not None
        dev = np.asarray(b.bin_matrix(X))
        host = np.stack([td.mappers[c].values_to_bins(X[:, c]).astype(dtype)
                         for c in used], axis=1)
        assert np.array_equal(dev, host)
        # scalar value_to_bin spot check on the corner rows
        for r in range(0, X.shape[0], 997):
            for j, c in enumerate(used):
                assert int(dev[r, j]) == td.mappers[c].value_to_bin(X[r, c])

    def test_missing_type_variants(self):
        rng = np.random.default_rng(5)
        n = 2000
        for zam, with_nan in [(False, False), (False, True), (True, False),
                              (True, True)]:
            vals = rng.normal(size=n)
            vals[rng.random(n) < 0.3] = 0.0
            if with_nan:
                vals[rng.random(n) < 0.2] = np.nan
            m = BinMapper()
            nz = vals[~((np.abs(vals) <= 1e-35) & ~np.isnan(vals))]
            m.find_bin(nz, n, max_bin=32, zero_as_missing=zam)
            b = DeviceBinner.build([m], [0], np.uint8, chunk_rows=256)
            dev = np.asarray(b.bin_matrix(vals[:, None]))[:, 0]
            assert np.array_equal(dev, m.values_to_bins(vals))

    def test_categorical_unseen_and_nan(self):
        rng = np.random.default_rng(6)
        vals = rng.choice([0, 1, 2, 5, 9], size=1000,
                          p=[0.4, 0.3, 0.2, 0.07, 0.03]).astype(float)
        m = BinMapper()
        m.find_bin(vals, 1000, max_bin=16, bin_type=BinType.CATEGORICAL)
        probe = np.array([0.0, 1.0, 9.0, 777.0, -1.0, -0.5, 3.5, np.nan,
                          np.inf, 1e18])
        b = DeviceBinner.build([m], [0], np.uint8, chunk_rows=256)
        dev = np.asarray(b.bin_matrix(probe[:, None]))[:, 0]
        assert np.array_equal(dev, m.values_to_bins(probe))
        assert int(dev[3]) == m.num_bin - 1  # unseen -> last bin

    def test_forced_bins_parity(self, tmp_path):
        X = _mixed_matrix(seed=9)
        forced = {0: [-1.0, 0.5], 4: [0.0, 1.0]}
        cfg = Config({"max_bin": 63})
        td = TrainingData()
        td.feature_names = [f"Column_{i}" for i in range(X.shape[1])]
        td._find_mappers(X, cfg, [3], {k: list(v)
                                       for k, v in forced.items()})
        used = td.used_feature_idx
        b = DeviceBinner.build(td.mappers, used, np.uint8, chunk_rows=1024)
        dev = np.asarray(b.bin_matrix(X))
        host = np.stack([td.mappers[c].values_to_bins(X[:, c])
                         .astype(np.uint8) for c in used], axis=1)
        assert np.array_equal(dev, host)

    def test_uint16_crossover(self):
        rng = np.random.default_rng(7)
        vals = rng.normal(size=5000)
        m = BinMapper()
        m.find_bin(vals, 5000, max_bin=400, min_data_in_bin=1)
        assert m.num_bin > 256  # crossover actually exercised
        b = DeviceBinner.build([m], [0], np.uint16, chunk_rows=2048)
        dev = np.asarray(b.bin_matrix(vals[:, None]))[:, 0]
        assert dev.dtype == np.uint16
        assert np.array_equal(dev, m.values_to_bins(vals).astype(np.uint16))

    def test_huge_category_ids_fall_back(self):
        m = BinMapper()
        m.find_bin(np.array([1e7, 1.0, 2.0] * 100), 300, max_bin=16,
                   bin_type=BinType.CATEGORICAL, min_data_in_bin=1)
        assert DeviceBinner.build([m], [0], np.uint8, 256) is None


class TestIngestEndToEnd:
    def test_dataset_bins_bit_identical(self):
        X = _mixed_matrix(seed=11)
        y = (np.nan_to_num(X[:, 0]) > 0).astype(float)
        kw = dict(label=y, categorical_features=[3])
        host = TrainingData.from_matrix(
            X, config=Config({"max_bin": 63, "tpu_ingest_device": "false"}),
            **kw)
        dev = TrainingData.from_matrix(
            X, config=Config({"max_bin": 63, "tpu_ingest_device": "true"}),
            **kw)
        assert dev.has_bins and dev._bins is None  # still device-resident
        assert np.array_equal(np.asarray(dev.bins), host.bins)
        assert dev._bins is not None  # property access materialized it

    def test_lazy_reductions_skip_host(self):
        X = _mixed_matrix(seed=12)
        td = TrainingData.from_matrix(
            X, config=Config({"tpu_ingest_device": "true"}))
        zf = td.column_zero_fraction()
        nz = td.column_nonzero_counts(
            np.array([m.default_bin for m in
                      (td.mappers[c] for c in td.used_feature_idx)]))
        samp = td.strided_row_sample(100)
        assert td._bins is None, "reductions must not materialize host bins"
        ref = TrainingData.from_matrix(
            X, config=Config({"tpu_ingest_device": "false"}))
        assert np.array_equal(zf, (ref.bins == 0).mean(axis=0))
        zb = np.array([ref.mappers[c].default_bin
                       for c in ref.used_feature_idx])
        assert np.array_equal(nz, (ref.bins != zb[None, :]).sum(axis=0))
        from lightgbm_tpu.io.bundling import _stride_sample

        assert np.array_equal(samp, _stride_sample(ref.bins, 100))

    def test_sampled_vs_full_equivalence(self):
        # bin_construct_sample_cnt >= n must bin-find on ALL rows: any
        # two over-sized settings give identical mappers
        X = _mixed_matrix(seed=13, n=1500)
        a = TrainingData.from_matrix(
            X, config=Config({"bin_construct_sample_cnt": 1500}))
        b = TrainingData.from_matrix(
            X, config=Config({"bin_construct_sample_cnt": 10 ** 7}))
        for ma, mb in zip(a.mappers, b.mappers):
            da, db = json.dumps(ma.to_dict()), json.dumps(mb.to_dict())
            assert da == db

    def test_trained_model_bit_identical(self):
        X = _mixed_matrix(seed=14)
        y = (np.nan_to_num(X[:, 0]) + (X[:, 3] == 2) > 0.3).astype(float)
        trees = {}
        for mode in ("false", "true"):
            ds = lgb.Dataset(X, label=y, categorical_feature=[3],
                             params={"max_bin": 63,
                                     "tpu_ingest_device": mode})
            bst = lgb.train({"objective": "binary", "num_leaves": 15,
                             "verbosity": -1, "tpu_ingest_device": mode},
                            ds, num_boost_round=6)
            s = bst.model_to_string()
            # strip the parameters trailer: tpu_ingest_device itself
            # legitimately differs there
            trees[mode] = s[:s.index("parameters:")]
        assert trees["false"] == trees["true"]

    def test_learner_bins_t_identical_device_layout(self):
        # enable_bundle=false + serial strategy = the device-side
        # transpose/pad path; the placed [G, n_pad] matrix must equal
        # the host-laid-out one byte for byte
        X = _mixed_matrix(seed=21, n=1200)
        y = (np.nan_to_num(X[:, 0]) > 0).astype(float)
        bt = {}
        for mode in ("false", "true"):
            ds = lgb.Dataset(X, label=y, categorical_feature=[3],
                             params={"enable_bundle": False,
                                     "tpu_ingest_device": mode})
            bst = lgb.train({"objective": "binary", "num_leaves": 7,
                             "verbosity": -1, "enable_bundle": False,
                             "tpu_ingest_device": mode},
                            ds, num_boost_round=2,
                            keep_training_booster=True)
            learner = bst._driver.learner
            bt[mode] = np.asarray(learner.bins_t)
            if mode == "true":
                # the device layout transposed in HBM; the host matrix
                # was never materialized by training
                assert ds._inner._bins is None
        assert np.array_equal(bt["false"], bt["true"])

    def test_device_ingest_chunking_boundaries(self):
        # multi-chunk with a ragged tail must equal single-chunk
        X = _mixed_matrix(seed=15, n=1111)
        cfgs = [Config({"tpu_ingest_device": "true",
                        "tpu_ingest_chunk_rows": c}) for c in (256, 4096)]
        a = TrainingData.from_matrix(X, config=cfgs[0])
        b = TrainingData.from_matrix(X, config=cfgs[1])
        assert np.array_equal(np.asarray(a.bins), np.asarray(b.bins))


class TestNumIterationsWarningDedupe:
    def test_warns_once_per_alias(self):
        import lightgbm_tpu.engine as engine

        X = np.random.default_rng(0).normal(size=(200, 4))
        y = (X[:, 0] > 0).astype(float)
        engine._warned_num_iter_aliases.discard("num_iterations")
        params = {"objective": "binary", "num_leaves": 4, "verbosity": -1,
                  "num_iterations": 2}
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            for _ in range(3):
                lgb.train(dict(params), lgb.Dataset(X, label=y),
                          num_boost_round=5)
        hits = [x for x in w if "num_iterations" in str(x.message)]
        assert len(hits) == 1
