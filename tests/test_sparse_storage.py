"""Sparse train-time storage (tpu_sparse_threshold; reference
OrderedSparseBin, src/io/ordered_sparse_bin.hpp / sparse_bin.hpp:73).

Contract: features below the nonzero-bin threshold are stored as padded
COO (row, bin) pairs; histograms come from an O(nnz) gather contraction
with the zero bin reconstructed from leaf totals (FixHistogram,
reference dataset.cpp:1044-1063), and partitions materialize the chosen
column on the fly.  Deterministic f64 runs must BIT-match dense storage
(the reconstruction stays in the accumulation dtype); default (hilo)
runs agree at decision level up to summation-order ulps.
"""

import jax
import numpy as np
import pytest

import lightgbm_tpu as lgb


@pytest.fixture
def _x64_reset():
    # deterministic mode flips jax_enable_x64 process-wide; undo so later
    # tests keep the default f32 promotion rules
    yield
    jax.config.update("jax_enable_x64", False)


def _sparse_problem(n=4000, n_dense=4, n_sparse=8, density=0.03, seed=3):
    rng = np.random.default_rng(seed)
    F = n_dense + n_sparse
    X = np.zeros((n, F))
    X[:, :n_dense] = rng.normal(size=(n, n_dense))
    for f in range(n_dense, F):
        nz = rng.choice(n, size=max(4, int(n * density)), replace=False)
        X[nz, f] = rng.normal(size=len(nz)) + (f - F // 2) * 0.5
    y = (X[:, 0] + 2.0 * X[:, n_dense + 1] - 1.5 * X[:, F - 3]
         + 0.3 * rng.normal(size=n) > 0).astype(np.float64)
    return X, y


BASE = {"objective": "binary", "num_leaves": 15, "max_bin": 63,
        "min_data_in_leaf": 5, "verbosity": -1, "enable_bundle": False,
        "tpu_shape_buckets": 0}


def _model(params, X, y, rounds=5):
    ds = lgb.Dataset(X, label=y, params=params)
    bst = lgb.train(params, ds, num_boost_round=rounds,
                    keep_training_booster=True)
    return bst


class TestSparseStorageParity:
    def test_f64_bitmatch_select_and_default(self, _x64_reset):
        X, y = _sparse_problem()
        models = {}
        for tag, extra in (
                ("dense", {"tpu_partition_impl": "select"}),
                ("sparse", {"tpu_sparse_threshold": 0.2,
                            "tpu_partition_impl": "select"}),
                ("auto", {"tpu_sparse_threshold": 0.2})):
            p = {**BASE, **extra, "deterministic": True}
            m = _model(p, X, y).model_to_string()
            models[tag] = m.split("\nparameters:")[0]
        assert models["sparse"] == models["dense"]
        assert models["auto"] == models["dense"]
        # the kernel partition has no form for sparse columns
        with pytest.raises(ValueError, match="dense numerical unpacked"):
            _model({**BASE, "tpu_sparse_threshold": 0.2,
                    "tpu_partition_impl": "kernel"}, X, y)

    def test_default_precision_decisions_agree(self):
        X, y = _sparse_problem()
        recs = {}
        for tag, extra in (("dense", {}),
                           ("sparse", {"tpu_sparse_threshold": 0.2})):
            p = {**BASE, **extra}
            bst = _model(p, X, y, rounds=3)
            d = bst.dump_model()
            feats = []
            for t in d["tree_info"]:
                def walk(nd):
                    if "split_feature" in nd:
                        feats.append((nd["split_feature"],
                                      nd.get("threshold")))
                        walk(nd["left_child"])
                        walk(nd["right_child"])
                walk(t["tree_structure"])
            recs[tag] = feats
        # identical split sets up to summation-order near-ties: demand
        # high overlap, not bit equality
        same = sum(a == b for a, b in zip(recs["dense"], recs["sparse"]))
        assert same / max(len(recs["dense"]), 1) >= 0.9, recs

    def test_sparse_train_auc_learns(self):
        X, y = _sparse_problem(density=0.02)
        p = {**BASE, "tpu_sparse_threshold": 0.2,
             "metric": ["auc"]}
        bst = _model(p, X, y, rounds=10)
        auc = dict((nm, v) for _, nm, v, _ in bst.eval_train())["auc"]
        assert auc > 0.85, auc


class TestSparseStorageGates:
    def test_rejects_feature_sharding(self):
        X, y = _sparse_problem(n=512)
        p = {**BASE, "tpu_sparse_threshold": 0.2,
             "tree_learner": "feature", "num_machines": 4}
        with pytest.raises(NotImplementedError, match="serial"):
            _model(p, X, y, rounds=1)

    def test_rejects_bundling(self):
        X, y = _sparse_problem(n=512)
        p = {**BASE, "tpu_sparse_threshold": 0.2, "enable_bundle": True}
        with pytest.raises(ValueError, match="enable_bundle"):
            _model(p, X, y, rounds=1)


@pytest.mark.slow
class TestBoschShapedMemory:
    """VERDICT r4 #7: the Bosch-shaped wide-sparse fixture must not pay
    dense HBM.  Scaled to 100k rows for the CPU tier; the storage-bytes
    assertion is shape-derived so it transfers to the 1.18M-row
    original (968 features at ~2% density)."""

    def test_storage_bound_and_training(self):
        n, F, density = 100_000, 968, 0.02
        rng = np.random.default_rng(7)
        rows = rng.integers(0, n, size=int(n * F * density))
        cols = rng.integers(8, F, size=len(rows))
        X = np.zeros((n, F), np.float32)
        X[rows, cols] = rng.normal(size=len(rows)).astype(np.float32)
        X[:, :8] = rng.normal(size=(n, 8)).astype(np.float32)
        y = ((X[:, 0] + X[:, 100] * 3 + X[:, 500] * 2) > 0
             ).astype(np.float64)
        p = {**BASE, "max_bin": 15, "tpu_sparse_threshold": 0.3,
             "num_leaves": 31}
        ds = lgb.Dataset(X, label=y, params=p)
        bst = lgb.train(p, ds, num_boost_round=2,
                        keep_training_booster=True)
        lr = bst._driver.learner
        assert lr.params.has_sparse
        # device-side bin storage: dense matrix + COO tables must be a
        # small fraction of the all-dense [F, n_pad] uint8 equivalent
        sidx = np.asarray(lr.meta["sparse_idx"])
        sbin = np.asarray(lr.meta["sparse_bin"])
        sparse_bytes = (lr.bins_t.size * lr.bins_t.dtype.itemsize
                        + sidx.nbytes + sbin.nbytes)
        dense_bytes = lr.g_pad * lr.n_pad  # uint8
        ratio = sparse_bytes / dense_bytes
        assert ratio < 0.25, (sparse_bytes, dense_bytes, ratio)
        # and the model actually trained on the sparse representation
        assert bst.num_trees() == 2
        assert "split_gain" in bst.model_to_string()


class TestSparseDataParallel:
    """Sparse storage composed with the data-parallel learner: per-shard
    COO tables ([d, Gs, M], shard-local row ids) sliced by axis_index
    inside the shard_map; the sparse contraction psums like the dense
    one and the zero bin reconstructs post-psum from global totals."""

    def test_f64_matches_serial(self, _x64_reset):
        X, y = _sparse_problem()
        p_ser = {**BASE, "deterministic": True,
                 "tpu_sparse_threshold": 0.2}
        p_par = {**p_ser, "tree_learner": "data", "num_machines": 8}
        models = {}
        for tag, p in (("serial", p_ser), ("data", p_par)):
            models[tag] = _model(p, X, y).model_to_string().split(
                "\nparameters:")[0]
        assert models["data"] == models["serial"]

    def test_default_precision_learns(self):
        X, y = _sparse_problem(density=0.02)
        p = {**BASE, "tpu_sparse_threshold": 0.2, "metric": ["auc"],
             "tree_learner": "data", "num_machines": 8}
        bst = _model(p, X, y, rounds=8)
        auc = dict((nm, v) for _, nm, v, _ in bst.eval_train())["auc"]
        assert auc > 0.85, auc

    def test_voting_sparse_parity_and_learns(self):
        """Voting composes with sparse storage: the local gain vote
        reconstructs zero bins from LOCAL totals, the voted aggregation
        from GLOBAL post-psum totals.  Voting is approximate by design,
        so the contract is root-decision parity with serial-sparse at a
        generous top_k plus end-to-end learning."""
        X, y = _sparse_problem(density=0.03)
        p_ser = {**BASE, "tpu_sparse_threshold": 0.2, "metric": ["auc"]}
        p_vot = {**p_ser, "tree_learner": "voting", "num_machines": 8,
                 "top_k": 8}
        roots = {}
        for tag, p in (("serial", p_ser), ("voting", p_vot)):
            bst = _model(p, X, y, rounds=6)
            d = bst.dump_model()["tree_info"][0]["tree_structure"]
            roots[tag] = (d["split_feature"], d["threshold"])
            if tag == "voting":
                auc = dict((nm, v)
                           for _, nm, v, _ in bst.eval_train())["auc"]
                assert auc > 0.85, auc
        assert roots["voting"] == roots["serial"], roots


class TestSparseEdgeCompositions:
    """Dense-vs-sparse f64 bit-parity under the features that interact
    with the COO path's masking and bin-space assumptions."""

    def _parity(self, X, y, extra=None, rounds=4, **data_kw):
        models = {}
        for tag, sp in (("dense", 0.0), ("sparse", 0.35)):
            p = {**BASE, "deterministic": True, "tpu_sparse_threshold": sp,
                 **(extra or {})}
            ds = lgb.Dataset(X, label=y, params=p, **data_kw)
            bst = lgb.train(p, ds, num_boost_round=rounds,
                            keep_training_booster=True)
            if tag == "sparse":
                assert bst._driver.learner.params.has_sparse
            models[tag] = bst.model_to_string().split("\nparameters:")[0]
        assert models["sparse"] == models["dense"]

    def test_categorical_sparse_column(self, _x64_reset):
        """A mostly-zero CATEGORICAL column stored sparse: the bin-space
        bitset decision and the cat split search must see the same
        histograms either way."""
        rng = np.random.default_rng(13)
        n = 3000
        X = np.zeros((n, 6))
        X[:, :3] = rng.normal(size=(n, 3))
        nz = rng.choice(n, size=200, replace=False)
        X[nz, 4] = rng.integers(1, 6, size=200)  # sparse categorical
        X[:, 5] = rng.integers(0, 4, size=n)     # dense categorical
        y = ((X[:, 0] > 0) ^ (X[:, 4] == 2)).astype(np.float64)
        self._parity(X, y, extra={"categorical_feature": "4,5"})

    def test_bagging_masks_sparse_rows(self, _x64_reset):
        """Bagging zeroes stats per row; the COO gather must respect the
        mask and the zero-bin reconstruction must use MASKED totals."""
        X, y = _sparse_problem()
        self._parity(X, y, extra={"bagging_fraction": 0.6,
                                  "bagging_freq": 1})

    def test_row_weights(self, _x64_reset):
        X, y = _sparse_problem()
        rng = np.random.default_rng(5)
        w = rng.random(len(y)) + 0.5
        self._parity(X, y, weight=w)
