"""Quantized-gradient histogram pipeline (tpu_hist_precision=int16|int8).

Covers the ISSUE-4 acceptance matrix: float modes are bitwise no-ops
under the new quant params, integer histograms match an np.int64 oracle
EXACTLY on both backends (xla and the pallas2 kernel), stochastic
rounding is unbiased in expectation and deterministic given the seed,
full trainings stay within 2e-3 of f32 quality on binary / multiclass /
regression, data-parallel int8 split decisions are bit-identical across
1/2/4 shard meshes (int32 psum is associative), and the optional leaf
refit changes values but never structure.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import lightgbm_tpu as lgb
from lightgbm_tpu.config import Config
from lightgbm_tpu.io.dataset import TrainingData
from lightgbm_tpu.models.learner import TPUTreeLearner
from lightgbm_tpu.ops import grower as G
from lightgbm_tpu.ops.histogram import (build_histogram,
                                        build_histogram_batched_t,
                                        pack_stats, quant_limit,
                                        quantize_values)


def _auc(y, score):
    """Rank-based AUC (no sklearn dependency in the test tier)."""
    n = len(y)
    order = np.argsort(score, kind="stable")
    rank = np.empty(n)
    rank[order] = np.arange(1, n + 1)
    pos = y > 0
    np_, nn = pos.sum(), n - pos.sum()
    return (rank[pos].sum() - np_ * (np_ + 1) / 2) / (np_ * nn)


def _binary_problem(n=3000, f=10, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, f))
    y = (X[:, 0] + 0.5 * X[:, 1] * X[:, 2]
         + 0.3 * rng.normal(size=n) > 0).astype(np.float64)
    return X, y


def _train(X, y, prec, rounds=20, keep=False, **extra):
    p = {"objective": "binary", "num_leaves": 31, "max_bin": 63,
         "min_data_in_leaf": 5, "verbosity": -1,
         "tpu_hist_precision": prec, **extra}
    ds = lgb.Dataset(X, label=y, params={"max_bin": p["max_bin"]})
    return lgb.train(p, ds, num_boost_round=rounds,
                     keep_training_booster=keep)


def _model_text(bst):
    return bst.model_to_string().split("\nparameters:")[0]


class TestQuantLimit:
    def test_type_max_when_rows_small(self):
        assert quant_limit("int8", 1000) == 127
        assert quant_limit("int16", 1000) == 32767

    def test_grid_narrows_for_large_row_counts(self):
        # int16 at 1M rows must cap so n * qmax fits int32
        q = quant_limit("int16", 1_000_000)
        assert q < 32767
        assert q * 1_000_000 <= 2 ** 31 - 1
        assert quant_limit("int8", 10_000_000) == 127

    def test_raises_past_int32_capacity(self):
        with pytest.raises(ValueError):
            quant_limit("int8", 2 ** 32)


class TestHistogramInt64Oracle:
    """int8/int16 histograms must equal exact int64 accumulation."""

    def _case(self, precision, n=2048, F=6, B=16, seed=1):
        rng = np.random.default_rng(seed)
        bins = rng.integers(0, B, size=(n, F)).astype(np.int32)
        q = quant_limit(precision, n)
        mask = (rng.random(n) < 0.8).astype(np.float32)
        g = (rng.integers(-q, q + 1, size=n) * (mask > 0)).astype(np.int32)
        h = (rng.integers(0, q + 1, size=n) * (mask > 0)).astype(np.int32)
        oracle = np.zeros((F, B, 3), np.int64)
        for f in range(F):
            np.add.at(oracle[f, :, 0], bins[:, f], g.astype(np.int64))
            np.add.at(oracle[f, :, 1], bins[:, f], h.astype(np.int64))
            np.add.at(oracle[f, :, 2], bins[:, f],
                      (mask > 0).astype(np.int64))
        return bins, g, h, mask, oracle

    @pytest.mark.parametrize("precision", ["int8", "int16"])
    def test_build_histogram_exact(self, precision):
        bins, g, h, mask, oracle = self._case(precision)
        stats = pack_stats(jnp.asarray(g), jnp.asarray(h),
                           jnp.asarray(mask), precision)
        assert stats.dtype == {"int8": jnp.int8,
                               "int16": jnp.int16}[precision]
        hist = np.asarray(build_histogram(
            jnp.asarray(bins), stats, 16, block_rows=512,
            precision=precision))
        assert hist.dtype == np.int32
        np.testing.assert_array_equal(hist.astype(np.int64), oracle)

    @pytest.mark.parametrize("impl", ["xla", "pallas2"])
    def test_batched_slots_exact(self, impl):
        n, F, B, K = 1024, 5, 16, 4
        bins, g, h, mask, _ = self._case("int8", n=n, F=F, B=B)
        rng = np.random.default_rng(2)
        leaf = rng.integers(0, K, size=n).astype(np.int32)
        oracle = np.zeros((K, F, B, 3), np.int64)
        for k in range(K):
            m = leaf == k
            for f in range(F):
                np.add.at(oracle[k, f, :, 0], bins[m, f],
                          g[m].astype(np.int64))
                np.add.at(oracle[k, f, :, 1], bins[m, f],
                          h[m].astype(np.int64))
                np.add.at(oracle[k, f, :, 2], bins[m, f],
                          (mask > 0)[m].astype(np.int64))
        block = 256
        nb = n // block
        bins_tb = jnp.asarray(np.ascontiguousarray(bins.T)
                              .reshape(F, nb, block).transpose(1, 0, 2))
        stats = pack_stats(jnp.asarray(g), jnp.asarray(h),
                           jnp.asarray(mask), "int8").reshape(3, nb, block)
        hist = np.asarray(build_histogram_batched_t(
            bins_tb, stats, jnp.asarray(leaf.reshape(nb, block)),
            jnp.arange(K, dtype=jnp.int32), B, "int8", impl=impl))
        np.testing.assert_array_equal(hist.astype(np.int64), oracle)


class TestStochasticRounding:
    def test_unbiased_in_expectation(self):
        x = jnp.full(200000, 0.3)
        r = np.asarray(quantize_values(x, 1.0, 127, "stochastic",
                                       12, 34, 0, 7))
        assert set(np.unique(r)) <= {0, 1}
        # sigma = sqrt(0.21 / n) ~ 0.001; 5-sigma band
        assert abs(r.mean() - 0.3) < 5e-3

    def test_deterministic_given_seed_and_offset(self):
        x = jnp.asarray(np.random.default_rng(0)
                        .normal(size=4096).astype(np.float32))
        a = np.asarray(quantize_values(x, 0.01, 127, "stochastic",
                                       12, 34, 0, 7))
        b = np.asarray(quantize_values(x, 0.01, 127, "stochastic",
                                       12, 34, 0, 7))
        c = np.asarray(quantize_values(x, 0.01, 127, "stochastic",
                                       99, 34, 0, 7))
        np.testing.assert_array_equal(a, b)
        assert not np.array_equal(a, c)

    def test_shard_offset_slices_the_global_stream(self):
        # rows [1024:2048] quantized as a "shard" (row_offset=1024) must
        # equal the same slice of the whole-array draw: the invariance
        # that makes data-parallel quantization shard-count independent
        x = jnp.asarray(np.random.default_rng(1)
                        .normal(size=2048).astype(np.float32))
        whole = np.asarray(quantize_values(x, 0.01, 127, "stochastic",
                                           5, 6, 0, 7))
        shard = np.asarray(quantize_values(x[1024:], 0.01, 127,
                                           "stochastic", 5, 6, 1024, 7))
        np.testing.assert_array_equal(whole[1024:], shard)

    def test_nearest_is_rint(self):
        x = jnp.asarray([0.4, 0.6, -0.4, -0.6, 1.5, 2.5])
        r = np.asarray(quantize_values(x, 1.0, 127, "nearest"))
        np.testing.assert_array_equal(r, np.rint(np.asarray(x)))

    def test_values_stay_on_grid(self):
        x = jnp.asarray(np.random.default_rng(2)
                        .normal(size=1000).astype(np.float32) * 100)
        r = np.asarray(quantize_values(x, jnp.max(jnp.abs(x)) / 127,
                                       127, "stochastic", 1, 2, 0, 3))
        assert r.min() >= -127 and r.max() <= 127


class TestFloatPathsUnchanged:
    def test_quant_params_are_noops_for_float_precisions(self):
        X, y = _binary_problem(n=1200)
        base = _model_text(_train(X, y, "hilo", rounds=6))
        flipped = _model_text(_train(X, y, "hilo", rounds=6,
                                     tpu_quant_round="nearest",
                                     tpu_quant_refit_leaves=False))
        assert flipped == base

    def test_quantized_training_deterministic_given_seed(self):
        X, y = _binary_problem(n=1200)
        a = _model_text(_train(X, y, "int8", rounds=8, seed=11))
        b = _model_text(_train(X, y, "int8", rounds=8, seed=11))
        assert a == b

    def test_invalid_quant_config_rejected(self):
        X, y = _binary_problem(n=400)
        with pytest.raises(ValueError):
            _train(X, y, "int4", rounds=1)
        with pytest.raises(ValueError):
            _train(X, y, "int8", rounds=1, tpu_quant_round="banker")
        with pytest.raises(ValueError):
            _train(X, y, "int8", rounds=1, tpu_sparse_threshold=0.5,
                   enable_bundle=False)


class TestTrainQualityParity:
    """Full-train quality within 2e-3 of f32 (ISSUE-4 acceptance)."""

    def test_binary_auc(self):
        X, y = _binary_problem()
        aucs = {}
        for prec in ("f32", "int16", "int8"):
            pred = _train(X, y, prec).predict(X, raw_score=True)
            aucs[prec] = _auc(y, pred)
        assert abs(aucs["int16"] - aucs["f32"]) < 2e-3, aucs
        assert abs(aucs["int8"] - aucs["f32"]) < 2e-3, aucs

    def test_regression_l2(self):
        rng = np.random.default_rng(3)
        X = rng.normal(size=(2000, 8))
        y = X[:, 0] * 2 + np.sin(X[:, 1]) + 0.1 * rng.normal(size=2000)
        mses = {}
        for prec in ("f32", "int16", "int8"):
            p = {"objective": "regression", "num_leaves": 31,
                 "max_bin": 63, "min_data_in_leaf": 5, "verbosity": -1,
                 "tpu_hist_precision": prec}
            ds = lgb.Dataset(X, label=y, params={"max_bin": 63})
            bst = lgb.train(p, ds, num_boost_round=20)
            mses[prec] = float(np.mean((bst.predict(X) - y) ** 2))
        assert mses["int16"] <= mses["f32"] * 1.05, mses
        assert mses["int8"] <= mses["f32"] * 1.05, mses

    def test_multiclass_logloss(self):
        rng = np.random.default_rng(4)
        n = 1500
        X = rng.normal(size=(n, 8))
        y = (np.argmax(X[:, :3] + 0.3 * rng.normal(size=(n, 3)), axis=1)
             .astype(np.float64))
        lls = {}
        for prec in ("f32", "int8"):
            p = {"objective": "multiclass", "num_class": 3,
                 "num_leaves": 15, "max_bin": 63, "min_data_in_leaf": 5,
                 "verbosity": -1, "tpu_hist_precision": prec}
            ds = lgb.Dataset(X, label=y, params={"max_bin": 63})
            bst = lgb.train(p, ds, num_boost_round=15)
            prob = np.clip(bst.predict(X), 1e-9, 1.0)
            lls[prec] = float(-np.mean(np.log(
                prob[np.arange(n), y.astype(int)])))
        assert lls["int8"] <= lls["f32"] + 2e-2, lls


class TestDataParallelBitwise:
    """int8 split decisions bit-identical across 1/2/4 shard meshes: the
    quantized rows are sharding-invariant (hashed global-row rounding),
    max-abs scales pmax exactly, and int32 histogram psum is associative
    — so EVERY record field (features, thresholds, gains, outputs)
    matches bitwise, not just approximately (contrast the float modes'
    0.85-agreement bound in test_parallel.py)."""

    def _grow_records(self, X, y, **cfg):
        params = {"objective": "binary", "max_bin": 63, "num_leaves": 15,
                  "min_data_in_leaf": 5, "tpu_block_rows": 512,
                  "tpu_hist_precision": "int8", "verbosity": -1}
        params.update(cfg)
        config = Config(params)
        td = TrainingData.from_matrix(X, y, config)
        learner = TPUTreeLearner(config, td)
        r = np.random.default_rng(3)
        grad = r.normal(size=learner.n).astype(np.float32)
        hess = np.abs(r.normal(size=learner.n)).astype(np.float32) + 0.1
        tree, leaf_ids, out = learner.train(jnp.asarray(grad),
                                            jnp.asarray(hess))
        return (np.asarray(jax.device_get(out["records"])),
                np.asarray(jax.device_get(leaf_ids)))

    def test_records_bitwise_across_shard_counts(self):
        rng = np.random.default_rng(7)
        X = rng.normal(size=(4096, 10))
        y = (X[:, 0] + 0.5 * X[:, 1] * X[:, 2] > 0).astype(np.float64)
        rec1, l1 = self._grow_records(X, y)
        rec2, l2 = self._grow_records(X, y, tree_learner="data",
                                      num_machines=2)
        rec4, l4 = self._grow_records(X, y, tree_learner="data",
                                      num_machines=4)
        assert (rec1[:, G.REC_DID_SPLIT] > 0.5).sum() > 5  # real splits
        np.testing.assert_array_equal(rec1, rec2)
        np.testing.assert_array_equal(rec1, rec4)
        np.testing.assert_array_equal(l1, l2)
        np.testing.assert_array_equal(l1, l4)


class TestDataParallelModelBitwise:
    """End-to-end: serial and 4-shard data-parallel int8 trainings emit
    BITWISE-identical model files (refit off: the refit leaf values are
    the one f32 psum whose shard-order ulps could reach the model)."""

    def test_model_string_bitwise(self):
        X, y = _binary_problem(n=4096)
        texts = []
        for cfg in ({}, {"tree_learner": "data", "num_machines": 4}):
            texts.append(_model_text(_train(
                X, y, "int8", rounds=6, tpu_quant_refit_leaves=False,
                tpu_block_rows=512, **cfg)))
        assert texts[0] == texts[1]


class TestLeafRefit:
    def test_refit_changes_values_not_structure(self):
        # ONE round: from round 2 on the refit legitimately changes the
        # trajectory (refitted leaf values feed the next iteration's
        # gradients), so only the first tree's structure must match
        X, y = _binary_problem(n=2000)
        on = _train(X, y, "int8", rounds=1, tpu_quant_refit_leaves=True)
        off = _train(X, y, "int8", rounds=1,
                     tpu_quant_refit_leaves=False)
        ta = on._driver.models[0]
        tb = off._driver.models[0]
        assert ta.num_leaves == tb.num_leaves > 2
        ni = ta.num_leaves - 1
        np.testing.assert_array_equal(ta.split_feature[:ni],
                                      tb.split_feature[:ni])
        np.testing.assert_array_equal(ta.threshold_in_bin[:ni],
                                      tb.threshold_in_bin[:ni])
        assert not np.array_equal(ta.leaf_value[:ta.num_leaves],
                                  tb.leaf_value[:tb.num_leaves])

    def test_refit_auc_close_to_f32(self):
        X, y = _binary_problem(n=2000)
        auc_f = _auc(y, _train(X, y, "f32", rounds=15)
                     .predict(X, raw_score=True))
        auc_q = _auc(y, _train(X, y, "int8", rounds=15,
                               tpu_quant_refit_leaves=True)
                     .predict(X, raw_score=True))
        assert abs(auc_q - auc_f) < 2e-3, (auc_f, auc_q)

    def test_refit_scores_match_materialized_trees(self):
        # the fused step's device score state must agree with the host
        # trees it lazily materializes (the refit overrides BOTH sides
        # from the same device vector)
        X, y = _binary_problem(n=1500)
        bst = _train(X, y, "int8", rounds=6, keep=True,
                     tpu_quant_refit_leaves=True)
        dev_scores = np.asarray(bst._driver.train_scores.numpy())[0]
        replay = bst.predict(X, raw_score=True)
        np.testing.assert_allclose(dev_scores, replay, rtol=1e-4,
                                   atol=1e-5)


class TestDeterministicModeKeepsInt:
    def test_deterministic_flag_does_not_force_f64(self):
        cfg = Config({"deterministic": True,
                      "tpu_hist_precision": "int8"})
        assert TPUTreeLearner._resolve_precision(cfg) == "int8"
        assert not jax.config.jax_enable_x64


class TestCompileCacheParam:
    def test_cache_dir_param_repoints_jax_cache(self, tmp_path):
        # tpu_compile_cache_dir must reach jax_compilation_cache_dir at
        # learner init (first device use) and actually persist entries
        # (the cache singleton latches its dir at first use; the wiring
        # resets it — see utils/backend.py enable_compilation_cache)
        import os

        cache = str(tmp_path / "xlacache")
        X, y = _binary_problem(n=500)
        prev = jax.config.jax_compilation_cache_dir
        try:
            bst = _train(X, y, "hilo", rounds=2, num_leaves=7,
                         tpu_compile_cache_dir=cache)
            assert (jax.config.jax_compilation_cache_dir or "") \
                .startswith(cache)
            entries = sum(len(f) for _, _, f in os.walk(cache))
            assert entries > 0
        finally:
            # restore the session's cache dir (already fingerprinted by
            # the import-time enable) and re-latch the singleton to it
            jax.config.update("jax_compilation_cache_dir", prev)
            try:
                import jax._src.compilation_cache as _cc

                _cc.reset_cache()
            except Exception:
                pass
