"""Device compute core: histogram kernel + split search + grower.

Validates the TPU formulation against straightforward numpy oracles
(histograms) and against brute-force split enumeration.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from lightgbm_tpu.ops.histogram import build_histogram, pack_stats
from lightgbm_tpu.ops.split import find_best_split_all_features, leaf_output


def _np_histogram(bins, grad, hess, mask, B):
    n, F = bins.shape
    out = np.zeros((F, B, 3))
    for f in range(F):
        for r in range(n):
            if mask[r] > 0:
                b = bins[r, f]
                out[f, b, 0] += grad[r]
                out[f, b, 1] += hess[r]
                out[f, b, 2] += 1
    return out


class TestHistogram:
    @pytest.mark.parametrize("precision", ["hilo", "f32"])
    def test_matches_numpy(self, precision):
        rng = np.random.default_rng(0)
        n, F, B = 1000, 5, 16
        bins = rng.integers(0, B, size=(n, F)).astype(np.int32)
        grad = rng.normal(size=n).astype(np.float32)
        hess = rng.uniform(0.1, 1.0, size=n).astype(np.float32)
        mask = (rng.random(n) < 0.8).astype(np.float32)
        ref = _np_histogram(bins, grad, hess, mask, B)
        stats = pack_stats(jnp.asarray(grad * mask), jnp.asarray(hess * mask),
                           jnp.asarray(mask), precision)
        hist = np.asarray(build_histogram(jnp.asarray(bins), stats, B,
                                          block_rows=256, precision=precision))
        tol = 1e-3 if precision == "hilo" else 1e-4
        np.testing.assert_allclose(hist[..., 0], ref[..., 0], atol=tol, rtol=tol)
        np.testing.assert_allclose(hist[..., 1], ref[..., 1], atol=tol, rtol=tol)
        np.testing.assert_allclose(hist[..., 2], ref[..., 2], atol=0.5)

    def test_hilo_much_better_than_bf16(self):
        rng = np.random.default_rng(1)
        n, B = 20000, 4
        bins = np.zeros((n, 1), np.int32)  # all rows -> one bin: stress summation
        grad = rng.normal(size=n).astype(np.float32)
        ones = np.ones(n, np.float32)
        exact = grad.astype(np.float64).sum()
        errs = {}
        for prec in ("hilo", "bf16"):
            stats = pack_stats(jnp.asarray(grad), jnp.asarray(ones),
                               jnp.asarray(ones), prec)
            hist = np.asarray(build_histogram(jnp.asarray(bins), stats, B,
                                              block_rows=4096, precision=prec))
            errs[prec] = abs(hist[0, 0, 0] - exact)
        assert errs["hilo"] < errs["bf16"] / 10


def _brute_force_best_split(hist, sum_g, sum_h, num_data, min_data, min_hess,
                            l1=0.0, l2=0.0):
    """Enumerate all (feature, threshold) splits; missing_type=None."""
    F, B, _ = hist.shape
    best = (-np.inf, -1, -1)
    for f in range(F):
        for t in range(B - 1):
            lg = hist[f, :t + 1, 0].sum()
            lh = hist[f, :t + 1, 1].sum()
            lc = hist[f, :t + 1, 2].sum()
            rg, rh, rc = sum_g - lg, sum_h - lh, num_data - lc
            if lc < min_data or rc < min_data or lh < min_hess or rh < min_hess:
                continue
            gain = lg * lg / (lh + l2 + 1e-38) + rg * rg / (rh + l2 + 1e-38)
            if gain > best[0]:
                best = (gain, f, t)
    return best


class TestSplitSearch:
    def test_matches_brute_force(self):
        rng = np.random.default_rng(2)
        F, B = 6, 16
        hist = np.zeros((F, B, 3), np.float32)
        hist[..., 0] = rng.normal(size=(F, B))
        hist[..., 1] = rng.uniform(0.5, 2.0, size=(F, B))
        hist[..., 2] = rng.integers(5, 50, size=(F, B))
        # make all features consistent: same totals
        sum_g = float(hist[0, :, 0].sum())
        sum_h = float(hist[0, :, 1].sum())
        cnt = float(hist[0, :, 2].sum())
        for f in range(1, F):
            scale_g = sum_g / hist[f, :, 0].sum()
            hist[f, :, 0] *= scale_g
            hist[f, :, 1] *= sum_h / hist[f, :, 1].sum()
            hist[f, :, 2] = hist[f, :, 2] * cnt / hist[f, :, 2].sum()
        cnt = float(hist[0, :, 2].sum())

        res = find_best_split_all_features(
            jnp.asarray(hist), jnp.float32(sum_g), jnp.float32(sum_h),
            jnp.float32(cnt),
            num_bin=jnp.full(F, B, jnp.int32),
            missing_type=jnp.zeros(F, jnp.int32),
            default_bin=jnp.zeros(F, jnp.int32),
            monotone=jnp.zeros(F, jnp.int32),
            penalty=jnp.ones(F, jnp.float32),
            feature_mask=jnp.ones(F, jnp.float32),
            l1=0.0, l2=0.0, max_delta_step=0.0,
            min_data_in_leaf=5.0, min_sum_hessian=1e-3, min_gain_to_split=0.0)
        bf_gain, bf_f, bf_t = _brute_force_best_split(
            hist, sum_g, sum_h, cnt, 5, 1e-3)
        assert int(res.feature) == bf_f
        assert int(res.threshold) == bf_t

    def test_min_data_respected(self):
        F, B = 2, 8
        hist = np.zeros((F, B, 3), np.float32)
        # all mass in bins 0 and 7; only split 0..6 feasible but leaves tiny
        hist[:, 0] = [10.0, 5.0, 3.0]
        hist[:, 7] = [-10.0, 5.0, 100.0]
        res = find_best_split_all_features(
            jnp.asarray(hist), jnp.float32(0.0), jnp.float32(10.0),
            jnp.float32(103.0),
            num_bin=jnp.full(F, B, jnp.int32),
            missing_type=jnp.zeros(F, jnp.int32),
            default_bin=jnp.zeros(F, jnp.int32),
            monotone=jnp.zeros(F, jnp.int32),
            penalty=jnp.ones(F, jnp.float32),
            feature_mask=jnp.ones(F, jnp.float32),
            l1=0.0, l2=0.0, max_delta_step=0.0,
            min_data_in_leaf=20.0, min_sum_hessian=1e-3, min_gain_to_split=0.0)
        assert float(res.gain) <= 0.0  # 3-row leaf violates min_data=20


class TestEndToEnd:
    def test_perfect_split_found(self):
        """A single feature perfectly separates labels -> tree must find it."""
        import lightgbm_tpu as lgb
        rng = np.random.default_rng(3)
        n = 500
        X = rng.normal(size=(n, 3))
        y = (X[:, 1] > 0.3).astype(np.float64)
        ds = lgb.Dataset(X, label=y, params={"max_bin": 64})
        bst = lgb.train({"objective": "binary", "num_leaves": 4,
                         "min_data_in_leaf": 5, "learning_rate": 0.5},
                        ds, num_boost_round=10, verbose_eval=False)
        pred = bst.predict(X)
        acc = ((pred > 0.5) == (y > 0)).mean()
        assert acc > 0.99
        # the first tree's root split must be on feature 1 near 0.3
        d = bst.dump_model()
        root = d["tree_info"][0]["tree_structure"]
        assert root["split_feature"] == 1
        assert abs(root["threshold"] - 0.3) < 0.2

    def test_monotone_constraints(self):
        import lightgbm_tpu as lgb
        rng = np.random.default_rng(4)
        n = 2000
        X = rng.uniform(-1, 1, size=(n, 2))
        y = 2 * X[:, 0] + 0.3 * np.sin(6 * X[:, 1]) + 0.1 * rng.normal(size=n)
        ds = lgb.Dataset(X, label=y, params={"max_bin": 64})
        bst = lgb.train({"objective": "regression", "num_leaves": 31,
                         "monotone_constraints": [1, 0],
                         "min_data_in_leaf": 20},
                        ds, num_boost_round=20, verbose_eval=False)
        # predictions must be monotone nondecreasing in feature 0
        xs = np.linspace(-0.95, 0.95, 50)
        for x1 in (-0.5, 0.0, 0.5):
            grid = np.column_stack([xs, np.full(50, x1)])
            p = bst.predict(grid)
            assert np.all(np.diff(p) >= -1e-9)


class TestPartitionImpls:
    """The kernel-lowered partition (interpret mode here) must grow the
    trees the select lowering grows, and the tables it has no form for
    (categorical, EFB-bundled) must refuse it by name and train under the
    default as under select."""

    def _train_dump(self, X, y, extra, impl):
        import lightgbm_tpu as lgb
        params = {"objective": "regression", "num_leaves": 31,
                  "min_data_in_leaf": 5, "max_bin": 64,
                  "tpu_partition_impl": impl, **extra}
        ds = lgb.Dataset(X, label=y, params={"max_bin": 64, **extra},
                         categorical_feature=extra.get("categorical_feature",
                                                       "auto"))
        bst = lgb.train(params, ds, num_boost_round=8, verbose_eval=False)
        # trees only: the parameters section embeds tpu_partition_impl
        # itself and must differ between the two runs
        return bst.model_to_string().split("parameters", 1)[0]

    def _refused_and_default_is_select(self, X, y, extra):
        with pytest.raises(ValueError, match="dense numerical unpacked"):
            self._train_dump(X, y, extra, "kernel")
        assert (self._train_dump(X, y, extra, "auto")
                == self._train_dump(X, y, extra, "select"))

    def test_numerical_identical(self):
        rng = np.random.default_rng(7)
        X = rng.normal(size=(3000, 6))
        y = X[:, 0] ** 2 + np.sin(3 * X[:, 1]) + 0.1 * rng.normal(size=3000)
        a = self._train_dump(X, y, {}, "select")
        b = self._train_dump(X, y, {}, "kernel")
        assert a == b

    def test_categorical_and_missing_refused(self):
        rng = np.random.default_rng(8)
        n = 3000
        Xc = rng.integers(0, 8, size=n).astype(np.float64)
        Xn = rng.normal(size=n)
        Xn[rng.random(n) < 0.2] = np.nan  # exercise the missing path
        X = np.column_stack([Xc, Xn])
        y = (Xc % 3 == 1).astype(float) * 2 + np.nan_to_num(Xn) + \
            0.1 * rng.normal(size=n)
        self._refused_and_default_is_select(
            X, y, {"categorical_feature": [0]})

    def test_missing_identical(self):
        rng = np.random.default_rng(8)
        n = 3000
        X = rng.normal(size=(n, 2))
        X[rng.random((n, 2)) < 0.2] = np.nan
        y = np.nan_to_num(X[:, 0]) + np.isnan(X[:, 1]) + \
            0.1 * rng.normal(size=n)
        a = self._train_dump(X, y, {}, "select")
        b = self._train_dump(X, y, {}, "kernel")
        assert a == b

    def test_bundled_refused(self):
        rng = np.random.default_rng(9)
        n = 4000
        # sparse one-hot-ish columns of few values so EFB actually bundles
        X = np.zeros((n, 9))
        grp = rng.integers(0, 6, size=n)
        for g in range(6):
            X[grp == g, g] = rng.integers(1, 4, size=(grp == g).sum())
        X[:, 6:] = rng.normal(size=(n, 3))
        y = X[:, 0] + 2 * X[:, 1] - X[:, 2] + X[:, 6] + \
            0.1 * rng.normal(size=n)
        self._refused_and_default_is_select(X, y, {"enable_bundle": True})


class TestBatchedHistogramImpls:
    """xla and pallas2 backends of the batched kernel must agree bit-for-bit
    (pallas2 runs in interpret mode on CPU)."""

    def test_pallas_bp_padding_parity(self):
        # B=15 pads Bp->16 inside the kernel: the padded bin rows must not
        # leak into the returned [K, F, B, 3] histograms
        from lightgbm_tpu.ops.histogram import (build_histogram_batched_t,
                                                pack_stats)
        rng = np.random.default_rng(5)
        nb, F, block, B, K = 2, 3, 128, 15, 4
        n = nb * block
        bins_t = jnp.asarray(
            rng.integers(0, B, size=(nb, F, block)), dtype=jnp.uint8)
        g = jnp.asarray(rng.normal(size=n).astype(np.float32))
        stats = pack_stats(g, jnp.abs(g) + 0.5, jnp.ones(n, jnp.float32),
                           "hilo")
        stats_blocks = stats.reshape(stats.shape[0], nb, block)
        leaf_blocks = jnp.asarray(
            rng.integers(0, K, size=(nb, block)), dtype=jnp.int32)
        slots = jnp.asarray([1, 0, -1, 3], dtype=jnp.int32)
        a = build_histogram_batched_t(bins_t, stats_blocks, leaf_blocks,
                                      slots, B, "hilo", impl="pallas2")
        b = build_histogram_batched_t(bins_t, stats_blocks, leaf_blocks,
                                      slots, B, "hilo", impl="xla")
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    @pytest.mark.parametrize("bins_dtype", [jnp.uint8, jnp.int32])
    def test_pallas2_matches_xla(self, bins_dtype):
        # per-feature one-hot variant at its bigger native blocks, on the
        # narrow dense storage (uint8, the learner's default when bins
        # fit) and on int32 bins
        from lightgbm_tpu.ops.histogram import (build_histogram_batched_t,
                                                pack_stats)
        rng = np.random.default_rng(6)
        nb, F, block, B, K = 2, 4, 512, 31, 6
        n = nb * block
        bins_t = jnp.asarray(
            rng.integers(0, B, size=(nb, F, block)), dtype=bins_dtype)
        g = jnp.asarray(rng.normal(size=n).astype(np.float32))
        stats = pack_stats(g, jnp.abs(g) + 0.2, jnp.ones(n, jnp.float32),
                           "hilo")
        stats_blocks = stats.reshape(stats.shape[0], nb, block)
        leaf_blocks = jnp.asarray(
            rng.integers(0, K + 1, size=(nb, block)), dtype=jnp.int32)
        slots = jnp.asarray([2, 0, -1, 5, 1, 4], dtype=jnp.int32)
        a = build_histogram_batched_t(bins_t, stats_blocks, leaf_blocks,
                                      slots, B, "hilo", impl="xla")
        b = build_histogram_batched_t(bins_t, stats_blocks, leaf_blocks,
                                      slots, B, "hilo", impl="pallas2")
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_pallas2_feature_chunked_grid(self, monkeypatch):
        # shrink the out-block VMEM budget so F=64 features are processed
        # in sublane-aligned divisor chunks (fblk=32 -> 2-chunk feature
        # grid axis), and the 2D (feature, row-block) grid must still
        # accumulate exactly
        from lightgbm_tpu.ops import histogram as H
        rng = np.random.default_rng(7)
        nb, F, block, B, K = 3, 64, 256, 16, 5
        Bp = 16
        ks_pad = 128
        monkeypatch.setattr(H, "_PERFEATURE_OUT_BUDGET",
                            32 * Bp * ks_pad * 4)  # fits fblk=32, not 64
        n = nb * block
        bins_t = jnp.asarray(
            rng.integers(0, B, size=(nb, F, block)), dtype=jnp.uint8)
        g = jnp.asarray(rng.normal(size=n).astype(np.float32))
        stats = H.pack_stats(g, jnp.abs(g) + 0.3, jnp.ones(n, jnp.float32),
                             "hilo")
        stats_blocks = stats.reshape(stats.shape[0], nb, block)
        leaf_blocks = jnp.asarray(
            rng.integers(0, K + 2, size=(nb, block)), dtype=jnp.int32)
        slots = jnp.asarray([0, 3, -1, 2, 6], dtype=jnp.int32)
        a = H.build_histogram_batched_t(bins_t, stats_blocks, leaf_blocks,
                                        slots, B, "hilo", impl="xla")
        b = H.build_histogram_batched_t(bins_t, stats_blocks, leaf_blocks,
                                        slots, B, "hilo", impl="pallas2")
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    @pytest.mark.parametrize("precision,K,F,live,chunked", [
        ("hilo", 1, 32, 28, False), ("hilo", 25, 32, 28, False),
        ("int8", 1, 32, 28, False), ("int8", 25, 32, 28, False),
        # fblk=32 (test_pallas2_feature_chunked_grid's budget): the live
        # count falls inside the second of two / three chunks
        ("hilo", 5, 64, 40, True), ("int8", 5, 96, 40, True)])
    def test_pallas2_live_columns(self, monkeypatch, precision, K, F, live,
                                  chunked):
        # a live count below the padded width: the kernel equals xla on
        # the live columns and is exactly zero on the padding, which xla
        # (contracting every column) fills with bin 0's mass
        from lightgbm_tpu.ops import histogram as H
        rng = np.random.default_rng(8)
        nb, block, B = 2, 256, 16
        if chunked:
            monkeypatch.setattr(H, "_PERFEATURE_OUT_BUDGET",
                                32 * 16 * 128 * 4)
        n = nb * block
        bins = rng.integers(0, B, size=(nb, F, block)).astype(np.uint8)
        bins[:, live:] = 0
        g = jnp.asarray(rng.normal(size=n).astype(np.float32))
        h = jnp.abs(g) + 0.3
        mask = jnp.ones(n, jnp.float32)
        if precision == "int8":
            g = H.quantize_values(g, jnp.max(jnp.abs(g)) / 127, 127,
                                  "nearest")
            h = H.quantize_values(h, jnp.max(h) / 127, 127, "nearest")
        stats = H.pack_stats(g, h, mask, precision)
        args = (jnp.asarray(bins), stats.reshape(stats.shape[0], nb, block),
                jnp.asarray(rng.integers(0, K + 2, size=(nb, block)),
                            dtype=jnp.int32),
                jnp.asarray(rng.permutation(K + 2)[:K], dtype=jnp.int32),
                B, precision)
        a = np.asarray(H.build_histogram_batched_t(*args, impl="xla"))
        b = np.asarray(H.build_histogram_batched_t(
            *args, impl="pallas2", live_columns=live))
        np.testing.assert_array_equal(a[:, :live], b[:, :live])
        assert a[:, live:].any() and not b[:, live:].any()
        with pytest.raises(ValueError, match="live_columns"):
            H.build_histogram_batched_t(*args, impl="pallas2",
                                        live_columns=F + 1)

    @pytest.mark.parametrize("precision", ["hilo", "int8"])
    @pytest.mark.parametrize("K", [1, 25])
    @pytest.mark.parametrize("F,live", [(32, 3), (32, 28), (32, 32),
                                        (96, 67)])
    @pytest.mark.parametrize("B", [15, 63, 255])
    def test_pallas2_column_groups(self, monkeypatch, B, F, live, K,
                                   precision):
        # groups of up to G = 4 columns, one dot each: 3 live is one short
        # group, 28 and 32 whole groups (the last beside padding, or none);
        # 96 stored run in three chunks of 32, where {0, 1, 2} are live in
        # every chunk and 3..31 under the chunk guard, seven groups of four
        # and one of one, all dead (zeroed) in the last chunk.  A group's
        # dots run over three 128-lane sub-blocks of the 384-row block (the
        # first stores the block's partial sums, the loop's one adds, the
        # last adds them to the accumulator); 15 bins are the packed 4-bit
        # rows, whose groups take the block whole
        # and whose Bp = 16 is half an int8 sublane tile: int8 there stays
        # ungrouped, the kernel as it was
        from lightgbm_tpu.ops import histogram as H
        rng = np.random.default_rng(28)
        nb, block = 2, 384
        packed = B == 15
        Bp = -(-B // 8) * 8
        S, itemsize = (5, 2) if precision == "hilo" else (3, 1)
        monkeypatch.setattr(H, "_PERFEATURE_GROUP_LANES", 128)
        assert H.perfeature_dot_lanes(block) == 128
        monkeypatch.setattr(H, "_PERFEATURE_OUT_BUDGET",
                            32 * Bp * 128 * 4)   # fblk = 32 at any B
        assert H.perfeature_chunks(F, B, K, S, 1) == (32, F // 32)
        want_g = 1 if packed and precision == "int8" else min(4, live)
        assert H.perfeature_columns_per_dot(
            B, block, precision, 32, live) == want_g
        n = nb * block
        bins = rng.integers(0, B, size=(nb, F, block)).astype(np.uint8)
        bins[:, live:] = 0
        g = jnp.asarray(rng.normal(size=n).astype(np.float32))
        h = jnp.abs(g) + 0.3
        if precision == "int8":
            g = H.quantize_values(g, jnp.max(jnp.abs(g)) / 127, 127,
                                  "nearest")
            h = H.quantize_values(h, jnp.max(h) / 127, 127, "nearest")
        stats = H.pack_stats(g, h, jnp.ones(n, jnp.float32), precision)
        rest = (stats.reshape(S, nb, block),
                jnp.asarray(rng.integers(0, K + 2, size=(nb, block)),
                            dtype=jnp.int32),
                jnp.asarray(rng.permutation(K + 2)[:K], dtype=jnp.int32),
                B, precision)
        a = np.asarray(H.build_histogram_batched_t(
            jnp.asarray(bins), *rest, impl="xla"))
        if packed:  # row j in the low nibble, row j + block/2 in the high
            bins = bins[..., :block // 2] | (bins[..., block // 2:] << 4)
        b = np.asarray(H.build_histogram_batched_t(
            jnp.asarray(bins), *rest, impl="pallas2", packed_rows=packed,
            live_columns=live))
        if precision == "hilo" and want_g > 1 and not packed:
            # three f32 partial sums per block where xla has one: a few
            # ulp of a bin's sum (int8 accumulates exactly in any order)
            np.testing.assert_allclose(a[:, :live], b[:, :live],
                                       rtol=1e-5, atol=1e-5)
        else:
            np.testing.assert_array_equal(a[:, :live], b[:, :live])
        assert not b[:, live:].any()

    @pytest.mark.parametrize("args,want", [
        # the three cells: 8192-row blocks, hilo, one chunk of 32 columns
        # (28 live) on Higgs, chunks of 32 with 67 live on the Criteo shard
        ((255, 8192, "hilo", 32, 28), 4), ((63, 8192, "hilo", 32, 28), 4),
        ((255, 8192, "hilo", 32, 67), 4),
        # never more columns than a chunk holds live
        ((63, 8192, "hilo", 32, 3), 3), ((15, 8192, "hilo", 32, 28), 4),
        ((63, 8192, "int8", 32, 28), 4), ((255, 16384, "int8", 32, 28), 4),
        # what the 2 MiB of a stacked [G * Bp, 1024] one-hot hold: f32 is
        # twice as wide as bf16, 511 bins twice as tall as 255, and a block
        # that is no whole number of 1024-lane sub-blocks is taken whole
        ((255, 8192, "f32", 32, 28), 2), ((511, 8192, "hilo", 8, 8), 2),
        ((255, 8320, "hilo", 32, 28), 1), ((15, 8320, "hilo", 32, 28), 4),
        # one column only, the kernel as it was: 1,023 and 4,095 bins
        # (int32 storage), and a Bp that is no whole sublane tile of the
        # dot's dtype
        ((1023, 8192, "hilo", 8, 8), 1), ((4095, 8192, "hilo", 8, 8), 1),
        ((20, 8192, "hilo", 32, 28), 1), ((15, 8192, "int8", 32, 28), 1)])
    def test_perfeature_columns_per_dot(self, args, want):
        from lightgbm_tpu.ops import histogram as H
        assert H.perfeature_columns_per_dot(*args) == want

    @pytest.mark.parametrize("layout", ["sparse", "streamed", "data"])
    def test_pallas2_layouts_match_xla_end_to_end(self, layout):
        """Every layout that hands the kernel a live count below its padded
        width grows the xla model: a padding column's histogram is read by
        no search, and leaf totals come from a live column."""
        import lightgbm_tpu as lgb
        rng = np.random.default_rng(16)
        n = 2048
        X = rng.normal(size=(n, 6))
        extra = {"sparse": {"tpu_sparse_threshold": 0.2,
                            "enable_bundle": False},
                 "streamed": {"tpu_stream_mode": "streamed",
                              "tpu_stream_block_rows": 1024,
                              "tpu_hist_precision": "int8"},
                 "data": {"tree_learner": "data", "num_machines": 4}}[layout]
        if layout == "sparse":
            X[:, 3:] = np.where(rng.random((n, 3)) < 0.05, X[:, 3:], 0.0)
        y = X[:, 0] - X[:, 1] + 2 * X[:, 4] + 0.1 * rng.normal(size=n)
        live = 3 if layout == "sparse" else 6

        def dump(impl):
            params = {"objective": "regression", "num_leaves": 15,
                      "min_data_in_leaf": 5, "max_bin": 32,
                      "tpu_hist_impl": impl, "tpu_block_rows": 256,
                      "verbosity": -1, **extra}
            ds = lgb.Dataset(X, label=y, params=params)
            bst = lgb.train(params, ds, num_boost_round=3,
                            keep_training_booster=True)
            assert bst._driver.learner.live_columns == (
                live if impl == "pallas2" else None)
            return bst.model_to_string().split("parameters", 1)[0]

        assert dump("pallas2") == dump("xla")

    def test_learner_reports_live_and_padding_columns(self):
        """28 columns pad to 32 for pallas2: the registry says what the
        grower was built with, and that the root runs at one slot."""
        import lightgbm_tpu as lgb
        from lightgbm_tpu import obs
        rng = np.random.default_rng(17)
        X = rng.normal(size=(512, 28))
        y = X[:, 0] + 0.1 * rng.normal(size=512)
        want = {"pallas2": (28, 4, 1), "xla": (32, 0, 0)}
        for impl, (live, padding, root_slots) in want.items():
            params = {"objective": "regression", "num_leaves": 4,
                      "max_bin": 16, "tpu_hist_impl": impl,
                      "tpu_block_rows": 256, "verbosity": -1}
            bst = lgb.train(params, lgb.Dataset(X, label=y, params=params),
                            num_boost_round=1, keep_training_booster=True)
            lrn = bst._driver.learner
            assert lrn.bins_t.shape[0] == live + padding == 32
            got = (obs.REGISTRY.value("lgbm_hist_columns", kind="live"),
                   obs.REGISTRY.value("lgbm_hist_columns", kind="padding"),
                   obs.REGISTRY.value("lgbm_hist_root_slots"))
            assert got == (live, padding, root_slots)

    def test_grower_pallas2_matches_xla_end_to_end(self):
        import lightgbm_tpu as lgb
        rng = np.random.default_rng(12)
        X = rng.normal(size=(1536, 4))
        y = np.sin(2 * X[:, 0]) + X[:, 1] + 0.1 * rng.normal(size=1536)

        def dump(impl):
            params = {"objective": "regression", "num_leaves": 15,
                      "min_data_in_leaf": 5, "max_bin": 32,
                      "tpu_hist_impl": impl, "tpu_block_rows": 512,
                      "verbosity": -1}
            ds = lgb.Dataset(X, label=y, params={"max_bin": 32})
            bst = lgb.train(params, ds, num_boost_round=3,
                            verbose_eval=False)
            return bst.model_to_string().split("parameters", 1)[0]

        assert dump("pallas2") == dump("xla")


class TestFrontierRamp:
    """tpu_ramp pre-rounds must grow BIT-IDENTICAL trees (the frontier
    after r rounds never exceeds 2^r, so every ramp pre-round covers all
    splittable leaves the full-K loop would take — see GrowerParams.ramp)."""

    def _dump(self, X, y, **extra):
        import lightgbm_tpu as lgb
        params = {"objective": "regression", "num_leaves": 63,
                  "min_data_in_leaf": 5, "max_bin": 64,
                  "tpu_split_batch": 8, "verbosity": -1, **extra}
        ds = lgb.Dataset(X, label=y, params={"max_bin": 64})
        bst = lgb.train(params, ds, num_boost_round=4, verbose_eval=False)
        return bst.model_to_string().split("parameters", 1)[0]

    def test_bit_identical_trees(self):
        rng = np.random.default_rng(13)
        X = rng.normal(size=(4096, 6))
        y = X[:, 0] ** 2 - X[:, 1] + 0.3 * np.sin(4 * X[:, 2]) \
            + 0.1 * rng.normal(size=4096)
        assert (self._dump(X, y, tpu_ramp=True)
                == self._dump(X, y, tpu_ramp=False))

    def test_bit_identical_with_categoricals(self):
        rng = np.random.default_rng(14)
        n = 3000
        Xc = rng.integers(0, 9, size=n).astype(np.float64)
        Xn = rng.normal(size=(n, 3))
        X = np.column_stack([Xc, Xn])
        y = (Xc % 2) * 1.5 + Xn[:, 0] + 0.1 * rng.normal(size=n)
        extra = {"categorical_feature": [0]}
        assert (self._dump(X, y, tpu_ramp=True, **extra)
                == self._dump(X, y, tpu_ramp=False, **extra))


class TestPallas2Bundled:
    """EFB bundles + the perfeature kernel: the padded column axis and the
    bundle-histogram expansion must compose (learner pads g_pad to a
    32-multiple for pallas2; padding columns are all-zero and unused)."""

    def test_bundled_pallas2_matches_xla(self):
        import lightgbm_tpu as lgb
        rng = np.random.default_rng(15)
        n = 4000
        X = np.zeros((n, 6))
        grp = rng.integers(0, 3, size=n)
        for g in range(3):
            X[grp == g, g] = rng.uniform(1, 2, size=(grp == g).sum())
        X[:, 3:] = rng.normal(size=(n, 3))
        y = X[:, 0] + 2 * X[:, 1] - X[:, 2] + X[:, 3] + \
            0.1 * rng.normal(size=n)

        def dump(impl):
            params = {"objective": "regression", "num_leaves": 15,
                      "min_data_in_leaf": 5, "max_bin": 32,
                      "enable_bundle": True, "tpu_hist_impl": impl,
                      "tpu_block_rows": 512, "verbosity": -1}
            ds = lgb.Dataset(X, label=y, params={"max_bin": 32})
            bst = lgb.train(params, ds, num_boost_round=3,
                            verbose_eval=False)
            return bst.model_to_string().split("parameters", 1)[0]

        assert dump("pallas2") == dump("xla")


class TestPackedBins:
    """4-bit two-rows-per-byte bin packing (reference dense_nbits_bin.hpp
    analog): the packed pallas path must reproduce the unpacked models
    bit-for-bit, and the learner must only enable it when the layout
    supports it."""

    def _train(self, **extra):
        import lightgbm_tpu as lgb

        rng = np.random.default_rng(4)
        X = rng.normal(size=(3000, 10))
        y = (X[:, 0] - 0.5 * X[:, 1] > 0).astype(np.float64)
        p = {"objective": "binary", "num_leaves": 15, "verbosity": -1,
             "max_bin": 15, "tpu_hist_impl": "pallas2",
             "tpu_block_rows": 512, **extra}
        ds = lgb.Dataset(X, label=y, params=p)
        bst = lgb.train(p, ds, num_boost_round=5,
                        keep_training_booster=True)
        return bst

    def test_packed_model_identical_to_unpacked(self):
        out = {}
        for pack in (True, False):
            bst = self._train(tpu_pack_bins=pack)
            assert bst._driver.learner.packed_bins == pack
            out[pack] = bst.model_to_string().split("\nparameters:")[0]
        assert out[True] == out[False]

    def test_packed_data_parallel_matches_unpacked(self):
        """The pack layout's blocks must coincide with the PER-SHARD
        grower blocks — a global-block layout split across data shards
        decodes the wrong rows silently (review finding, round 4)."""
        out = {}
        for pack in (True, False):
            bst = self._train(tree_learner="data", num_machines=8,
                              tpu_block_rows=256, tpu_pack_bins=pack)
            if pack:
                assert bst._driver.learner.packed_bins
            out[pack] = bst.model_to_string().split("\nparameters:")[0]
        assert out[True] == out[False]

    def test_packing_skipped_when_unsupported(self):
        # too many bins
        assert not self._train(max_bin=63)._driver.learner.packed_bins
        # xla impl
        assert not self._train(
            tpu_hist_impl="xla")._driver.learner.packed_bins
        # odd effective block (sub-256 alignment)
        assert not self._train(
            tpu_block_rows=128)._driver.learner.packed_bins


class TestKernelPartition:
    """tpu_partition_impl=kernel (one Pallas pass a round) must reproduce
    the unrolled "select" lowering bit-for-bit on plain tables, and be
    refused by name on categorical, EFB-bundled and packed-bin ones,
    which train under the default as under select."""

    def _model(self, seed, cat=False, **extra):
        import lightgbm_tpu as lgb

        rng = np.random.default_rng(seed)
        X = rng.normal(size=(2500, 8))
        cat_idx = []
        if cat:
            X[:, 3] = rng.integers(0, 7, size=2500)
            cat_idx = [3]
        y = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(np.float64)
        p = {"objective": "binary", "num_leaves": 15, "verbosity": -1,
             "max_bin": 31, "tpu_block_rows": 512, **extra}
        ds = lgb.Dataset(X, label=y, params=p,
                         categorical_feature=cat_idx or "auto")
        return lgb.train(p, ds, num_boost_round=5) \
            .model_to_string().split("\nparameters:")[0]

    @pytest.mark.parametrize("cfg", [
        {},
        {"cat": True},
        {"max_bin": 15, "tpu_hist_impl": "pallas2",
         "tpu_block_rows": 512},  # packed bins active
    ])
    def test_kernel_matches_select_or_is_refused(self, cfg):
        cfg = dict(cfg)
        cat = cfg.pop("cat", False)
        a = self._model(9, cat=cat, tpu_partition_impl="select", **cfg)
        if cat or cfg:
            with pytest.raises(ValueError, match="dense numerical unpacked"):
                self._model(9, cat=cat, tpu_partition_impl="kernel", **cfg)
            b = self._model(9, cat=cat, **cfg)          # the default
        else:
            b = self._model(9, cat=cat, tpu_partition_impl="kernel", **cfg)
        assert a == b

    def test_kernel_is_refused_with_bundles(self):
        import lightgbm_tpu as lgb

        rng = np.random.default_rng(11)
        # mutually exclusive columns of few values, so that EFB bundles
        X = rng.normal(size=(3000, 10))
        grp = rng.integers(0, 6, size=3000)
        for g in range(6):
            X[:, g] = np.where(grp == g, rng.integers(1, 4, size=3000), 0.0)
        y = (X[:, :6].sum(axis=1) + X[:, 6] > 2).astype(np.float64)
        out = {}
        for impl in ("select", "auto", "kernel"):
            p = {"objective": "binary", "num_leaves": 15, "verbosity": -1,
                 "max_bin": 31, "tpu_partition_impl": impl}
            ds = lgb.Dataset(X, label=y, params=p)
            if impl == "kernel":
                with pytest.raises(ValueError,
                                   match="dense numerical unpacked"):
                    lgb.train(p, ds, num_boost_round=5)
                continue
            bst = lgb.train(p, ds, num_boost_round=5,
                            keep_training_booster=True)
            assert bst._driver.learner.bundle_plan is not None
            out[impl] = bst.model_to_string().split("\nparameters:")[0]
        assert out["select"] == out["auto"]


class TestAutoHistResolution:
    """tpu_hist_impl=auto / tpu_block_rows=0 resolution (models/learner.py
    _resolve_hist_impl): platform- and VMEM-aware backend choice."""

    def _resolve(self, **params):
        from lightgbm_tpu.config import Config
        from lightgbm_tpu.models.learner import TPUTreeLearner
        cfg = Config({"objective": "binary",
                      **{k: v for k, v in params.items() if k != "_bins"}})
        prec = params.get("tpu_hist_precision", "hilo")
        return TPUTreeLearner._resolve_hist_impl(
            cfg, params.get("_bins", 255), prec)

    def test_cpu_auto_is_xla_streaming(self):
        # tests pin the cpu backend -> auto must never pick pallas here
        impl, block = self._resolve(num_leaves=255)
        assert impl == "xla"
        assert block == 16384

    def test_explicit_impl_and_block_pass_through(self):
        impl, block = self._resolve(tpu_hist_impl="pallas2",
                                    tpu_block_rows=128)
        assert (impl, block) == ("pallas2", 128)
        impl, block = self._resolve(tpu_hist_impl="xla")
        assert (impl, block) == ("xla", 16384)

    def test_auto_is_a_rule_never_a_probe(self, monkeypatch):
        # auto resolves from (platform, precision) alone: no kernel runs
        # to decide it, int16 stays on xla
        def resolve(prec):
            return self._resolve(num_leaves=255, tpu_hist_precision=prec)[0]

        for prec in ("hilo", "int8", "int16"):
            assert resolve(prec) == "xla"            # cpu

        class _Tpu:
            platform = "tpu"

        monkeypatch.setattr(jax, "devices", lambda *a, **k: [_Tpu()])
        assert resolve("hilo") == "pallas2"
        assert resolve("int8") == "pallas2"
        assert resolve("int16") == "xla"
        assert resolve("f32") == "xla"

    @pytest.mark.parametrize("key,value,instead", [
        ("tpu_hist_impl", "fused", "pallas2"),
        ("tpu_hist_impl", "pallas", "pallas2"),
        ("tpu_partition_impl", "vselect", "auto"),
        ("tpu_partition_impl", "gather", "select")])
    def test_removed_impl_is_refused_by_name(self, key, value, instead):
        # the values whose implementations were deleted: the error names
        # the value and what serves in its place
        import lightgbm_tpu as lgb
        rng = np.random.default_rng(0)
        X = rng.normal(size=(256, 4))
        p = {"objective": "regression", "verbosity": -1, key: value}
        with pytest.raises(
                ValueError,
                match=f"{key}={value} was removed; use {key}={instead}"):
            lgb.train(p, lgb.Dataset(X, label=X[:, 0], params=p),
                      num_boost_round=1)

    def test_auto_vmem_branch_on_faked_tpu(self, monkeypatch):
        # exercise the auto branch's VMEM arithmetic by faking the platform
        class _Dev:
            platform = "tpu"
        monkeypatch.setattr(jax, "devices", lambda *a: [_Dev()])
        # Higgs shape -> the perfeature kernel at multi-k-row blocks
        # (docs/PERF_NOTES.md round-3 sweep winner)
        impl, block = self._resolve(num_leaves=255)
        assert (impl, block) == ("pallas2", 8192)
        # feature width never gates the choice (the kernel chunks the
        # feature axis itself); >256-bin data stores int32 bins whose
        # sublane tile is 8, so the kernel can retreat to 8-wide feature
        # chunks and 1024 bins still fits the VMEM accumulator budget
        impl, block = self._resolve(num_leaves=255, _bins=1024,
                                    max_bin=1024)
        assert (impl, block) == ("pallas2", 8192)
        # but a bin axis too tall for even the minimum 8-feature chunk
        # must fall back to the xla scan
        impl, block = self._resolve(num_leaves=255, _bins=2048,
                                    max_bin=2048)
        assert (impl, block) == ("xla", 16384)
        # explicit blocks beyond the hardware-validated range also fall
        # back (the [Bp, block]/[K*S, block] temporaries scale with block)
        impl, block = self._resolve(num_leaves=255, tpu_block_rows=32768)
        assert (impl, block) == ("xla", 32768)
        # f32 stays on the xla Precision.HIGHEST path in auto mode
        impl, block = self._resolve(num_leaves=255,
                                    tpu_hist_precision="f32")
        assert impl == "xla"
        # explicit non-lane-aligned block disables the pallas2 auto pick
        impl, block = self._resolve(num_leaves=255, tpu_block_rows=192)
        assert (impl, block) == ("xla", 192)


class TestSplitBatchAlpha:
    """tpu_split_batch_alpha near-tie guard (grower round body): at
    alpha ~ 1 only leaves within a hair of the round-max gain split, so
    batched growth must reduce to strict best-first (K=1) growth.  The
    comparison is the split multiset + predictions, not model text:
    near-tied leaves may split in one round instead of two consecutive
    ones, permuting leaf numbering without changing the tree function."""

    def _model(self, X, y, **extra):
        import lightgbm_tpu as lgb
        # num_leaves=16 with K=8 makes the leaf budget bind: WHICH splits
        # make the cut depends on growth order, so unguarded batching
        # demonstrably diverges from sequential and the alpha guard is
        # load-bearing in the equality assertion below
        params = {"objective": "regression", "num_leaves": 16,
                  "min_data_in_leaf": 5, "max_bin": 64,
                  "verbosity": -1, **extra}
        ds = lgb.Dataset(X, label=y, params={"max_bin": 64})
        bst = lgb.train(params, ds, num_boost_round=2, verbose_eval=False)
        splits = []

        def walk(nd):
            if "split_feature" in nd:
                splits.append((nd["split_feature"],
                               round(nd["threshold"], 6)))
                walk(nd["left_child"])
                walk(nd["right_child"])

        for t in bst.dump_model()["tree_info"]:
            walk(t["tree_structure"])
        return sorted(splits), bst.predict(X)

    def test_strict_alpha_reduces_to_sequential(self):
        rng = np.random.default_rng(31)
        X = rng.normal(size=(4096, 6))
        y = X[:, 0] ** 2 - X[:, 1] + 0.3 * np.sin(4 * X[:, 2]) \
            + 0.1 * rng.normal(size=4096)
        splits_seq, pred_seq = self._model(X, y, tpu_split_batch=1)
        # precondition: without the guard, batching picks a different
        # split set under this binding budget — otherwise the guarded
        # assertion below would pass vacuously
        splits_raw, _ = self._model(X, y, tpu_split_batch=8)
        assert splits_raw != splits_seq
        splits_a, pred_a = self._model(X, y, tpu_split_batch=8,
                                       tpu_split_batch_alpha=0.999)
        assert splits_a == splits_seq
        np.testing.assert_allclose(pred_a, pred_seq)
