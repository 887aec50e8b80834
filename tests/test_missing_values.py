"""Columns with missing values and unequal cardinality, against the plain
numpy reference in tests/plain_reference.py: what the `criteo-13m-67`
configuration (PR 27) makes the program do that no Higgs-shaped test did.

* binning: NaN / zero routing and the missing type of a column of 3, 9, 200
  and 1000 distinct values, host and device ingest;
* the `pallas2` kernel in interpret mode on its FEATURE-CHUNKED grid at the
  configuration's own width (67 live columns stored as 96, 255 bins, the
  real VMEM budget, so three chunks by the code's own arithmetic);
* the split scan on hand-made leaves: the two directions disagreeing, the
  2-bin NaN column, zero-as-missing, and a 20-row child of a 13M-row leaf;
* one 31-leaf tree on 4,000 rows of the benchmark's generator: every split,
  default direction, leaf count and leaf value.
"""

import numpy as np
import pytest

import jax.numpy as jnp

import lightgbm_tpu as lgb
from benchmarks.datagen import criteo_like
from benchmarks.lib import reference as public_rule
from lightgbm_tpu.ops import histogram as H
from lightgbm_tpu.ops import split as SP
from tests import plain_reference as ref

BASE = {"objective": "binary", "max_bin": 255, "verbosity": -1}


# ---- (a) binning ------------------------------------------------------------------------
@pytest.mark.parametrize("ingest", ["host", "device"])
@pytest.mark.parametrize("mode", ["nan", "no_nan", "zero_as_missing",
                                  "use_missing_off"])
@pytest.mark.parametrize("distinct", [3, 9, 200, 1000])
def test_a_column_is_binned_by_the_missing_rule(distinct, mode, ingest):
    rng = np.random.default_rng(distinct)
    n = 6000
    # integers from zero up, so exact zeros are among them
    col = rng.integers(0, distinct, size=n).astype(np.float64)
    if mode != "no_nan":
        col[rng.random(n) < 0.1] = np.nan
    X = np.stack([col, rng.normal(size=n)], axis=1)
    params = dict(BASE, use_missing=mode != "use_missing_off",
                  zero_as_missing=mode == "zero_as_missing",
                  tpu_ingest_device=ingest == "device")
    ds = lgb.Dataset(X, label=rng.integers(0, 2, size=n).astype(np.float64),
                     params=params)
    ds.construct()
    inner = ds._inner
    assert (inner.device_ingest_bins() is not None) == (ingest == "device")
    m, bins = inner.mappers[0], np.asarray(inner.bins)[:, 0]
    want_type = ref.expected_missing_type(
        col, use_missing=params["use_missing"],
        zero_as_missing=params["zero_as_missing"])
    assert int(m.missing_type) == want_type
    assert ref.bins_follow_the_rule(col, bins, m.num_bin, want_type,
                                    m.default_bin) is None
    present = np.unique(col[~np.isnan(col)])
    occupied = len(np.unique(bins[~np.isnan(col)]))
    if distinct <= 200:
        # no more values than bins: every value (each has far more than
        # min_data_in_bin = 3 of the 6,000 rows) gets a bin of its own
        assert m.distinct_path and occupied == len(present)
        assert m.num_bin == len(present) + (want_type == ref.NAN)
    else:
        assert not m.distinct_path and occupied <= 255 and m.num_bin <= 255


def test_device_ingest_keys_a_large_chunk_in_slabs_to_the_same_bins(monkeypatch):
    """A chunk of over 32 MiB (65,536 rows x 67 columns) is keyed on the host
    slab by slab; here every chunk is made to count as large, and slabs of
    1,000 values cut rows of 67 unevenly."""
    from lightgbm_tpu.ops import binning

    data = criteo_like.make({"features": 67}, seed=3, rows=5000, stream=0)
    bins = {}
    for path in ("whole", "slabs"):
        if path == "slabs":
            monkeypatch.setattr(binning, "_PREP_WHOLE_BYTES", 0)
            monkeypatch.setattr(binning, "_PREP_SLAB_VALUES", 1000)
        ds = lgb.Dataset(data["X"], label=data["y"],
                         params=dict(BASE, tpu_ingest_device=True,
                                     tpu_ingest_chunk_rows=2048))
        ds.construct()
        assert ds._inner.device_ingest_bins() is not None
        bins[path] = np.asarray(ds._inner.bins)
    np.testing.assert_array_equal(bins["whole"], bins["slabs"])


# ---- (b) the kernel on its feature-chunked grid ---------------------------------------------
LIVE, STORED, BINS = 67, 96, 255


def ragged_bins(rng, nb, block):
    """[nb, 96, block] uint8: 67 columns of 2 to 255 bins, 29 of padding."""
    widths = np.r_[[2, 6, 15, 48, 112, 128], np.full(LIVE - 6, BINS)]
    bins = np.zeros((nb, STORED, block), np.uint8)
    for c, w in enumerate(widths):
        bins[:, c] = rng.integers(0, w, size=(nb, block))
    return bins


@pytest.mark.parametrize("precision", ["hilo", "int8"])
@pytest.mark.parametrize("slots", [1, 4, 25])
def test_chunked_kernel_equals_the_numpy_histogram(slots, precision):
    planes = 5 if precision == "hilo" else 3
    # the configuration's grid, by the kernel's own arithmetic and budget
    assert H.perfeature_chunks(STORED, BINS, slots, planes, 1) == (32, 3)
    assert H.perfeature_chunks(32, BINS, slots, planes, 1) == (32, 1)
    rng = np.random.default_rng(slots)
    nb, block = 2, 256
    n = nb * block
    bins = ragged_bins(rng, nb, block)
    g = rng.normal(size=n).astype(np.float32)
    h = (np.abs(g) + 0.1).astype(np.float32)
    if precision == "int8":
        g = np.asarray(H.quantize_values(jnp.asarray(g), np.abs(g).max() / 127,
                                         127, "nearest"), np.float32)
        h = np.asarray(H.quantize_values(jnp.asarray(h), h.max() / 127,
                                         127, "nearest"), np.float32)
    stats = H.pack_stats(jnp.asarray(g), jnp.asarray(h),
                         jnp.ones(n, jnp.float32), precision)
    leaf = rng.integers(0, slots + 2, size=(nb, block)).astype(np.int32)
    slot_leaves = rng.permutation(slots + 2)[:slots].astype(np.int32)
    if slots > 1:
        slot_leaves[1] = -1  # a dead slot
    got = np.asarray(H.build_histogram_batched_t(
        jnp.asarray(bins), stats.reshape(planes, nb, block),
        jnp.asarray(leaf), jnp.asarray(slot_leaves), BINS, precision,
        impl="pallas2", live_columns=LIVE), np.float64)
    assert got.shape == (slots, STORED, BINS, 3)
    rows = np.moveaxis(bins, 1, 2).reshape(n, STORED)[:, :LIVE]
    want = ref.histogram(rows, g, h, leaf.reshape(n), slot_leaves, BINS)
    assert not got[:, LIVE:].any()  # padding columns: exact zeros
    if precision == "int8":
        # integer statistics, int32 accumulation: nothing to round
        np.testing.assert_array_equal(got[:, :LIVE], want)
        return
    np.testing.assert_array_equal(got[:, :LIVE, :, 2], want[..., 2])
    # hilo carries each statistic as two bf16 halves: the hi half rounds at
    # 2^-9 of the value, the lo half at 2^-9 of what is left, so a bin's sum
    # is off by at most 2^-18 of the sum of magnitudes in it (2^-16 asked
    # for here; the f32 accumulation of 512 rows is far below that).  The
    # PR-21 defect, the lo half lost, is off by up to 2^-9 of it and fails.
    magnitudes = ref.histogram(rows, np.abs(g), np.abs(h), leaf.reshape(n),
                               slot_leaves, BINS)
    err = np.abs(got[:, :LIVE] - want)[..., :2]
    assert (err <= magnitudes[..., :2] * 2.0 ** -16 + 1e-9).all()
    lost_lo = np.asarray(jnp.asarray(g).astype(jnp.bfloat16), np.float64)
    without_lo = ref.histogram(rows, lost_lo, h, leaf.reshape(n),
                               slot_leaves, BINS)
    assert (np.abs(without_lo - want)[..., 0]
            > magnitudes[..., 0] * 2.0 ** -16 + 1e-9).any()


# ---- (c) the split scan on hand-made leaves ------------------------------------------------------
def leaf_hist(rows):
    """[1, bins, 3] histogram from (g, h, count) per bin."""
    return np.asarray(rows, np.float64)[None]


def rows_of(counts, rate, p=0.2):
    """Per-bin (g, h, n) of a first tree: `counts` rows per bin of which
    the share `rate` is positive, at the constant score p."""
    counts, rate = np.asarray(counts, float), np.asarray(rate, float)
    return np.stack([counts * (p - rate), counts * p * (1 - p), counts], 1)


CASES = {
    # the two directions find different thresholds.  Missing rows as
    # positive as the low bins: sent left they join them at threshold 1
    # (gain 177), sent right the best left is every present row (81)
    "directions_disagree_left": dict(
        rows=rows_of([300, 300, 300, 300, 200], [.5, .8, .4, .4, .8]),
        missing_type=ref.NAN, want=dict(threshold=1, default_left=True)),
    # missing rows far more positive than any bin: splitting them off
    # alone (threshold 3, missing right: 525) beats keeping them left (70)
    "directions_disagree_right": dict(
        rows=rows_of([300, 300, 300, 300, 200], [.1, .1, .3, .3, .9]),
        missing_type=ref.NAN, want=dict(threshold=3, default_left=False)),
    # one real bin and the NaN bin: the only split is present | missing,
    # and the reference states it with default_left=False
    "two_bin_nan": dict(
        rows=rows_of([700, 300], [.1, .6]), missing_type=ref.NAN,
        want=dict(threshold=0, default_left=False)),
    # zero_as_missing: the zero bin (bin 1 here) is no threshold and its
    # rows follow the default direction
    "zero_as_missing": dict(
        rows=rows_of([200, 500, 200, 200], [.1, .6, .1, .5]),
        missing_type=ref.ZERO, zero_bin=1,
        want=dict(threshold=2, default_left=False)),
    # no missing type: both directions sum the same sides, -1 keeps the tie
    "no_missing": dict(
        rows=rows_of([300, 300, 300], [.1, .2, .6]), missing_type=ref.NONE,
        want=dict(threshold=1, default_left=True)),
}


def program_split(hist, num_bin, missing_type, zero_bin, totals=None):
    hist32 = jnp.asarray(hist, jnp.float32)
    F = hist.shape[0]
    sg, sh, n = (hist[0].sum(axis=0) if totals is None else totals)
    res = SP.find_best_split_all_features(
        hist32, jnp.float32(sg), jnp.float32(sh), jnp.float32(n),
        jnp.asarray(num_bin, jnp.int32), jnp.asarray(missing_type, jnp.int32),
        jnp.asarray(zero_bin, jnp.int32), jnp.zeros(F, jnp.int32),
        jnp.ones(F, jnp.float32), jnp.ones(F, jnp.float32),
        l1=0.0, l2=0.0, max_delta_step=0.0, min_data_in_leaf=20.0,
        min_sum_hessian=1e-3, min_gain_to_split=0.0)
    return {k: np.asarray(v) for k, v in res._asdict().items()
            if v is not None}


@pytest.mark.parametrize("case", sorted(CASES))
def test_split_scan_equals_the_enumeration(case):
    c = CASES[case]
    hist = leaf_hist(c["rows"])
    nb, mt, zb = [hist.shape[1]], [c["missing_type"]], [c.get("zero_bin", 0)]
    want = ref.best_split(hist, nb, mt, zb)
    got = program_split(hist, nb, mt, zb)
    for key, value in c["want"].items():
        assert want[key] == value, "the case no longer shows what it names"
    assert (got["feature"], got["threshold"], bool(got["default_left"])) \
        == (want["feature"], want["threshold"], want["default_left"])
    # f32 sums of a few hundred rows against float64: 1e-6 of the value
    # (of the leaf's whole mass where the gradients of a side cancel)
    room = dict(rtol=1e-6, atol=1e-6 * np.abs(hist).sum())
    np.testing.assert_allclose(
        [got["left_sum_g"], got["left_sum_h"], got["left_count"]],
        want["left"], **room)
    np.testing.assert_allclose([got["right_sum_g"], got["right_sum_h"]],
                               want["right"][:2], **room)
    np.testing.assert_allclose(got["gain"], want["gain"], rtol=1e-5)


def test_a_small_child_of_a_large_leaf_keeps_its_sums():
    """13M rows at hessian 0.0338 a row, the last bin a 20-row child.  The
    leaf's totals come from the rows' f32 values, the bins from their hi +
    lo bf16 halves: on a first tree they differ by one systematic 2^-17,
    here 3.4 against the child's 0.68.  Taking the right side as total
    minus left made that child's hessian anything from negative to twice
    its value and its leaf value off by 7.3 (PR 27, on the chip)."""
    p = 0.035
    counts = np.r_[np.full(200, 66_000), [600, 300, 150, 60, 20]]
    rate = np.r_[np.full(200, p), [.04, .05, .06, .08, .5]]
    rows = rows_of(counts, rate, p)
    rows[:, :2] *= 1 + 2.0 ** -17                  # what the bins hold
    hist = leaf_hist(rows)
    exact = rows_of(counts, rate, p).sum(axis=0)   # what the totals hold
    nb, mt, zb = [len(counts)], [ref.NONE], [0]
    want = ref.best_split(hist, nb, mt, zb)
    got = program_split(hist, nb, mt, zb, totals=exact)
    assert (got["threshold"], bool(got["default_left"])) \
        == (want["threshold"], want["default_left"])
    # every candidate's right side, the 20-row one too, to f32's rounding
    # of its own sum
    np.testing.assert_allclose([got["right_sum_g"], got["right_sum_h"]],
                               want["right"][:2], rtol=1e-6)
    last = program_split(hist[:, -2:], [2], mt, zb, totals=rows[-2:].sum(0))
    np.testing.assert_allclose(last["right_sum_h"], rows[-1, 1], rtol=1e-6)
    np.testing.assert_allclose(last["right_output"],
                               -rows[-1, 0] / rows[-1, 1], rtol=1e-6)


# ---- (c) + (d) one tree -------------------------------------------------------------------------
@pytest.fixture(scope="module")
def click_rows():
    data = criteo_like.make({"features": 67}, seed=11, rows=4000, stream=0)
    return data["X"], data["y"]


@pytest.mark.parametrize("impl", ["pallas2", "xla"])
def test_one_tree_equals_the_enumerating_reference(click_rows, impl):
    X, y = click_rows
    leaves, lr = 31, 0.1
    params = dict(BASE, num_leaves=leaves, learning_rate=lr,
                  tpu_hist_impl=impl)
    ds = lgb.Dataset(X, label=y, params=params)
    bst = lgb.Booster(params=params, train_set=ds)
    assert not bst.update()
    tree = public_rule.parse_model(bst.model_to_string())[0]
    inner = ds._inner
    used = inner.used_feature_idx
    mappers = [inner.mappers[c] for c in used]
    num_bin = np.array([m.num_bin for m in mappers])
    missing = np.array([int(m.missing_type) for m in mappers])
    zero_bin = np.array([m.default_bin for m in mappers])
    assert {ref.NONE, ref.NAN} <= set(missing)
    p = y.mean()
    splits, leaf_of_row, sums = ref.grow_tree(
        np.asarray(inner.bins), p - y, np.full(len(y), p * (1 - p)),
        num_bin, missing, zero_bin, leaves)
    assert len(splits) == leaves - 1 == len(tree["split_feature"])
    # the same splits in the same order: column, default direction and
    # missing type as the model text states them
    assert [used[s["feature"]] for s in splits] \
        == tree["split_feature"].tolist()
    decision = tree["decision_type"]
    assert [s["default_left"] for s in splits] \
        == ((decision & 2) != 0).tolist()
    assert [missing[s["feature"]] for s in splits] \
        == ((decision >> 2) & 3).tolist()
    assert any(missing[s["feature"]] == ref.NAN for s in splits)
    assert len({s["default_left"] for s in splits
                if missing[s["feature"]] == ref.NAN}) == 2
    # the same partition: the raw rows walked by the published decision
    # rule (thresholds as real values, missing routing first) land in the
    # leaves the reference put their bins in
    np.testing.assert_array_equal(public_rule.leaf_index(tree, X),
                                  leaf_of_row)
    np.testing.assert_array_equal(tree["leaf_count"], sums[:, 2])
    # leaf values from float64 sums of the rows.  hilo carries the hessian
    # p(1-p), the same for every row, 2^-18 off at most, and a value is
    # lr * G / H with |G / H| <= 1/p = 28: up to 1e-5 (3.2e-6 seen, so the
    # 1e-6 ISSUE 27 asked for is under hilo's own rounding), and the lo
    # half lost (2^-9) would miss 1e-5 by a factor of a hundred
    want = np.log(p / (1 - p)) - lr * sums[:, 0] / sums[:, 1]
    np.testing.assert_allclose(tree["leaf_value"], want, atol=1e-5, rtol=0)
