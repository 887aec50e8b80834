"""The readers of the program's layout gauges (`lib/program_gauges.py`,
`hist_feature_chunks`, `hist_bin_occupancy`): each on a hand-made registry
snapshot, None where the program has no such gauge (the parent of the PR
that added them) or runs another kernel, and once against the program's own
registry after a learner has laid out a table."""

import pytest

from benchmarks.lib import harness, program_gauges

# `obs.REGISTRY.snapshot()` as it prints a run of criteo-13m-67.train: a
# gauge per label set, a histogram as a dict, other families beside them
SNAPSHOT = {
    'lgbm_hist_columns{kind="live"}': 67.0,
    'lgbm_hist_columns{kind="padding"}': 29.0,
    'lgbm_hist_grid{axis="feature_chunks"}': 3.0,
    'lgbm_hist_grid{axis="columns_per_chunk"}': 32.0,
    'lgbm_hist_grid{axis="row_blocks"}': 1664.0,
    'lgbm_hist_bins{kind="live"}': 15750.0,
    'lgbm_hist_bins{kind="stored"}': 17152.0,
    "lgbm_hist_root_slots": 1.0,
    'lgbm_compile_seconds{site="grower.grow"}': {"count": 1, "sum": 51.0},
}
XLA_KERNEL = {**SNAPSHOT, **{k: 0.0 for k in SNAPSHOT
                             if k.startswith(("lgbm_hist_grid",
                                              "lgbm_hist_bins"))}}
PARENT = {k: v for k, v in SNAPSHOT.items()
          if not k.startswith(("lgbm_hist_grid", "lgbm_hist_bins"))}


def reader(name):
    return harness.load_module(harness.BENCH_DIR, "layer_metrics", name)


def test_a_gauge_is_read_by_its_printed_key():
    g = program_gauges.gauge
    assert g(SNAPSHOT, "lgbm_hist_root_slots") == 1.0
    assert g(SNAPSHOT, "lgbm_hist_grid", axis="row_blocks") == 1664.0
    assert g(SNAPSHOT, "lgbm_hist_grid", axis="nope") is None
    assert g(SNAPSHOT, "lgbm_hist_grid") is None         # no such bare key
    assert g(SNAPSHOT, "lgbm_compile_seconds", site="grower.grow") is None
    assert g(None, "lgbm_hist_root_slots") is None       # no registry at all


@pytest.mark.parametrize("snap, want", [
    (SNAPSHOT, 3.0), ({**SNAPSHOT,
                       'lgbm_hist_grid{axis="feature_chunks"}': 1.0}, 1.0),
    (XLA_KERNEL, None), (PARENT, None), ({}, None), (None, None)])
def test_hist_feature_chunks(snap, want):
    assert reader("hist_feature_chunks").from_snapshot(snap) == want


@pytest.mark.parametrize("snap, want", [
    (SNAPSHOT, 100.0 * 15750 / 17152),
    ({'lgbm_hist_bins{kind="live"}': 28 * 255.0,
      'lgbm_hist_bins{kind="stored"}': 28 * 256.0}, 100.0 * 255 / 256),
    ({'lgbm_hist_bins{kind="live"}': 28 * 63.0,
      'lgbm_hist_bins{kind="stored"}': 28 * 64.0}, 100.0 * 63 / 64),
    (XLA_KERNEL, None), (PARENT, None),
    ({'lgbm_hist_bins{kind="live"}': 5.0}, None), (None, None)])
def test_hist_bin_occupancy(snap, want):
    got = reader("hist_bin_occupancy").from_snapshot(snap)
    assert got == want if want is None else got == pytest.approx(want)


def test_the_learner_sets_what_the_readers_read():
    """The program's side of the contract, at toy size: a 67-column table
    under `pallas2` at 255 bins lays out 96 stored columns in 3 chunks."""
    import lightgbm_tpu as lgb
    from benchmarks.datagen import criteo_like

    data = criteo_like.make({"features": 67}, seed=5, rows=3000, stream=0)
    params = {"objective": "binary", "num_leaves": 255, "max_bin": 255,
              "verbosity": -1, "tpu_hist_impl": "pallas2"}
    lgb.Booster(params=params,
                train_set=lgb.Dataset(data["X"], label=data["y"],
                                      params=params))
    snap = program_gauges.snapshot()
    assert reader("hist_feature_chunks").from_snapshot(snap) == 3.0
    g = program_gauges.gauge
    assert g(snap, "lgbm_hist_grid", axis="columns_per_chunk") == 32.0
    assert g(snap, "lgbm_hist_columns", kind="live") == 67.0
    assert g(snap, "lgbm_hist_bins", kind="stored") == 67 * 256.0
    share = reader("hist_bin_occupancy").from_snapshot(snap)
    assert 50.0 < share < 100.0 * 255 / 256
    assert g(snap, "lgbm_dataset_columns", missing="nan") == 9.0
    assert g(snap, "lgbm_dataset_columns", missing="none") == 58.0
    # the 13 counts of few values and, at 3,000 rows, nothing else
    assert 3.0 <= g(snap, "lgbm_dataset_distinct_path_columns") <= 13.0
    # no column is 80 % zeros, so the EFB greedy has nothing to place
    assert g(snap, "lgbm_efb_candidates") == 0.0
    assert g(snap, "lgbm_efb_bundles") == 0.0
