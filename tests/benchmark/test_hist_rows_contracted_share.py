"""The reader of the histogram kernel's contracted rows
(`hist_rows_contracted_share`, `lgbm_hist_rows_per_tree{kind=}`): on
hand-made registry snapshots, None where the program has no such gauge (the
parent of the PR that added it), has read no tree or counts nothing, its
note, its entry of BENCHMARK.json, and once against the program's own
registry after a booster has grown and read two trees."""

import json
import os
import types

import pytest

from benchmarks.lib import harness, program_gauges

# `obs.REGISTRY.snapshot()` after a run of higgs-27m-255.train (my chip run,
# PR 36: 15 calls sweep the padded table, 30.7 % of it contracted)
SNAPSHOT = {
    'lgbm_hist_rows_per_tree{kind="swept"}': 408944640.0,
    'lgbm_hist_rows_per_tree{kind="contracted"}': 125400000.0,
    'lgbm_hist_rows_per_tree{kind="live"}': 104400000.0,
    'lgbm_hist_grid{axis="columns_per_dot"}': 4.0,
    "lgbm_hist_root_slots": 1.0,
}
SWEPT, CONTRACTED, LIVE = (f'lgbm_hist_rows_per_tree{{kind="{kind}"}}'
                          for kind in ("swept", "contracted", "live"))
PARENT = {k: v for k, v in SNAPSHOT.items() if "rows_per_tree" not in k}


def reader():
    return harness.load_module(harness.BENCH_DIR, "layer_metrics",
                               "hist_rows_contracted_share")


def without(key):
    return {k: v for k, v in SNAPSHOT.items() if k != key}


@pytest.mark.parametrize("snap, want", [
    (SNAPSHOT, 100.0 * 125400000 / 408944640),
    # the unpacked sweep (G == 1, 4-bit, the XLA scan): every row contracted
    ({**SNAPSHOT, CONTRACTED: SNAPSHOT[SWEPT]}, 100.0),
    # four shards, summed: the share is the shards' rows over their sweeps
    ({k: 4 * v for k, v in SNAPSHOT.items()}, 100.0 * 125400000 / 408944640),
    ({**SNAPSHOT, CONTRACTED: 0.0}, None),     # a kernel that counts nothing
    ({**SNAPSHOT, SWEPT: 0.0}, None),          # no tree read yet
    (without(SWEPT), None), (without(CONTRACTED), None), (without(LIVE), None),
    (PARENT, None), ({}, None), (None, None)])
def test_hist_rows_contracted_share(snap, want):
    assert reader().from_snapshot(snap) == want


def test_the_note_has_the_three_row_counts_and_the_packings_efficiency(
        monkeypatch):
    said = []
    run = types.SimpleNamespace(cell=types.SimpleNamespace(
        say=lambda what, **f: said.append((what, f))))
    monkeypatch.setattr(program_gauges, "snapshot", lambda: dict(SNAPSHOT))
    assert reader().read(run) == pytest.approx(30.664, rel=1e-4)
    assert said == [("hist_rows_contracted_share", {
        "swept": 408944640.0, "contracted": 125400000.0, "live": 104400000.0,
        "live_over_contracted": 104400000 / 125400000})]
    # a reader that finds nothing returns nothing, and says nothing
    monkeypatch.setattr(program_gauges, "snapshot", lambda: dict(PARENT))
    assert reader().read(run) is None and len(said) == 1


# the cells whose traced run on the chip reports the metric (my chip runs,
# PR 38): every cell that trains
REPORTED_IN = ["higgs-27m-255.train", "higgs-27m-63.train",
               "criteo-13m-67.train", "criteo-27m-67.train-data4",
               "mslr-7m-63.train-rank"]


def test_the_metric_is_declared_for_the_cells_that_report_it():
    with open(os.path.join(os.path.dirname(harness.BENCH_DIR),
                           "BENCHMARK.json")) as f:
        spec = json.load(f)
    entry = next(m for m in spec["per_layer"]
                 if m["name"] == "hist_rows_contracted_share")
    assert entry == {
        "name": "hist_rows_contracted_share", "unit": "%", "better": "lower",
        "source": "program_counter", "layer": "histogram_kernel",
        "moves": "train_iters_per_s", "workloads": REPORTED_IN}


def test_the_learner_sets_what_the_reader_reads():
    """The program's side of the contract, at toy size: no gauge before the
    host has read a tree, then contracted <= swept, live <= contracted,
    and swept a whole number of sweeps of the padded table."""
    import lightgbm_tpu as lgb
    from benchmarks.datagen import higgs_like
    from lightgbm_tpu import obs

    data = higgs_like.make({"features": 28}, seed=7, rows=3000, stream=0)
    params = {"objective": "binary", "num_leaves": 15, "max_bin": 63,
              "verbosity": -1}
    bst = lgb.Booster(params=params,
                      train_set=lgb.Dataset(data["X"], label=data["y"],
                                            params=params))
    for kind in ("swept", "contracted", "live"):
        obs.REGISTRY.set_gauge("lgbm_hist_rows_per_tree", 0.0, kind=kind)
    assert reader().from_snapshot(program_gauges.snapshot()) is None
    bst.update()
    bst.update()
    bst.model_to_string()                      # the host reads the trees
    snap = program_gauges.snapshot()
    rows = program_gauges.hist_rows_per_tree(snap)
    share = reader().from_snapshot(snap)
    assert 0 < rows["live"] <= rows["contracted"] <= rows["swept"]
    assert rows["swept"] % bst._driver.learner.n_pad == 0
    assert share == 100.0 * rows["contracted"] / rows["swept"] <= 100.0
