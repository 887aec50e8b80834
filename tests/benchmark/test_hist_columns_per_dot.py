"""The reader of the histogram kernel's group width (`hist_columns_per_dot`,
`lgbm_hist_grid{axis="columns_per_dot"}`): on hand-made registry snapshots,
None where the program has no such gauge (the parent of the PR that added
it) or runs another kernel, and once against the program's own registry
after a learner has laid out a table.  Beside `test_program_gauges.py`,
which has the readers the benchmark had before."""

import json
import os

import pytest

from benchmarks.lib import harness, program_gauges

# `obs.REGISTRY.snapshot()` as it prints a run of criteo-13m-67.train
SNAPSHOT = {
    'lgbm_hist_columns{kind="live"}': 67.0,
    'lgbm_hist_grid{axis="feature_chunks"}': 3.0,
    'lgbm_hist_grid{axis="columns_per_chunk"}': 32.0,
    'lgbm_hist_grid{axis="row_blocks"}': 1664.0,
    'lgbm_hist_grid{axis="columns_per_dot"}': 4.0,
    "lgbm_hist_root_slots": 1.0,
}
KEY = 'lgbm_hist_grid{axis="columns_per_dot"}'
PARENT = {k: v for k, v in SNAPSHOT.items() if k != KEY}


def reader():
    return harness.load_module(harness.BENCH_DIR, "layer_metrics",
                               "hist_columns_per_dot")


@pytest.mark.parametrize("snap, want", [
    (SNAPSHOT, 4.0), ({**SNAPSHOT, KEY: 1.0}, 1.0),
    ({**SNAPSHOT, KEY: 28.0}, 28.0),
    ({**SNAPSHOT, KEY: 0.0}, None),            # the xla scan: no groups
    (PARENT, None), ({}, None), (None, None)])
def test_hist_columns_per_dot(snap, want):
    assert reader().from_snapshot(snap) == want


# the cells whose traced run on the chip reports the metric (my chip runs,
# PR 35): every cell that trains, the four-chip and the ranking one too
REPORTED_IN = ["higgs-27m-255.train", "higgs-27m-63.train",
               "criteo-13m-67.train", "criteo-27m-67.train-data4",
               "mslr-7m-63.train-rank"]


def test_the_metric_is_declared_for_the_cells_that_report_it():
    with open(os.path.join(os.path.dirname(harness.BENCH_DIR),
                           "BENCHMARK.json")) as f:
        spec = json.load(f)
    entry = next(m for m in spec["per_layer"]
                 if m["name"] == "hist_columns_per_dot")
    assert entry == {
        "name": "hist_columns_per_dot", "unit": "columns",
        "better": "higher", "source": "program_counter",
        "layer": "histogram_kernel", "moves": "train_iters_per_s",
        "workloads": REPORTED_IN}


def test_the_learner_sets_what_the_reader_reads():
    """The program's side of the contract, at toy size: the gauge is what
    the kernel's own function answers for the table the learner laid out
    (96 stored columns in chunks of 32, 67 live, one row block)."""
    import lightgbm_tpu as lgb
    from benchmarks.datagen import criteo_like
    from lightgbm_tpu.ops.histogram import perfeature_columns_per_dot

    data = criteo_like.make({"features": 67}, seed=5, rows=3000, stream=0)
    params = {"objective": "binary", "num_leaves": 255, "max_bin": 255,
              "verbosity": -1, "tpu_hist_impl": "pallas2"}
    bst = lgb.Booster(params=params,
                      train_set=lgb.Dataset(data["X"], label=data["y"],
                                            params=params))
    snap = program_gauges.snapshot()
    assert program_gauges.gauge(snap, "lgbm_hist_grid",
                                axis="row_blocks") == 1.0
    block = bst._driver.learner.n_pad
    want = perfeature_columns_per_dot(255, block, "hilo", 32, 67)
    assert want >= 2
    assert reader().from_snapshot(snap) == float(want)
