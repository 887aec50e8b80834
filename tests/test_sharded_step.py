"""The training step on a row-sharded table (PR 32): one step for every
`tree_learner`, nothing with a row axis closed over.

* the job against `tests/plain_reference.py`: four virtual devices,
  `tree_learner=data`, a click-log table with NaN columns; tree 0 of the
  sharded job equals the float64 reference grown on the WHOLE table,
  under the all-reduce and the reduce-scatter alike;
* the sharded model against the serial one on the same table;
* no program of the step holds a row-shaped constant, on any strategy
  (`lgbm_step_row_constant_bytes`), and the measure itself sees a
  captured table;
* a second data set of the same shape compiles nothing of the step: the
  persistent cache answers `learner.pre` / `learner.post`, the grower is
  not even traced again;
* the gauges of the data axis and the arithmetic behind the exchange's
  bytes; shards placed from the device-ingested matrix with no host copy.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import lightgbm_tpu as lgb
from benchmarks.datagen import criteo_like
from benchmarks.lib import reference as public_rule
from lightgbm_tpu import obs
from lightgbm_tpu.parallel.mesh import (exchange_bytes_per_tree,
                                        tree_hist_slots)
from lightgbm_tpu.utils.backend import enable_compilation_cache
from lightgbm_tpu.utils.compile_ledger import (LEDGER, closed_over_bytes,
                                               ledger_jit)
from tests import plain_reference as ref

SHARDS = 4
BASE = {"objective": "binary", "verbosity": -1, "max_bin": 63,
        "min_data_in_leaf": 20}
DATA4 = {"tree_learner": "data", "num_machines": SHARDS}
ROW_CONSTANTS = "lgbm_step_row_constant_bytes"


@pytest.fixture(scope="module")
def click_rows():
    data = criteo_like.make({"features": 67}, seed=11, rows=4000, stream=0)
    return data["X"], data["y"]


def booster(params, X, y, rounds=1, **dataset_kw):
    ds = lgb.Dataset(X, label=y, params=params, **dataset_kw)
    bst = lgb.Booster(params=params, train_set=ds)
    for _ in range(rounds):
        assert not bst.update()
    return bst, ds


def structure(text):
    """What of a model text does not depend on the order of a float32
    sum: per tree the split columns, thresholds, decision types and the
    leaf row counts."""
    return [(t["split_feature"].tolist(), t["threshold"].tolist(),
             t["decision_type"].tolist(), t["leaf_count"].tolist())
            for t in public_rule.parse_model(text)]


# ---- the job against the plain reference on the whole table ------------------------
@pytest.mark.parametrize("agg", ["psum", "scatter"])
def test_the_sharded_tree_equals_the_reference_on_the_whole_table(
        click_rows, agg):
    X, y = click_rows
    leaves, lr = 31, 0.1
    params = dict(BASE, **DATA4, num_leaves=leaves, learning_rate=lr,
                  tpu_hist_agg=agg)
    bst, ds = booster(params, X, y)
    learner = bst._driver.learner
    assert learner.hist_agg == agg and learner.d_shards == SHARDS
    assert len({s.device for s in learner.bins_t.addressable_shards}) == SHARDS
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
    assert len(splits) == leaves - 1
    # the same splits in the same order: column, threshold bin, the side
    # the missing rows take
    assert [used[s["feature"]] for s in splits] \
        == tree["split_feature"].tolist()
    want_thr = [mappers[s["feature"]].bin_to_value(s["threshold"])
                for s in splits]
    np.testing.assert_array_equal(tree["threshold"], want_thr)
    decision = tree["decision_type"]
    assert [s["default_left"] for s in splits] \
        == ((decision & 2) != 0).tolist()
    assert any(missing[s["feature"]] == ref.NAN for s in splits)
    # the same partition of ALL rows, and exact leaf counts: every shard's
    # rows are in every count
    np.testing.assert_array_equal(public_rule.leaf_index(tree, X),
                                  leaf_of_row)
    np.testing.assert_array_equal(tree["leaf_count"], sums[:, 2])
    # leaf values from float64 sums of all rows.  As on one device
    # (tests/test_missing_values.py): hilo carries the constant hessian
    # p(1-p) to 2^-18 and a value is lr * G / H with |G / H| <= 1/p = 28,
    # so up to 1e-5; four partial sums instead of one move it less
    want = np.log(p / (1 - p)) - lr * sums[:, 0] / sums[:, 1]
    np.testing.assert_allclose(tree["leaf_value"], want, atol=1e-5, rtol=0)


@pytest.mark.parametrize("agg", ["psum", "scatter"])
def test_the_sharded_model_equals_the_serial_one(click_rows, agg):
    """Float64 histograms (`deterministic`) sum alike in any order: the
    texts are the same bytes.  At the shipping precision the four partial
    sums round differently from one, so the trees agree in what does not
    hang on a float32 sum's last bit: splits, thresholds, leaf counts."""
    X, y = click_rows
    params = dict(BASE, num_leaves=15, tpu_hist_agg=agg)
    for extra in ({"deterministic": True}, {}):
        try:
            texts = [booster(dict(params, **extra, **over), X, y, rounds=3)[0]
                     .model_to_string().split("\nparameters:")[0]
                     for over in ({}, DATA4)]
        finally:
            jax.config.update("jax_enable_x64", False)
        if extra:
            assert texts[0] == texts[1]
        else:
            assert structure(texts[0]) == structure(texts[1])


# ---- nothing with a row axis is closed over -------------------------------------------
def row_constants():
    return {k: v for k, v in obs.REGISTRY.snapshot().items()
            if k.startswith(ROW_CONSTANTS + "{")}


STRATEGIES = {
    "serial": {},
    "data": DATA4,
    "voting": {"tree_learner": "voting", "num_machines": SHARDS},
    "feature": {"tree_learner": "feature", "num_machines": SHARDS},
}


@pytest.mark.parametrize("strategy", sorted(STRATEGIES))
def test_no_program_of_the_step_holds_a_row_constant(click_rows, strategy):
    X, y = click_rows
    weight = np.linspace(0.5, 1.5, len(y))
    for objective in (
            {"objective": "binary", "pos_bagging_fraction": 0.7,
             "neg_bagging_fraction": 0.9, "bagging_freq": 1},
            {"objective": "regression_l1"},
            {"objective": "multiclassova", "num_class": 2}):
        params = dict(BASE, num_leaves=7, **objective, **STRATEGIES[strategy])
        for family in [f for f in obs.REGISTRY._families
                       if f == ROW_CONSTANTS]:
            del obs.REGISTRY._families[family]
        bst, _ = booster(params, X, y, weight=weight)
        assert bst._driver._train_step is not None or \
            objective["objective"] == "regression_l1"
        if bst._driver._train_step is None:
            continue  # a renewing objective trains on the synchronous path
        sites = row_constants()
        grower = ("grower.grow" if strategy == "serial"
                  else f"grower.{strategy}")
        programs = ["learner.pre", grower, "learner.post"]
        if strategy in ("data", "voting"):
            programs.append("learner.gather")  # the leaf ids' all-gather
        assert set(sites) == {f'{ROW_CONSTANTS}{{site="{s}"}}'
                              for s in programs}
        assert not any(sites.values()), sites


def test_the_measure_sees_a_captured_table():
    table = jnp.ones((3, 640), jnp.uint8)
    labels = np.ones(640, np.float32)

    def closes_over(x):
        return x + table.sum() + jnp.asarray(labels)[0]

    def takes(x, table, labels):
        return x + table.sum() + labels[0]

    x = jax.ShapeDtypeStruct((), jnp.float32)
    assert closed_over_bytes(ledger_jit(closes_over, site="t"), (x,), {},
                             {640}) == 3 * 640 + 4 * 640
    assert closed_over_bytes(ledger_jit(closes_over, site="t"), (x,), {},
                             {641}) == 0
    assert closed_over_bytes(ledger_jit(takes, site="t"),
                             (x, table, labels), {}, {640}) == 0


# ---- a second data set of the shape compiles nothing of the step -----------------------
@pytest.mark.parametrize("strategy", ["serial", "data"])
def test_a_second_data_set_of_the_shape_hits_the_cache(tmp_path, strategy):
    params = dict(BASE, num_leaves=7, tpu_compile_cache_dir=str(tmp_path),
                  **STRATEGIES[strategy])
    tables = [criteo_like.make({"features": 67}, seed=s, rows=3000, stream=0)
              for s in (5, 6)]
    assert not np.array_equal(tables[0]["y"], tables[1]["y"])
    was = LEDGER.enabled
    LEDGER.enable()
    try:
        booster(params, tables[0]["X"], tables[0]["y"], rounds=2)
        first = len(LEDGER.compiles())
        booster(params, tables[1]["X"], tables[1]["y"], rounds=2)
        rows = [r for r in LEDGER.compiles()[first:]
                if r["site"].startswith(("learner.", "grower."))]
    finally:
        LEDGER.enable(was)
        enable_compilation_cache()  # back to the package's default
    # pre and post (and the leaf ids' all-gather) are closures of their
    # Booster: traced again, answered by the cache; the memoized grower is
    # the first Booster's executable
    assert {r["site"] for r in rows} == {"learner.pre", "learner.post"} | (
        {"learner.gather"} if strategy == "data" else set())
    assert [r["cache"] for r in rows] == ["hit"] * len(rows), rows


# ---- the data axis's gauges ---------------------------------------------------------------
def test_the_exchange_arithmetic_of_the_four_chip_cell():
    slots = tree_hist_slots(255, 25, True, 4)
    assert slots == [1, 1, 4, 16] + [25] * 11
    assert tree_hist_slots(255, 25, False, 4)[:3] == [1, 25, 25]
    assert tree_hist_slots(2, 1, True, 2) == [1, 1]
    scatter = exchange_bytes_per_tree(slots, 96, 256, 4, True)
    assert scatter["reduce_scatter"] == 297 * 96 * 256 * 3 * 4 == 87_588_864
    assert scatter["all_gather"] == (1 + 2 * 296) * 3 * 4
    assert scatter["all_reduce"] == (1 + 2 * 296) * 10 * 4
    assert exchange_bytes_per_tree(slots, 96, 256, 4, False) == {
        "reduce_scatter": 0, "all_gather": 0, "all_reduce": 87_588_864}


def test_the_layout_states_its_shards(click_rows):
    X, y = click_rows
    bst, _ = booster(dict(BASE, num_leaves=15, **DATA4), X, y)
    learner = bst._driver.learner
    g = obs.REGISTRY.value
    rps = learner.n_pad // SHARDS
    assert g("lgbm_data_shards") == SHARDS
    assert [g("lgbm_shard_rows", shard=str(k)) for k in range(SHARDS)] \
        == [rps] * SHARDS
    table_rows = [g("lgbm_shard_table_rows", shard=str(k))
                  for k in range(SHARDS)]
    assert sum(table_rows) == len(y) and table_rows[0] == rps
    assert (g("lgbm_hist_agg", mode="scatter"),
            g("lgbm_hist_agg", mode="psum")) == (1, 0)
    p = learner.params
    want = exchange_bytes_per_tree(
        tree_hist_slots(p.num_leaves, p.split_batch, p.ramp, p.ramp_step),
        learner.g_pad, p.num_bins, 4, True)
    assert {op: g("lgbm_exchange_bytes_per_tree", op=op) for op in want} \
        == want
    booster(dict(BASE, num_leaves=15), X, y)
    assert g("lgbm_data_shards") == 1
    assert g("lgbm_hist_agg", mode="scatter") == 0
    assert g("lgbm_exchange_bytes_per_tree", op="reduce_scatter") == 0


@pytest.mark.parametrize("strategy", ["data", "voting", "feature"])
def test_shards_come_from_the_device_ingest_with_no_host_copy(click_rows,
                                                             strategy):
    X, y = click_rows
    base = dict(BASE, num_leaves=15, tpu_ingest_chunk_rows=256,
                **STRATEGIES[strategy])
    texts = {}
    for ingest in ("true", "false"):
        params = dict(base, tpu_ingest_device=ingest, tpu_ingest_min_rows=1)
        bst, ds = booster(params, X, y, rounds=2)
        inner = ds._inner
        assert (inner.device_ingest_bins() is not None) == (ingest == "true")
        if ingest == "true":
            # the [n, F] host matrix was never asked for
            assert inner._bins is None
            # rows sharded: ingest dealt them to the chips that train on
            # them, and no chip was handed the whole table
            parts = getattr(inner.device_ingest_bins(), "parts", None)
            if strategy == "feature":
                assert parts is None
            else:
                assert [next(iter(p.devices())) for p in parts] \
                    == jax.devices()[:SHARDS]
                assert max(p.shape[0] for p in parts) == 1024 < len(y)
        shards = bst._driver.learner.bins_t.addressable_shards
        assert len({s.device for s in shards}) == SHARDS
        texts[ingest] = bst.model_to_string().split("\nparameters:")[0]
    assert texts["true"] == texts["false"]
