"""The score update's lookup of a row's leaf value (`ops/lookup.py`): the
one-hot contraction against `table[ids]` bit for bit, the rule that picks a
form, the score update built on it (`learner._post`), and a booster trained
with the rule forced to each form.

The forcing is a monkeypatch of the rule (`lookup.lookup_form`): there is no
option for it.  On this CPU the rule itself always answers "gather".
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import lightgbm_tpu as lgb
from lightgbm_tpu.ops import lookup as lk


def bits(x):
    return np.asarray(x, np.float32).view(np.int32)


def force(monkeypatch, form):
    """The rule answers `form`; returns the list of what it was asked."""
    asked = []
    monkeypatch.setattr(
        lk, "lookup_form",
        lambda platform, entries: asked.append((platform, entries)) or form)
    return asked


def table_and_ids(entries, n, seed):
    rng = np.random.default_rng(seed)
    table = rng.standard_normal(entries).astype(np.float32)
    ids = rng.integers(0, entries, n, dtype=np.int32)
    ids[0], ids[-1] = 0, entries - 1
    ids[n // 2], ids[n // 3] = entries - 1, 0
    return table, ids


# ---- the lookup alone ------------------------------------------------------------
@pytest.mark.parametrize("entries", [2, 31, 255, 256, 1023])
@pytest.mark.parametrize("n", [8192, 1024 * 3, 1000, 8192 + 1024, 9001])
def test_onehot_is_the_gather_bit_for_bit(entries, n):
    table, ids = table_and_ids(entries, n, seed=entries * 31 + n)
    got = lk._lookup(jnp.asarray(table), jnp.asarray(ids), form="onehot")
    assert got.shape == (n,) and got.dtype == jnp.float32
    np.testing.assert_array_equal(bits(got), bits(table[ids]))
    want = lk._lookup(jnp.asarray(table), jnp.asarray(ids), form="gather")
    np.testing.assert_array_equal(bits(got), bits(want))


@pytest.mark.parametrize("entries", [2, 31, 255, 256, 1023])
def test_every_entry_is_reached(entries):
    table = (np.arange(entries, dtype=np.float32) + 0.5) * np.float32(-1.25)
    ids = np.concatenate([np.arange(entries), np.arange(entries)[::-1]]
                         ).astype(np.int32)
    got = lk._lookup(jnp.asarray(table), jnp.asarray(ids), form="onehot")
    np.testing.assert_array_equal(bits(got), bits(table[ids]))


SPECIAL = {
    "minus_zero": np.float32(-0.0),
    "inf": np.float32(np.inf),
    "minus_inf": np.float32(-np.inf),
    "nan": np.float32(np.nan),
    "nan_with_payload": np.array([0x7FC12345], np.int32).view(np.float32)[0],
    "subnormal": np.float32(1e-40),
    "largest": np.finfo(np.float32).max,
}


@pytest.mark.parametrize("name", sorted(SPECIAL))
@pytest.mark.parametrize("entries, at", [(31, 7), (255, 0), (255, 254)])
def test_a_special_entry_comes_back_as_it_is_and_touches_no_other_row(
        name, entries, at):
    """A multiply by a 0/1 mask would turn `inf` into NaN for every row, and
    a sum of values would lose `-0.0`: the bytes of the bit pattern do
    neither."""
    table, ids = table_and_ids(entries, 4096, seed=at + entries)
    table[at] = SPECIAL[name]
    got = bits(lk._lookup(jnp.asarray(table), jnp.asarray(ids),
                          form="onehot"))
    np.testing.assert_array_equal(got, bits(table[ids]))
    assert (got[ids == at] == bits(SPECIAL[name])).all()
    assert np.isfinite(got[ids != at].view(np.float32)).all()


# ---- the rule --------------------------------------------------------------------
@pytest.mark.parametrize("platform, entries, want", [
    ("cpu", 2, "gather"), ("cpu", 255, "gather"), ("cpu", 4096, "gather"),
    ("gpu", 255, "gather"),
    ("tpu", 2, "onehot"), ("tpu", 31, "onehot"), ("tpu", 64, "onehot"),
    ("tpu", 255, "onehot"), ("tpu", 256, "onehot"), ("tpu", 1023, "onehot"),
    ("tpu", lk.ONEHOT_MAX_ENTRIES, "onehot"),
    ("tpu", lk.ONEHOT_MAX_ENTRIES + 1, "gather"), ("tpu", 131072, "gather"),
])
def test_the_rule(platform, entries, want):
    assert lk.lookup_form(platform, entries) == want


def test_the_rule_is_asked_with_what_the_call_can_observe(monkeypatch):
    asked = force(monkeypatch, "onehot")
    table, ids = table_and_ids(31, 100, seed=3)
    got = lk.lookup(jnp.asarray(table), jnp.asarray(ids))
    assert asked == [(jax.devices()[0].platform, 31)]
    np.testing.assert_array_equal(bits(got), bits(table[ids]))


# ---- a booster under each form ---------------------------------------------------
def train(monkeypatch, form, params, X, y, rounds):
    asked = force(monkeypatch, form)
    bst = lgb.Booster(params=params,
                      train_set=lgb.Dataset(X, label=y, params=params))
    for _ in range(rounds):
        bst.update()
    assert asked and set(asked) == {("cpu", params["num_leaves"])}
    return (bst.model_to_string(),
            np.asarray(bst._driver.train_scores.scores).copy())


@pytest.fixture(scope="module")
def table():
    rng = np.random.default_rng(31)
    X = rng.standard_normal((4000, 12)).astype(np.float32)
    y = (X[:, 0] + 0.5 * X[:, 1] * X[:, 2]
         + 0.3 * rng.standard_normal(4000) > 0).astype(np.float32)
    return X, y


@pytest.mark.parametrize("extra, rounds", [
    ({}, 20),                                     # pre -> grow -> post
    ({"tpu_shape_buckets": 0}, 20),               # exact shapes, no bucket
    ({"objective": "regression_l1"}, 6),          # the synchronous path
    ({"objective": "multiclass", "num_class": 3}, 4),   # traced class_id
], ids=["bucketed", "exact_shape", "synchronous", "multiclass"])
def test_a_booster_is_the_same_under_each_form(monkeypatch, table, extra,
                                               rounds):
    """The same model text, the same scores bit for bit.  `learning_rate`
    is a power of two, so a leaf's scaled value is exact and the gather
    form's fused multiply-add on this CPU (see the next test) rounds as
    two roundings do."""
    X, y = table
    if extra.get("objective") == "multiclass":
        y = np.digitize(X[:, 0], [-0.5, 0.5]).astype(np.float32)
    params = {"objective": "binary", "num_leaves": 31, "learning_rate": 0.25,
              "min_data_in_leaf": 5, "verbosity": -1, **extra}
    text_g, scores_g = train(monkeypatch, "gather", params, X, y, rounds)
    text_o, scores_o = train(monkeypatch, "onehot", params, X, y, rounds)
    assert text_g == text_o
    assert scores_g.shape == (params.get("num_class", 1), len(y))
    np.testing.assert_array_equal(bits(scores_g), bits(scores_o))
    assert text_g.count("Tree=") == rounds * params.get("num_class", 1)


def recorded(driver):
    """Wrap the driver's fused step: what went into and came out of each
    call, on the host (the scores copied first: the step donates them)."""
    seen, step = [], driver._train_step

    def wrapped(base_scores, scores, key, bag_key, pool, class_id, *a, **k):
        before = np.asarray(scores).copy()
        out = step(base_scores, scores, key, bag_key, pool, class_id, *a, **k)
        records, after, ids, leaf_output = (np.asarray(o) for o in out[:4])
        # the step hands the leaf ids back on the padded row axis
        seen.append((before, class_id, records[0, 14] > 0.5, leaf_output,
                     ids[:before.shape[1]], after))
        return out
    driver._train_step = wrapped
    return seen


@pytest.mark.parametrize("extra", [
    {}, {"tpu_shape_buckets": 0},
    {"objective": "multiclass", "num_class": 3},
], ids=["bucketed", "exact_shape", "multiclass"])
@pytest.mark.parametrize("form", ["onehot", "gather"])
def test_the_score_update_at_the_cells_learning_rate(monkeypatch, table,
                                                     form, extra):
    """`_post`'s contract at learning_rate 0.1, the cells': every row gets
    f32(score + f32(leaf * lr)), two roundings, which the one-hot form gives
    to the bit: its table passes through its bit pattern, so nothing of the
    multiply reaches the add.  The gather form on this CPU does not: XLA's
    loop fusion takes the [L] multiply back into the gather's consumer,
    LLVM contracts it with the add into one fused multiply-add, and an
    optimization barrier on the table is expanded away before the fusion
    (PERF.md §6, PR 31).  That is the parent's behaviour on a CPU, alike
    under every topology; it is held here to an ulp of the scaled leaf
    value and one of the score, which both roundings are within."""
    X, y = table
    if extra.get("objective") == "multiclass":
        y = np.digitize(X[:, 0], [-0.5, 0.5]).astype(np.float32)
    params = {"objective": "binary", "num_leaves": 31, "learning_rate": 0.1,
              "min_data_in_leaf": 5, "verbosity": -1, **extra}
    force(monkeypatch, form)
    bst = lgb.Booster(params=params,
                      train_set=lgb.Dataset(X, label=y, params=params))
    seen = recorded(bst._driver)
    for _ in range(5):
        bst.update()
    assert len(seen) == 5 * params.get("num_class", 1)
    moved = 0
    for before, class_id, any_split, leaf_output, ids, after in seen:
        assert any_split
        scaled = leaf_output.astype(np.float32) * np.float32(0.1)
        want = before.copy()
        want[class_id] = before[class_id] + scaled[ids]
        if form == "onehot":
            np.testing.assert_array_equal(bits(after), bits(want))
        else:
            off = np.abs(after[class_id].astype(np.float64) - want[class_id])
            assert (off <= np.spacing(np.abs(scaled[ids]))
                    + np.spacing(np.abs(want[class_id]))).all()
            others = np.arange(len(before)) != class_id
            np.testing.assert_array_equal(bits(after[others]),
                                          bits(before[others]))
        moved += int((bits(after) != bits(before)).sum())
    assert moved > len(y)


@pytest.mark.parametrize("form", ["gather", "onehot"])
def test_a_tree_that_did_not_split_moves_no_score(monkeypatch, table, form):
    """`any_split` false: the table is all zeros and every score keeps its
    bits (a leaf value of the unsplit root, were it looked up, would not)."""
    X, y = table
    asked = force(monkeypatch, form)
    params = {"objective": "binary", "num_leaves": 31, "learning_rate": 0.25,
              "min_gain_to_split": 1e30, "verbosity": -1}
    init = np.random.default_rng(9).standard_normal(len(y))
    bst = lgb.Booster(params=params,
                      train_set=lgb.Dataset(X, label=y, init_score=init,
                                            params=params))
    before = np.asarray(bst._driver.train_scores.scores).copy()
    bst.update()
    after = np.asarray(bst._driver.train_scores.scores)
    assert asked and np.abs(before).min() > 0
    np.testing.assert_array_equal(bits(before), bits(after))
