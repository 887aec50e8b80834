"""LambdaRank's gradients inside the device step (`ops/lambdarank.py`,
`models/objectives_ext.LambdarankNDCG.gradients`), CPU, small sizes, seeded.

* the device lambdas against the float64 oracle (`get_gradients`, the host
  loop over queries) on ragged queries: lengths 1 and 2, a length on each
  side of every bucket edge, all-equal labels (1/maxDCG = 0), all-equal
  scores, tied scores, `lambdamart_norm` on and off, weights;
* a variant in bfloat16, one without the stable order among ties and one
  without the query's log2(1 + S) / S factor each fail that tolerance;
* the layout's shapes follow from the multiset of lengths alone;
  1/maxDCG vectorised over the layout equals the loop to the last bit;
* through `lgb.Booster.update()`: the fused step is built, its model text
  agrees with the synchronous path's, no program of the step holds a row-
  or query-shaped constant, a second data set of the same lengths compiles
  nothing; `rank_xendcg`, and `lambdarank` on a row-sharded learner, keep
  the synchronous path.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import lightgbm_tpu as lgb
from benchmarks.lib import reference as public_rule
from lightgbm_tpu import obs
from lightgbm_tpu.config import Config
from lightgbm_tpu.io.dataset import Metadata
from lightgbm_tpu.models.objectives import create_objective
from lightgbm_tpu.models.objectives_ext import LambdarankNDCG
from lightgbm_tpu.ops import lambdarank
from lightgbm_tpu.utils.backend import enable_compilation_cache
from lightgbm_tpu.utils.compile_ledger import LEDGER

# float32 sums of up to 1,251 terms of mixed sign (here up to 385), each
# term rounded to 2^-24 of itself and the terms up to the size of the
# query's largest |lambda|: a row's sum is off by a few 1e-7 of that
# largest value (measured 5e-7 on these queries), and a defect moves it
# by 1e-3 or more.  2e-5 stands between, forty times the sound reading.
TOL = 2e-5
EDGES = (8, 16, 24, 32, 48, 64, 96, 128, 192, 256)
LENGTHS = (1, 2, 5, 5, 5, 385) + tuple(e + d for e in EDGES for d in (0, 1))
ROW_CONSTANTS = "lgbm_step_row_constant_bytes"


def ragged(seed=0):
    """(lengths, labels, scores): the shapes the issue lists."""
    rng = np.random.default_rng(seed)
    n = sum(LENGTHS)
    bounds = np.concatenate([[0], np.cumsum(LENGTHS)])
    label = rng.integers(0, 5, size=n).astype(np.float32)
    score = rng.normal(size=n).astype(np.float32)

    def rows(k):   # of the k-th query
        return slice(bounds[k], bounds[k + 1])

    label[rows(3)] = 2.0                       # all-equal labels: c = 0
    score[rows(4)] = 0.25                      # all-equal scores
    for k in (7, 12, 20):                      # tied scores inside a query
        score[rows(k)] = np.round(score[rows(k)])
    return list(LENGTHS), label, score


def objective(lengths, label, weight=None, **params):
    obj = create_objective(Config({"objective": "lambdarank", **params}))
    n = len(label)
    obj.init(Metadata(n, label=label, weight=weight, group_sizes=lengths), n)
    return obj


def device_gradients(obj, score, pad=0):
    rows = {k: jnp.asarray(np.pad(v, (0, pad)))
            for k, v in obj.row_arrays().items()}
    rows["layout"] = jax.tree.map(jnp.asarray, obj.layout_arrays())
    g, h = jax.jit(obj.gradients)(
        jnp.asarray(np.pad(score, (0, pad)))[None, :], rows)
    return np.asarray(g)[0], np.asarray(h)[0]


def worst_error(obj, score, got):
    """Largest |device - oracle| of lambda and hessian over the rows,
    relative to the largest |lambda| of the row's query; a query whose
    oracle is all zero must be all zero."""
    g0, h0 = obj.get_gradients(score[None, :].astype(np.float64))
    g, h = got
    worst = 0.0
    for a, b in zip(obj.query_boundaries[:-1], obj.query_boundaries[1:]):
        top = np.abs(g0[0, a:b]).max()
        if top == 0:
            assert not g[a:b].any() and not h[a:b].any()
            continue
        worst = max(worst, np.abs(g[a:b] - g0[0, a:b]).max() / top,
                    np.abs(h[a:b] - h0[0, a:b]).max() / top)
    return worst


@pytest.mark.parametrize("weighted", [False, True])
@pytest.mark.parametrize("norm", [True, False])
def test_device_lambdas_agree_with_the_float64_oracle(norm, weighted):
    lengths, label, score = ragged()
    weight = (np.random.default_rng(5).uniform(0.5, 2.0, len(label))
              .astype(np.float32) if weighted else None)
    obj = objective(lengths, label, weight, lambdamart_norm=norm)
    # the step hands the scores over padded: the rows past the data set's
    # get zeros
    g, h = device_gradients(obj, score, pad=37)
    assert not g[len(label):].any() and not h[len(label):].any()
    assert worst_error(obj, score, (g[:len(label)], h[:len(label)])) < TOL
    one, c0 = slice(0, 1), slice(8, 13)   # a query of one row; c = 0
    assert not g[one].any() and not g[c0].any() and not h[c0].any()


def in_bfloat16(bucket):
    def run(s, label, gain, c, **kw):
        lam, hes = bucket(s.astype(jnp.bfloat16), label,
                          gain.astype(jnp.bfloat16),
                          c.astype(jnp.bfloat16), **kw)
        return lam.astype(jnp.float32), hes.astype(jnp.float32)
    return run


FAULTS = {
    "bfloat16": ("_bucket", in_bfloat16),
    "unstable_ties": ("_beats", lambda _: (
        lambda s_s, s_o, pos_s, pos_o: s_o > s_s)),
    "no_norm_factor": ("_norm_factor", lambda _: jnp.ones_like),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_faulty_variant_fails_the_tolerance(monkeypatch, fault):
    name, make = FAULTS[fault]
    monkeypatch.setattr(lambdarank, name, make(getattr(lambdarank, name)))
    lengths, label, score = ragged()
    obj = objective(lengths, label)
    assert worst_error(obj, score, device_gradients(obj, score)) > 10 * TOL


# ---- the layout ---------------------------------------------------------------------
@pytest.mark.parametrize("length, want", [
    (1, 8), (8, 8), (9, 16), (16, 16), (17, 24), (24, 24), (25, 32),
    (33, 48), (49, 64), (65, 96), (97, 128), (129, 192), (193, 256),
    (257, 384), (1251, 1536)])
def test_padded_length_rule(length, want):
    assert lambdarank.padded_length(length) == want


@pytest.mark.parametrize("count, short, want", [
    (1, True, 128), (128, True, 128), (129, True, 256), (7000, True, 7168),
    (1, False, 8), (9, False, 9), (17, False, 18), (1000, False, 1024),
    (9000, False, 9216)])
def test_padded_count_rule(count, short, want):
    assert lambdarank.padded_count(count, short) == want


def test_the_shapes_follow_from_the_multiset_of_lengths():
    lengths, label, _ = ragged()
    rng = np.random.default_rng(9)
    shapes = []
    for _ in range(2):
        order = rng.permutation(len(lengths))
        lens = [lengths[i] for i in order]
        lab = rng.integers(0, 5, size=len(label))
        bounds = np.concatenate([[0], np.cumsum(lens)])
        layout, _, stats = lambdarank.query_layout(
            bounds, lab, np.arange(5.0), 20)
        shapes.append(jax.tree.map(lambda a: (a.shape, a.dtype), layout))
        assert stats["pairs_slots"] == sum(
            b["label"].size * int(name[3:]) for name, b in layout.items())
    assert shapes[0] == shapes[1]


def test_inverse_max_dcg_equals_the_loop_to_the_last_bit():
    lengths, label, _ = ragged(seed=3)
    for k in (1, 3, 20, 500):
        obj = objective(lengths, label, max_position=k)
        loop = np.zeros(obj.num_queries)
        for q in range(obj.num_queries):
            a, b = obj.query_boundaries[q], obj.query_boundaries[q + 1]
            mdcg = obj._max_dcg_at_k(k, obj.label_np[a:b])
            loop[q] = 1.0 / mdcg if mdcg > 0 else 0.0
        assert np.array_equal(obj.inverse_max_dcgs, loop)
        assert (loop == 0).any() and (loop > 0).any()


def test_the_layout_states_its_gauges():
    lengths, label, _ = ragged()
    objective(lengths, label)
    snap = obs.REGISTRY.snapshot()
    assert snap["lgbm_rank_queries"] == len(lengths)
    assert snap['lgbm_rank_query_len{stat="max"}'] == max(lengths)
    assert snap["lgbm_rank_buckets"] == 12   # 8 ... 384, and 512 for 385
    bounds = np.concatenate([[0], np.cumsum(lengths)])
    pairs = sum((label[a:b, None] != label[None, a:b]).sum()
                for a, b in zip(bounds[:-1], bounds[1:]))
    assert snap['lgbm_rank_pairs{kind="valid"}'] == pairs
    assert snap['lgbm_rank_pairs{kind="slots"}'] > pairs


# ---- through the public entry points ----------------------------------------------------
def search_log(seed, lengths=None):
    rng = np.random.default_rng(seed)
    if lengths is None:
        lengths = rng.integers(1, 40, size=200)
    n = int(np.sum(lengths))
    X = rng.normal(size=(n, 10))
    y = np.clip(np.round(X[:, 0] + 0.5 * X[:, 1]
                         + rng.normal(size=n) * 0.5 + 1), 0, 4)
    return X, y, lengths


PARAMS = {"objective": "lambdarank", "num_leaves": 15, "min_data_in_leaf": 5,
          "verbosity": -1}


def train(params, X, y, lengths, rounds=5):
    ds = lgb.Dataset(X, label=y, group=lengths, params=params)
    bst = lgb.Booster(params=params, train_set=ds)
    for _ in range(rounds):
        assert not bst.update()
    return bst


def test_fused_and_synchronous_paths_grow_the_same_trees(monkeypatch):
    X, y, lengths = search_log(1)
    fused = train(PARAMS, X, y, lengths)
    assert fused._driver._train_step is not None
    assert obs.REGISTRY.snapshot()[
        'lgbm_train_step_fused{objective="lambdarank"}'] == 1
    monkeypatch.setattr(LambdarankNDCG, "steps_on_device",
                        lambda self, learner: False)
    sync = train(PARAMS, X, y, lengths)
    assert sync._driver._train_step is None
    assert obs.REGISTRY.snapshot()[
        'lgbm_train_step_fused{objective="lambdarank"}'] == 0
    a, b = (public_rule.parse_model(m.model_to_string())
            for m in (fused, sync))
    assert len(a) == len(b) == 5
    # the structure is equal; the values differ in their last float32
    # digits: the device sums each row's pairs in float32 in row order, the
    # host sums them in float64 in sorted order and rounds once
    for ta, tb in zip(a, b):
        for key in ("split_feature", "threshold", "decision_type",
                    "leaf_count"):
            assert np.array_equal(ta[key], tb[key]), key
        np.testing.assert_allclose(ta["leaf_value"], tb["leaf_value"],
                                   rtol=2e-5, atol=1e-7)


def test_no_program_of_the_step_holds_a_row_or_query_constant():
    X, y, lengths = search_log(2)
    del obs.REGISTRY._families[ROW_CONSTANTS]
    train(dict(PARAMS, bagging_fraction=0.8, bagging_freq=1,
               feature_fraction=0.8), X, y, lengths, rounds=2)
    sites = {k: v for k, v in obs.REGISTRY.snapshot().items()
             if k.startswith(ROW_CONSTANTS)}
    assert set(sites) == {f'{ROW_CONSTANTS}{{site="{s}"}}' for s in
                          ("learner.pre", "grower.grow", "learner.post")}
    assert not any(sites.values()), sites


def test_a_second_data_set_of_the_same_lengths_compiles_nothing(tmp_path):
    params = dict(PARAMS, tpu_compile_cache_dir=str(tmp_path))
    X, y, lengths = search_log(3)
    X2, y2, lengths2 = search_log(
        4, np.random.default_rng(4).permutation(lengths))
    assert not np.array_equal(lengths, lengths2)
    was = LEDGER.enabled
    LEDGER.enable()
    try:
        train(params, X, y, lengths, rounds=2)
        first = len(LEDGER.compiles())
        train(params, X2, y2, lengths2, rounds=2)
        rows = [r for r in LEDGER.compiles()[first:]
                if r["site"].startswith(("learner.", "grower."))]
    finally:
        LEDGER.enable(was)
        enable_compilation_cache()  # back to the package's default
    # pre and post are closures of their Booster: traced again, answered
    # by the cache, the queries being arguments; the grower is memoized
    assert {r["site"] for r in rows} == {"learner.pre", "learner.post"}
    assert [r["cache"] for r in rows] == ["hit"] * len(rows), rows


@pytest.mark.parametrize("params", [
    {"objective": "rank_xendcg"},
    {"objective": "lambdarank", "tree_learner": "data", "num_machines": 8},
], ids=["rank_xendcg", "lambdarank_data8"])
def test_the_synchronous_path_stays_where_it_was(params):
    X, y, lengths = search_log(5)
    bst = train(dict(PARAMS, **params), X, y, lengths, rounds=3)
    assert bst._driver._train_step is None
    assert bst.num_trees() == 3
    if params["objective"] == "lambdarank":
        assert bst._driver.learner.mesh is not None
        serial = train(PARAMS, X, y, lengths, rounds=3)
        a, b = (public_rule.parse_model(m.model_to_string())
                for m in (bst, serial))
        assert [t["split_feature"].tolist() for t in a] == \
            [t["split_feature"].tolist() for t in b]
