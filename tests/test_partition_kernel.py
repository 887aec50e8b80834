"""The row partition as one Pallas pass a round (`ops/partition.py`,
`tpu_partition_impl=kernel`): leaf ids equal to the `select` lowering's
element for element, the same model text, the rule that picks it, and the
gauge that says how many sweeps of the leaf ids a tree costs.  On the CPU
the kernel runs in interpret mode (`ops/histogram.pallas_interpret`)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lightgbm_tpu.ops.partition import (partition_kernel_fits,
                                        partition_rows, partition_steps)
from lightgbm_tpu.ops.split import (MISSING_NAN, MISSING_ZERO,
                                    go_right_scalars, numeric_go_left)

MISSING = {"none": 0, "zero": MISSING_ZERO, "nan": MISSING_NAN}


def select_ids(bins_t, leaf_ids, sel, do_k, new_ids, feat, thr, dleft,
               mt, nbf, db):
    """`exec_round`'s `select` branch on a dense numerical table: one
    pass per slot, each from the round's old ids."""
    new = leaf_ids
    for k in range(len(sel)):
        go_left = numeric_go_left(bins_t[feat[k]].astype(jnp.int32),
                                  mt[feat[k]], nbf[feat[k]], db[feat[k]],
                                  thr[k], dleft[k])
        new = jnp.where((leaf_ids == sel[k]) & do_k[k] & ~go_left,
                        new_ids[k], new)
    return new


def kernel_ids(bins_t, leaf_ids, sel, do_k, new_ids, feat, thr, dleft,
               mt, nbf, db):
    """The `kernel` branch, as `exec_round` calls it."""
    return partition_rows(
        bins_t, leaf_ids, jnp.where(do_k, sel, -1), new_ids, feat, thr,
        go_right_scalars(mt[feat], nbf[feat], db[feat], thr, dleft))


def a_round(seed, n, n_table, F, Kr, missing, dleft, dtype=np.uint8,
            bins=64):
    """A frontier of 2 * Kr + 3 leaves, Kr of them split; rows past
    `n_table` are the zero-filled padding tail, as the learner pads."""
    rng = np.random.default_rng(seed)
    nbf = rng.integers(bins // 2, bins + 1, F).astype(np.int32)
    bins_t = (rng.integers(0, 1 << 30, (F, n)) % nbf[:, None]).astype(dtype)
    bins_t[:, n_table:] = 0
    leaves = 2 * Kr + 3
    feat = rng.integers(0, F, Kr).astype(np.int32)
    # thresholds on both sides of the missing bin, and at it
    db = rng.integers(0, bins // 2, F).astype(np.int32)
    thr = rng.integers(0, nbf[feat] - 1).astype(np.int32)
    thr[::3] = db[feat][::3]
    return dict(
        bins_t=jnp.asarray(bins_t),
        leaf_ids=jnp.asarray(rng.integers(0, leaves, n).astype(np.int32)),
        sel=jnp.asarray(rng.permutation(leaves)[:Kr].astype(np.int32)),
        do_k=jnp.asarray(np.arange(Kr) % 3 != 1 if Kr > 1
                         else np.ones(1, bool)),
        new_ids=jnp.asarray(leaves + np.arange(Kr, dtype=np.int32)),
        feat=jnp.asarray(feat), thr=jnp.asarray(thr),
        dleft=jnp.asarray(np.full(Kr, dleft)),
        mt=jnp.asarray(np.full(F, MISSING[missing], np.int32)),
        nbf=jnp.asarray(nbf), db=jnp.asarray(db))


@pytest.mark.parametrize("dleft", [False, True], ids=["right", "left"])
@pytest.mark.parametrize("missing", list(MISSING))
@pytest.mark.parametrize("Kr", [1, 4, 16, 25])
def test_kernel_ids_equal_select_ids(Kr, missing, dleft):
    """Three grid steps (3 x 104 sublane-rows of 128), the last 1,000 rows
    padding, a third of the slots switched off."""
    n = 128 * 312
    assert partition_steps(n, 16, 1) == (104, 8)
    r = a_round(Kr * 7 + dleft, n, n - 1000, 16, Kr, missing, dleft)
    want, got = select_ids(**r), kernel_ids(**r)
    assert int(jnp.sum(want != r["leaf_ids"])) > 0      # rows did move
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("n, F, dtype, steps", [
    (128 * 7, 8, np.uint8, (7, 7)),          # no whole register: one block
    (128 * 8 * 64, 24, np.uint8, (256, 64)),  # two steps of four chunks
    (128 * 16, 8, np.int32, (16, 16)),       # bins over 256: one word each
    (128 * 24, 40, np.int32, (24, 8)),
])
def test_kernel_ids_equal_select_ids_by_shape(n, F, dtype, steps):
    assert partition_steps(n, F, np.dtype(dtype).itemsize) == steps
    r = a_round(n + F, n, n - 77, F, 4, "nan", True, dtype,
                bins=64 if dtype == np.uint8 else 1000)
    np.testing.assert_array_equal(np.asarray(kernel_ids(**r)),
                                  np.asarray(select_ids(**r)))


@pytest.mark.parametrize("missing", list(MISSING))
def test_go_right_scalars_restate_numeric_go_left(missing):
    """Every (bin, threshold, direction) of a 12-bin feature: the two
    compares the kernel makes against what the other lowerings compute."""
    nbf, db = 12, 4
    col, thr, dleft = np.meshgrid(np.arange(nbf), np.arange(nbf - 1),
                                  [False, True], indexing="ij")
    mt = MISSING[missing]
    flip = go_right_scalars(mt, nbf, db, jnp.asarray(thr), jnp.asarray(dleft))
    right = (col > thr) ^ (col == np.asarray(flip))
    left = numeric_go_left(jnp.asarray(col), mt, nbf, db, jnp.asarray(thr),
                           jnp.asarray(dleft))
    np.testing.assert_array_equal(right, ~np.asarray(left))


def test_shapes_the_kernel_has_no_view_of_are_refused_by_name():
    bins_t = jnp.zeros((12, 256), jnp.uint8)        # 12 columns: no tile
    z = jnp.zeros(1, jnp.int32)
    with pytest.raises(ValueError, match="tpu_partition_impl=select"):
        partition_rows(bins_t, jnp.zeros(256, jnp.int32), z, z, z, z, z)
    assert partition_steps(1000, 8, 1) is None      # rows off 128
    assert partition_steps(1024, 8, 2) is None      # no 16-bit bins
    # the rule wants whole registers and a narrow row besides
    assert partition_kernel_fits(8192 * 4, 32, 1)
    assert partition_kernel_fits(8192 * 4, 96, 1)
    assert not partition_kernel_fits(128 * 7, 32, 1)
    assert not partition_kernel_fits(8192 * 4, 512, 1)
    assert not partition_kernel_fits(8192 * 4, 96, 4)


# ---- through the public entry points ---------------------------------------
def nan_table(seed=5, n=4000, f=8):
    """A noisy regression target, so that every leaf keeps a positive gain
    and a tree grows to all of its 255 leaves."""
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, f))
    X[rng.random((n, f)) < 0.15] = np.nan           # NaN in every column
    y = (np.nan_to_num(X[:, 0]) ** 2 + np.sin(3 * np.nan_to_num(X[:, 1]))
         + 0.5 * np.isnan(X[:, 2]) + 0.3 * rng.normal(size=n))
    return X, y


def trees_of(X, y, rounds=2, **params):
    import lightgbm_tpu as lgb

    p = {"objective": "regression", "num_leaves": 255, "min_data_in_leaf": 2,
         "max_bin": 63, "verbosity": -1, "tpu_block_rows": 1024, **params}
    bst = lgb.train(p, lgb.Dataset(X, label=y, params=p),
                    num_boost_round=rounds, keep_training_booster=True)
    learner = bst._driver.learner
    return (bst.model_to_string().split("\nparameters:")[0],
            learner.params.partition_impl)


LAYOUTS = {"serial": {},
           "data8": {"tree_learner": "data", "num_machines": 8}}


@pytest.fixture(scope="module")
def select_trees():
    """The `select` model text per layout, trained once."""
    cache = {}

    def of(layout):
        if layout not in cache:
            text, impl = trees_of(*nan_table(), tpu_partition_impl="select",
                                  **LAYOUTS[layout])
            assert impl == "select" and text.count("num_leaves=255") == 2
            cache[layout] = text
        return cache[layout]
    return of


@pytest.mark.parametrize("layout", list(LAYOUTS))
def test_255_leaf_model_text_is_selects(select_trees, layout):
    """Two trees of 255 leaves on a table with NaN in every column; under
    `data8` eight row shards on the virtual devices, each partitioning its
    own rows inside shard_map."""
    text, resolved = trees_of(*nan_table(), tpu_partition_impl="kernel",
                              **LAYOUTS[layout])
    assert resolved == "kernel" and text == select_trees(layout)


def test_default_resolves_select_on_a_cpu(select_trees):
    text, resolved = trees_of(*nan_table())
    assert resolved == "select" and text == select_trees("serial")


class _Tpu:
    platform = "tpu"


def _tables():
    rng = np.random.default_rng(11)
    n = 2048
    dense = rng.normal(size=(n, 8))
    cat = dense.copy()
    cat[:, 3] = rng.integers(0, 7, n)
    sparse = np.where(rng.random((n, 10)) < 0.85, 0.0,
                      rng.normal(size=(n, 10)))
    # six mutually exclusive columns of three values, five sixths zeros: EFB has
    # something to bundle
    exclusive = dense.copy()
    group = rng.integers(0, 6, n)
    for g in range(6):
        exclusive[:, g] = np.where(group == g, rng.integers(1, 4, n), 0.0)
    return {
        "dense": (dense, {}, {}),
        "categorical": (cat, {"categorical_feature": [3]}, {}),
        "bundled": (exclusive, {}, {"enable_bundle": True}),
        "sparse": (sparse, {}, {"enable_bundle": False,
                                "tpu_sparse_threshold": 0.2}),
        "packed": (dense, {}, {"max_bin": 15, "tpu_hist_impl": "pallas2",
                               "tpu_block_rows": 512}),
    }


@pytest.mark.parametrize("kind", ["dense", "categorical", "bundled",
                                  "sparse", "packed"])
def test_the_rule_takes_the_kernel_for_dense_unpacked_tables_only(
        kind, monkeypatch):
    """What the learner observes of each table (recorded while it is built
    on the CPU), handed to the rule again with a TPU faked: `kernel` for
    the dense numerical table, `select` for the four others."""
    import lightgbm_tpu as lgb
    from lightgbm_tpu.models.learner import TPUTreeLearner

    X, ds_kw, extra = _tables()[kind]
    y = (np.nan_to_num(X).sum(axis=1) > 0).astype(np.float64)
    rule = TPUTreeLearner._resolve_partition_impl
    seen = []

    def spy(config, **observed):
        seen.append((config, observed))
        return rule(config, **observed)

    monkeypatch.setattr(TPUTreeLearner, "_resolve_partition_impl",
                        staticmethod(spy))
    p = {"objective": "binary", "num_leaves": 15, "verbosity": -1,
         "max_bin": 31, **extra}
    bst = lgb.Booster(params=p, train_set=lgb.Dataset(X, label=y, params=p,
                                                      **ds_kw))
    assert bst._driver.learner.params.partition_impl == "select"   # a CPU
    (config, observed), = seen
    assert observed["dense_unpacked"] == (kind == "dense")
    monkeypatch.setattr(jax, "devices", lambda *a, **k: [_Tpu()])
    assert rule(config, **observed) == ("kernel" if kind == "dense"
                                        else "select")


@pytest.mark.parametrize("kind", ["categorical", "bundled", "sparse",
                                  "packed"])
def test_an_explicit_kernel_is_refused_off_dense_unpacked_tables(kind):
    import lightgbm_tpu as lgb

    X, ds_kw, extra = _tables()[kind]
    y = (np.nan_to_num(X).sum(axis=1) > 0).astype(np.float64)
    p = {"objective": "binary", "num_leaves": 15, "verbosity": -1,
         "max_bin": 31, "tpu_partition_impl": "kernel", **extra}
    with pytest.raises(ValueError, match="dense numerical unpacked"):
        lgb.Booster(params=p, train_set=lgb.Dataset(X, label=y, params=p,
                                                    **ds_kw))


@pytest.mark.parametrize("impl, passes", [("select", 296), ("kernel", 14)])
def test_the_gauge_counts_the_sweeps_of_a_255_leaf_tree(impl, passes):
    """1 + 4 + 16 ramp slots and 11 loop rounds of 25: 296 slots in 14
    rounds (the root's histogram call splits nothing)."""
    import lightgbm_tpu as lgb
    from lightgbm_tpu import obs

    X, y = nan_table(n=1024)
    p = {"objective": "regression", "num_leaves": 255, "verbosity": -1,
         "tpu_partition_impl": impl}
    lgb.Booster(params=p, train_set=lgb.Dataset(X, label=y, params=p))
    snap = obs.REGISTRY.snapshot()
    got = {i: snap[f'lgbm_partition_passes_per_tree{{impl="{i}"}}']
           for i in ("select", "kernel")}
    assert got.pop(impl) == passes and set(got.values()) == {0}


# ---- compiled for a described v5e: what interpret mode cannot refuse --------
@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("n, F, dtype, Kr", [
    (27_262_976, 32, jnp.uint8, 25),     # the Higgs cells' round loop
    (13_631_488, 96, jnp.uint8, 16),     # the Criteo shard's last pre-round
    (8192 * 4, 32, jnp.uint8, 1),        # the first pre-round, a small table
    (8192 * 4, 16, jnp.int32, 4),        # bins over 256
])
def test_mosaic_takes_the_kernel_and_xla_copies_nothing(
        one_chip, monkeypatch, n, F, dtype, Kr):
    """The cells' shapes compile for the chip (PR 18's partition kernel
    passed every interpret-mode test and was refused by Mosaic), the bin
    matrix and the ids reach the kernel as bitcasts, and the ids are
    rewritten in place."""
    from lightgbm_tpu.ops import histogram

    monkeypatch.setattr(histogram, "pallas_interpret", lambda: False)

    def spec(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    slot = spec((Kr,), jnp.int32)
    compiled = jax.jit(partition_rows, donate_argnums=1).lower(
        spec((F, n), dtype), spec((n,), jnp.int32), slot, slot, slot, slot,
        slot).compile()
    text = compiled.as_text()
    assert text.count("%partition_rows") >= 1
    assert "output_to_operand_aliasing" in text
    mem = compiled.memory_analysis()
    # no copy of the matrix, no second [n] buffer
    assert mem.temp_size_in_bytes < n
    assert mem.alias_size_in_bytes == 4 * n
