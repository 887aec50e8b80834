"""Row compaction in the pallas2 histogram kernel (interpret mode off-TPU):
a row block's live rows (leaf one of the call's slots) are left-packed
before the one-hot, only the lane sub-blocks that then are full are
contracted, and the part-filled last one is carried into the next packed
block.  The histograms against the xla scan's over slots x live share x
feature chunks x precision, the edges of the mechanism (no live row, one
row, a sub-block's last lane and the next, a dead slot beside the packed
tail, a padded last block, the all-live root call, the carry across a
block, a dead or unpacked one, the last block and a chunk), what the kernel says it
did with the rows against numpy, the two arms that do not pack, whole
255-leaf trees, the `lgbm_hist_rows_per_tree` gauges against a recount from
the model text, and the cells' kernel shapes compiled for a described v5e.

A file of its own: the test runner hands a file to one worker whole."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import lightgbm_tpu as lgb
from benchmarks.lib import reference
from lightgbm_tpu import obs
from lightgbm_tpu.ops import histogram as H

LANES, BLOCK, NB, B = 128, 512, 3, 63     # four sub-blocks a block
SLOTS = {1: [3], 4: [7, -1, 0, 12], 16: list(range(1, 32, 2)),
         25: [-1 if k % 6 == 5 else 2 * k + 1 for k in range(25)]}


@pytest.fixture
def small_lanes(monkeypatch):
    """Sub-blocks of 128 lanes and feature chunks of 32 columns, so that a
    512-row block has four sub-blocks and 96 columns three chunks."""
    monkeypatch.setattr(H, "_PERFEATURE_GROUP_LANES", LANES)
    monkeypatch.setattr(H, "_PERFEATURE_OUT_BUDGET", 32 * 64 * 128 * 4)


@functools.lru_cache(maxsize=None)
def builder(impl, precision, num_bins, live_columns=None, packed=False):
    return jax.jit(lambda bins, stats, leaf, slots:
                   H.build_histogram_batched_t(
                       bins, stats, leaf, slots, num_bins, precision,
                       impl=impl, packed_rows=packed,
                       live_columns=live_columns if impl == "pallas2"
                       else None, with_rows=True))


def table(rng, nb, F, block, precision, num_bins=B):
    n = nb * block
    bins = rng.integers(0, num_bins, size=(nb, F, block)).astype(np.uint8)
    g = jnp.asarray(rng.normal(size=n).astype(np.float32))
    h = jnp.abs(g) + 0.3
    if precision == "int8":
        g = H.quantize_values(g, jnp.max(jnp.abs(g)) / 127, 127, "nearest")
        h = H.quantize_values(h, jnp.max(h) / 127, 127, "nearest")
    stats = H.pack_stats(g, h, jnp.ones(n, jnp.float32), precision)
    return bins, stats.reshape(stats.shape[0], nb, block)


def leaves(rng, live, slots):
    """Leaf ids `live.shape`: one of the live slots' where `live`, a leaf
    no slot has elsewhere (never negative: the grower's are not)."""
    ids = np.array([s for s in slots if s >= 0])
    return np.where(live, rng.choice(ids, size=live.shape),
                    100 + rng.integers(0, 9, size=live.shape)).astype(np.int32)


def both(bins, stats, leaf, slots, precision, num_bins=B, live_columns=None,
         packed=False, xla_bins=None):
    args = (jnp.asarray(stats), jnp.asarray(leaf),
            jnp.asarray(slots, dtype=jnp.int32))
    a, _ = builder("xla", precision, num_bins)(
        jnp.asarray(bins if xla_bins is None else xla_bins), *args)
    b, rows = builder("pallas2", precision, num_bins, live_columns, packed)(
        jnp.asarray(bins), *args)
    return np.asarray(a), np.asarray(b), np.asarray(rows).astype(np.int64)


def assert_same(a, b, precision, live_columns=None):
    if live_columns is not None:
        assert not b[:, live_columns:].any()
        a, b = a[:, :live_columns], b[:, :live_columns]
    if precision == "int8":
        np.testing.assert_array_equal(a, b)
    else:
        # a block's sub-blocks are summed apart: a few ulp of a bin's sum
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)


def want_rows(leaf, slots, lanes):
    """[calls, sub-blocks contracted, live rows] of a call, by the carried
    rule: every sub-block of a block that packing would not shorten, and
    the packed blocks' live rows in whole sub-blocks, the last one
    part-filled (a feature chunk's; every chunk sees the same rows)."""
    live = np.isin(leaf, [s for s in slots if s >= 0]).sum(axis=1)
    packs = (live > 0) & (live <= BLOCK - lanes)
    unpacked = (live > BLOCK - lanes).sum()
    return [1, int(BLOCK // lanes * unpacked + -(-live[packs].sum() // lanes)),
            int(live.sum())]


SHARES = {"none": 0.0, "one-row": None, "3%": 0.03, "21%": 0.21,
          "50%": 0.5, "all": 1.0}


@pytest.mark.parametrize("precision", ["hilo", "int8"])
@pytest.mark.parametrize("chunks", [1, 3])
@pytest.mark.parametrize("share", list(SHARES))
@pytest.mark.parametrize("K", list(SLOTS))
def test_packed_kernel_equals_xla(small_lanes, K, share, chunks, precision):
    rng = np.random.default_rng(36 + K)
    F, live_columns = (32, 28) if chunks == 1 else (96, 67)
    assert H.perfeature_chunks(F, B, K, 5 if precision == "hilo" else 3,
                               1) == (32, chunks)
    bins, stats = table(rng, NB, F, BLOCK, precision)
    if share == "one-row":
        live = np.zeros((NB, BLOCK), bool)
        live[1, 317] = True
    else:
        live = rng.random((NB, BLOCK)) < SHARES[share]
    leaf = leaves(rng, live, SLOTS[K])
    a, b, rows = both(bins, stats, leaf, SLOTS[K], precision,
                      live_columns=live_columns)
    assert_same(a, b, precision, live_columns)
    assert list(rows) == want_rows(leaf, SLOTS[K], LANES)
    assert rows[2] == live.sum()


def test_a_block_with_no_live_row_does_no_dot(small_lanes):
    """The middle block holds no row of the slots: the kernel reports no
    sub-block of it contracted, the others are as xla has them; a call
    whose only block is dead returns zeros."""
    rng = np.random.default_rng(1)
    bins, stats = table(rng, NB, 32, BLOCK, "hilo")
    live = rng.random((NB, BLOCK)) < 0.3
    live[1] = False
    leaf = leaves(rng, live, SLOTS[4])
    a, b, rows = both(bins, stats, leaf, SLOTS[4], "hilo", live_columns=28)
    assert_same(a, b, "hilo", 28)
    assert list(rows) == want_rows(leaf, SLOTS[4], LANES)
    # only the dead block: no sub-block at all
    a, b, rows = both(bins[1:2], stats[:, 1:2], leaf[1:2], SLOTS[4], "hilo",
                      live_columns=28)
    assert not a[[0, 2, 3]].any() and not b.any() and list(rows) == [1, 0, 0]


@pytest.mark.parametrize("count, sub_blocks", [(1024, 1), (1025, 2),
                                               (2047, 2), (2048, 2)])
def test_a_sub_blocks_last_lane_and_the_next(count, sub_blocks):
    """At the kernel's own 1024 lanes: 1024 live rows fill one sub-block to
    its last lane, the 1025th opens the next."""
    rng = np.random.default_rng(count)
    assert H.perfeature_dot_lanes(2048) == 1024
    bins, stats = table(rng, 1, 32, 2048, "int8")
    live = np.zeros((1, 2048), bool)
    live[0, rng.permutation(2048)[:count]] = True
    leaf = leaves(rng, live, SLOTS[4])
    a, b, rows = both(bins, stats, leaf, SLOTS[4], "int8", live_columns=28)
    assert_same(a, b, "int8", 28)
    assert list(rows) == [1, sub_blocks, count]


def test_a_dead_slot_collects_nothing_beside_the_packed_tail(small_lanes):
    """The lanes past a block's live rows carry a leaf id no slot has; a
    dead slot's id is -1, and its histogram stays empty."""
    rng = np.random.default_rng(2)
    bins, stats = table(rng, NB, 32, BLOCK, "int8")
    slots = [5, -1, -1, 9]
    leaf = leaves(rng, rng.random((NB, BLOCK)) < 0.4, slots)
    a, b, rows = both(bins, stats, leaf, slots, "int8", live_columns=28)
    assert_same(a, b, "int8", 28)
    assert not b[1].any() and not b[2].any() and b[0].any() and b[3].any()


def test_a_padded_last_block(small_lanes):
    """The grower pads the table to whole blocks with rows of leaf 0 and
    zero stats: live where leaf 0 is a slot, and they add nothing."""
    rng = np.random.default_rng(3)
    bins, stats = table(rng, NB, 32, BLOCK, "hilo")
    pad = slice(BLOCK - 200, BLOCK)
    bins[-1, :, pad] = 0
    stats = stats.at[:, -1, pad].set(0)
    slots = [0, 4, 6, -1]
    leaf = leaves(rng, rng.random((NB, BLOCK)) < 0.2, slots)
    leaf[-1, pad] = 0
    a, b, rows = both(bins, stats, leaf, slots, "hilo", live_columns=28)
    assert_same(a, b, "hilo", 28)
    assert list(rows) == want_rows(leaf, slots, LANES)
    assert b[0, :28, 0, 2].sum() == a[0, :28, 0, 2].sum()


def test_rows_the_kernel_reports_are_numpys(small_lanes):
    """Blocks of very different live shares: the sub-blocks contracted are
    every sub-block of the blocks packing would not shorten and the
    ceiling of the packed blocks' summed live rows, not a ceiling a
    block."""
    rng = np.random.default_rng(4)
    nb = 6
    bins, stats = table(rng, nb, 32, BLOCK, "int8")
    share = np.array([0.0, 0.01, 0.24, 0.26, 0.9, 1.0])[:, None]
    leaf = leaves(rng, rng.random((nb, BLOCK)) < share, SLOTS[16])
    _, _, rows = both(bins, stats, leaf, SLOTS[16], "int8", live_columns=28)
    per_block = np.isin(leaf, SLOTS[16]).sum(axis=1)
    assert per_block[0] == 0 and per_block[-1] == BLOCK
    packs = (per_block > 0) & (per_block <= BLOCK - LANES)
    assert list(rows) == [1, int(BLOCK // LANES * (per_block > BLOCK - LANES).sum()
                                 + np.ceil(per_block[packs].sum() / LANES)),
                          int(per_block.sum())]
    assert rows[1] < int(np.ceil(per_block / LANES).sum())


# live rows a block (of 512 at 128 lanes; 384 and under are packed), the
# sub-blocks the carried rule contracts, and what a ceiling a block would
CARRIES = {
    "crosses-a-block": ([80, 80, 80], 2, 3),
    "dead-block-between": ([50, 0, 50], 1, 2),
    "unpacked-block-with-carry-pending": ([40, 400, 40], 5, 6),
    "flush-at-a-packed-last-block": ([0, 0, 30], 1, 1),
    "flush-at-a-dead-last-block": ([30, 0, 0], 1, 1),
    "flush-after-an-unpacked-last-block": ([30, 0, 500], 5, 5),
    "fills-the-sub-block-exactly": ([100, 28, 0], 1, 2),
    "fills-it-behind-a-full-one": ([100, 156, 0], 2, 3),
    "one-row-over": ([100, 29, 0], 2, 2),
}


@pytest.mark.parametrize("precision", ["hilo", "int8"])
@pytest.mark.parametrize("case", list(CARRIES))
def test_a_part_filled_sub_block_is_carried(small_lanes, case, precision):
    """A packed block's part-filled sub-block waits for the next packed
    block's rows and is contracted once full, or at the last block: the
    histograms are xla's and the count is numpy's."""
    counts, carried, per_block = CARRIES[case]
    rng = np.random.default_rng(sum(counts) + len(case))
    bins, stats = table(rng, NB, 32, BLOCK, precision)
    live = np.zeros((NB, BLOCK), bool)
    for blk, count in enumerate(counts):
        live[blk, rng.permutation(BLOCK)[:count]] = True
    leaf = leaves(rng, live, SLOTS[4])
    a, b, rows = both(bins, stats, leaf, SLOTS[4], precision,
                      live_columns=28)
    assert_same(a, b, precision, 28)
    assert list(rows) == want_rows(leaf, SLOTS[4], LANES)
    assert list(rows) == [1, carried, sum(counts)]
    assert int(sum(-(-c // LANES) for c in counts)) == per_block


@pytest.mark.parametrize("counts", [[80, 80, 80], [100, 300, 0]])
def test_each_feature_chunk_starts_with_no_carry(small_lanes, counts):
    """On the three-chunk grid each chunk sweeps the same rows over its
    own columns; a chunk's last block flushes the carry, and the next
    chunk's first block finds none, its rows in its own columns."""
    rng = np.random.default_rng(sum(counts))
    assert H.perfeature_chunks(96, B, 4, 5, 1) == (32, 3)
    bins, stats = table(rng, NB, 96, BLOCK, "hilo")
    live = np.zeros((NB, BLOCK), bool)
    for blk, count in enumerate(counts):
        live[blk, rng.permutation(BLOCK)[:count]] = True
    leaf = leaves(rng, live, SLOTS[4])
    a, b, rows = both(bins, stats, leaf, SLOTS[4], "hilo", live_columns=67)
    assert_same(a, b, "hilo", 67)
    assert list(rows) == want_rows(leaf, SLOTS[4], LANES)
    assert rows[1] == -(-sum(counts) // LANES)


def test_the_root_call_runs_the_unpacked_sweep(small_lanes, monkeypatch):
    """Every block of the root call is all-live (leaf ids all zero, one
    slot, 0): no block is packed.  With `pltpu.roll` poisoned a call that
    packs goes wrong and the root call does not."""
    from jax.experimental.pallas import tpu as pltpu
    rng = np.random.default_rng(5)
    bins, stats = table(rng, NB, 32, BLOCK, "int8")
    root = np.zeros((NB, BLOCK), np.int32)
    part = leaves(rng, rng.random((NB, BLOCK)) < 0.5, [0])
    monkeypatch.setattr(pltpu, "roll", lambda x, shift, axis: x * 0)
    builder.cache_clear()
    try:
        a, b, rows = both(bins, stats, root, [0], "int8", live_columns=28)
        np.testing.assert_array_equal(a[:, :28], b[:, :28])
        assert list(rows) == [1, NB * BLOCK // LANES, NB * BLOCK]
        a, b, _ = both(bins, stats, part, [0], "int8", live_columns=28)
        assert (a[:, :28] != b[:, :28]).any()
    finally:
        builder.cache_clear()


@pytest.mark.parametrize("arm", ["ungrouped", "4-bit"])
def test_the_arms_that_do_not_pack_sweep_every_row(small_lanes, arm):
    """`G == 1` (here int8 at 15 bins: Bp is half a sublane tile) and the
    4-bit stride layout keep the unpacked sweep: equal to xla, every
    sub-block contracted."""
    rng = np.random.default_rng(6)
    precision, packed = ("int8", False) if arm == "ungrouped" \
        else ("hilo", True)
    assert (H.perfeature_columns_per_dot(15, BLOCK, precision, 32, 28)
            == 1) == (arm == "ungrouped")
    bins, stats = table(rng, NB, 32, BLOCK, precision, num_bins=15)
    leaf = leaves(rng, rng.random((NB, BLOCK)) < 0.2, SLOTS[4])
    stored = bins
    if packed:  # row j in the low nibble, row j + block/2 in the high
        stored = bins[..., :BLOCK // 2] | (bins[..., BLOCK // 2:] << 4)
    a, b, rows = both(stored, stats, leaf, SLOTS[4], precision, num_bins=15,
                      live_columns=28, packed=packed, xla_bins=bins)
    assert_same(a, b, precision, 28)
    live = int(np.isin(leaf, [7, 0, 12]).sum())
    assert list(rows) == [1, NB * BLOCK // LANES, live]


# ---- whole trees ---------------------------------------------------------------
def _table(n, seed=36):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, 10))
    y = (X[:, 0] + 0.5 * X[:, 1] * X[:, 2] + np.sin(3 * X[:, 3])
         + 0.3 * rng.normal(size=n) > 0).astype(np.float64)
    return X, y


def _params(impl, **more):
    return {"objective": "binary", "num_leaves": 255, "max_bin": 63,
            "min_data_in_leaf": 2, "verbosity": -1, "tpu_block_rows": 2048,
            "tpu_quant_refit_leaves": False, "tpu_hist_precision": "int8",
            "tpu_hist_impl": impl, **more}


@pytest.mark.parametrize("layout", [{}, {"tree_learner": "data",
                                         "num_machines": 8}],
                         ids=["serial", "data-8"])
def test_255_leaf_model_text_equals_xlas(layout):
    """Blocks of 2048 rows, two sub-blocks of the kernel's own 1024 lanes:
    int32 accumulation is exact in any grouping, so the model text is
    xla's to the byte, 255 leaves a tree."""
    X, y = _table(16384)
    texts = []
    for impl in ("xla", "pallas2"):
        p = _params(impl, **layout)
        bst = lgb.train(p, lgb.Dataset(X, label=y, params={"max_bin": 63}),
                        num_boost_round=2)
        texts.append(bst.model_to_string().split("\nparameters:")[0])
    assert texts[0].count("num_leaves=255") == 2
    assert texts[0] == texts[1]


@pytest.mark.parametrize("impl", ["pallas2", "xla"])
def test_gauges_are_a_recount_of_the_tree(impl):
    """`lgbm_hist_rows_per_tree{kind=}` against the model text: live is
    n + sum over splits of min(left, right), what
    `benchmarks/lib/reference.histogrammed_rows` gives; swept is calls x
    padded rows; contracted lies between (all of swept under xla)."""
    n = 8192                       # whole blocks: no padding row is live
    X, y = _table(n, seed=7)
    p = _params(impl, num_leaves=31)
    bst = lgb.Booster(params=p, train_set=lgb.Dataset(X, label=y, params=p))
    bst.update()
    trees = reference.parse_model(bst.model_to_string())
    live, histograms = reference.histogrammed_rows(trees[0])
    snap = obs.REGISTRY.snapshot()
    got = {k: snap[f'lgbm_hist_rows_per_tree{{kind="{k}"}}']
           for k in ("swept", "contracted", "live")}
    learner = bst._driver.learner
    assert learner.n_pad == n and histograms == 31
    assert got["live"] == live
    assert got["swept"] % n == 0 and 5 <= got["swept"] // n <= 31
    assert got["live"] <= got["contracted"] <= got["swept"]
    assert got["contracted"] % 1024 == 0
    assert (got["contracted"] == got["swept"]) == (impl == "xla")
    # a second tree: the gauges are means over the trees grown
    bst.update()
    trees = reference.parse_model(bst.model_to_string())
    both_live = sum(reference.histogrammed_rows(t)[0] for t in trees)
    snap = obs.REGISTRY.snapshot()
    assert snap['lgbm_hist_rows_per_tree{kind="live"}'] == both_live / 2


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


@pytest.mark.parametrize("nb, F, live, num_bins, K", [
    (3328, 32, 28, 255, 25),    # higgs-27m-255: the round loop
    (3328, 32, 28, 63, 1),      # higgs-27m-63: the root and first pre-round
    (1664, 96, 67, 255, 25),    # criteo-13m-67, a chip of criteo-27m-67
    (832, 160, 137, 63, 16),    # mslr-7m-63: the last pre-round
    (3328, 32, 28, 63, 4),
])
def test_mosaic_takes_the_packing_kernel(one_chip, monkeypatch, nb, F, live,
                                         num_bins, K):
    """The five cells' kernel shapes compile for the chip with the pack in
    them, one custom call whose second output is the blocks' live rows."""
    monkeypatch.setattr(H, "pallas_interpret", lambda: False)

    def spec(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    compiled = jax.jit(lambda b, s, l, k: H.build_histogram_batched_t(
        b, s, l, k, num_bins, "hilo", impl="pallas2", live_columns=live,
        with_rows=True)).lower(
        spec((nb, F, 8192), jnp.uint8), spec((5, nb, 8192), jnp.bfloat16),
        spec((nb, 8192), jnp.int32), spec((K,), jnp.int32)).compile()
    text = compiled.as_text()
    assert text.count("custom-call(") >= 1 and f"s32[{nb}]" in text
