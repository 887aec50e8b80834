"""The parts of the yardstick that need no trace: operation counts, peaks,
timing arithmetic, the generator, the plain reference, the forest tiling."""

import time

import numpy as np
import pytest

from benchmarks.lib import forest, opcount, peaks, reference, timing
from benchmarks.lib.harness import BENCH_DIR, load_module


# ---- operations and bytes -------------------------------------------------------
@pytest.mark.parametrize("hist_rows, features, bins, histograms, bin_bytes, "
                         "want_ops, want_bytes", [
    # 155 histogrammed rows of 28 one-byte bins: three additions a row and
    # column; a row's 28 bins and its float32 gradient and hessian read
    # once; 4 histograms of 28 x 255 bins in three float32 planes written
    (155, 28, 255, 4, 1, 3 * 155 * 28, 155 * 36 + 4 * 28 * 255 * 12),
    # Higgs at 255 bins as ISSUE 35 reckons it: 3.9 n rows, 255 histograms
    (3.9 * 27262976, 28, 255, 255, 1, 3 * 3.9 * 27262976 * 28,
     3.9 * 27262976 * 36 + 255 * 28 * 255 * 12),
    (1000, 67, 63, 2, 2, 201000, 1000 * (134 + 8) + 2 * 67 * 63 * 12),
    (0, 28, 255, 1, 1, 0, 28 * 255 * 12),     # a stump no row was counted for
])
def test_tree_histogram_work_against_hand_counts(
        hist_rows, features, bins, histograms, bin_bytes, want_ops,
        want_bytes):
    assert opcount.tree_histogram_work(
        hist_rows, features, bins, histograms, bin_bytes) == (
            pytest.approx(want_ops, rel=1e-15),
            pytest.approx(want_bytes, rel=1e-15))


def test_the_issues_reckoning_of_higgs_at_255_bins():
    """3.85e9 bytes, 4.70 ms of HBM, memory-bound: what `PERF.md` predicts
    the two shares from."""
    ops, byts = opcount.tree_histogram_work(3.9 * 27262976, 28, 255, 255)
    p = peaks.peaks_for("TPU v5 lite")
    assert byts == pytest.approx(3.85e9, rel=2e-3)
    share, bound = opcount.roofline(ops, byts, 4.3098, p["bf16_flops"],
                                    p["hbm_bytes_per_s"])
    assert bound == "memory" and share == pytest.approx(0.109, rel=5e-3)


WINDOW_FACTS = {"hist_rows_by_tree": [400, 300, 200, 100],
                "histograms_by_tree": [4, 3, 2, 1], "first_window_tree": 1,
                "iterations": 2, "features": 5, "bins": 7}


@pytest.mark.parametrize("over, want", [
    ({}, opcount.tree_histogram_work(500, 5, 7, 5)),       # trees 1 and 2
    ({"data_shards": 4.0}, opcount.tree_histogram_work(125, 5, 7, 5)),
    ({"data_shards": None}, opcount.tree_histogram_work(500, 5, 7, 5)),
    # fewer trees than iterations (an iteration failed): those that are there
    ({"iterations": 9}, opcount.tree_histogram_work(600, 5, 7, 6)),
    ({"first_window_tree": 4}, None),
    ({"hist_rows_by_tree": []}, None),
    ({"hist_rows_by_tree": None}, None),
])
def test_the_windows_work_is_its_own_trees_a_shards_share_of_the_rows(
        over, want):
    assert opcount.window_histogram_work({**WINDOW_FACTS, **over}) == want


def test_forest_walk_against_hand_counts():
    ops, byts = opcount.forest_walk(rows=10, trees=3, depth=4, features=28)
    assert ops == 10 * 3 * 4 * 4
    assert byts == 10 * 3 * 4 * 24 + 10 * 28 * 4 + 10 * 4


@pytest.mark.parametrize("ops, byts, seconds, want_share, want_bound", [
    (197e12, 1.0, 2.0, 50.0, "compute"),     # one second of MXU in two
    (1.0, 819e9, 4.0, 25.0, "memory"),       # one second of HBM in four
])
def test_roofline(ops, byts, seconds, want_share, want_bound):
    p = peaks.peaks_for("TPU v5 lite")
    share, bound = opcount.roofline(ops, byts, seconds, p["bf16_flops"],
                                    p["hbm_bytes_per_s"])
    assert share == pytest.approx(want_share) and bound == want_bound


def test_an_unknown_device_kind_is_an_error():
    assert peaks.peaks_for("TPU v5 lite")["int8_ops"] == 393e12
    with pytest.raises(KeyError, match="TPU v5p"):
        peaks.peaks_for("TPU v5p")


# ---- the wait for the device ------------------------------------------------------------
def test_sync_skips_a_donated_array_and_raises_what_else_fails(monkeypatch):
    import jax
    import jax.numpy as jnp

    from benchmarks.lib import device

    gone = jnp.ones(3)
    gone.delete()
    monkeypatch.setattr(jax, "live_arrays", lambda: [gone, jnp.ones(3)])
    device.sync()

    class Broken:
        def is_deleted(self):
            return False

        def block_until_ready(self):
            raise RuntimeError("RESOURCE_EXHAUSTED: out of memory")

    monkeypatch.setattr(jax, "live_arrays", lambda: [Broken()])
    with pytest.raises(RuntimeError, match="RESOURCE_EXHAUSTED"):
        device.sync()


# ---- timing ---------------------------------------------------------------------------
def test_window_runs_whole_steps_past_the_deadline():
    calls = []
    walls, elapsed = timing.run_window(lambda: calls.append(1), 0.05)
    assert len(walls) == len(calls) >= 1
    assert 0.05 <= elapsed < 0.5 and sum(walls) <= elapsed


def test_a_step_can_end_its_window():
    calls = []

    def step():
        calls.append(1)
        return timing.STOP if len(calls) == 3 else None

    walls, elapsed = timing.run_window(step, 60.0)
    assert len(walls) == len(calls) == 3 and elapsed < 1.0


class FakeCell:
    """What `timing.iteration_window` asks of a cell, untraced."""
    trace, out_dir = False, None

    def __init__(self, seconds, **traffic):
        from benchmarks.lib.spans import Spans

        self.seconds, self.spans, self.said = seconds, Spans(), []
        self.traffic = {"warmup_iters": 1, "group_iters": 1,
                        "trace_iters": 3, **traffic}

    def say(self, what, **fields):
        self.said.append((what, fields))


def raises_at(n, calls):
    def update():
        calls.append(1)
        if len(calls) == n:
            raise RuntimeError("the device is gone")
    return update


@pytest.mark.parametrize("how, seconds, traffic, update, want", [
    # the clock ends the window, after a whole group
    ("clock", 0.05, {}, lambda calls: lambda: calls.append(1), None),
    ("clock, groups of 2", 0.05, {"group_iters": 2},
     lambda calls: lambda: calls.append(1), None),
    # an iteration that splits no leaf trained nothing: failed, not the end
    ("nothing trained", 0.05, {}, lambda calls: lambda: calls.append(1) or 1,
     "all failed"),
    # a step that raises is a failed iteration and ends the window (STOP)
    ("raises at 3", 60.0, {}, lambda calls: raises_at(3, calls), (3, 1)),
    ("raises at 3, groups of 2", 60.0, {"group_iters": 2},
     lambda calls: raises_at(3, calls), (3, 1)),
])
def test_iteration_window_on_a_fake_step(how, seconds, traffic, update, want,
                                         capsys):
    cell, calls = FakeCell(seconds, **traffic), []
    t0 = time.perf_counter()
    win = timing.iteration_window(cell, update(calls))
    took = time.perf_counter() - t0
    n = cell.traffic["group_iters"]
    assert win.iterations == len(calls) and win.group_iters == n
    assert t0 <= win.start and win.window_s <= took < 5.0
    assert sum(win.walls) <= win.window_s
    if isinstance(want, tuple):
        assert (win.iterations, win.failed) == want
        assert len(win.walls) == -(-want[0] // n)
        assert ("an iteration raised",
                {"error": "RuntimeError('the device is gone')"}) in cell.said
        assert "the device is gone" in capsys.readouterr().err
    else:
        assert win.window_s >= seconds and win.iterations == n * len(win.walls)
        assert win.failed == (win.iterations if want else 0)
    # the spans the per-layer readers look for, one a call
    assert len(cell.spans.walls("bench/update")) == win.iterations
    assert len(cell.spans.walls("bench/sync")) == len(win.walls) - (
        1 if isinstance(want, tuple) else 0)
    # the window as the result's line carries it, and as the note says it
    facts = win.facts()
    assert set(facts) == {"iterations", "window_s", "iteration_ms",
                          "slowest_group"}
    assert (facts["iterations"], facts["window_s"]) == (win.iterations,
                                                        win.window_s)
    if not isinstance(want, tuple):
        slowest = facts["slowest_group"]
        assert 1e-3 * (slowest["update_ms"] + slowest["sync_ms"]) <= (
            win.walls[slowest["index"]])
    ms = facts["iteration_ms"]
    assert set(ms) == {"n", "median", "q1", "q3", "min", "max"}
    assert ms["n"] == len(win.walls)
    assert ms["min"] <= ms["q1"] <= ms["median"] <= ms["q3"] <= ms["max"]
    assert ms["max"] == pytest.approx(1e3 * max(win.walls) / n)
    what, said = cell.said[-1]
    assert what == "groups" and said["by_group"] == [n / w for w in win.walls]
    assert (said["first_iteration_index"], said["last_iteration_index"]) == (
        1, 1 + win.iterations)
    assert {k: said[k] for k in facts} == facts


@pytest.mark.parametrize("where", ["update_ms", "sync_ms"])
def test_a_stalled_group_is_named_with_where_the_host_spent_it(where,
                                                               monkeypatch):
    """The third group of four takes 40 ms more, in `update()` or in the
    wait for the device: the window's facts say which group and where."""
    cell, calls, waits = FakeCell(60.0, group_iters=2), [], []

    def update():
        calls.append(1)
        if where == "update_ms" and len(calls) == 5:
            time.sleep(0.04)

    def sync():
        waits.append(1)
        if where == "sync_ms" and len(waits) == 3:
            time.sleep(0.04)

    monkeypatch.setattr(timing.device, "sync", sync)
    monkeypatch.setattr(timing, "run_window", lambda step, seconds: (
        [timed(step) for _ in range(4)], 1.0))
    slowest = timing.iteration_window(cell, update).facts()["slowest_group"]
    other = "sync_ms" if where == "update_ms" else "update_ms"
    assert slowest["index"] == 2 and slowest[where] >= 40.0 > slowest[other]


def timed(step):
    t0 = time.perf_counter()
    step()
    return time.perf_counter() - t0


def test_a_jobs_window_hands_back_its_start_its_readings_and_their_sum():
    cell, calls = FakeCell(0.03), []
    start, walls, elapsed = timing.window(cell, lambda: calls.append(1),
                                          traced=None)
    assert len(walls) == len(calls) >= 1 and sum(walls) <= elapsed
    assert start <= time.perf_counter() - elapsed


def test_summary_quartiles():
    s = timing.summary([1.0, 2.0, 3.0, 4.0, 5.0])
    assert (s["n"], s["median"], s["q1"], s["q3"]) == (5, 3.0, 2.0, 4.0)
    assert timing.summary([]) == {"n": 0}


# ---- the generator ----------------------------------------------------------------------
@pytest.fixture(scope="module")
def higgs_like():
    return load_module(BENCH_DIR, "datagen", "higgs_like")


def test_rows_are_a_function_of_seed_stream_and_position(higgs_like):
    spec = {"features": 28}
    a = higgs_like.make(spec, 5, 3000, stream=0)
    b = higgs_like.make(spec, 5, 3000, stream=0)
    assert np.array_equal(a["X"], b["X"]) and np.array_equal(a["y"], b["y"])
    assert a["X"].shape == (3000, 28) and a["X"].dtype == np.float64
    assert set(np.unique(a["y"])) == {0.0, 1.0}
    other_seed = higgs_like.make(spec, 6, 3000, stream=0)
    other_stream = higgs_like.make(spec, 5, 3000, stream=1)
    assert not np.array_equal(a["X"], other_seed["X"])
    assert not np.array_equal(a["X"], other_stream["X"])
    # a longer table of the same seed starts with the same rows
    longer = higgs_like.make(spec, 5, 5000, stream=0)
    assert np.array_equal(longer["X"][:3000], a["X"])


def test_rows_do_not_depend_on_the_thread_count(higgs_like, monkeypatch):
    spec = {"features": 6}
    monkeypatch.setattr(higgs_like, "SLAB_ROWS", 1000)
    many = higgs_like.make(spec, 9, 4500, stream=0)
    monkeypatch.setattr(higgs_like, "THREADS", 1)
    one = higgs_like.make(spec, 9, 4500, stream=0)
    assert np.array_equal(many["X"], one["X"])
    assert np.array_equal(many["y"], one["y"])


# ---- a table a seed --------------------------------------------------------------
SEEDED = {
    "higgs_like": {"features": 28},
    "criteo_like": {"features": 67},
    "mslr_like": {"features": 137, "rows": 6810888, "queries": 56757,
                  "max_query_len": 1251},
}


@pytest.mark.parametrize("generator", sorted(SEEDED))
def test_a_seed_draws_its_own_table_and_draws_it_again(generator):
    """`--seed` is the table: `correct` and a claimed gain are read on
    tables nobody tuned to.  Seeds as large as the driver's."""
    gen, spec = load_module(BENCH_DIR, "datagen", generator), SEEDED[generator]
    a = gen.make(spec, 3800000101, 3000, stream=0)
    again = gen.make(spec, 3800000101, 3000, stream=0)
    other = gen.make(spec, 2 ** 31 + 7, 3000, stream=0)
    assert all(np.array_equal(a[k], again[k], equal_nan=True) for k in a)
    assert not np.array_equal(a["X"], other["X"], equal_nan=True)
    assert not np.array_equal(a["y"], other["y"])


def test_labels_follow_the_rule_of_the_seed(higgs_like):
    d = higgs_like.make({"features": 28}, 3, 200_000, stream=0)
    X, y = d["X"], d["y"]
    assert 0.45 < y.mean() < 0.55
    # the squares of the first eight features carry signal, the later do not
    first = np.corrcoef((X[:, :8] ** 2).sum(axis=1), y)[0, 1]
    later = np.corrcoef((X[:, 8:16] ** 2).sum(axis=1), y)[0, 1]
    assert first > 0.2 and abs(later) < 0.02


# ---- the plain reference ------------------------------------------------------------------
def hand_tree():
    """Three leaves: x0 <= 0.5 -> leaf 0; else x1 <= -1 -> leaf 1, else 2.
    Node 1 sends missing (NaN) left; node 0 has no missing rule."""
    return {"num_leaves": 3, "num_cat": 0,
            "split_feature": np.array([0, 1]),
            "threshold": np.array([0.5, -1.0]),
            "decision_type": np.array([0, 2 | (2 << 2)]),
            "left_child": np.array([-1, -2]),
            "right_child": np.array([1, -3]),
            "leaf_value": np.array([0.1, 0.2, 0.4]),
            "leaf_count": np.array([2, 2, 1])}


HAND_ROWS = np.array([[0.5, 9.0],        # x0 <= 0.5            -> leaf 0
                      [np.nan, 9.0],     # NaN counts as 0 here -> leaf 0
                      [0.6, -1.0],       # x1 <= -1             -> leaf 1
                      [0.6, np.nan],     # NaN goes left here   -> leaf 1
                      [0.6, -0.9]])      #                      -> leaf 2


def test_walker_follows_the_published_decision_rule():
    tree = hand_tree()
    assert reference.leaf_index(tree, HAND_ROWS).tolist() == [0, 0, 1, 1, 2]
    assert reference.leaf_index_threaded(tree, HAND_ROWS, threads=2).tolist() \
        == [0, 0, 1, 1, 2]
    got = reference.walk([tree, tree], HAND_ROWS)
    assert got == pytest.approx([0.2, 0.2, 0.4, 0.4, 0.8])


def test_zero_as_missing_takes_the_default_side():
    tree = hand_tree()
    tree["decision_type"] = np.array([1 << 2, 0])   # zero is missing, go right
    rows = np.array([[0.0, 0.0], [1e-36, 0.0], [-0.5, 0.0]])
    assert reference.leaf_index(tree, rows).tolist() == [2, 2, 0]


def test_a_stump_of_one_leaf():
    stump = {"num_leaves": 1, "leaf_value": np.array([0.7])}
    assert reference.walk([stump], np.zeros((3, 2))) == pytest.approx([0.7] * 3)


def four_leaves():
    """Split 0 parts 100 rows 40 / 60; split 1 parts the 40 into leaves of
    10 and 30, split 2 the 60 into leaves of 5 and 55."""
    return {"num_leaves": 4, "left_child": np.array([1, -1, -2]),
            "right_child": np.array([2, -3, -4]),
            "leaf_count": np.array([10, 5, 30, 55])}


def test_histogrammed_rows_of_a_hand_built_tree():
    tree = four_leaves()
    assert reference.child_counts(tree).tolist() == [[40, 10, 5], [60, 30, 55]]
    # the root's 100, then the smaller child of each split: 40, 10, 5
    assert reference.histogrammed_rows(tree) == (155, 4)
    # under the most a tree of two levels can: 100 * (1 + 2 / 2)
    assert 155 <= 100 * (1 + reference.depth(tree) / 2)
    # the hand tree of the walker's tests: 5 rows, then min(2, 3), min(2, 1)
    assert reference.histogrammed_rows(hand_tree()) == (5 + 2 + 1, 3)
    # a chain, as min_data_in_leaf=1 grows them: every split takes one row off
    chain = {"num_leaves": 4, "left_child": np.array([-1, -2, -3]),
             "right_child": np.array([1, 2, -4]),
             "leaf_count": np.array([1, 1, 1, 97])}
    assert reference.histogrammed_rows(chain) == (100 + 1 + 1 + 1, 4)


@pytest.mark.parametrize("stump, want", [
    ({"num_leaves": 1, "leaf_value": np.array([0.7])}, (0, 1)),
    ({"num_leaves": 1, "leaf_count": np.array([9])}, (9, 1)),
])
def test_histogrammed_rows_of_a_stump(stump, want):
    assert reference.histogrammed_rows(stump) == want


def test_a_tree_numbered_child_first_is_refused():
    tree = four_leaves()
    tree["left_child"] = np.array([1, -1, -2])
    tree["right_child"] = np.array([2, -3, 1])      # split 2's child is split 1
    with pytest.raises(reference.ModelTextError, match="before the split"):
        reference.histogrammed_rows(tree)


def test_the_facts_a_training_job_states_of_its_trees():
    trees = reference.parse_model(MODEL_TEXT)
    assert reference.window_histogram_facts(trees, 1) == {
        "hist_rows_by_tree": [5 + 2 + 1, 5 + 2], "histograms_by_tree": [3, 2],
        "first_window_tree": 1}
    assert reference.window_histogram_facts([], 1)["hist_rows_by_tree"] == []


def test_first_tree_recount():
    # 10 rows, 4 positive: p = 0.4.  Leaf 0 holds 6 rows with 1 positive,
    # leaf 1 holds 4 rows with 3.  value = logit(p) - lr*(n*p - pos)/(n*p*(1-p))
    y = np.array([1, 0, 0, 0, 0, 0, 1, 1, 1, 0], float)
    leaf = np.array([0] * 6 + [1] * 4)
    logit = np.log(0.4 / 0.6)
    values = np.array([logit - 0.1 * (2.4 - 1) / (6 * 0.24),
                       logit - 0.1 * (1.6 - 3) / (4 * 0.24)])
    tree = {"num_leaves": 2, "leaf_value": values,
            "leaf_count": np.array([6, 4])}
    off, err, _ = reference.recount_first_tree(tree, leaf, y, 0.1)
    assert off == 0 and err < 1e-12
    tree["leaf_value"] = values + np.array([0.0, 0.01])
    off, err, worst = reference.recount_first_tree(tree, leaf, y, 0.1)
    assert off == 0 and err == pytest.approx(0.01) and worst == 1
    tree["leaf_count"] = np.array([4, 6])
    assert reference.recount_first_tree(tree, leaf, y, 0.1)[0] == 2


@pytest.mark.parametrize("score, y, want", [
    ([0.1, 0.4, 0.35, 0.8], [0, 0, 1, 1], 0.75),    # one of four pairs wrong
    ([1, 2, 3, 4], [0, 0, 1, 1], 1.0),
    ([4, 3, 2, 1], [0, 0, 1, 1], 0.0),
    ([0.5, 0.5, 0.5, 0.5], [0, 1, 0, 1], 0.5),      # all tied
    ([0.2, 0.5, 0.5, 0.9], [0, 0, 1, 1], 0.875),    # one tie counts a half
])
def test_auc(score, y, want):
    assert reference.auc(score, y) == pytest.approx(want)


def test_auc_needs_both_classes():
    with pytest.raises(ValueError):
        reference.auc([0.1, 0.2], [1, 1])


# ---- model text: parse and tile ------------------------------------------------------------
MODEL_TEXT = """tree
version=v3
num_class=1
max_feature_idx=1
feature_names=Column_0 Column_1
tree_sizes=1 2

Tree=0
num_leaves=3
num_cat=0
split_feature=0 1
threshold=0.5 -1
decision_type=0 10
left_child=-1 -2
right_child=1 -3
leaf_value=0.1 0.2 0.4
leaf_count=2 2 1
shrinkage=0.1


Tree=1
num_leaves=2
num_cat=0
split_feature=1
threshold=0
decision_type=2
left_child=-1
right_child=-2
leaf_value=-1 1
leaf_count=3 2
shrinkage=0.1


end of trees

parameters:
[num_leaves: 3]
end of parameters
tpu_bin_mappers:{"kept": "as it was"}
"""


def test_model_text_parses_to_the_trees_it_states():
    trees = reference.parse_model(MODEL_TEXT)
    assert [t["num_leaves"] for t in trees] == [3, 2]
    assert trees[0]["decision_type"].tolist() == [0, 10]
    # tree 1 has no missing rule, so the NaN of row 3 counts as 0 and goes left
    assert reference.walk(trees, HAND_ROWS) == pytest.approx(
        [1.1, 1.1, -0.8, -0.8, -0.6])


def test_tiling_repeats_the_trees_and_keeps_the_trailers():
    tiled = forest.tile_model_text(MODEL_TEXT, 5)
    trees = reference.parse_model(tiled)
    assert [t["num_leaves"] for t in trees] == [3, 2, 3, 2, 3]
    assert tiled.count("\nTree=") == 5 and "\nTree=4\n" in tiled
    assert tiled.endswith('tpu_bin_mappers:{"kept": "as it was"}\n')
    assert tiled.startswith("tree\nversion=v3\n")
    sizes = [int(s) for s in tiled.split("tree_sizes=")[1].split("\n")[0].split()]
    chunks = tiled[tiled.index("Tree=0"):tiled.index("end of trees")]
    assert sum(sizes) == len(chunks) and len(sizes) == 5
    base = reference.walk(reference.parse_model(MODEL_TEXT), HAND_ROWS)
    assert reference.walk(reference.parse_model(
        forest.tile_model_text(MODEL_TEXT, 6)), HAND_ROWS) == pytest.approx(
            3 * base)


def test_tiling_refuses_text_without_trees():
    with pytest.raises(ValueError):
        forest.tile_model_text("tree\nversion=v3\n", 3)
    with pytest.raises(reference.ModelTextError):
        reference.parse_model(MODEL_TEXT.replace("num_cat=0", "num_cat=1", 1))
