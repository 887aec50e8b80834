"""Job `train_sharded` and the three readers of the four-chip cell: the
job refuses a program whose source cannot name the gauge of its step's
row constants, the sharded checks read the gauges and say "not observable"
where one is gone, the recount that decides `correct` reads a sound tree
at 0 and a faulty one far over the cell's slack, and the readers of the
collectives and of the histogram work's roofline with a shard's rows on
traces built by hand (two chips, a `while` around a body, an asynchronous
pair) and on the recorded one-chip trace, which has no collective."""

import json
import os
import types

import numpy as np
import pytest

from benchmarks.lib import harness, opcount, peaks, program_gauges, xplane
from benchmarks.lib.harness import load_module
from tests.benchmark.test_xplane import (TRAIN_FACTS, TRAIN_WINDOW, US,
                                         fake_run, reader)

JOB = load_module(harness.BENCH_DIR, "jobs", "train_sharded")
CELL = "criteo-27m-67.train-data4"
# what the cell's traced run on the chip reports of the three metrics that
# were pinned to the cells of traffic `train` (my chip run, PR 35)
ON_THE_CHIP = {"score_update_ms_per_iter", "partition_ms_per_iter",
               "hist_columns_per_dot"}


def snapshot(**over):
    snap = {
        'lgbm_step_row_constant_bytes{site="learner.pre"}': 0.0,
        'lgbm_step_row_constant_bytes{site="grower.data"}': 0.0,
        'lgbm_step_row_constant_bytes{site="learner.post"}': 0.0,
        "lgbm_data_shards": 4.0,
        'lgbm_hist_agg{mode="scatter"}': 1.0,
        'lgbm_hist_agg{mode="psum"}': 0.0,
        'lgbm_hist_grid{axis="row_blocks"}': 832.0,
        **{f'lgbm_shard_rows{{shard="{k}"}}': 6815744.0 for k in range(4)},
        **{f'lgbm_shard_table_rows{{shard="{k}"}}': r for k, r in
           enumerate([6815744.0] * 3 + [6115268.0])},
        'lgbm_exchange_bytes_per_tree{op="reduce_scatter"}': 87246720.0,
    }
    snap.update(over)
    return {k: v for k, v in snap.items() if v is not None}


def cell(chips=4):
    return types.SimpleNamespace(
        devices=[object()] * chips,
        traffic={"sharded": {"hist_agg": "scatter"}})


def test_the_checks_hold_on_the_gauges_of_a_sound_run():
    checks, notes = JOB.sharded_checks(cell(), snapshot())
    assert checks == {"step_holds_no_row_constant": True,
                      "data_shards_equal_chips": True,
                      "shard_rows_equal_within_one_block": True,
                      "hist_agg_as_resolved": True}
    assert notes["shard_rows"] == [6815744.0] * 4
    assert notes["shard_table_rows"]['shard="3"'] == 6115268.0
    assert harness.correct(checks)


@pytest.mark.parametrize("over, check, want", [
    ({'lgbm_step_row_constant_bytes{site="grower.data"}': 2.6e9},
     "step_holds_no_row_constant", False),
    ({"lgbm_data_shards": 2.0}, "data_shards_equal_chips", False),
    ({'lgbm_shard_rows{shard="3"}': 6815744.0 - 8193},
     "shard_rows_equal_within_one_block", False),
    ({'lgbm_shard_rows{shard="3"}': 6815744.0 - 8192},
     "shard_rows_equal_within_one_block", True),
    # no kernel grid stated (the xla scan): no block, so no difference
    ({'lgbm_hist_grid{axis="row_blocks"}': 0.0,
      'lgbm_shard_rows{shard="3"}': 6815743.0},
     "shard_rows_equal_within_one_block", False),
    ({'lgbm_hist_agg{mode="scatter"}': 0.0, 'lgbm_hist_agg{mode="psum"}': 1.0},
     "hist_agg_as_resolved", False),
    # a gauge that is gone is not observable, and not passed
    ({"lgbm_data_shards": None}, "data_shards_equal_chips", None),
    ({'lgbm_shard_rows{shard="2"}': None},
     "shard_rows_equal_within_one_block", None),
    ({'lgbm_hist_agg{mode="scatter"}': None}, "hist_agg_as_resolved", None),
    ({f'lgbm_step_row_constant_bytes{{site="{s}"}}': None for s in
      ("learner.pre", "grower.data", "learner.post")},
     "step_holds_no_row_constant", None),
])
def test_a_check_fails_or_is_not_observable(over, check, want):
    checks, _ = JOB.sharded_checks(cell(), snapshot(**over))
    assert checks[check] is want
    assert harness.correct(checks) is (want is True)


def test_without_the_program_every_check_is_not_observable():
    checks, notes = JOB.sharded_checks(cell(), None)
    assert set(checks.values()) == {None}
    assert notes["step_row_constant_bytes"] is None


# ---- the refusal before the table is drawn ----------------------------------------
def package(tmp_path, monkeypatch, name, text):
    """A stand-in program: a package `name` of one module holding `text`."""
    (tmp_path / name).mkdir()
    (tmp_path / name / "__init__.py").write_text("")
    (tmp_path / name / "learner.py").write_text(text)
    monkeypatch.syspath_prepend(str(tmp_path))
    return name


def test_this_program_names_the_gauge_and_is_let_through():
    assert JOB.source_names(JOB.ROW_CONSTANTS)
    JOB.refuse_a_program_that_cannot_say()


def test_a_program_that_names_the_gauge_anywhere_is_let_through(
        tmp_path, monkeypatch):
    name = package(tmp_path, monkeypatch, "names_it",
                   f'GAUGE = "{JOB.ROW_CONSTANTS}"\n')
    JOB.refuse_a_program_that_cannot_say(name)


@pytest.mark.parametrize("name, text", [
    ("no_such_program", None),
    ("names_nothing", "x = 1\n"),
    ("names_another", 'GAUGE = "lgbm_data_shards"\n'),
])
def test_the_job_refuses_before_the_table_is_drawn(tmp_path, monkeypatch,
                                                   name, text):
    if text is not None:
        package(tmp_path, monkeypatch, name, text)
    with pytest.raises(JOB.ShardedStepHoldsTheTable,
                       match="does not state lgbm_step_row_constant") as e:
        JOB.refuse_a_program_that_cannot_say(name)
    assert "before the table is drawn" in str(e.value)


def test_the_refusal_comes_first_and_builds_nothing(monkeypatch):
    """`run` refuses before it loads the job it wraps."""
    monkeypatch.setattr(JOB, "source_names", lambda gauge, package: False)
    loaded = []
    c = types.SimpleNamespace(load=lambda *a: loaded.append(a))
    with pytest.raises(JOB.ShardedStepHoldsTheTable):
        JOB.run(c)
    assert loaded == []


# ---- the recount's two sides, through the harness's own comparison --------------
@pytest.fixture(scope="module")
def sharded_tree():
    """Tree 0 of the cell's job at rehearsal size (four virtual chips,
    20,000 click-log rows with NaN columns), its rows and labels."""
    import lightgbm_tpu as lgb

    from benchmarks.datagen import criteo_like
    from benchmarks.lib import reference

    conf = harness.load_json(os.path.join(
        harness.BENCH_DIR, "configs", "criteo-27m-67.json"))
    params = {**conf["params"], **conf["rehearse"]["params"],
              "tree_learner": "data", "num_machines": 4}
    rows = conf["rehearse"]["data"]["rows"]
    d = criteo_like.make(conf["data"], 1234567, rows, stream=0)
    bst = lgb.Booster(params=params, train_set=lgb.Dataset(
        d["X"], label=d["y"], params=params))
    bst.update()
    tree = reference.parse_model(bst.model_to_string())[0]
    return reference, conf, params, tree, d["X"], d["y"]


def test_a_sound_sharded_tree_recounts_to_the_row(sharded_tree):
    reference, conf, params, tree, X, y = sharded_tree
    off, err, _ = reference.recount_first_tree(
        tree, reference.leaf_index(tree, X), y, params["learning_rate"])
    assert off == 0 and err <= conf["correct"]["leaf_value_tol"]


def faulty(tree, how, rows):
    """The tree a faulty exchange would have written: `nan_side` sends the
    missing rows of the first split on a NaN column down the other side
    than the counts were taken for; `lost_shard` states the counts of
    three shards of four (every leaf a quarter short)."""
    t = {k: np.array(v) if isinstance(v, np.ndarray) else v
         for k, v in tree.items()}
    if how == "nan_side":
        node = next(i for i, dt in enumerate(t["decision_type"])
                    if (dt >> 2) & 3 == 2)
        t["decision_type"][node] ^= 2
    else:
        t["leaf_count"] = t["leaf_count"] - t["leaf_count"] // 4
    return t


@pytest.mark.parametrize("how", ["nan_side", "lost_shard"])
def test_a_faulty_tree_reads_far_over_the_slack(sharded_tree, how):
    """The smallest of these readings, as a share of the rows, is what
    `leaf_count_slack` is held against in the configuration's file."""
    reference, conf, params, tree, X, y = sharded_tree
    bad = faulty(tree, how, len(y))
    off, _, _ = reference.recount_first_tree(
        bad, reference.leaf_index(bad, X), y, params["learning_rate"])
    share = off / len(y)
    print(f"{how}: worst leaf off by {off} rows of {len(y)} ({share:.4%})")
    assert share > 0.001
    # at the cell's size the same share is thousands of times the slack
    assert share * conf["data"]["rows"] > 1000 * conf["correct"][
        "leaf_count_slack"]


# ---- the readers ---------------------------------------------------------------------
def op(text, start, dur):
    return (text, start * 1e3, dur * 1e3)   # microseconds -> nanoseconds


KERNEL = ("%hist_build.7 = f32[8192,125]{1,0} custom-call(u8[124,32,8192]{2,1,0}"
          " %a, bf16[124,5,8192]{2,1,0} %b, s32[124,1,8192]{2,1,0} %c, "
          "s32[25,1]{1,0} %d), custom_call_target=\"tpu_custom_call\"")
SCATTER = ("%reduce_scatter.45 = f32[25,24,255,3]{2,1,3,0:T(8,128)S(1)} "
           "reduce-scatter(f32[25,96,255,3]{2,1,3,0} %pad_maximum_fusion.29)")
GATHER = "%all-gather.15 = s32[4,1,50]{2,1,0:T(1,128)S(1)} all-gather(%bitcast.9)"
PSUM = "%psum.399 = s32[]{:T(128)} all-reduce(s32[] %select_n.4286)"
START = "%all-reduce-start.3 = f32[4]{0} all-reduce-start(f32[4]{0} %x)"
DONE = "%all-reduce-done.3 = f32[4]{0} all-reduce-done(f32[4]{0} %all-reduce-start.3)"
WHILE = "%while.9 = (s32[], f32[4]{0}) while((s32[], f32[4]{0}) %tuple.1)"
FUSION = "%fusion.12 = f32[96]{0} fusion(f32[96]{0} %p), kind=kLoop"


@pytest.fixture(scope="module")
def two_chips():
    """Chip 0: a `while` over [0, 100] whose body runs the kernel [0, 40],
    a reduce-scatter [40, 50], an all-gather [50, 52], a fusion [52, 60],
    an async all-reduce whose start [60, 61] and done [69, 70] stand
    around a fusion [61, 66]; then a bare all-reduce [110, 114], of which
    a window to 112 holds half.  Chip 1: the kernel [0, 44] and a
    reduce-scatter [44, 50]."""
    chip0 = [op(WHILE, 0, 100), op(KERNEL, 0, 40), op(SCATTER, 40, 10),
             op(GATHER, 50, 2), op(FUSION, 52, 8), op(START, 60, 1),
             op(FUSION, 61, 5), op(DONE, 69, 1), op(PSUM, 110, 4)]
    chip1 = [op(KERNEL, 0, 44), op(SCATTER, 44, 6)]
    return xplane.Trace(ops={0: xplane.Events.of(chip0),
                             1: xplane.Events.of(chip1)},
                        modules={}, host={}, on_device=True)


def test_an_instructions_kind_not_its_name():
    kind_of = reader("collective_ms_per_iter").kind_of
    assert [kind_of(n) for n in (SCATTER, GATHER, PSUM, START, DONE, WHILE,
                                 KERNEL, FUSION, "bench/window")] == [
        "reduce-scatter", "all-gather", "all-reduce", "all-reduce-start",
        "all-reduce-done", "while", "custom-call", "fusion", None]


def test_collective_time_is_per_chip_and_iteration(two_chips):
    run = fake_run(two_chips, (0.0, 112 * US), {"iterations": 2})
    # chip 0: 10 + 2 + 1 + 1 + 2 (cut at 112); chip 1: 6; two chips, two
    # iterations
    assert reader("collective_ms_per_iter").read(run) == pytest.approx(
        (16 + 6) / 2 / 2 * 1e-3)
    (what, table), = run.said
    assert what.startswith("collectives in the window")
    assert table["chips"] == 2 and table["rows"][0][:2] == ["reduce-scatter", 2]
    assert {r[0]: r[1] for r in table["rows"]} == {
        "reduce-scatter": 2, "all-gather": 1, "all-reduce": 1,
        "all-reduce-start": 1, "all-reduce-done": 1}


def test_a_synchronous_collective_is_exposed_whole(two_chips):
    run = fake_run(two_chips, (0.0, 112 * US), {"iterations": 2})
    # nothing runs under any of them: the `while` that encloses them is no
    # instruction of its own
    assert reader("collective_exposed_ms_per_iter").read(run) == \
        pytest.approx((16 + 6) / 2 / 2 * 1e-3)


def test_what_runs_under_a_collective_is_not_exposed():
    """An all-reduce [0, 10] under which a fusion runs [2, 7] on the same
    chip (as an asynchronous one's body would): 5 of its 10 are exposed."""
    trace = xplane.Trace(
        ops={0: xplane.Events.of([op(PSUM, 0, 10), op(FUSION, 2, 5)])},
        modules={}, host={}, on_device=True)
    run = fake_run(trace, (0.0, 20 * US), {"iterations": 1})
    assert reader("collective_ms_per_iter").read(run) == pytest.approx(10e-3)
    assert reader("collective_exposed_ms_per_iter").read(run) == \
        pytest.approx(5e-3)


def test_the_roofline_counts_one_shards_rows_over_a_chips_kernel_time(
        two_chips):
    """Two shards on two chips: every chip histograms half of each tree's
    rows and builds every histogram whole, in its own kernel time (40 and
    44 us here, so 42 a chip).  `hist_shard_roofline` was this arithmetic
    beside a reader that counted the whole table for every chip."""
    facts = {"iterations": 2, "rows": 2 * 65536, "features": 67, "bins": 255,
             "data_shards": 2.0, "hist_rows_by_tree": [0, 300000, 200000],
             "histograms_by_tree": [1, 255, 255], "first_window_tree": 1}
    run = fake_run(two_chips, (0.0, 112 * US), facts)
    got = reader("hist_kernel_roofline").read(run)
    ops, byts = opcount.tree_histogram_work(500000 / 2, 67, 255, 510)
    peak = peaks.peaks_for("TPU v5 lite")
    want, bound = opcount.roofline(ops, byts, 42 * US, peak["bf16_flops"],
                                   peak["hbm_bytes_per_s"])
    assert got == pytest.approx(want, rel=1e-12) and bound == "memory"
    said = run.said[-1][1]
    assert said["kernel_s_per_chip"] == pytest.approx(42 * US)
    assert (said["operations"], said["bytes"]) == (ops, byts)
    # unsharded facts on the same trace count every row for every chip
    whole = reader("hist_kernel_roofline").read(
        fake_run(two_chips, (0.0, 112 * US), dict(facts, data_shards=None)))
    rows_part = 250000 * (67 + 8) / 819e9 / (42 * US) * 100
    assert whole - got == pytest.approx(rows_part, rel=1e-9)
    # and the step's share is the same work over the window, per chip
    assert reader("train_step_mfu").read(run) == pytest.approx(
        want * 42 / 112, rel=1e-12)


def test_on_one_chip_and_on_the_cpu_the_readers_say_nothing():
    one = xplane.load(os.path.join(harness.BENCH_DIR, "fixtures",
                                   "v5e_train_2iters.textproto"))
    run = fake_run(one, TRAIN_WINDOW, dict(TRAIN_FACTS))
    for name in ("collective_ms_per_iter", "collective_exposed_ms_per_iter"):
        assert reader(name).read(run) is None
    cpu = xplane.Trace(ops=one.ops, modules={}, host={}, on_device=False)
    run = fake_run(cpu, TRAIN_WINDOW, dict(TRAIN_FACTS, data_shards=4.0))
    assert reader("collective_ms_per_iter").read(run) is None
    assert reader("collective_exposed_ms_per_iter").read(run) is None


def test_the_cell_and_its_metrics_are_declared():
    with open(os.path.join(os.path.dirname(harness.BENCH_DIR),
                           "BENCHMARK.json")) as f:
        spec = json.load(f)
    entry = next(w for w in spec["workloads"] if w["name"] == CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        "criteo-27m-67", "train-data4", 4)
    has = {m["name"] for m in spec["end_to_end"] + spec["per_layer"]
           if CELL in m.get("workloads", [])}
    assert has == {"train_iters_per_s", "device_idle_share", "train_step_mfu",
                   "driver_host_ms_per_iter", "hist_build_ms_per_iter",
                   "grow_other_ms_per_iter",   # `jit_grow(` runs sharded too
                   "hist_kernel_roofline",     # a shard's rows a chip, PR 35
                   "hist_feature_chunks", "hist_bin_occupancy",
                   "hist_rows_contracted_share",   # summed over the shards
                   "collective_ms_per_iter",
                   "collective_exposed_ms_per_iter"} | ON_THE_CHIP
    for name in ("collective_ms_per_iter", "collective_exposed_ms_per_iter"):
        m = next(m for m in spec["per_layer"] if m["name"] == name)
        assert m["workloads"] == [CELL] and m["moves"] == "train_iters_per_s"
    assert "hist_shard_roofline" not in {m["name"] for m in spec["per_layer"]}
    conf = harness.load_json(os.path.join(
        harness.BENCH_DIR, "configs", "criteo-27m-67.json"))
    assert conf["data"]["rows"] == 4 * 1_700_000_000 // 128 == 53_125_000
    traffic = harness.load_json(os.path.join(
        harness.BENCH_DIR, "traffic", "train-data4.json"))
    assert traffic["job"] == "train_sharded"
    assert traffic["params"] == {"tree_learner": "data", "num_machines": 4}
