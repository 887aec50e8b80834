"""The ranking cell `mslr-7m-63.train-rank`: what BENCHMARK.json declares of
it, its rehearsal end to end on the CPU, the plain reference of its job
(`lib/rank_reference.py`) against a loop over pairs, the job's checks
against faulty variants of the program, the generator's fixed multiset of
query lengths, and each of its four per-layer readers on a small fixture
(None where its source is gone)."""

import json
import os
import types

import numpy as np
import pytest

from benchmarks.lib import harness, opcount_rank, rank_reference, xplane
from benchmarks.lib import reference as public_rule
from tests.benchmark.test_harness import ROOT, SPEC, run_cell
from tests.test_lambdarank_device import FAULTS as PROGRAM_FAULTS
from tests.benchmark.test_xplane import US, fake_run, reader

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "mslr-7m-63.train-rank"
NEW = {
    "objective_grad_ms_per_iter": ("ms", "lower", "device_trace",
                                   "train_iters_per_s"),
    "lambda_grad_roofline": ("%", "higher", "device_trace",
                             "train_iters_per_s"),
    "lambda_pair_occupancy": ("%", "higher", "program_counter",
                              "train_iters_per_s"),
    "rank_layout_s": ("s", "lower", "program_span", "setup_s"),
}
JOINED = ("device_idle_share", "driver_host_ms_per_iter",
          "hist_build_ms_per_iter", "grow_other_ms_per_iter",
          "hist_kernel_roofline", "hist_feature_chunks", "hist_bin_occupancy",
          # PR 35: the step's share of the peak, and the three that had been
          # pinned to traffic `train` though the cell's traced run reports them
          "train_step_mfu", "score_update_ms_per_iter",
          "partition_ms_per_iter", "hist_columns_per_dot",
          "hist_rows_contracted_share")   # PR 38
NOT_JOINED = ("collective_ms_per_iter", "collective_exposed_ms_per_iter")


# ---- what is declared ----------------------------------------------------------------
def test_the_cell_and_its_configuration_are_declared():
    entry = next(w for w in SPEC["workloads"] if w["name"] == CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        "mslr-7m-63", "train-rank", 1)
    conf_entry = next(c for c in SPEC["configs"] if c["name"] == "mslr-7m-63")
    assert conf_entry["reduced"] == ["data.rows", "trees"]
    assert "Experiments.rst" in conf_entry["source"]
    assert "GPU-Performance.rst" in conf_entry["source"]
    _, _, conf, traffic = harness.resolve_cell(ROOT, harness.BENCH_DIR, CELL,
                                               rehearse=False)
    assert conf["params"] == {
        "objective": "lambdarank", "num_leaves": 255, "max_bin": 63,
        "learning_rate": 0.1, "min_data_in_leaf": 1,
        "min_sum_hessian_in_leaf": 100, "verbosity": -1}
    assert conf["data"] == {
        "generator": "mslr_like", "features": 137, "rows": 6810888,
        "queries": 56757, "max_query_len": 1251, "holdout_rows": 131072}
    assert conf["data"]["rows"] == 3 * 2270296 < 2 ** 24
    assert conf["data"]["queries"] == 3 * 18919
    assert (traffic["job"], traffic["warmup_iters"], traffic["group_iters"],
            traffic["trace_iters"]) == ("train_rank", 1, 1, 3)
    assert set(conf["correct"]) >= {
        "leaf_count_slack", "tree_0_leaf_value_tol", "tree_1_leaf_value_tol",
        "holdout_ndcg_floor", "why"}


@pytest.mark.parametrize("name", sorted(NEW))
def test_a_new_metric_is_declared_for_the_cell_alone(name):
    unit, better, source, moves = NEW[name]
    entry = next(m for m in SPEC["per_layer"] if m["name"] == name)
    assert entry == {"name": name, "unit": unit, "better": better,
                     "source": source, "layer": "objective", "moves": moves,
                     "workloads": [CELL]}


def test_the_cell_joins_the_lists_the_issue_names_and_no_other():
    by_name = {m["name"]: m for m in SPEC["per_layer"] + SPEC["end_to_end"]}
    for name in JOINED + ("train_iters_per_s",):
        assert by_name[name]["workloads"][-1] == CELL
    for name in NOT_JOINED:
        assert CELL not in by_name[name]["workloads"]
    # the cell's own four, found by name: a later PR appends its entries
    assert {m["name"] for m in SPEC["per_layer"]
            if m.get("workloads") == [CELL]} == set(NEW)


# ---- the rehearsal -------------------------------------------------------------------------
@pytest.fixture(scope="module")
def rehearsal():
    rc, lines, err = run_cell(ROOT, "--workload", CELL, "--seed",
                              "2200000033", "--seconds", "1", "--trace", "1",
                              "--rehearse-cpu")
    assert rc == 0, err
    return [json.loads(x) for x in lines]


def test_the_cell_rehearses_and_prints_the_result_line(rehearsal):
    result = rehearsal[-1]
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] == 3 and result["device"]["platform"] == "cpu"
    # what the program states is read; no device plane on the CPU, so the
    # two device readers say nothing
    metrics = result["metrics"]
    assert {"lambda_pair_occupancy", "rank_layout_s"} <= set(metrics)
    assert not {"objective_grad_ms_per_iter", "lambda_grad_roofline"} \
        & set(metrics)
    assert 0 < metrics["lambda_pair_occupancy"]["value"] < 100
    assert metrics["rank_layout_s"]["unit"] == "s"


def test_the_rehearsals_checks_and_facts(rehearsal):
    checks = next(n for n in rehearsal if n.get("note") == "checks")
    assert {"fused_step_built_for_objective", "gradient_program_named",
            "step_holds_no_row_constant", "tree_0_leaf_values_within_tol",
            "tree_1_leaf_values_within_tol", "holdout_ndcg_at_or_above_floor",
            "first_tree_leaf_counts_match_host_recount",
            "no_compilation_in_window"} <= set(checks)
    assert all(v is True for k, v in checks.items() if k != "note")
    facts = next(n for n in rehearsal if n.get("note") == "facts")
    assert facts["tree_0_distinct_scores"] == 15  # so ties inside the queries
    assert facts["fused_step"] == 1 and facts["gradient_programs"] >= 1
    assert not any(facts["step_row_constant_bytes"].values())


def test_a_program_without_a_layout_for_the_device_is_refused(monkeypatch):
    job = harness.load_module(harness.BENCH_DIR, "jobs", "train_rank")
    sharded = harness.load_module(harness.BENCH_DIR, "jobs", "train_sharded")
    cell = types.SimpleNamespace(load=lambda kind, name: sharded)
    job.refuse_a_program_without_device_gradients(cell)   # this program
    monkeypatch.setattr(sharded, "source_names", lambda gauge: False)
    with pytest.raises(job.RankingGradientsOnTheHost, match="host loop"):
        job.refuse_a_program_without_device_gradients(cell)


# ---- the plain reference ---------------------------------------------------------------------
def small_queries(seed=3, queries=50):
    rng = np.random.default_rng(seed)
    group = rng.integers(1, 30, size=queries)
    n = int(group.sum())
    label = rng.integers(0, 5, size=n)
    label[:group[0]] = 1                        # a query with c = 0
    score = np.round(rng.normal(size=n), 1)     # ties
    return score, label, group


@pytest.mark.parametrize("norm", [True, False])
def test_reference_lambdas_equal_the_loop_over_pairs(norm):
    score, label, group = small_queries()
    fast = rank_reference.lambdas(score, label, group, norm=norm)
    slow = rank_reference.lambdas_pair_by_pair(score, label, group, norm=norm)
    for a, b in zip(fast, slow):
        np.testing.assert_allclose(a, b, rtol=0, atol=1e-14)
    assert np.abs(slow[0]).max() > 0.1 and not slow[0][:group[0]].any()
    # lambdas of a query cancel; hessians are not negative
    bounds = np.concatenate([[0], np.cumsum(group)])
    assert np.abs(np.add.reduceat(fast[0], bounds[:-1])).max() < 1e-12
    assert fast[1].min() >= 0


def test_reference_ndcg():
    label = np.array([3, 2, 0, 1, 0, 0])
    group = [4, 2]
    gain = rank_reference.default_label_gain()
    best = rank_reference.ndcg_at_k([4, 3, 1, 2, 0, 0], label, group, 3)
    assert best == pytest.approx(1.0)            # ideal order; no positives: 1
    got = rank_reference.ndcg_at_k([1, 2, 3, 4, 0, 0], label, group, 2)
    dcg = gain[1] + gain[0] / np.log2(3)
    ideal = gain[3] + gain[2] / np.log2(3)
    assert got == pytest.approx((dcg / ideal + 1.0) / 2)


def test_the_pass_is_counted_from_rows_and_pairs():
    ops, byts = opcount_rank.lambda_grad(1000, 50000)
    assert (ops, byts) == (25000 * 24 + 1000 * 8, 1000 * 20)


# ---- the generator ------------------------------------------------------------------------------
@pytest.fixture(scope="module")
def mslr_like():
    return harness.load_module(harness.BENCH_DIR, "datagen", "mslr_like")


def test_the_multiset_of_lengths_is_the_specs_and_the_seed_permutes_it(
        mslr_like):
    lens = mslr_like.query_lengths(56757, 6810888, 1251)
    assert (lens.sum(), lens.min(), lens.max()) == (6810888, 1, 1251)
    assert float((lens.astype(np.float64) ** 2).sum()) == pytest.approx(
        1.2e9, rel=0.01)
    spec = {"features": 137, "rows": 20000, "queries": 167,
            "max_query_len": 1251}
    a = mslr_like.make(spec, 7, 20000, 0)
    b = mslr_like.make(spec, 8, 20000, 0)
    assert a["X"].shape == (20000, 137) and a["group"].sum() == 20000
    assert not np.array_equal(a["group"], b["group"])
    assert np.array_equal(np.sort(a["group"]), np.sort(b["group"]))
    again = mslr_like.make(spec, 7, 20000, 0)
    assert all(np.array_equal(a[k], again[k]) for k in a)
    hold = mslr_like.make(spec, 7, 8192, 1)
    assert hold["group"].sum() == 8192 and len(hold["group"]) == 68


def test_the_columns_and_grades_are_as_the_configuration_assumes(mslr_like):
    spec = {"features": 137, "rows": 20000, "queries": 167,
            "max_query_len": 1251}
    d = mslr_like.make(spec, 11, 20000, 0)
    X, y = d["X"], d["y"]
    assert np.isfinite(X).all()
    assert (X == 0).mean(axis=0).max() < 0.6     # under the EFB gate's 80 %
    shares = np.bincount(y.astype(int), minlength=5) / len(y)
    # 167 queries' offsets move the shares of 20,000 rows by a few points
    assert np.abs(shares - [0.52, 0.32, 0.13, 0.02, 0.01]).max() < 0.08
    kinds = mslr_like._kinds()
    assert (np.bincount(kinds) == [56, 25, 56]).all()
    ratios = X[:, kinds == 1]
    assert ratios.min() > 0 and ratios.max() < 1
    counts = X[:, kinds == 0]
    assert np.array_equal(counts, np.floor(counts)) and counts.min() >= 0


# ---- the job's checks against faulty variants of the program -----------------------------------
FAULTS = {None: None, **PROGRAM_FAULTS}


@pytest.mark.parametrize("fault", FAULTS, ids=lambda f: f or "sound")
def test_the_jobs_checks_pass_the_program_and_fail_each_fault(
        monkeypatch, mslr_like, fault):
    import lightgbm_tpu as lgb
    from lightgbm_tpu.ops import lambdarank

    if fault:
        name, make = FAULTS[fault]
        monkeypatch.setattr(lambdarank, name, make(getattr(lambdarank, name)))
    _, _, conf, _ = harness.resolve_cell(ROOT, harness.BENCH_DIR, CELL,
                                         rehearse=True)
    data = dict(conf["data"], rows=6000, queries=50, holdout_rows=2400)
    train = mslr_like.make(data, 5, 6000, 0)
    hold = mslr_like.make(data, 5, 2400, 1)
    params = conf["params"]
    ds = lgb.Dataset(train["X"], label=train["y"], group=train["group"],
                     params=params)
    bst = lgb.Booster(params=params, train_set=ds)
    for _ in range(2):
        assert not bst.update()
    assert bst._driver._train_step is not None
    job = harness.load_module(harness.BENCH_DIR, "jobs", "train_rank")
    tab = types.SimpleNamespace(train=train, hold=hold, params=params)
    trees = public_rule.parse_model(bst.model_to_string())
    checks, notes = job.rank_checks(tab, trees, conf["correct"],
                                    conf["objective"])
    # the job wants a tree per iteration and every check to hold.  Without
    # an order among ties every row of a query has rank 0 at score 0, the
    # discounts cancel, the gradients are zeros and no first tree is grown
    sound = len(trees) == 2 and all(checks.values())
    assert sound == (fault is None), (checks, notes)
    if fault is None:
        assert notes["tree_1_worst_leaf_value_error"] < 1e-6
        assert notes["tree_0_distinct_scores"] == 15


# ---- the readers ---------------------------------------------------------------------------------
@pytest.fixture(scope="module")
def own():
    # device 0: jit__pre [0,4], jit_grow [4,60], jit__post [60,70], ...
    return xplane.load(os.path.join(HERE, "score_update_trace.textproto"))


@pytest.mark.parametrize("window, iterations, want_us", [
    ((0.0, 100.0), 2, 4 / 2),        # the one gradient program, two iterations
    ((2.0, 100.0), 1, 2.0),          # cut at the window's start
    ((10.0, 60.0), 1, None),         # none inside the window
])
def test_objective_grad_is_the_pre_programs_inside_the_window(
        own, window, iterations, want_us):
    run = fake_run(own, (window[0] * US, window[1] * US),
                   {"iterations": iterations})
    got = reader("objective_grad_ms_per_iter").read(run)
    assert got is None if want_us is None else \
        got == pytest.approx(want_us * 1e-3)


def test_objective_grad_reads_none_without_a_gradient_program(own):
    trace = xplane.Trace(
        ops=own.ops, host=own.host, on_device=True,
        modules={0: own.modules[0].select(
            lambda n: not n.startswith("jit__pre("))})
    run = fake_run(trace, (0.0, 100 * US), {"iterations": 2})
    assert reader("objective_grad_ms_per_iter").read(run) is None
    assert reader("lambda_grad_roofline").read(run) is None


def with_gauges(monkeypatch, gauges):
    from benchmarks.lib import program_gauges
    monkeypatch.setattr(program_gauges, "snapshot", lambda: gauges)


def test_the_roofline_is_the_least_time_over_the_programs(monkeypatch, own):
    with_gauges(monkeypatch, {'lgbm_rank_pairs{kind="valid"}': 8.0e8,
                              'lgbm_rank_pairs{kind="slots"}': 2.0e9})
    run = fake_run(own, (0.0, 100 * US), {"iterations": 1, "rows": 6810888})
    ops, byts = opcount_rank.lambda_grad(6810888, 8.0e8)
    least = max(ops / 197e12, byts / 819e9)
    assert reader("lambda_grad_roofline").read(run) == pytest.approx(
        100 * least / 4e-6)
    assert run.said[0][1]["bound"] == "memory"
    assert reader("lambda_pair_occupancy").read(run) == pytest.approx(40.0)
    # a device with no published peaks (the CPU of a rehearsal): nothing
    cpu = fake_run(own, (0.0, 100 * US), {"iterations": 1, "rows": 10},
                   kind="cpu")
    assert reader("lambda_grad_roofline").read(cpu) is None


@pytest.mark.parametrize("gauges", [None, {}, {"lgbm_rank_queries": 3.0},
                                    {'lgbm_rank_pairs{kind="valid"}': 5.0}])
def test_the_gauge_readers_say_nothing_where_the_gauges_are_gone(
        monkeypatch, own, gauges):
    with_gauges(monkeypatch, gauges)
    run = fake_run(own, (0.0, 100 * US), {"iterations": 1, "rows": 10})
    assert reader("lambda_pair_occupancy").read(run) is None
    if not gauges or 'lgbm_rank_pairs{kind="valid"}' not in gauges:
        assert reader("lambda_grad_roofline").read(run) is None


def test_rank_layout_is_the_programs_span_in_set_up(monkeypatch):
    from benchmarks.lib import program_spans
    S = program_spans.Span
    spans = [S(1, None, "booster/init", 1.0, 9.0, {}),
             S(2, 1, "objective/init", 1.0, 4.0, {}),
             S(3, 2, "rank/query_layout", 1.5, 3.5, {"queries": 7}),
             S(4, None, "rank/query_layout", 20.0, 21.0, {})]
    monkeypatch.setattr(program_spans, "setup_spans",
                        lambda run: [s for s in spans if s.end <= 10.0])
    assert reader("rank_layout_s").read(None) == pytest.approx(2.0)
    monkeypatch.setattr(program_spans, "setup_spans", lambda run: spans[:2])
    assert reader("rank_layout_s").read(None) is None
    monkeypatch.setattr(program_spans, "setup_spans", lambda run: None)
    assert reader("rank_layout_s").read(None) is None
