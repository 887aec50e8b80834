"""Job `train`: boosting iterations per second through the public entry
points, at the configuration's parameters and nothing else.

Set-up (all of it `setup_s`): the table and the hold-out from the seed,
`Dataset.construct`, `Booster(...)`, `warmup_iters` iterations (the first
compiles or loads every program).
Window (`lib/timing.iteration_window`): `Booster.update()` in groups of
`group_iters`, each group ended by a wait for the device, until the host
clock passes `--seconds`; the metric is iterations over elapsed time.  A
traced run measures `trace_iters` iterations under the profiler instead.
Correctness is decided after the window, by `lib/reference.py`.
"""

import numpy as np

from benchmarks.lib import device, reference, sut, table, timing
from benchmarks.lib.harness import Outcome, compare, within


def run(cell) -> Outcome:
    import lightgbm_tpu as lgb

    conf, traffic, spans = cell.config, cell.traffic, cell.spans
    correct, warmup = conf["correct"], int(traffic["warmup_iters"])
    tab = table.build(cell)
    params = tab.params
    with spans.span("bench/setup/learner"):
        bst = lgb.Booster(params=params, train_set=tab.dataset)
    stalled = False
    with spans.span("bench/setup/warmup"):
        for _ in range(warmup):
            stalled |= bool(bst.update())
        device.sync()
    setup_compiles = cell.compiles.snapshot()
    setup_s = cell.since_start()

    win = timing.iteration_window(cell, bst.update)
    iterations, elapsed = win.iterations, win.window_s
    window_compiles = cell.compiles.snapshot().programs - setup_compiles.programs

    # ---- after the window: is what was trained right? -------------------------
    trees = reference.parse_model(bst.model_to_string())
    leaves = int(params["num_leaves"])
    leaf = reference.leaf_index_threaded(trees[0], tab.train["X"])
    count_off, worst_err, worst_leaf = reference.recount_first_tree(
        trees[0], leaf, tab.train["y"], float(params["learning_rate"]))
    k = int(correct["holdout_auc_trees"])
    auc = reference.auc(reference.walk(trees[:k], tab.hold["X"]),
                        tab.hold["y"])
    compared = {
        "first_tree_leaf_count_off_by":
            compare(count_off, "<=", int(correct["leaf_count_slack"])),
        "first_tree_leaf_value_error":
            compare(worst_err, "<=", float(correct["leaf_value_tol"])),
        "holdout_auc": compare(auc, ">=", float(correct["holdout_auc_floor"])),
    }
    checks = {
        "a_tree_per_iteration":
            len(trees) == warmup + iterations and not stalled,
        "every_tree_has_all_leaves":
            all(t["num_leaves"] == leaves for t in trees),
        "leaf_values_finite":
            all(np.isfinite(t["leaf_value"]).all() for t in trees),
        "first_tree_leaf_counts_match_host_recount":
            within(compared["first_tree_leaf_count_off_by"]),
        "first_tree_leaf_values_within_tol":
            within(compared["first_tree_leaf_value_error"]),
        "holdout_auc_at_or_above_floor": within(compared["holdout_auc"]),
        "no_oom_event_or_ladder_step": sut.no_oom_so_far(),
        "no_compilation_in_window": window_compiles == 0,
    }
    # the path engaged: each fact the configuration or the traffic mix
    # expects, as the program states it (None where it no longer does)
    observed = {"hist_impl": sut.hist_impl(bst),
                "device_ingest": sut.ingest_on_device(tab.dataset),
                "bins_shard_devices": sut.bins_shard_devices(bst)}
    for fact, want in {**conf.get("expect", {}),
                       **traffic.get("expect", {})}.items():
        got = observed[fact]
        checks[f"{fact}_as_expected"] = None if got is None else got == want
    notes, facts = table.setup_facts(cell, tab, setup_compiles,
                                     window_compiles)
    notes.update(holdout_auc=auc, holdout_auc_trees=k,
                 first_tree_worst_leaf_value_error=worst_err,
                 first_tree_worst_leaf=worst_leaf,
                 first_tree_worst_leaf_count_off_by=count_off,
                 **observed,
                 trees=len(trees), setup_s=setup_s, window_s=elapsed,
                 iterations=iterations)
    rows = int(tab.data["rows"])
    facts.update(table.histogram_facts(cell, trees, warmup, rows),
                 iterations=iterations, window_start=win.start, rows=rows,
                 features=int(tab.data["features"]),
                 bins=int(params["max_bin"]))
    return Outcome(
        attempted=iterations, failed=win.failed, checks=checks,
        end_to_end={"train_iters_per_s": iterations / elapsed,
                    "setup_s": setup_s},
        facts=facts, notes=notes, compared=compared,
        result_facts=win.facts())
