"""Job `train_rank`: job `train`'s set-up and window for a ranking
objective, the table's query sizes handed to `lgb.Dataset(group=)`, and
the checks a ranking model can be held to.

Set-up (all of it `setup_s`): the table, its queries and the hold-out from
the seed, `Dataset.construct`, `Booster(...)` (which lays the queries out
for the device), `warmup_iters` iterations.  Window: job `train`'s
(`lib/timing.iteration_window`).

Correctness, after the window, by `lib/reference.py` (the trees as the
model text states them) and `lib/rank_reference.py` (LambdaRank's lambdas
and NDCG in float64 numpy, nothing of the program):

* a tree per iteration, every leaf grown, finite values, tree 0's leaf row
  counts against a host recount;
* tree 0's leaf values against -lr * sum(lambda) / sum(hessian) of the
  reference's lambdas at score 0: labels and queries alone;
* tree 1's leaf values against the reference's lambdas at the scores tree 0
  gives every training row: a few hundred distinct scores, so ties inside
  every query, which is where the stable order, the |delta score| norm and
  the query's log2(1 + S) / S factor act (at score 0 the sort is the
  identity and best = worst);
* hold-out NDCG@10 of the first trees at or above a floor;
* before anything is drawn, a program that states no query layout for the
  device is refused (its gradients are a host loop; exit code 1 in seconds);
* the path engaged, as the program states it: the histogram kernel, device
  ingest, the fused step built for this objective, a gradient program at
  the ledger's `learner.pre` site, no row-shaped or query-shaped constant
  in any program of the step, no OOM event, nothing compiled in the window.
"""

import time

import numpy as np

from benchmarks.lib import (device, program_gauges, rank_reference, reference,
                            sut, table, timing)
from benchmarks.lib.harness import Outcome, compare

GRADIENT_SITE = "learner.pre"
PAIR_GAUGE = "lgbm_rank_pairs"


class RankingGradientsOnTheHost(RuntimeError):
    pass


def refuse_a_program_without_device_gradients(cell) -> None:
    """Before the table is drawn: a program whose source nowhere names the
    gauge of the query layout's pairs has no layout for the device; it
    computes the lambdas query by query on the host (half a millisecond a
    query, ~30 s an iteration at this cell's size, the chip idle), so the
    cell would time numpy and a run would not end inside its limit."""
    names = cell.load("jobs", "train_sharded").source_names
    if not names(PAIR_GAUGE):
        raise RankingGradientsOnTheHost(
            "train_rank refuses this program before the table is drawn: it "
            f"does not state {PAIR_GAUGE}, so it lays no queries out for "
            "the device and its ranking gradients are a host loop over "
            "queries; at this cell's size one iteration would outlast the "
            "window.")


def build(cell) -> table.Table:
    """`lib/table.build` with the query sizes: the parameters as they are
    run, the table and the hold-out from the seed, the table binned."""
    import lightgbm_tpu as lgb

    spans = cell.spans
    params = {**cell.config["params"], **cell.traffic.get("params", {})}
    data = {**cell.config["data"], **cell.traffic.get("data", {})}
    if cell.trace:
        params["tpu_telemetry"] = "trace"
    sut.ledger()
    gen = cell.load("datagen", data["generator"])
    with spans.span("bench/setup/make_data"):
        train = gen.make(data, cell.seed, int(data["rows"]), stream=0)
        hold = gen.make(data, cell.seed, int(data["holdout_rows"]), stream=1)
    with spans.span("bench/setup/ingest"):
        ds = lgb.Dataset(train["X"], label=train["y"], group=train["group"],
                         params=params)
        ds.construct()
        device.sync()
    return table.Table(params, data, train, hold, ds,
                       spans.walls("bench/setup/ingest")[-1])


def rank_checks(tab, trees, correct: dict, objective: dict):
    """(checks, notes) of the trained trees against the references."""
    X, y, group = tab.train["X"], tab.train["y"], tab.train["group"]
    lr = float(tab.params["learning_rate"])
    leaves = int(tab.params["num_leaves"])
    checks, notes = {}, {}
    score = np.zeros(len(y))
    for i, tree in enumerate(trees[:2]):
        t0 = time.perf_counter()
        lam, hes = rank_reference.lambdas(score, y, group, **objective)
        leaf = reference.leaf_index_threaded(tree, X)
        if i == 0:
            n = np.bincount(leaf, minlength=leaves)
            off = int(np.abs(n[:tree["num_leaves"]]
                             - tree["leaf_count"][:tree["num_leaves"]]).max())
            checks["first_tree_leaf_counts_match_host_recount"] = \
                off <= int(correct["leaf_count_slack"])
            notes["first_tree_worst_leaf_count_off_by"] = off
        err, worst = rank_reference.worst_leaf_value_error(
            tree, leaf, lam, hes, lr)
        checks[f"tree_{i}_leaf_values_within_tol"] = \
            err <= float(correct[f"tree_{i}_leaf_value_tol"])
        notes[f"tree_{i}_worst_leaf_value_error"] = err
        notes[f"tree_{i}_worst_leaf"] = worst
        notes[f"tree_{i}_check_s"] = time.perf_counter() - t0
        score = score + tree["leaf_value"][leaf]
        if i == 0:
            # the ties tree 1's gradients are computed over
            notes["tree_0_distinct_scores"] = int(len(np.unique(score)))
    k = int(correct["holdout_ndcg_trees"])
    at = int(correct["holdout_ndcg_at"])
    ndcg = rank_reference.ndcg_at_k(
        reference.walk(trees[:k], tab.hold["X"]), tab.hold["y"],
        tab.hold["group"], at, label_gain=objective.get("label_gain"))
    checks["holdout_ndcg_at_or_above_floor"] = \
        ndcg >= float(correct["holdout_ndcg_floor"])
    notes.update(holdout_ndcg=ndcg, holdout_ndcg_at=at, holdout_ndcg_trees=k)
    return checks, notes


def run(cell) -> Outcome:
    import lightgbm_tpu as lgb

    conf, traffic, spans = cell.config, cell.traffic, cell.spans
    correct, warmup = conf["correct"], int(traffic["warmup_iters"])
    refuse_a_program_without_device_gradients(cell)
    tab = build(cell)
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
    t_checks = time.perf_counter()
    trees = reference.parse_model(bst.model_to_string())
    leaves = int(params["num_leaves"])
    snap = program_gauges.snapshot()
    sites = cell.load("jobs", "train_sharded").row_constants(snap)
    fused = program_gauges.gauge(snap, "lgbm_train_step_fused",
                                 objective=str(params["objective"]))
    grad_programs = sut.ledger_programs(GRADIENT_SITE)
    checks = {
        "a_tree_per_iteration":
            len(trees) == warmup + iterations and not stalled,
        "every_tree_has_all_leaves":
            all(t["num_leaves"] == leaves for t in trees),
        "leaf_values_finite":
            all(np.isfinite(t["leaf_value"]).all() for t in trees),
        "fused_step_built_for_objective": None if fused is None else fused == 1,
        "gradient_program_named":
            None if grad_programs is None else grad_programs >= 1,
        "step_holds_no_row_constant":
            None if sites is None else not any(sites.values()),
        "no_oom_event_or_ladder_step": sut.no_oom_so_far(),
        "no_compilation_in_window": window_compiles == 0,
    }
    if len(trees) >= 2:
        ref_checks, ref_notes = rank_checks(
            tab, trees, correct, conf.get("objective", {}))
    else:
        ref_checks, ref_notes = {"two_trees_to_check": False}, {}
    checks.update(ref_checks)
    # the numbers `rank_checks` compared, beside their limits
    compared = {name: compare(ref_notes[name], holds, correct[limit])
                for name, holds, limit in (
                    ("first_tree_worst_leaf_count_off_by", "<=",
                     "leaf_count_slack"),
                    ("tree_0_worst_leaf_value_error", "<=",
                     "tree_0_leaf_value_tol"),
                    ("tree_1_worst_leaf_value_error", "<=",
                     "tree_1_leaf_value_tol"),
                    ("holdout_ndcg", ">=", "holdout_ndcg_floor"))
                if name in ref_notes}
    observed = {"hist_impl": sut.hist_impl(bst),
                "device_ingest": sut.ingest_on_device(tab.dataset)}
    for fact, want in {**conf.get("expect", {}),
                       **traffic.get("expect", {})}.items():
        got = observed[fact]
        checks[f"{fact}_as_expected"] = None if got is None else got == want
    notes, facts = table.setup_facts(cell, tab, setup_compiles,
                                     window_compiles)
    notes.update(ref_notes, **observed, step_row_constant_bytes=sites,
                 fused_step=fused, gradient_programs=grad_programs,
                 queries=int(len(tab.train["group"])),
                 rank_gauges={k: v for k, v in (snap or {}).items()
                              if k.startswith("lgbm_rank_")},
                 trees=len(trees), setup_s=setup_s, window_s=elapsed,
                 iterations=iterations,
                 checks_s=time.perf_counter() - t_checks)
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
