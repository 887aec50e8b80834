"""Job `predict`: rows scored per second by `Booster.predict` on a forest
of the published size, closed loop, one caller (a table-scoring job waits
for each batch before it sends the next).

Set-up (all of it `setup_s`): the hold-out and a training table from the
seed (the traffic mix's `data.rows`: as many rows as real tree depths need,
not the configuration's table, which no request touches),
`forest_train_iters` boosting iterations on it, their trees tiled to
`forest_trees` through the public model text (`lib/forest.py`) and loaded
with `Booster(model_str=...)`, one call to compile the batch's shape.
Window: `predict(batch, raw_score=True)` over the hold-out in batches of
`batch_rows`, cycling, until the host clock passes `--seconds`; the metric
is rows returned over elapsed time.  `predict` returns host scores, so each
call ends with its work done.  A traced run measures `trace_calls` calls
under the profiler instead.  Afterwards the first `check_rows` scores of
the window's last call are held against `lib/reference.py`'s float64 walk
of the same model text.
"""

import numpy as np

from benchmarks.lib import forest, reference, sut, table, timing
from benchmarks.lib.harness import Outcome, compare, within


def run(cell) -> Outcome:
    import lightgbm_tpu as lgb

    traffic, spans = cell.traffic, cell.spans
    kwargs = dict(traffic.get("predict_kwargs", {}))
    batch_rows = int(traffic["batch_rows"])
    tab = table.build(cell)
    hold = tab.hold["X"]
    with spans.span("bench/setup/train_forest"):
        bst = lgb.Booster(params=tab.params, train_set=tab.dataset)
        for _ in range(int(traffic["forest_train_iters"])):
            bst.update()
        text = bst.model_to_string()
    with spans.span("bench/setup/load_forest"):
        text = forest.tile_model_text(text, int(traffic["forest_trees"]))
        # the scoring job holds the forest, not the table it came from
        del bst
        tab.dataset = tab.train = None
        big = lgb.Booster(model_str=text)
    batches = [hold[lo:lo + batch_rows]
               for lo in range(0, len(hold) - batch_rows + 1, batch_rows)]
    walker_before = sut.ledger_programs("predict.class_scores")
    with spans.span("bench/setup/first_call"):
        big.predict(batches[0], raw_score=True, **kwargs)
    walker_after = sut.ledger_programs("predict.class_scores")
    setup_compiles = cell.compiles.snapshot()
    setup_s = cell.since_start()

    failed = rows_returned = calls = 0
    last = None  # (batch, scores) of the newest call that returned

    def one_call():
        nonlocal failed, rows_returned, calls, last
        batch = batches[calls % len(batches)]
        calls += 1
        with spans.span("bench/predict_call"):
            try:
                out = big.predict(batch, raw_score=True, **kwargs)
            except Exception as e:  # a raised call is a failed call
                cell.say("predict raised", error=repr(e)[:300])
                failed += 1
                return
        if out.shape != (len(batch),) or not np.isfinite(out).all():
            failed += 1
        else:
            rows_returned += len(batch)
            last = batch, out

    def traced_calls():
        for _ in range(int(traffic["trace_calls"])):
            one_call()

    window_start, walls, elapsed = timing.window(cell, one_call, traced_calls)
    if cell.trace:
        walls = spans.walls("bench/predict_call", window_start)
    window_compiles = cell.compiles.snapshot().programs - setup_compiles.programs
    cell.say("calls", calls=calls, batch_rows=batch_rows,
             call_s=timing.summary(walls))

    # ---- after the window: are the scores right? --------------------------------
    if last is None:
        raise RuntimeError("no call of the window returned scores")
    n = int(traffic["check_rows"])
    check, got = last[0][:n], last[1][:n]
    trees = reference.parse_model(text)
    want = reference.walk(trees, check)
    err = float(np.max(np.abs(got - want)))
    largest = float(np.abs(want).max())
    tol = float(traffic["score_rtol"]) * max(1.0, largest)
    walker_compiled = (None if None in (walker_before, walker_after)
                       else walker_after > walker_before)
    compared = {"max_abs_score_error": compare(err, "<=", tol)}
    checks = {
        "forest_has_the_trees_asked_for":
            len(trees) == int(traffic["forest_trees"]) == big.num_trees(),
        "device_scores_within_tol_of_float64_walk":
            within(compared["max_abs_score_error"]),
        "no_oom_event_or_ladder_step": sut.no_oom_so_far(),
        "no_compilation_in_window": window_compiles == 0,
    }
    if "device_walker" in traffic.get("expect", {}):
        checks["device_walker_as_expected"] = (
            None if walker_compiled is None
            else walker_compiled == bool(traffic["expect"]["device_walker"]))
    depth = max(reference.depth(t) for t in trees)
    notes, facts = table.setup_facts(cell, tab, setup_compiles,
                                     window_compiles)
    notes.update(max_abs_score_error=err, score_tol=tol,
                 max_abs_score=largest,
                 device_walker_compiled=walker_compiled,
                 forest_trees=len(trees), forest_depth=depth,
                 setup_s=setup_s, window_s=elapsed, calls=calls,
                 rows_returned=rows_returned)
    facts.update(calls=calls, batch_rows=batch_rows,
                 window_start=window_start, trees=len(trees), depth=depth,
                 features=int(tab.data["features"]))
    return Outcome(
        attempted=calls, failed=failed, checks=checks,
        end_to_end={"predict_rows_per_s": rows_returned / elapsed,
                    "setup_s": setup_s},
        facts=facts, notes=notes, compared=compared)
