"""The reader of the score update's device time (`score_update_ms_per_iter`,
the `jit__post(` events of the trace's `XLA Modules` line): on a trace of its
own with two score updates, one of them half outside the window; on the
benchmark's hand trace, which has one; on the trace recorded on a v5e; and
None where no such program ran."""

import json
import os

import pytest

from benchmarks.lib import harness, xplane
from tests.benchmark.test_xplane import (TRAIN_FACTS, TRAIN_WINDOW, US,
                                         fake_run, reader)

HERE = os.path.dirname(os.path.abspath(__file__))
NAME = "score_update_ms_per_iter"


@pytest.fixture(scope="module")
def own():
    return xplane.load(os.path.join(HERE, "score_update_trace.textproto"))


@pytest.mark.parametrize("window, iterations, want_us", [
    ((0.0, 100.0), 2, (10 + 6) / 2),     # the second one is cut at 100
    ((0.0, 110.0), 2, (10 + 12) / 2),    # both whole
    ((65.0, 100.0), 1, 5 + 6),           # the first one is cut at 65
    ((0.0, 60.0), 1, None),              # none inside the window
    ((71.0, 93.0), 1, None),             # between the two
])
def test_score_update_is_the_post_programs_inside_the_window(
        own, window, iterations, want_us):
    run = fake_run(own, (window[0] * US, window[1] * US),
                   {"iterations": iterations})
    got = reader(NAME).read(run)
    if want_us is None:
        assert got is None
    else:
        assert got == pytest.approx(want_us * 1e-3)


def test_the_window_is_the_benchmarks_own_span(own):
    assert xplane.window_of(own, "bench/window") == (
        0.0, pytest.approx(100 * US))


def test_on_the_benchmarks_hand_trace():
    hand = xplane.load(os.path.join(HERE, "synthetic_trace.textproto"))
    run = fake_run(hand, (0.0, 100 * US), {"iterations": 2})
    assert reader(NAME).read(run) == pytest.approx(0.005)   # [70,80] over 2
    # the grow program's time is the other reader's, and stays there
    assert reader("grow_other_ms_per_iter").read(run) == pytest.approx(0.025)


def test_on_the_recorded_v5e_trace():
    """65,536 rows, 15 leaves: the parent's gather.  The figure is the sum
    of the two `jit__post(` events' durations taken from the file with a
    plain loop, over 2 iterations."""
    trace = xplane.load(os.path.join(harness.BENCH_DIR, "fixtures",
                                     "v5e_train_2iters.textproto"))
    ev = trace.modules[0]
    by_hand = sum(d for n, s, d in zip(ev.names, ev.start, ev.dur)
                  if n.startswith("jit__post(")
                  and s >= TRAIN_WINDOW[0] and s + d <= TRAIN_WINDOW[1] + 1e-9)
    run = fake_run(trace, TRAIN_WINDOW, dict(TRAIN_FACTS))
    got = reader(NAME).read(run)
    assert by_hand > 0 and got == pytest.approx(1e3 * by_hand / 2, rel=1e-6)


def test_a_trace_with_no_score_update_reads_none(own):
    trace = xplane.Trace(
        ops=own.ops, host=own.host, on_device=True,
        modules={0: own.modules[0].select(
            lambda n: not n.startswith("jit__post("))})
    run = fake_run(trace, (0.0, 100 * US), {"iterations": 2})
    assert reader(NAME).read(run) is None
    empty = xplane.Trace(ops={}, modules={}, host=own.host, on_device=True)
    assert reader(NAME).read(fake_run(empty, (0.0, 100 * US),
                                      {"iterations": 2})) is None


def declared(name):
    with open(os.path.join(os.path.dirname(harness.BENCH_DIR),
                           "BENCHMARK.json")) as f:
        spec = json.load(f)
    train = [w["name"] for w in spec["workloads"] if w["traffic"] == "train"]
    return next(m for m in spec["per_layer"] if m["name"] == name), train


def test_the_metric_is_declared_for_the_train_cells():
    entry, train = declared(NAME)
    assert entry == {
        "name": NAME, "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "boosting_driver",
        "moves": "train_iters_per_s", "workloads": train}


def test_the_entry_before_it_is_as_it_was():
    """What `test_hist_columns_per_dot.py` asserts of `per_layer[-1]`, by
    name: that entry is no longer the last, so that test fails until a
    `benchmark` PR makes it a lookup by name (PERF.md §7)."""
    entry, train = declared("hist_columns_per_dot")
    assert entry == {
        "name": "hist_columns_per_dot", "unit": "columns",
        "better": "higher", "source": "program_counter",
        "layer": "histogram_kernel", "moves": "train_iters_per_s",
        "workloads": train}
