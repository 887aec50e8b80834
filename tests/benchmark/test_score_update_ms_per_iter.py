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


# the cells whose traced run on the chip reports a score update program
# (my chip runs, PR 35)
REPORTED_IN = ["higgs-27m-255.train", "higgs-27m-63.train",
               "criteo-13m-67.train", "criteo-27m-67.train-data4",
               "mslr-7m-63.train-rank"]


def declared(name):
    with open(os.path.join(os.path.dirname(harness.BENCH_DIR),
                           "BENCHMARK.json")) as f:
        spec = json.load(f)
    return next(m for m in spec["per_layer"] if m["name"] == name)


def test_the_metric_is_declared_for_the_cells_that_report_it():
    assert declared(NAME) == {
        "name": NAME, "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "boosting_driver",
        "moves": "train_iters_per_s", "workloads": REPORTED_IN}


def test_the_histogram_kernels_metrics_are_declared_by_name():
    """The roofline over the trees' work and the step's share of the peak
    (PR 35), in every cell that trains, the second on the `device` layer;
    `hist_columns_per_dot` is looked up by name in its own file."""
    cells = declared("hist_build_ms_per_iter")["workloads"]
    assert len(cells) == 5 and set(REPORTED_IN) <= set(cells)
    assert declared("hist_kernel_roofline") == {
        "name": "hist_kernel_roofline", "unit": "%", "better": "higher",
        "source": "device_trace", "layer": "histogram_kernel",
        "moves": "train_iters_per_s", "workloads": cells}
    assert declared("train_step_mfu") == {
        "name": "train_step_mfu", "unit": "%", "better": "higher",
        "source": "device_trace", "layer": "device",
        "moves": "train_iters_per_s", "workloads": cells}
    assert declared("train_step_mfu")["layer"] == \
        declared("device_idle_share")["layer"]
