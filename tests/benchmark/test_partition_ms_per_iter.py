"""The reader of the row partition's device time (`partition_ms_per_iter`,
the `%partition_rows*` custom calls of the trace's `XLA Ops` line): on a
trace of its own with two chips, one call cut by the window's end; that the
histogram kernel's readers still read the histogram kernel alone there; and
None on the traces recorded before the kernel existed."""

import json
import os

import pytest

from benchmarks.lib import harness, xplane
from tests.benchmark.test_xplane import (TRAIN_FACTS, TRAIN_WINDOW, US,
                                         fake_run, reader)

HERE = os.path.dirname(os.path.abspath(__file__))
NAME = "partition_ms_per_iter"


@pytest.fixture(scope="module")
def own():
    return xplane.load(os.path.join(HERE, "partition_trace.textproto"))


@pytest.mark.parametrize("window, iterations, want_us", [
    # chip 0: 6 + 8 + 5 (cut at 100); chip 1: 8 + 4; over two chips
    ((0.0, 100.0), 2, (19 + 12) / 2 / 2),
    ((0.0, 110.0), 2, (24 + 12) / 2 / 2),    # the third call whole
    ((43.0, 72.0), 1, (3 + 2 + 5 + 2) / 2),  # every call cut
    ((0.0, 39.0), 1, None),                  # before the first call
    ((79.0, 94.0), 1, None),                 # between the calls
])
def test_partition_is_the_kernels_calls_inside_the_window(
        own, window, iterations, want_us):
    run = fake_run(own, (window[0] * US, window[1] * US),
                   {"iterations": iterations})
    got = reader(NAME).read(run)
    if want_us is None:
        assert got is None
    else:
        assert got == pytest.approx(want_us * 1e-3)


def test_the_histogram_kernels_readers_do_not_count_it(own):
    """`hist_build_ms_per_iter` reads `%hist_build*` alone (28 + 24 on
    either chip), and `grow_other_ms_per_iter` is the grow program less
    that, so the partition's calls stay inside it."""
    t0, t1 = xplane.window_of(own, "bench/window")
    assert (t0, t1) == (0.0, pytest.approx(100 * US))
    run = fake_run(own, (t0, t1), {"iterations": 2})
    assert reader("hist_build_ms_per_iter").read(run) == pytest.approx(
        0.052 / 2)
    # jit_grow [10,100] on chip 0 and [10,90] on chip 1, over two chips
    assert reader("grow_other_ms_per_iter").read(run) == pytest.approx(
        (0.090 + 0.080) / 2 / 2 - 0.052 / 2)


@pytest.mark.parametrize("path, window, facts", [
    (os.path.join(HERE, "synthetic_trace.textproto"), (0.0, 100 * US),
     {"iterations": 2}),
    (os.path.join(harness.BENCH_DIR, "fixtures",
                  "v5e_train_2iters.textproto"), TRAIN_WINDOW, TRAIN_FACTS),
], ids=["hand", "recorded-v5e"])
def test_a_trace_without_the_kernel_reads_none(path, window, facts):
    """The hand trace and the one recorded on a v5e in PR 22 ran the
    partition as XLA fusions: the reader says nothing and does not raise,
    as it must on the parent of the PR that added the kernel."""
    run = fake_run(xplane.load(path), window, dict(facts))
    assert reader("hist_build_ms_per_iter").read(run) is not None
    assert reader(NAME).read(run) is None


def test_an_empty_trace_reads_none(own):
    empty = xplane.Trace(ops={}, modules={}, host=own.host, on_device=True)
    assert reader(NAME).read(fake_run(empty, (0.0, 100 * US),
                                      {"iterations": 2})) is None


# the cells whose traced run on the chip reports the partition's kernel
# (my chip runs, PR 35)
REPORTED_IN = ["higgs-27m-255.train", "higgs-27m-63.train",
               "criteo-13m-67.train", "criteo-27m-67.train-data4",
               "mslr-7m-63.train-rank"]


def test_the_metric_is_declared_for_the_cells_that_report_it():
    with open(os.path.join(os.path.dirname(harness.BENCH_DIR),
                           "BENCHMARK.json")) as f:
        spec = json.load(f)
    by_name = {m["name"]: m for m in spec["per_layer"]}
    assert by_name[NAME] == {
        "name": NAME, "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "grower",
        "moves": "train_iters_per_s", "workloads": REPORTED_IN}
    assert by_name[NAME]["layer"] == by_name["grow_other_ms_per_iter"]["layer"]
