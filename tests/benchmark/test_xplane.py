"""The trace reduction, on a trace small enough to reduce by hand and on a
trimmed trace recorded on a v5e (PR 22; `benchmarks/fixtures/`)."""

import os
import types

import numpy as np
import pytest

from benchmarks.lib import xplane
from benchmarks.lib.harness import BENCH_DIR, Run, load_module

HERE = os.path.dirname(os.path.abspath(__file__))
US = 1e-6


@pytest.fixture(scope="module")
def hand():
    return xplane.load(os.path.join(HERE, "synthetic_trace.textproto"))


@pytest.fixture(scope="module")
def v5e_train():
    return xplane.load(os.path.join(BENCH_DIR, "fixtures",
                                    "v5e_train_2iters.textproto"))


@pytest.fixture(scope="module")
def v5e_predict():
    return xplane.load(os.path.join(BENCH_DIR, "fixtures",
                                    "v5e_predict_1call.textproto"))


def fake_run(trace, window, facts, kind="TPU v5 lite", spans=()):
    said = []
    cell = types.SimpleNamespace(
        devices=[types.SimpleNamespace(device_kind=kind)],
        spans=types.SimpleNamespace(
            walls=lambda name, since=0.0: [w for n, w in spans if n == name]),
        load=lambda kind_, name: load_module(BENCH_DIR, kind_, name),
        say=lambda what, **f: said.append((what, f)))
    run = Run(cell, facts, trace, window)
    run.said = said
    return run


def reader(name):
    return load_module(BENCH_DIR, "layer_metrics", name)


# ---- interval arithmetic -------------------------------------------------------
@pytest.mark.parametrize("start, dur, want", [
    ([0, 5, 20], [10, 10, 5], [[0, 15], [20, 25]]),      # overlap merges
    ([0, 10], [10, 5], [[0, 15]]),                       # touching merges
    ([5, 0], [1, 2], [[0, 2], [5, 6]]),                  # unsorted input
    ([3], [0], []),                                      # empty interval
])
def test_union(start, dur, want):
    assert xplane.union(np.array(start, float), np.array(dur, float)) == want


def test_gaps_are_the_window_less_the_intervals():
    iv = [[0, 10], [20, 60], [70, 80]]
    assert xplane.gaps(iv, 0, 100) == [[10, 20], [60, 70], [80, 100]]
    assert xplane.gaps(iv, 5, 75) == [[10, 20], [60, 70]]
    assert xplane.gaps([], 2, 3) == [[2, 3]]
    assert xplane.length(iv) == 60


# ---- the hand trace ------------------------------------------------------------
def test_planes_and_lines_are_told_apart(hand):
    assert hand.on_device and set(hand.ops) == {0} and set(hand.modules) == {0}
    assert len(hand.ops[0]) == 5          # the async line is not an op line
    assert set(hand.host) == {"python", "other/1"}


def test_busy_union_and_idle_share(hand):
    t0, t1 = xplane.window_of(hand, "bench/window")
    assert (t0, t1) == (0.0, pytest.approx(100 * US))
    # A 10 + while 40 (its body lies inside it) + D 10
    assert xplane.busy_seconds(hand, t0, t1) == pytest.approx(60 * US)
    run = fake_run(hand, (t0, t1), {})
    assert reader("device_idle_share").read(run) == pytest.approx(40.0)
    # a window that cuts events counts only what lies inside it
    assert xplane.busy_seconds(hand, 5 * US, 30 * US) == pytest.approx(15 * US)


def test_self_time_takes_the_body_out_of_the_while(hand):
    own = xplane.self_times(hand.ops[0])
    by_name = dict(zip((n.split(" ")[0] for n in hand.ops[0].names), own))
    assert by_name["%while.7"] == pytest.approx(20 * US)
    assert by_name["%hist_build.3"] == pytest.approx(10 * US)
    top = xplane.top_device_ops(hand, 0.0, 100 * US, n=3)
    # the two executions of %fusion.1 are one entry; %fusion.2 is cut by n
    assert dict(top) == {"%fusion.1 fusion": pytest.approx(20 * US),
                         "%while.7 while": pytest.approx(20 * US),
                         "%hist_build.3 custom-call": pytest.approx(10 * US)}
    assert [v for _, v in top] == sorted((v for _, v in top), reverse=True)


def test_idle_gaps_go_to_the_innermost_span_of_the_driving_thread(hand):
    got = dict(xplane.idle_gaps_by_host_span(hand, 0.0, 100 * US))
    # [10,20] falls in bench/update (its inner span ended at 12);
    # [60,70] and [80,100] fall in bench/sync; the other thread is ignored
    assert got == {"bench/sync": pytest.approx(30 * US),
                   "bench/update": pytest.approx(10 * US)}


def test_scope_attribution_and_per_iteration_division(hand):
    run = fake_run(hand, (0.0, 100 * US), {"iterations": 2})
    assert reader("hist_build_ms_per_iter").read(run) == pytest.approx(0.005)
    # the grow program ran 60 us, 10 of them in the kernel, over 2 iterations
    assert reader("grow_other_ms_per_iter").read(run) == pytest.approx(0.025)


def test_exposed_part_of_a_collective(hand):
    coll = xplane.Events.of([("all-gather", 30e3, 15e3)])   # [30,45] us
    compute = hand.ops[0].select(lambda n: not n.startswith("%while"))
    # B covers [30,35] and C covers [40,45]: [35,40] is exposed
    assert xplane.exposed(coll, compute) == pytest.approx(5 * US)
    assert xplane.exposed(coll, xplane.Events()) == pytest.approx(15 * US)


def test_a_reader_that_finds_nothing_returns_nothing(hand):
    run = fake_run(hand, (0.0, 100 * US), {"calls": 1, "window_start": 0.0})
    for name in ("forest_walk_ms_per_call", "forest_walk_roofline",
                 "predict_host_ms_per_call"):
        assert reader(name).read(run) is None


# ---- the recorded v5e trace --------------------------------------------------------
# 65,536 rows x 28 features, 15 leaves, 63 bins, two iterations.  The figures
# beside each assertion were taken from the events with plain loops and a
# one-nanosecond time grid, not with the code under test.
TRAIN_WINDOW = (0.047007225, 0.056754515)   # bench/update #0 .. jit__post #1
TRAIN_FACTS = {"iterations": 2, "rows": 65536, "features": 28, "bins": 63}


def test_recorded_trace_has_the_names_the_readers_rely_on(v5e_train):
    assert v5e_train.on_device and set(v5e_train.ops) == {0}
    programs = {n.split("(")[0] for n in v5e_train.modules[0].names}
    assert programs == {"jit_copy", "jit__pre", "jit_grow", "jit__post"}
    kernel = v5e_train.ops[0].select(lambda n: n.startswith("%hist_build"))
    assert len(kernel) == 30 and all("custom-call(" in n for n in kernel.names)
    driver = xplane.host_line_with(v5e_train, "bench/")
    assert {"bench/update", "train/iteration"} <= set(driver.names)


def test_recorded_trace_reduces_to_the_figures_counted_by_hand(v5e_train):
    t0, t1 = TRAIN_WINDOW
    assert xplane.busy_seconds(v5e_train, t0, t1) == pytest.approx(
        8567675e-9, rel=1e-6)
    run = fake_run(v5e_train, TRAIN_WINDOW, TRAIN_FACTS)
    assert reader("device_idle_share").read(run) == pytest.approx(
        12.10198, rel=1e-5)
    assert reader("hist_build_ms_per_iter").read(run) == pytest.approx(
        6030982e-6 / 2, rel=1e-9)
    assert reader("grow_other_ms_per_iter").read(run) == pytest.approx(
        (8534230 - 6030982) * 1e-6 / 2, rel=1e-9)


# ---- the histogram work's two shares, on the recorded kernel's time ---------------
# three trees of 15 leaves; the window's two iterations grew trees 1 and 2,
# which histogram 150,000 + 140,000 rows in 15 + 15 histograms
HIST_FACTS = dict(TRAIN_FACTS, hist_rows_by_tree=[170000, 150000, 140000],
                  histograms_by_tree=[15, 15, 15], first_window_tree=1)
HIST_BYTES = 290000 * (28 + 8) + 30 * 28 * 63 * 12
KERNEL_S = 6030982e-9


def test_roofline_share_is_the_trees_work_over_the_kernels_time(v5e_train):
    run = fake_run(v5e_train, TRAIN_WINDOW, dict(HIST_FACTS))
    # 3 x 290,000 x 28 additions are 0.12 us of the MXU's peak, the bytes
    # 13.5 us of HBM: memory-bound, over 6.030982 ms of kernel time
    want = 100 * (HIST_BYTES / 819e9) / KERNEL_S
    assert reader("hist_kernel_roofline").read(run) == pytest.approx(
        want, rel=1e-12)
    assert want == pytest.approx(0.22422, rel=1e-4)
    what, said = run.said[0]
    assert what == "hist_kernel_roofline" and said["bound"] == "memory"
    assert (said["operations"], said["bytes"]) == (3 * 290000 * 28, HIST_BYTES)
    assert said["kernel_s_per_chip"] == pytest.approx(KERNEL_S, rel=1e-9)
    # nothing on the line is read from a call's operands or its output
    assert set(said) == {"bound", "kernel_s_per_chip", "operations", "bytes"}


def another_kernel(trace, rename, speed_up):
    """`trace` with every `%hist_*` call renamed and `speed_up` times
    shorter: what a later kernel's trace would hold."""
    ev = trace.ops[0]
    mine = np.array([n.startswith("%hist_") for n in ev.names])
    ops = xplane.Events([rename(n) if m else n for n, m in zip(ev.names, mine)],
                        ev.start, np.where(mine, ev.dur / speed_up, ev.dur))
    return xplane.Trace({0: ops}, trace.modules, trace.host, True)


OTHER_SIGNATURES = {
    # a prefetched count before the operands, a second output
    "a prefetched count and a second output": lambda n: n.replace(
        " custom-call(", " custom-call(s32[8]{0} %live, ").replace(
            " = f32[", " = (s32[8]{0}, f32[", 1),
    # what the kernel returns since PR 36: the histograms and, second, a
    # block's live rows (`ops/histogram.py`: `[F x Bp, K x S]`, `[nb]`)
    "the histograms and the blocks' live rows": lambda n: n.replace(
        " custom-call(", ", s32[8]{0:T(128)S(6)}) custom-call(", 1).replace(
            " = f32[", " = (f32[", 1),
    # a compaction pass of the layer, named as the layer's passes are
    "a pass named hist_pack": lambda n: n.replace("%hist_build", "%hist_pack"),
}


@pytest.mark.parametrize("how", sorted(OTHER_SIGNATURES))
def test_another_operand_list_at_half_the_time_reads_twice_the_share(
        v5e_train, how):
    """What the re-basing is for: the share does not depend on the call's
    operands or on rows x slots, so a kernel that does the same trees' work
    in half the time reads twice the share, not `None` and not over 100."""
    base = reader("hist_kernel_roofline").read(
        fake_run(v5e_train, TRAIN_WINDOW, dict(HIST_FACTS)))
    other = another_kernel(v5e_train, OTHER_SIGNATURES[how], 2.0)
    assert not any(n in v5e_train.ops[0].names
                   for n in other.ops[0].names if n.startswith("%hist_"))
    run = fake_run(other, TRAIN_WINDOW, dict(HIST_FACTS))
    # twice to the last digits (the window's clip adds start and duration)
    assert reader("hist_kernel_roofline").read(run) == pytest.approx(
        2 * base, rel=1e-12)
    assert reader("hist_build_ms_per_iter").read(run) == pytest.approx(
        1e3 * KERNEL_S / 2 / 2, rel=1e-9)


@pytest.mark.parametrize("shards", [2, 4])
def test_shards_divide_the_rows_and_keep_the_histograms_whole(v5e_train,
                                                              shards):
    run = fake_run(v5e_train, TRAIN_WINDOW,
                   dict(HIST_FACTS, data_shards=float(shards)))
    rows = 290000 // shards
    byts = rows * (28 + 8) + 30 * 28 * 63 * 12
    assert reader("hist_kernel_roofline").read(run) == pytest.approx(
        100 * (byts / 819e9) / KERNEL_S, rel=1e-12)
    assert run.said[0][1]["operations"] == 3 * rows * 28


def test_a_window_of_one_iteration_reads_that_trees_work_over_its_calls(
        v5e_train):
    """The kernels' events are clipped to the window and the work is the
    window's trees': the second iteration alone reads tree 2's 140,000 rows
    and 15 histograms over the 15 calls that end in it."""
    calls = v5e_train.ops[0].select(lambda n: n.startswith("%hist_build"))
    order = np.argsort(calls.start)
    cut = float(calls.start[order[15]]) - 1e-9
    window = (cut, TRAIN_WINDOW[1])
    run = fake_run(v5e_train, window,
                   dict(HIST_FACTS, iterations=1, first_window_tree=2))
    seconds = float(calls.dur[order[15:]].sum())
    byts = 140000 * (28 + 8) + 15 * 28 * 63 * 12
    assert reader("hist_kernel_roofline").read(run) == pytest.approx(
        100 * (byts / 819e9) / seconds, rel=1e-9)
    assert reader("hist_build_ms_per_iter").read(run) == pytest.approx(
        1e3 * seconds, rel=1e-9)


@pytest.mark.parametrize("facts", [
    dict(TRAIN_FACTS),                                   # a job that states none
    dict(HIST_FACTS, hist_rows_by_tree=[]),              # no tree parsed
    dict(HIST_FACTS, first_window_tree=3),               # none in the window
], ids=["no facts", "no trees", "no tree in the window"])
def test_without_the_trees_work_the_two_shares_say_nothing(v5e_train, facts):
    run = fake_run(v5e_train, TRAIN_WINDOW, facts)
    assert reader("hist_kernel_roofline").read(run) is None
    assert reader("train_step_mfu").read(run) is None
    assert run.said == []


def test_without_kernel_events_the_roofline_says_nothing(v5e_train):
    none = another_kernel(v5e_train, lambda n: n.replace("%hist_", "%other_"),
                          1.0)
    run = fake_run(none, TRAIN_WINDOW, dict(HIST_FACTS))
    assert reader("hist_build_ms_per_iter").read(run) is None
    assert reader("hist_kernel_roofline").read(run) is None
    # the step's share needs no kernel: it is what bounds a step without one
    assert reader("train_step_mfu").read(run) is not None


@pytest.mark.parametrize("name", ["hist_kernel_roofline", "train_step_mfu"])
def test_roofline_needs_published_peaks(v5e_train, name):
    run = fake_run(v5e_train, TRAIN_WINDOW, dict(HIST_FACTS), kind="TPU v9")
    with pytest.raises(KeyError, match="no published peaks"):
        reader(name).read(run)


def test_step_mfu_is_the_same_work_over_the_window(v5e_train):
    """A stated window of 9.747290 ms: the least time for the trees' work
    over it, under the kernel's share by the kernel's part of the window."""
    window_s = TRAIN_WINDOW[1] - TRAIN_WINDOW[0]
    run = fake_run(v5e_train, TRAIN_WINDOW, dict(HIST_FACTS))
    got = reader("train_step_mfu").read(run)
    assert got == pytest.approx(100 * (HIST_BYTES / 819e9) / window_s,
                                rel=1e-12)
    assert got == pytest.approx(
        reader("hist_kernel_roofline").read(run) * KERNEL_S / window_s)
    half = (TRAIN_WINDOW[0], TRAIN_WINDOW[0] + window_s / 2)
    assert reader("train_step_mfu").read(
        fake_run(v5e_train, half, dict(HIST_FACTS))) == pytest.approx(2 * got)
    cpu = xplane.Trace(v5e_train.ops, {}, {}, on_device=False)
    assert reader("train_step_mfu").read(
        fake_run(cpu, TRAIN_WINDOW, dict(HIST_FACTS))) is None


def test_recorded_predict_call(v5e_predict):
    # one call: bench/predict_call [44.297365, 1787.587107] ms, the walk
    # program ran 1644.777863 ms of it
    t0, t1 = 0.044297365, 1.787587107
    facts = {"calls": 1, "window_start": 0.0, "batch_rows": 65536,
             "trees": 40, "depth": 7, "features": 28}
    run = fake_run(v5e_predict, (t0, t1), facts,
                   spans=[("bench/predict_call", t1 - t0)])
    assert reader("forest_walk_ms_per_call").read(run) == pytest.approx(
        1644.777863, rel=1e-9)
    assert reader("predict_host_ms_per_call").read(run) == pytest.approx(
        1743.289742 - 1644.777863, rel=1e-6)
    # 65,536 rows x 40 trees x 7 levels x 24 bytes, and the rows' bins and
    # scores, at 819 GB/s, over 1.644777863 s
    byts = 65536 * 40 * 7 * 24 + 65536 * 28 * 4 + 65536 * 4
    assert reader("forest_walk_roofline").read(run) == pytest.approx(
        100 * (byts / 819e9) / 1.644777863)
    assert run.said[-1] == ("forest_walk_roofline", {
        "bound": "memory", "operations": 65536 * 40 * 7 * 4, "bytes": byts})


def test_short_names():
    assert xplane.short_name(
        "%hist_build.16 = f32[8192,125]{1,0:T(8,128)S(1)} custom-call(u8[1]"
        "{0} %a), custom_call_target=\"tpu_custom_call\""
    ) == "%hist_build.16 custom-call"
    assert xplane.short_name(
        "%while.108 = (pred[255]{0}, s32[255]{0}) while((pred[255]{0}, "
        "s32[255]{0}) %tuple), condition=%c, body=%b") == "%while.108 while"
    assert xplane.short_name("dot_general.7") == "dot_general.7"
