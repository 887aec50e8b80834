"""Share of the one-hot rows the histogram kernel builds that any row can
hit, in percent: the live columns' own bin counts over live columns x bins
padded to 8 (`lgbm_hist_bins{kind="live"|"stored"}`, set by the learner at
layout).  255 of 256 where every column fills its bins; lower where columns
of few distinct values sit beside full ones: what the kernel still
contracts for nothing after padding columns and dead slots went (PR 26).
None where the program sets no such gauge or the kernel is another."""

from benchmarks.lib import program_gauges


def from_snapshot(snap):
    live = program_gauges.gauge(snap, "lgbm_hist_bins", kind="live")
    stored = program_gauges.gauge(snap, "lgbm_hist_bins", kind="stored")
    if not live or not stored:
        return None
    return 100.0 * live / stored


def read(run):
    return from_snapshot(program_gauges.snapshot())
