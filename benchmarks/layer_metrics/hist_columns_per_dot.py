"""Columns whose one-hots one dot of the histogram kernel stacks, G
(`lgbm_hist_grid{axis="columns_per_dot"}`, set by the learner at layout from
the function the kernel forms its groups by).  Every dot hands the MXU the
per-slot operand anew, so a call hands it over live / G times per lane
sub-block instead of once per column: 1 is the ungrouped kernel.  None
where the program sets no such gauge (the parent of the PR that added it),
and where the kernel is not the grouping one (the gauge reads 0)."""

from benchmarks.lib import program_gauges


def from_snapshot(snap):
    return program_gauges.gauge(snap, "lgbm_hist_grid",
                                axis="columns_per_dot") or None


def read(run):
    return from_snapshot(program_gauges.snapshot())
