"""Feature chunks of the histogram kernel's grid: how many times a call
sweeps the rows (`lgbm_hist_grid{axis="feature_chunks"}`, set by the learner
at layout from the kernel's own chunking arithmetic).  1 where the whole
accumulator fits the kernel's VMEM budget (32 stored columns at 255 bins),
3 at 96 stored columns; each further chunk rebuilds the per-slot operand
once more per row block.  None where the program sets no such gauge, and
where the kernel is not the chunking one (the gauge reads 0)."""

from benchmarks.lib import program_gauges


def from_snapshot(snap):
    return program_gauges.gauge(snap, "lgbm_hist_grid",
                                axis="feature_chunks") or None


def read(run):
    return from_snapshot(program_gauges.snapshot())
