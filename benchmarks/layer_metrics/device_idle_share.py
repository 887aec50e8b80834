"""Share of the traced window in which no operation ran on the device, in
percent: 100 * (1 - union of the device-op intervals / window), averaged
over the chips used.  Near 0 the host keeps the device fed; what fills the
rest is in the run's `breakdown.idle_gaps`."""

from benchmarks.lib import xplane


def read(run):
    t0, t1 = run.window
    if not run.trace.ops or t1 <= t0:
        return None
    return 100.0 * (1.0 - xplane.busy_seconds(run.trace, t0, t1) / (t1 - t0))
