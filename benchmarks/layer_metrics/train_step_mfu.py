"""The training step's share of the chip's peak, in percent: the least time
a chip could take for the histogram work of the window's trees
(`hist_kernel_roofline`'s numerator: `lib/opcount.window_histogram_work`
against the published peaks) over the whole traced window.  Beside the
kernels' roofline it is the share that stays bounded when a later PR takes
a kernel off the path: whatever builds the histograms, the step cannot take
less than this.

It counts the histogram work alone: the ranking gradients, the row
partition, the split search and the score update are left out, so it
reads low, and lower than the roofline's share by what the step spends
outside the kernels.  None where the job states no trees, and on a trace
with no device plane (a rehearsal: no number of a CPU run is a device
number); a device without published peaks is an error."""

from benchmarks.lib import opcount, peaks


def read(run):
    work = opcount.window_histogram_work(run.facts)
    t0, t1 = run.window
    if work is None or not run.trace.on_device or t1 <= t0:
        return None
    peak = peaks.peaks_for(run.cell.devices[0].device_kind)
    share, _ = opcount.roofline(*work, t1 - t0, peak["bf16_flops"],
                                peak["hbm_bytes_per_s"])
    return share
