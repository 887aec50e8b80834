"""The histogram kernels' share of their roofline, in percent: the least
time a chip could take for the histogram work of the window's trees
(`lib/opcount.window_histogram_work`: the rows those trees histogram, from
the public model text, a shard's share of them on a row-sharded job,
against the published peaks) over the time the trace gives the layer's
kernels per chip (`hist_build_ms_per_iter`'s events).

The work is the trees', not the kernel's: nothing is read from a call's
operands, so a kernel with another signature, a kernel that contracts only
the rows a histogram needs, or a pass beside it is read on the same scale.
The chip has no published vector-unit peak; the three additions a row and
column stand against the bf16 matrix peak, and the work is memory-bound in
every cell, so the share reads low by nature: today's kernel does each
addition as 2 x bins x planes multiply-adds over every row of the table at
every call.  What it is for is a number that rises with any honest gain in
the layer and cannot pass 100 % while every histogrammed row is read once.

Which bound holds goes on an earlier line.  None where the job states no
trees or no kernel ran; a device without published peaks is an error.

The line's earlier note of the MXU share of the dense contraction a call
was built as went in PR 38: since PR 36 a call contracts the sub-blocks that
hold a live row, not every row it sweeps, so a count over all its rows
(`rows x features x bins x slots`) is no longer what the MXU did, and over
the kernel's shorter time it would read above 100 %.  What a call still
contracts is `hist_rows_contracted_share`."""

from benchmarks.lib import opcount, peaks


def read(run):
    hist = run.cell.load("layer_metrics", "hist_build_ms_per_iter")
    events = hist.events(run)
    chip_seconds = sum(ev.total() for ev in events) / max(len(events), 1)
    work = opcount.window_histogram_work(run.facts)
    if work is None or not chip_seconds:
        return None
    peak = peaks.peaks_for(run.cell.devices[0].device_kind)
    share, bound = opcount.roofline(*work, chip_seconds, peak["bf16_flops"],
                                    peak["hbm_bytes_per_s"])
    run.cell.say("hist_kernel_roofline", bound=bound,
                 kernel_s_per_chip=chip_seconds, operations=work[0],
                 bytes=work[1])
    return share
