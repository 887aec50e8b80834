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

Which bound holds goes on an earlier line, and with it, where every call's
output still reads as `[features x bins, slots x planes]`, the share of the
MXU's peak that the dense contraction the calls were built as
(`lib/opcount.hist_contraction`) comes to: how full the MXU is inside the
formulation, a note with no claim on it.  None where the job states no
trees or no kernel ran; a device without published peaks is an error."""

import re

from benchmarks.lib import opcount, peaks

_OUT_COLUMNS = re.compile(r"^%\S+ = [a-z]+\d+\[\d+,(\d+)\]\S* custom-call\(")


def dense_mxu_share(facts, events, chip_seconds: float, peak_ops: float):
    """Percent of `peak_ops` that the calls' dense contractions come to,
    None where a call's output is not the `[., slots x planes]` one."""
    rows = facts["rows"] / (facts.get("data_shards") or 1)
    ops = 0
    for ev in events:
        for name in ev.names:
            m = _OUT_COLUMNS.match(name)
            if m is None:
                return None
            ops += opcount.hist_contraction(rows, facts["features"],
                                            facts["bins"], int(m.group(1)),
                                            planes=1)[0]
    return 100.0 * ops / len(events) / peak_ops / chip_seconds


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
                 bytes=work[1], dense_contraction_mxu_share=dense_mxu_share(
                     run.facts, events, chip_seconds, peak["bf16_flops"]))
    return share
