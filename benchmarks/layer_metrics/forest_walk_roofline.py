"""The forest-walk program's share of its roofline, in percent: the least
time the chip could take for the window's calls (`lib/opcount.forest_walk`
over the batch's rows, the forest's trees and its deepest tree's levels,
against the published peaks) over the device time of the walk program.  The
walk compares and selects on the vector units, whose peak no table
publishes; the MXU's bf16 peak stands in, and since a level moves 24 bytes
for 4 operations the memory bound holds either way.  Which bound holds goes
on an earlier line."""

from benchmarks.lib import opcount, peaks


def read(run):
    walk_ms = run.metric("forest_walk_ms_per_call")
    if walk_ms is None:
        return None
    facts = run.facts
    ops, byts = opcount.forest_walk(facts["batch_rows"], facts["trees"],
                                    facts["depth"], facts["features"])
    peak = peaks.peaks_for(run.cell.devices[0].device_kind)
    share, bound = opcount.roofline(ops, byts, walk_ms * 1e-3,
                                    peak["bf16_flops"],
                                    peak["hbm_bytes_per_s"])
    run.cell.say("forest_walk_roofline", bound=bound, operations=ops,
                 bytes=byts)
    return share
