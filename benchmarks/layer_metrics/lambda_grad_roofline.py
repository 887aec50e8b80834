"""The ranking gradient pass's share of its roofline, in percent: the least
time the chip could take for one pass (`lib/opcount_rank.lambda_grad`, from
the rows and the program's count of valid pairs, against the published
peaks) over the time the trace gives the gradient program
(`objective_grad_ms_per_iter`).  The same work whatever implements it, so a
pass that moves toward its memory bound moves this.  The chip publishes no
vector-unit peak; the operations stand against the bf16 matrix peak, which
no elementwise pass can reach, so the share reads low by nature.  Which
bound held goes on an earlier line.  None where the program states no pair
count, no gradient program ran, or the device has no published peaks (the
CPU of a rehearsal)."""

from benchmarks.lib import opcount, opcount_rank, peaks, program_gauges


def read(run):
    ms = run.metric("objective_grad_ms_per_iter")
    pairs = program_gauges.gauge(program_gauges.snapshot(), "lgbm_rank_pairs",
                                 kind="valid")
    if not ms or not pairs:
        return None
    try:
        peak = peaks.peaks_for(run.cell.devices[0].device_kind)
    except KeyError:
        return None
    ops, byts = opcount_rank.lambda_grad(run.facts["rows"], pairs)
    share, bound = opcount.roofline(ops, byts, ms / 1e3, peak["bf16_flops"],
                                    peak["hbm_bytes_per_s"])
    run.cell.say("lambda_grad_roofline", bound=bound, pass_s=ms / 1e3,
                 operations=ops, bytes=byts)
    return share
