"""Share of the pair slots the gradient pass computes that the equations
visit, in percent: ordered pairs of one query's rows with different labels
over the slots of the padded query layout
(`lgbm_rank_pairs{kind="valid"|"slots"}`, set by the objective when it lays
the queries out).  What the padding by length and the pairs of equal labels
cost.  None where the program sets no such gauge."""

from benchmarks.lib import program_gauges


def from_snapshot(snap):
    valid = program_gauges.gauge(snap, "lgbm_rank_pairs", kind="valid")
    slots = program_gauges.gauge(snap, "lgbm_rank_pairs", kind="slots")
    if not valid or not slots:
        return None
    return 100.0 * valid / slots


def read(run):
    return from_snapshot(program_gauges.snapshot())
