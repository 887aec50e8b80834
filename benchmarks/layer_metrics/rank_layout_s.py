"""Seconds the ranking objective took to lay the queries out for the
device (the buckets by padded length, 1/maxDCG of every query): the
program's `rank/query_layout` spans in set-up, on the host clock.  None
where the program records no such span."""

from benchmarks.lib import program_spans


def read(run):
    return program_spans.setup_seconds(run, "rank/query_layout")
