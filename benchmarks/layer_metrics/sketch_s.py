"""Seconds of bin finding in set-up: the program's `sketch` spans under
`dataset/construct` (sampling the rows, the per-feature quantile search),
on the host clock.  None where the program records no such span."""

from benchmarks.lib import program_spans


def read(run):
    return program_spans.setup_seconds(run, "sketch",
                                       under="dataset/construct")
