"""Seconds the learner took to lay the binned table out on the device
(transpose, padding, metadata planes): the program's `layout` span,
compiles and cache loads of its eager operations included.  None where
the program records no such span."""

from benchmarks.lib import program_spans


def read(run):
    return program_spans.setup_seconds(run, "layout")
