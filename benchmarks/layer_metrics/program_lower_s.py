"""Seconds of set-up JAX spent lowering jaxprs to MLIR modules: the self
seconds of the program's `program/lower` spans (`lib/program_births.py`;
a trace a lowering rule asked for is `program_trace_s`'s).  Paid by every
process before the persistent cache can be asked for the executable.
None where the program records no such span."""

from benchmarks.lib import program_births


def read(run):
    births = program_births.of_setup(run)
    return births and (births.self_seconds("program/lower") or None)
