"""Seconds of set-up JAX spent tracing functions to jaxprs: the self
seconds of the program's `program/trace` spans (`lib/program_births.py`),
so a trace's inner traces are in it once and a compile or a lowering that
ran inside a trace is not.  Every process pays them, whatever the
persistent cache holds.  The run's earlier line has the births of set-up
by site, stage and enclosing span.  None where the program records no
such span."""

from benchmarks.lib import program_births


def read(run):
    births = program_births.of_setup(run)
    if births is None:
        return None
    run.cell.say(
        "program births in set-up by site",
        columns=["site", "stage", "programs", "seconds", "self_seconds",
                 "under"],
        rows=births.table(), spans=len(births.stages),
        inner_traces=sum(int(s.tags.get("inner", 0))
                         for s in births.stages))
    return births.self_seconds("program/trace") or None
