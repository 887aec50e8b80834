"""Traces of set-up that produced no program: per `ledger_jit` site and
function, the outermost `program/trace` spans less the `compile` spans
(the programs produced there, compiled or loaded), floored at 0, summed.
Each is a function traced for something other than a program (a gauge's
`jit.trace()`, an ahead-of-time `lower()`) or traced again for a program
it already has.  JAX reports a trace that its own trace cache answered
too, in microseconds, so the run's earlier line lists each such function
with the seconds of every one of its traces: two long ones are the work
done twice, a long and a short one the cache at work.  None where the
program records no trace span, or every trace led to a program."""

from benchmarks.lib import program_births


def read(run):
    births = program_births.of_setup(run)
    if births is None:
        return None
    rows = births.traced_more_than_produced()
    run.cell.say(
        "functions traced more often than programs of them were produced",
        columns=["site", "function", "traces", "programs",
                 "seconds_by_trace"],
        rows=rows)
    return sum(r[2] - r[3] for r in rows) or None
