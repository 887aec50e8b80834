"""Device milliseconds per iteration in the row partition's Pallas pass:
the events of the instructions named `%partition_rows.<n>` (the kernel of
`ops/partition.py`, one call per grower round) on the trace's `XLA Ops`
line, clipped to the window, per iteration and chip.  None where no such
instruction ran: a program whose partition is XLA fusions (`select`, the
parent of PR 33) has nothing to tell apart inside `grow_other_ms_per_iter`."""


def read(run):
    t0, t1 = run.window
    per_dev = [ev.select(lambda n: n.startswith("%partition_rows"))
               .clip(t0, t1).total() for ev in run.trace.ops.values()]
    if not per_dev or not sum(per_dev):
        return None
    return 1e3 * sum(per_dev) / len(per_dev) / run.facts["iterations"]
