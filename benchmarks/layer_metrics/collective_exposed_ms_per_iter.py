"""Device milliseconds per chip and iteration in which a collective of the
data axis runs and no other instruction does on that chip
(`lib/xplane.exposed`, the collectives of `collective_ms_per_iter` against
every other instruction of the same chip; a `while` or a `conditional`
encloses its body's events and is no instruction of its own here).  A
synchronous collective holds the core, so all of it is exposed; an
asynchronous one is exposed where nothing was scheduled under it.  None
where no collective ran."""

from benchmarks.lib import xplane


def read(run):
    if not run.trace.on_device:
        return None
    chips = run.cell.load("layer_metrics", "collective_ms_per_iter").split(run)
    bare = sum(xplane.exposed(coll, others) for coll, others in chips)
    if not bare:
        return None
    return 1e3 * bare / len(chips) / run.facts["iterations"]
