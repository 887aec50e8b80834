"""Device milliseconds per chip and iteration in the data axis's
collectives: the executed `reduce-scatter`, `all-gather` and `all-reduce`
instructions (and their `-start` / `-done` halves where the compiler made
them asynchronous), found by the instruction's kind and not its name (JAX
names a `psum_scatter`'s instruction `%reduce_scatter.45`, a `psum`'s
`%psum.399`), summed inside the traced window, averaged over the chips.
The run's earlier lines get the table by kind: events, seconds.  None where
no such instruction ran (one chip, or the CPU, which has no device plane).
"""

import functools
import re

from benchmarks.lib import xplane

KIND = re.compile(r"^(reduce-scatter|all-gather|all-reduce)(-start|-done)?$")
ENCLOSING = {"while", "conditional", "call"}  # their events hold a body's


@functools.lru_cache(maxsize=None)  # a window repeats a few hundred names
def kind_of(name: str):
    """`%psum.399 = s32[] all-reduce(...)` -> `all-reduce`; None for an
    event that is no instruction."""
    parts = xplane.short_name(name).split(" ")
    return parts[1] if name.startswith("%") and len(parts) == 2 else None


def is_collective(name: str) -> bool:
    return bool(KIND.match(kind_of(name) or ""))


def split(run):
    """Per chip: (the collectives' events, every other instruction's that
    encloses no other), inside the window."""
    t0, t1 = run.window
    out = []
    for ev in run.trace.ops.values():
        ev = ev.clip(t0, t1)
        out.append((ev.select(is_collective),
                    ev.select(lambda n: not is_collective(n)
                              and kind_of(n) not in ENCLOSING)))
    return out


def read(run):
    if not run.trace.on_device:
        return None
    chips = split(run)
    total = sum(coll.total() for coll, _ in chips)
    if not total:
        return None
    by_kind = {}
    for coll, _ in chips:
        for name, dur in zip(coll.names, coll.dur):
            row = by_kind.setdefault(kind_of(name), [0, 0.0])
            row[0], row[1] = row[0] + 1, row[1] + float(dur)
    run.cell.say("collectives in the window, all chips",
                 columns=["kind", "events", "seconds"],
                 rows=sorted(([k, *v] for k, v in by_kind.items()),
                             key=lambda r: -r[2]), chips=len(chips))
    return 1e3 * total / len(chips) / run.facts["iterations"]
