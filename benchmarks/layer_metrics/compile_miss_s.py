"""Seconds of set-up in which the compiler itself ran: the program's
`compile` spans that the persistent cache did not answer (`cache=miss`),
summed.  `compile_s` beside it also counts what the cache's answers cost.
The run's earlier lines get the table by `ledger_jit` site: programs,
hits, misses, seconds.  None where the program records no compile span,
and where the cache answered every program (a line carries positive
values only)."""

from benchmarks.lib import program_spans


def read(run):
    spans = program_spans.setup_spans(run)
    compiles = spans and program_spans.named(spans, "compile")
    if not compiles:
        return None
    sites = {}
    for s in compiles:
        row = sites.setdefault(s.tags.get("site"), [0, 0, 0, 0.0])
        row[0] += 1
        row[1 if s.tags.get("cache") == "hit" else 2] += 1
        row[3] += s.seconds
    run.cell.say(
        "compiles in set-up by site",
        columns=["site", "programs", "hits", "misses", "seconds"],
        rows=sorted(([site, *row] for site, row in sites.items()),
                    key=lambda r: -r[4]),
        programs=len(compiles), seconds=sum(s.seconds for s in compiles))
    return sum(s.seconds for s in compiles
               if s.tags.get("cache") == "miss") or None
