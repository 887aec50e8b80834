"""Programs JAX produced in set-up outside every `ledger_jit` site: the
program's `compile` spans with site `(none)`, counted.  Each is an eager
`jnp` operation or a bare `jax.jit`, a program and a cache load the
compile ledger cannot name; the run's earlier lines list them by the name
JAX gives them and by the span they fell in, after the table of every
compile span by site: programs, hits and misses of the persistent cache,
seconds (`compile_s` and `first_setup_s` are what show a cold run; a cached
one has no miss to time).  None where the program records no compile span
or every one has a site."""

from collections import Counter

from benchmarks.lib import program_spans

NO_SITE = "(none)"


def by_site(compiles) -> list:
    """[site, programs, hits, misses, seconds] per `ledger_jit` site, the
    longest first."""
    sites = {}
    for s in compiles:
        row = sites.setdefault(s.tags.get("site"), [0, 0, 0, 0.0])
        row[0] += 1
        row[1 if s.tags.get("cache") == "hit" else 2] += 1
        row[3] += s.seconds
    return sorted(([site, *row] for site, row in sites.items()),
                  key=lambda r: -r[4])


def read(run):
    spans = program_spans.setup_spans(run)
    compiles = spans and program_spans.named(spans, "compile")
    if not compiles:
        return None
    run.cell.say(
        "compiles in set-up by site",
        columns=["site", "programs", "hits", "misses", "seconds"],
        rows=by_site(compiles), programs=len(compiles),
        seconds=sum(s.seconds for s in compiles))
    loose = [s for s in compiles if s.tags.get("site") == NO_SITE]
    names = {s.id: s.name for s in spans}
    run.cell.say(
        "programs outside every ledger site",
        by_fun_name=Counter(s.tags.get("fun_name") for s in loose)
        .most_common(),
        by_span=Counter(names.get(s.parent_id, "(no span)") for s in loose)
        .most_common(),
        seconds=sum(s.seconds for s in loose))
    return len(loose) or None
