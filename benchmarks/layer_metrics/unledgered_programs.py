"""Programs JAX produced in set-up outside every `ledger_jit` site: the
program's `compile` spans with site `(none)`, counted.  Each is an eager
`jnp` operation or a bare `jax.jit`, a program and a cache load the
compile ledger cannot name; the run's earlier lines list them by the name
JAX gives them and by the span they fell in.  None where the program
records no compile span or every one has a site."""

from collections import Counter

from benchmarks.lib import program_spans

NO_SITE = "(none)"


def read(run):
    spans = program_spans.setup_spans(run)
    compiles = spans and program_spans.named(spans, "compile")
    if not compiles:
        return None
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
