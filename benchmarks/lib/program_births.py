"""The stages of the programs born in a run's set-up, for the per-layer
readers.

Under `tpu_telemetry=trace` the program's compile ledger records a span
for each stage of a program's birth that JAX reports: `program/trace`
(the function to a jaxpr), `program/lower` (the jaxpr to an MLIR module)
and `compile` (the module to an executable, or its load from the
persistent cache), each tagged with the `ledger_jit` site it is charged to
and the name JAX gives the function.  The spans nest as the stages do: a
trace a lowering rule asks for is that lowering's child, an eager op a
trace runs is that trace's.  A stage is *outermost* when none of its
ancestors is a stage; its *self* seconds are its length less its
children's, so self seconds summed over every stage are a wall.  A trace
directly inside a trace has no span: it is in the enclosing span's time
and in its `inner=` tag.  Read through `program_spans.setup_spans`, so cut
at the window's start; None where the program records no `program/trace`
span (the parent of the PR that added them among them).
"""

import re

from . import program_spans

STAGE_OF = {"program/trace": "trace", "program/lower": "lower",
            "compile": "compile"}
_WRAPPED = re.compile(r"^\w+\((.*)\)$")


def program(fun_name) -> str:
    """The function a stage's `fun_name` names: JAX calls the trace of
    `f` "f", its module and its executable "jit(f)"."""
    m = _WRAPPED.match(fun_name or "")
    return m.group(1) if m else (fun_name or "")


class Births:
    """The stage spans of a run's set-up among all its set-up spans."""

    def __init__(self, spans):
        self.by_id = {s.id: s for s in spans}
        self.stages = [s for s in spans if s.name in STAGE_OF]
        self.own = program_spans.self_seconds(spans)

    def ancestors(self, span):
        while span.parent_id in self.by_id:
            span = self.by_id[span.parent_id]
            yield span

    def outermost(self, span) -> bool:
        return not any(a.name in STAGE_OF for a in self.ancestors(span))

    def under(self, span) -> str:
        """The nearest enclosing span that is no stage."""
        return next((a.name for a in self.ancestors(span)
                     if a.name not in STAGE_OF), "(no span)")

    def self_seconds(self, name: str):
        """Summed self seconds of the stage spans called `name`."""
        return sum(self.own[s.id] for s in self.stages if s.name == name)

    def table(self) -> list:
        """[site, stage, programs, seconds, self seconds, under] per
        site, stage and enclosing span, the longest first: outermost
        events and their seconds, and every event's self seconds."""
        rows = {}
        for s in self.stages:
            key = (s.tags.get("site"), STAGE_OF[s.name], self.under(s))
            row = rows.setdefault(key, [0, 0.0, 0.0])
            if self.outermost(s):
                row[0] += 1
                row[1] += s.seconds
            row[2] += self.own[s.id]
        return sorted(([site, stage, *row, under]
                       for (site, stage, under), row in rows.items()),
                      key=lambda r: -r[3])

    def traced_more_than_produced(self) -> list:
        """[site, function, traces, programs, seconds of each trace] for
        every function traced at a site more often than a program of it
        was produced there, the most spare traces first.  A spare trace
        that took microseconds found the earlier one in JAX's own trace
        cache; one as long as the first was the work done twice."""
        traces, programs = {}, {}
        for s in self.stages:
            key = (s.tags.get("site"), program(s.tags.get("fun_name")))
            if s.name == "compile":
                programs[key] = programs.get(key, 0) + 1
            elif s.name == "program/trace" and self.outermost(s):
                traces.setdefault(key, []).append(s.seconds)
        rows = [[site, fun, len(secs), programs.get((site, fun), 0), secs]
                for (site, fun), secs in traces.items()
                if len(secs) > programs.get((site, fun), 0)]
        return sorted(rows, key=lambda r: (r[3] - r[2], -sum(r[4])))


def of_setup(run):
    """The births of a run's set-up; None where the program records no
    trace span."""
    spans = program_spans.setup_spans(run)
    if not spans or not program_spans.named(spans, "program/trace"):
        return None
    return Births(spans)
