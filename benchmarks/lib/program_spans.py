"""The program's own spans of a run's set-up, for the per-layer readers.

Under `tpu_telemetry=trace` (a traced run, `lib/table.py`) the program
buffers every span it closes: `dataset/construct` and its `sketch`,
`binning`, `ingest/stage`; `booster/init` and its `learner/init`, `layout`;
`train/iteration`; and one `compile` span per program JAX produced, tagged
with the `ledger_jit` site that asked for it.  They are read here through
`lightgbm_tpu.obs`, put on `time.perf_counter` seconds with the tracer's
`origin_ns()`, and cut at the run's `facts["window_start"]`: what ended
before the window is set-up.  Like `lib/sut.py` this reaches past the
public entry points, so it answers None where the program no longer has
a name (the parent of the PR that added the names among them), and a
reader that gets None reports nothing.
"""

from typing import NamedTuple, Optional


class Span(NamedTuple):
    id: int
    parent_id: Optional[int]   # None at a thread's root
    name: str
    start: float               # seconds on time.perf_counter
    end: float
    tags: dict

    @property
    def seconds(self) -> float:
        return self.end - self.start


def setup_spans(run):
    """The program's spans that ended before the run's window, oldest
    first; None where the program or the run cannot say."""
    window_start = run.facts.get("window_start")
    if window_start is None:
        return None
    try:
        from lightgbm_tpu import obs

        origin = obs.origin_ns() / 1e9
        spans = [Span(e["id"], e["parent_id"], e["name"],
                      origin + e["ts"] / 1e6,
                      origin + (e["ts"] + e["dur"]) / 1e6, e["tags"])
                 for e in obs.events() if e["kind"] == "span"]
    except (ImportError, AttributeError, KeyError):
        return None
    return sorted((s for s in spans if s.end <= window_start),
                  key=lambda s: s.start)


def named(spans, name: str, under: Optional[str] = None) -> list:
    """The spans called `name`; with `under`, only those with an ancestor
    of that name."""
    by_id = {s.id: s for s in spans}

    def has_ancestor(s):
        while s.parent_id in by_id:
            s = by_id[s.parent_id]
            if s.name == under:
                return True
        return False

    return [s for s in spans
            if s.name == name and (under is None or has_ancestor(s))]


def setup_seconds(run, name: str, under: Optional[str] = None):
    """Summed length of the run's set-up spans called `name` (see
    `named`), None where there is none."""
    found = named(setup_spans(run) or [], name, under)
    return sum(s.seconds for s in found) if found else None


def self_seconds(spans) -> dict:
    """id -> a span's length less its children's (a thread's spans nest,
    so the children of one span do not overlap)."""
    out = {s.id: s.seconds for s in spans}
    for s in spans:
        if s.parent_id in out:
            out[s.parent_id] -= s.seconds
    return out


def by_name(spans) -> list:
    """[name, spans, seconds, self seconds] per span name, longest
    first: the set-up table of a run."""
    own = self_seconds(spans)
    rows = {}
    for s in spans:
        row = rows.setdefault(s.name, [s.name, 0, 0.0, 0.0])
        row[1] += 1
        row[2] += s.seconds
        row[3] += own[s.id]
    return sorted(rows.values(), key=lambda r: -r[2])


def union(intervals) -> list:
    """Sorted, disjoint (start, end) pairs covering the same time."""
    out = []
    for a, b in sorted(i for i in intervals if i[1] > i[0]):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def overlap(cover, a: float, b: float) -> float:
    """Seconds of [a, b] that the disjoint intervals `cover` take."""
    return sum(max(0.0, min(b, y) - max(a, x)) for x, y in cover)
