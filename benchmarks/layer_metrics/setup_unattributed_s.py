"""Seconds of set-up that no span of the program's claims: process start
(`cell.t0`) to the window's start, less the union of the program's
`dataset/construct`, `booster/init` and warm-up `train/iteration` spans
and the benchmark's own `bench/setup/make_data`.  What is left is the
import of JAX and the program, the start of the TPU, waits for the device
between those spans, and whatever still has no name.  So this plus the
union is the set-up wall by construction.  The run's earlier lines get
the remainder split by the benchmark's own set-up spans, and the
program's set-up spans by name with their self time.  None where the
program records none of the three."""

from benchmarks.lib import program_spans

ATTRIBUTED = ("dataset/construct", "booster/init", "train/iteration")
OWN = "bench/setup/make_data"


def read(run):
    spans = program_spans.setup_spans(run)
    claimed = spans and [s for s in spans if s.name in ATTRIBUTED]
    if not claimed:
        return None
    t0, t1 = run.cell.t0, run.facts["window_start"]
    bench = [r for r in run.cell.spans.rows
             if r[0].startswith("bench/setup/") and r[2] <= t1]
    cover = program_spans.union(
        [(s.start, s.end) for s in claimed]
        + [(a, b) for name, a, b in bench if name == OWN])
    left = (t1 - t0) - program_spans.overlap(cover, t0, t1)
    inside = {name: (b - a) - program_spans.overlap(cover, a, b)
              for name, a, b in bench if name != OWN}
    run.cell.say(
        "set-up by the program's spans",
        setup_wall_s=t1 - t0, attributed_s=(t1 - t0) - left,
        unattributed_s=left,
        unattributed_inside_bench_spans=inside,
        unattributed_outside_them=left - sum(inside.values()),
        columns=["span", "count", "seconds", "self_seconds"],
        rows=program_spans.by_name(spans))
    return left
