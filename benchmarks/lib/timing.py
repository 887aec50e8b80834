"""Timing shared by the jobs: one windowed loop, the window of a job around
it (traced or untraced), the window of the jobs that train, and the median
/ quartile summary of its readings."""

import time
import traceback
from dataclasses import dataclass

import numpy as np

from . import device
from .spans import WINDOW_SPAN

STOP = "stop"  # a step returns it to end its window early


def run_window(step, seconds: float):
    """Call `step()` until the host clock passes `seconds` or a step
    returns STOP; returns the wall of every call, and the time from the
    first call's start to the last one's end.  `step` must end with its
    work done: JAX returns before the device finishes, so a step that only
    enqueues ends with a wait for the device."""
    walls = []
    start = time.perf_counter()
    while True:
        t0 = time.perf_counter()
        stop = step() is STOP
        now = time.perf_counter()
        walls.append(now - t0)
        if stop or now - start >= seconds:
            return walls, now - start


def window(cell, step, traced):
    """A job's window: (its start on `time.perf_counter`, the wall of every
    reading, the time it measured).  Untraced, `step()` until the host clock
    passes `cell.seconds` (`run_window`); traced, one call of `traced()`
    under the profiler and the window's span, whose wall is the one
    reading."""
    start = time.perf_counter()
    if cell.trace:
        with cell.spans.traced_window(cell.out_dir):
            traced()
        walls = cell.spans.walls(WINDOW_SPAN, start)
        return start, walls, walls[0]
    walls, elapsed = run_window(step, cell.seconds)
    return start, walls, elapsed


@dataclass
class IterationWindow:
    """What `iteration_window` measured."""
    start: float       # on time.perf_counter: the readers' `window_start`
    window_s: float
    iterations: int
    failed: int
    group_iters: int
    walls: list        # seconds, one a group of `group_iters` iterations
    slowest: dict      # the longest group: where the host spent it

    def facts(self) -> dict:
        """The window as the result's line carries it."""
        return {"iterations": self.iterations, "window_s": self.window_s,
                "iteration_ms": summary(1e3 * w / self.group_iters
                                        for w in self.walls),
                "slowest_group": self.slowest}


def iteration_window(cell, update) -> IterationWindow:
    """The window of a job that trains: `update()` (one boosting iteration;
    truthy where no leaf could be split, so nothing trained) in groups of
    the traffic's `group_iters`, each ended by a wait for the device, until
    the host clock passes `cell.seconds`; a traced run measures one group
    of `trace_iters` under the profiler instead.  An iteration that trains
    nothing is a failed one; so is a call or a wait that raises (a device
    failure may only show at the wait), and it ends the window."""
    spans, traffic = cell.spans, cell.traffic
    n = int(traffic["trace_iters" if cell.trace else "group_iters"])
    failed = iterations = 0

    def group():
        nonlocal failed, iterations
        try:
            for _ in range(n):
                iterations += 1
                with spans.span("bench/update"):
                    if update():
                        failed += 1
            with spans.span("bench/sync"):
                device.sync()
        except Exception as e:
            traceback.print_exc()
            cell.say("an iteration raised", error=repr(e)[:300])
            failed += 1
            return STOP

    start, walls, elapsed = window(cell, group, group)
    win = IterationWindow(start, elapsed, iterations, failed, n, walls,
                          slowest_group(spans, start))
    rates = [n / w for w in walls]
    first = int(traffic["warmup_iters"])
    cell.say("groups", group_iters=n, iterations_per_s=summary(rates),
             by_group=rates, first_iteration_index=first,
             last_iteration_index=first + iterations, **win.facts())
    return win


def slowest_group(spans, since: float) -> dict:
    """The longest group of a window by its `bench/update` and `bench/sync`
    spans: which it was, and its milliseconds in `update()` (the host's
    dispatch) and in the wait for the device.  A stalled iteration shows
    here as the host's or the device's."""
    groups, update = [], 0.0
    for name, t0, t1 in spans.rows:
        if t0 < since:
            continue
        if name == "bench/update":
            update += t1 - t0
        elif name == "bench/sync":
            groups.append((update, t1 - t0))
            update = 0.0
    if not groups:
        return {}
    i = max(range(len(groups)), key=lambda g: sum(groups[g]))
    return {"index": i, "update_ms": 1e3 * groups[i][0],
            "sync_ms": 1e3 * groups[i][1]}


def summary(values) -> dict:
    v = np.asarray(list(values), np.float64)
    if not len(v):
        return {"n": 0}
    q1, med, q3 = np.percentile(v, [25, 50, 75])
    return {"n": int(len(v)), "median": float(med), "q1": float(q1),
            "q3": float(q3), "min": float(v.min()), "max": float(v.max())}
