"""Timing arithmetic shared by the jobs: one windowed loop, and the
median / quartile summary of its readings."""

import time

import numpy as np

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


def summary(values) -> dict:
    v = np.asarray(list(values), np.float64)
    if not len(v):
        return {"n": 0}
    q1, med, q3 = np.percentile(v, [25, 50, 75])
    return {"n": int(len(v)), "median": float(med), "q1": float(q1),
            "q3": float(q3), "min": float(v.min()), "max": float(v.max())}
