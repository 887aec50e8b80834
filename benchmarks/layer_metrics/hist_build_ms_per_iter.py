"""Device milliseconds per iteration in the histogram kernel: the events
of the instructions the `hist_build` scope names (on a TPU the Pallas
call, `%hist_build.<n> = ... custom-call(...)`), summed over the traced
window and divided by its iterations."""


def events(run):
    """The kernel's events inside the window, every device's in one list."""
    t0, t1 = run.window
    return [ev.select(lambda n: n.startswith("%hist_build")).clip(t0, t1)
            for ev in run.trace.ops.values()]


def read(run):
    per_dev = [ev.total() for ev in events(run)]
    if not per_dev or not sum(per_dev):
        return None
    return 1e3 * sum(per_dev) / len(per_dev) / run.facts["iterations"]
