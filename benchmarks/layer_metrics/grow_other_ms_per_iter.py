"""Device milliseconds per iteration that the grow program (`jit_grow`)
spends outside the histogram kernel: split search, row partition, the
`while_loop`'s own glue.  Program time from the trace's `XLA Modules` line
less `hist_build_ms_per_iter`.  The scopes inside it (`split_search`) name
no instruction and so cannot be told apart in device events."""


def read(run):
    t0, t1 = run.window
    grow = [ev.select(lambda n: n.startswith("jit_grow(")).clip(t0, t1).total()
            for ev in run.trace.modules.values()]
    hist = run.metric("hist_build_ms_per_iter")
    if not grow or not sum(grow) or hist is None:
        return None
    return 1e3 * sum(grow) / len(grow) / run.facts["iterations"] - hist
