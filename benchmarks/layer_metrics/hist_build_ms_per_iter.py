"""Device milliseconds per iteration in the histogram layer's kernels: the
events of the custom calls named `%hist_*` (on a TPU the Pallas call under
the scope `hist_build`, `%hist_build.<n> = ... custom-call(...)`; a later
pass of the layer, such as one that packs a block's live rows, is named
`hist_<what>` and is counted with it), summed over the traced window, per
chip, and divided by its iterations."""


def events(run):
    """The layer's kernel events inside the window, a list per device."""
    t0, t1 = run.window
    return [ev.select(lambda n: n.startswith("%hist_")
                      and "custom-call(" in n).clip(t0, t1)
            for ev in run.trace.ops.values()]


def read(run):
    per_dev = [ev.total() for ev in events(run)]
    if not per_dev or not sum(per_dev):
        return None
    return 1e3 * sum(per_dev) / len(per_dev) / run.facts["iterations"]
