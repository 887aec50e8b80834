"""Device milliseconds per iteration in the score update: the program that
adds each row's leaf value to its score (`jit__post`, dispatched once an
iteration after `jit_grow`).  Program time from the trace's `XLA Modules`
line, clipped to the window.  None where no such program ran (a booster
on the single fused `step` program, whose score update is inside it)."""


def read(run):
    t0, t1 = run.window
    post = [ev.select(lambda n: n.startswith("jit__post(")).clip(t0, t1).total()
            for ev in run.trace.modules.values()]
    if not post or not sum(post):
        return None
    return 1e3 * sum(post) / len(post) / run.facts["iterations"]
