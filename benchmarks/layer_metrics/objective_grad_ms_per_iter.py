"""Device milliseconds per iteration in the gradient program: `jit__pre`,
dispatched once an iteration before `jit_grow` (the objective's gradients
and hessians at the current scores, the bagging and feature masks).  Program
time from the trace's `XLA Modules` line, clipped to the window.  None where
no such program ran (a booster on the synchronous path, whose gradients are
computed on the host)."""


def read(run):
    t0, t1 = run.window
    pre = [ev.select(lambda n: n.startswith("jit__pre(")).clip(t0, t1).total()
           for ev in run.trace.modules.values()]
    if not pre or not sum(pre):
        return None
    return 1e3 * sum(pre) / len(pre) / run.facts["iterations"]
