"""Device milliseconds per `predict` call in the forest-walk program
(`jit__class_scores_kernel` on the trace's `XLA Modules` line), over the
calls of the traced window."""


def read(run):
    t0, t1 = run.window
    walk = [ev.select(lambda n: n.startswith("jit__class_scores_kernel("))
            .clip(t0, t1).total() for ev in run.trace.modules.values()]
    if not walk or not sum(walk):
        return None
    return 1e3 * sum(walk) / len(walk) / run.facts["calls"]
