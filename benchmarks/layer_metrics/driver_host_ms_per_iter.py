"""Host milliseconds inside one `Booster.update()`, the median over the
window's iterations, from the benchmark's own span around each call.  On
the asynchronous path that is the time to enqueue an iteration; where the
objective runs on the host it is the whole host step."""

import numpy as np


def read(run):
    walls = run.cell.spans.walls("bench/update", run.facts["window_start"])
    return 1e3 * float(np.median(walls)) if walls else None
