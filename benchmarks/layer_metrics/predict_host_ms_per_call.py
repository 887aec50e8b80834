"""Host milliseconds per `predict` call outside the forest walk: the call's
wall (the benchmark's own span) less the device time of the walk program
in it — binning the rows on the host, the upload, the float64 copy back."""

import numpy as np


def read(run):
    walls = run.cell.spans.walls("bench/predict_call",
                                 run.facts["window_start"])
    walk = run.metric("forest_walk_ms_per_call")
    if not walls or walk is None:
        return None
    return 1e3 * float(np.mean(walls)) - walk
