"""Higgs-shaped rows from a seed: dense standard-normal features and a
binary label with a nonlinear signal.

The label rule is the one `bench.make_data` has used since PR 1 (squares of
the first eight features plus a random linear form, logistic noise); it is
copied here so that the benchmark owns its inputs.  What differs: the rows
are drawn slab by slab into one preallocated float64 table, a few slabs at a
time on threads (numpy releases the GIL while it draws), so that ten million
rows cost seconds of set-up and no second copy of the table is made.  The
slab boundaries and the streams are fixed by (seed, stream, rows), never by
the thread count, so the same seed gives the same rows on any machine.
"""

from concurrent.futures import ThreadPoolExecutor

import numpy as np

SLAB_ROWS = 1 << 20
THREADS = 4


def make(spec: dict, seed: int, rows: int, stream: int):
    """`rows` x spec["features"] float64 features and {0, 1} float64 labels.

    `stream` separates tables drawn from one seed (0 the training table, 1
    the hold-out); the weights of the label rule depend on the seed alone,
    so every stream of a seed follows the same rule.
    """
    f = int(spec["features"])
    X = np.empty((rows, f), np.float64)
    y = np.empty(rows, np.float64)
    w = np.random.default_rng([seed, 0]).normal(size=f)
    starts = range(0, rows, SLAB_ROWS)

    def slab(i):
        lo = starts[i]
        hi = min(lo + SLAB_ROWS, rows)
        rng = np.random.default_rng([seed, 1 + stream, i])
        rng.standard_normal(out=X[lo:hi])
        z = ((X[lo:hi, :8] ** 2 - 1.0).sum(axis=1) * 0.3
             + X[lo:hi] @ w * 0.5)
        y[lo:hi] = z + rng.logistic(size=hi - lo) > 0

    with ThreadPoolExecutor(THREADS) as pool:
        list(pool.map(slab, range(len(starts))))
    return {"X": X, "y": y}
