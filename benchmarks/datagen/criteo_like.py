"""Click-log rows from a seed, shaped like the table of the reference's
data-parallel experiment (LightGBM docs/Experiments.rst:192-227: the Criteo
terabyte log, 13 integer and 26 categorical columns, the categorical ones
replaced by their click-through rate and their count over the first ten
days, 67 features).  No network here, so the rows are synthetic; every
distribution below is this file's own choice and is listed under the
configuration's `assumed`.

    columns  0-12  integer counts, floor(exp(N(mu_j, sigma_j))): from 8
                   distinct values (column 0) to far over 10^4 (column 12),
                   5 % to 60 % of every column's values exact zeros (so
                   the EFB search, which takes a column as a candidate
                   from 80 % of its rows in bin 0, has none), and NaN in
                   nine of them at the fixed rates of NAN_RATES
    columns 13-38  click-through rates in (0, 1): Beta with mean BASE_RATE,
                   concentration log-spaced from 20 to 2000
    columns 39-64  category counts: log-uniform integers on 1..10^7
    columns 65-66  standard normal (the source describes 13 + 26 x 2 = 65
                   columns; the other two are not described)

The schema (which column has which shape) is the deployment's and is the
same for every seed.  The seed draws the rows and the weights of the label
rule: Bernoulli of a logistic in log1p of the counts, the logit of some
rates, log10 of some category counts, and the MISSING INDICATORS of three
count columns (so the side a split sends its missing rows to carries
signal).  A fixed calibration slab of the seed scales the weights so that
the logit's spread is LOGIT_STD whatever the draw of the weights (the
attainable AUC is then near 0.77 for every seed) and solves the intercept
for a positive rate of BASE_RATE.  No two columns are mutually exclusive.

As `higgs_like`: slabs on threads into one preallocated float64 table, the
streams fixed by (seed, stream, slab), never by the thread count.
"""

from concurrent.futures import ThreadPoolExecutor

import numpy as np

SLAB_ROWS = 1 << 20
THREADS = 8
BASE_RATE = 0.035
N_COUNT, N_RATE, N_CAT, N_NORMAL = 13, 26, 26, 2
N_FEATURES = N_COUNT + N_RATE + N_CAT + N_NORMAL
NAN_RATES = (0.0, 0.0, 0.02, 0.05, 0.1, 0.2, 0.3, 0.45, 0.77)
CALIBRATION_ROWS = 1 << 16
LOGIT_STD = 1.0
LOGIT_CLIP = 4.0  # of a rate's logit about the base rate's: a smoothed rate

# floor(exp(N(mu, sigma))): the share of zeros is Phi(-mu / sigma), the
# largest value of 13M rows about exp(mu + 5.3 sigma)
COUNT_MU = np.array([0.30, -0.05, 0.70, 1.20, -0.20, 1.80, 0.50, 2.05, 1.00,
                     3.00, -0.30, 4.00, 4.80])
COUNT_SIGMA = np.array([0.35, 0.60, 0.80, 1.00, 1.10, 1.20, 1.30, 1.60, 1.70,
                        2.00, 1.90, 2.60, 3.00])
RATE_CONCENTRATION = np.geomspace(20.0, 2000.0, N_RATE)
CAT_LOG_MAX = np.log(1e7)
# columns whose missing indicator enters the label, and how strongly
MISSING_SIGNAL = {4: 0.9, 6: -0.7, 8: 0.6}


def _rule(seed: int):
    """The label rule from the seed alone: the weights of each group of
    columns, scaled on a fixed calibration slab to a logit of spread
    LOGIT_STD, and the intercept that gives that slab the base rate of
    positives in expectation (bisection on a monotone mean)."""
    rng = np.random.default_rng([seed, 0])
    w = {"count": rng.normal(size=N_COUNT) * 0.25,
         "rate": rng.normal(size=N_RATE) * 0.25,
         "cat": rng.normal(size=N_CAT) * 0.1,
         "normal": rng.normal(size=N_NORMAL) * 0.2,
         "intercept": 0.0}
    X = np.empty((CALIBRATION_ROWS, N_FEATURES), np.float64)
    _draw(np.random.default_rng([seed, 0, 1]), CALIBRATION_ROWS, X)
    scale = LOGIT_STD / _logit(X, w, missing_signal=False).std()
    for k in ("count", "rate", "cat", "normal"):
        w[k] = w[k] * scale
    z = _logit(X, w)
    lo, hi = -30.0, 30.0
    for _ in range(60):
        mid = 0.5 * (lo + hi)
        if np.mean(1.0 / (1.0 + np.exp(-(z + mid)))) < BASE_RATE:
            lo = mid
        else:
            hi = mid
    w["intercept"] = 0.5 * (lo + hi)
    return w


def _draw(rng, m: int, out: np.ndarray) -> None:
    """`m` rows of features into `out` [m, 67]."""
    c0, c1, c2 = N_COUNT, N_COUNT + N_RATE, N_COUNT + N_RATE + N_CAT
    z = rng.standard_normal(size=(m, N_COUNT))
    np.floor(np.exp(z * COUNT_SIGMA + COUNT_MU), out=out[:, :c0])
    u = rng.random(size=(m, N_COUNT))
    for j in range(N_COUNT):
        rate = NAN_RATES[j % len(NAN_RATES)]
        if rate:
            out[u[:, j] < rate, j] = np.nan
    out[:, c0:c1] = rng.beta(BASE_RATE * RATE_CONCENTRATION,
                             (1.0 - BASE_RATE) * RATE_CONCENTRATION,
                             size=(m, N_RATE))
    # Beta(a < 1, .) underflows to 0.0 or rounds to 1.0 once in ~10^8
    # draws; a rate is strictly inside (0, 1)
    np.clip(out[:, c0:c1], 1e-12, 1.0 - 1e-12, out=out[:, c0:c1])
    np.floor(np.exp(rng.random(size=(m, N_CAT)) * CAT_LOG_MAX),
             out=out[:, c1:c2])
    out[:, c2:] = rng.standard_normal(size=(m, N_NORMAL))


def _logit(X: np.ndarray, w: dict, missing_signal: bool = True):
    c0, c1, c2 = N_COUNT, N_COUNT + N_RATE, N_COUNT + N_RATE + N_CAT
    counts = X[:, :c0]
    nan = np.isnan(counts)
    z = (np.log1p(np.where(nan, 0.0, counts)) - 1.0) @ w["count"]
    r = X[:, c0:c1]
    base = np.log(BASE_RATE / (1.0 - BASE_RATE))
    z += np.clip(np.log(r / (1.0 - r)) - base, -LOGIT_CLIP, LOGIT_CLIP) \
        @ w["rate"]
    z += (np.log10(X[:, c1:c2]) - 3.5) @ w["cat"]
    z += X[:, c2:] @ w["normal"]
    if missing_signal:
        for j, strength in MISSING_SIGNAL.items():
            z += strength * nan[:, j]
    return z + w["intercept"]


def make(spec: dict, seed: int, rows: int, stream: int):
    """`rows` x 67 float64 features (NaN where a count is missing) and
    {0, 1} float64 labels.  `stream` separates tables drawn from one seed
    (0 the training table, 1 the hold-out); the label rule depends on the
    seed alone."""
    f = int(spec["features"])
    if f != N_FEATURES:
        raise ValueError(f"criteo_like draws {N_FEATURES} columns, the "
                         f"configuration asks for {f}")
    X = np.empty((rows, f), np.float64)
    y = np.empty(rows, np.float64)
    w = _rule(seed)
    starts = range(0, rows, SLAB_ROWS)

    def slab(i):
        lo = starts[i]
        hi = min(lo + SLAB_ROWS, rows)
        rng = np.random.default_rng([seed, 1 + stream, i])
        _draw(rng, hi - lo, X[lo:hi])
        z = _logit(X[lo:hi], w)
        y[lo:hi] = rng.random(size=hi - lo) < 1.0 / (1.0 + np.exp(-z))

    with ThreadPoolExecutor(THREADS) as pool:
        list(pool.map(slab, range(len(starts))))
    return {"X": X, "y": y}
