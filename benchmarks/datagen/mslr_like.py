"""Search-log rows from a seed, shaped like the table of the reference's
learning-to-rank experiment (LightGBM docs/Experiments.rst, the MS LTR rows:
MSLR-WEB30K fold 1, 2,270,296 training rows x 137 columns in 18,919
queries).  No network here, so the rows are synthetic; every distribution
below is this file's own choice and is listed under the configuration's
`assumed`.

Queries.  The MULTISET of query lengths is fixed by the spec, not by the
seed: the `queries` quantiles of a lognormal of spread LEN_SIGMA, its
location solved so that they sum to `rows`, the lowest set to 1 and the
highest to `max_query_len` (the data set's documented extremes, 1 and 1,251
documents), the rounding's remainder spread one row at a time over the
middle quantiles.  The seed permutes which query gets which length, so every
seed has the same shapes in the program and another table.

Columns (the data set documents 136 features: five text streams, body,
anchor, title, url and whole document, times 25 kinds, plus 11 page-level
ones; the source's table counts 137 columns, the last is drawn as one more
page-level score).  Per stream, the 25 kinds as three families:

    10 counts   floor(exp(.)): covered terms, stream length, tf sums, ...
     5 ratios   logistic(.) in (0, 1): covered-term ratios, normalised tf
    10 scores   real: tf-idf, BM25, language-model scores

and the 12 page-level columns as 6 counts and 6 scores.  Every column is
`loc_j + w_j * t + u_j * q + sigma_j * noise`, pushed through its family's
map: `t` the document's latent relevance, `q` its query's latent offset
(some queries are easy: long documents, many matches, for every document),
the weights drawn from the seed.  No column is NaN; a count column's exact
zeros stay under 60 % of its rows (COUNT_LOC), below the 80 % at which the
EFB search takes a column as a candidate, so the search returns the trivial
plan.

Grades 0-4: thresholds on `t + GRADE_Q * q + noise` at the normal quantiles
that give about 52 / 32 / 13 / 2 / 1 %, so a query's offset moves all its
grades together (some queries hold no positive grade at all) and the grade
is a noisy monotone function of what the columns see.

As `criteo_like`: slabs (of whole queries) on threads into one preallocated
float64 table, the streams fixed by (seed, stream, slab), never by the
thread count.
"""

from concurrent.futures import ThreadPoolExecutor
from statistics import NormalDist

import numpy as np

SLAB_ROWS = 1 << 19
THREADS = 8
STREAMS, COUNTS, RATIOS, SCORES = 5, 10, 5, 10
PAGE_COUNTS, PAGE_SCORES = 6, 6
N_FEATURES = STREAMS * (COUNTS + RATIOS + SCORES) + PAGE_COUNTS + PAGE_SCORES
LEN_SIGMA = 0.62
GRADE_SHARES = (0.52, 0.32, 0.13, 0.02, 0.01)
GRADE_Q, GRADE_NOISE = 0.6, 0.8
COUNT_LOC = (0.2, 3.5)   # of log-counts, column by column within a family


def query_lengths(queries: int, rows: int, max_len: int) -> np.ndarray:
    """The fixed multiset (ascending): see the module's text."""
    if queries < 2 or rows < queries:
        raise ValueError(f"{queries} queries cannot hold {rows} rows")
    z = np.array([NormalDist().inv_cdf((i + 0.5) / queries)
                  for i in range(queries)])

    def at(mu):
        lens = np.clip(np.rint(np.exp(mu + LEN_SIGMA * z)), 1, max_len)
        lens[0], lens[-1] = 1, max_len
        return lens.astype(np.int64)

    lo, hi = 0.0, np.log(max_len)
    for _ in range(60):
        mid = 0.5 * (lo + hi)
        lo, hi = (mid, hi) if at(mid).sum() < rows else (lo, mid)
    lens = at(hi)
    # the remainder, a row at a time from the middle outwards
    order = np.argsort(np.abs(np.arange(queries) - queries // 2),
                       kind="stable")[:queries - 2]
    order = order[(order > 0) & (order < queries - 1)]
    over = int(lens.sum() - rows)
    step = -1 if over > 0 else 1
    i = 0
    while over:
        j = order[i % len(order)]
        if 1 <= lens[j] + step <= max_len:
            lens[j] += step
            over += step
        i += 1
    return np.sort(lens)


def _blocks():
    """(first column, end, family) of each run of columns of one family:
    0 count, 1 ratio, 2 score."""
    out, a = [], 0
    for _ in range(STREAMS):
        for width, kind in ((COUNTS, 0), (RATIOS, 1), (SCORES, 2)):
            out.append((a, a + width, kind))
            a += width
    out.append((a, a + PAGE_COUNTS, 0))
    out.append((a + PAGE_COUNTS, a + PAGE_COUNTS + PAGE_SCORES, 2))
    return out


def _kinds():
    """Per column its family."""
    return np.concatenate([np.full(b - a, kind) for a, b, kind in _blocks()])


def _rule(seed: int):
    """The columns' weights and the grade thresholds, from the seed alone."""
    rng = np.random.default_rng([seed, 0])
    kinds = _kinds()
    f = len(kinds)
    w = np.abs(rng.normal(size=f)) * 0.5 + 0.05       # all see `t`, some well
    w *= rng.choice([1.0, -1.0], size=f, p=[0.85, 0.15])
    u = rng.normal(size=f) * 0.4
    sigma = rng.uniform(0.6, 1.4, size=f)
    loc = np.where(kinds == 0, rng.uniform(*COUNT_LOC, size=f),
                   rng.normal(size=f))
    spread = np.sqrt(1.0 + GRADE_Q ** 2 + GRADE_NOISE ** 2)
    cuts = np.array([NormalDist().inv_cdf(p) * spread
                     for p in np.cumsum(GRADE_SHARES)[:-1]])
    return {"w": w, "u": u, "sigma": sigma, "loc": loc, "cuts": cuts}


def make(spec: dict, seed: int, rows: int, stream: int):
    """`rows` x 137 float64 features, float64 grades 0-4 and the int64
    query sizes (`group`, summing to `rows`).  `stream` separates tables
    drawn from one seed (0 the training table, 1 the hold-out); the rule
    depends on the seed alone.  A table of fewer rows than the spec's has
    its share of the spec's queries, by the same rule of lengths."""
    f = int(spec["features"])
    if f != N_FEATURES:
        raise ValueError(f"mslr_like draws {N_FEATURES} columns, the "
                         f"configuration asks for {f}")
    queries = max(2, round(rows * int(spec["queries"]) / int(spec["rows"])))
    lens = query_lengths(queries, rows, int(spec["max_query_len"]))
    group = np.random.default_rng([seed, 1 + stream]).permutation(lens)
    bounds = np.concatenate([[0], np.cumsum(group)])
    rule = _rule(seed)
    X = np.empty((rows, f), np.float64)
    y = np.empty(rows, np.float64)
    # slabs of whole queries, about SLAB_ROWS rows each
    cut = np.searchsorted(bounds, np.arange(0, rows, SLAB_ROWS))
    cut = np.unique(np.concatenate([cut, [queries]]))

    def slab(i):
        q0, q1 = cut[i], cut[i + 1]
        lo, hi = bounds[q0], bounds[q1]
        m = hi - lo
        rng = np.random.default_rng([seed, 1 + stream, 1 + i])
        t = rng.standard_normal(m)
        q = np.repeat(rng.standard_normal(q1 - q0), group[q0:q1])
        out = X[lo:hi]
        rng.standard_normal(out=out)
        out *= rule["sigma"]
        out += rule["loc"]
        out += t[:, None] * rule["w"]
        out += q[:, None] * rule["u"]
        for a, b, kind in _blocks():
            block = out[:, a:b]
            if kind == 0:
                np.minimum(block, 12.0, out=block)
                np.exp(block, out=block)
                np.floor(block, out=block)
            elif kind == 1:
                np.negative(block, out=block)
                np.exp(block, out=block)
                block += 1.0
                np.reciprocal(block, out=block)
        rel = t + GRADE_Q * q + GRADE_NOISE * rng.standard_normal(m)
        y[lo:hi] = np.searchsorted(rule["cuts"], rel)

    with ThreadPoolExecutor(THREADS) as pool:
        list(pool.map(slab, range(len(cut) - 1)))
    return {"X": X, "y": y, "group": group.astype(np.int64)}
