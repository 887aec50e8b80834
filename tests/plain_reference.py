"""A plain numpy reference of training on columns with missing values: what
`criteo-13m-67` (PR 27) adds to what the tests had.  Float64, row by row or
bin by bin, no kernels, nothing imported from the program.

* `expected_missing_type`, `bins_follow_the_rule`: how a column with NaN or
  zeros is binned under `use_missing` / `zero_as_missing` (the reference's
  bin.cpp FindBin / ValueToBin): NaN to the last bin of a NaN-missing column,
  to the zero bin otherwise; bins ordered as the values are.
* `histogram`: sums of (gradient, hessian, 1) by `np.add.at` over (leaf
  slot, column, bin).
* `best_split`: every (column, direction, threshold) of one leaf in float64,
  the missing mass sent left and right in turn, the reference's tie rules
  (feature_histogram.hpp FindBestThresholdSequence: direction -1 first and
  from the top bin down, a later candidate wins only by a strictly larger
  gain (larger by more than float64's rounding, `TIE`), so ties go to the larger threshold in direction -1, the smaller in
  +1, direction -1 across the two, and the lower column).
* `grow_tree`: leaf-wise best-first growth by `best_split`, children numbered
  as the reference numbers them (the left child keeps the leaf, the right is
  the next new leaf); `goes_left` is the decision rule on bins.
"""

import numpy as np

NONE, ZERO, NAN = 0, 1, 2
K_ZERO = 1e-35
K_EPSILON = 1e-15
# two candidates that part the rows alike have one gain; summed over other
# bins in another order it comes out a few ulps apart.  Such a pair is a tie
TIE = 1e-9


# ---- (a) binning ----------------------------------------------------------------
def expected_missing_type(values, use_missing=True, zero_as_missing=False):
    if not use_missing:
        return NONE
    if zero_as_missing:
        return ZERO
    return NAN if np.isnan(values).any() else NONE


def bins_follow_the_rule(values, bins, num_bin, missing_type, zero_bin):
    """None where `bins` is a lawful binning of `values`, else what is wrong.

    Lawful: a NaN sits in the last bin of a NaN-missing column and in the
    zero bin otherwise (a ZERO-missing column keeps its zeros and its NaN
    together there: that bin IS the missing one); present values sit in
    bins below the NaN bin, and a larger value never sits in a lower bin.
    """
    values, bins = np.asarray(values, np.float64), np.asarray(bins, np.int64)
    nan = np.isnan(values)
    if missing_type == NAN:
        if not (bins[nan] == num_bin - 1).all():
            return "a NaN outside the last bin of a NaN-missing column"
        if (bins[~nan] >= num_bin - 1).any():
            return "a present value in the NaN bin"
    elif not (bins[nan] == zero_bin).all():
        return "a NaN outside the zero bin of a column that is not NaN-missing"
    if not (bins[np.abs(values) <= K_ZERO] == zero_bin).all():
        return "a zero outside the zero bin"
    order = np.argsort(values[~nan], kind="stable")
    if (np.diff(bins[~nan][order]) < 0).any():
        return "a larger value in a lower bin"
    if bins.min() < 0 or bins.max() >= num_bin:
        return "a bin outside 0..num_bin-1"
    return None


# ---- (b) histogram ----------------------------------------------------------------
def histogram(bins, grad, hess, leaf_of_row, slot_leaves, num_bins):
    """[slots, columns, num_bins, 3] float64 sums of (g, h, 1) over the rows
    whose leaf is the slot's (a slot of leaf -1 is dead and stays zero)."""
    n, cols = bins.shape
    out = np.zeros((len(slot_leaves), cols, num_bins, 3), np.float64)
    slot_of_leaf = {int(leaf): k for k, leaf in enumerate(slot_leaves)
                    if leaf >= 0}
    slot = np.array([slot_of_leaf.get(int(leaf), -1) for leaf in leaf_of_row])
    rows = np.flatnonzero(slot >= 0)
    stats = np.stack([grad, hess, np.ones(n)], axis=1).astype(np.float64)
    for c in range(cols):
        np.add.at(out, (slot[rows], c, bins[rows, c]), stats[rows])
    return out


# ---- (c) best split ------------------------------------------------------------------
def _leaf_gain(g, h):
    return g * g / h


def best_split(hist, num_bin, missing_type, zero_bin, *, min_data_in_leaf=20,
               min_sum_hessian=1e-3):
    """The best split of one leaf from its [columns, bins, 3] float64
    histogram: a dict (feature, threshold, default_left, gain over the
    unsplit leaf, left / right sums and counts) or None where no candidate
    is lawful.  Each side is summed from its own bins."""
    cols = hist.shape[0]
    tot = hist[0].sum(axis=0)
    shift = _leaf_gain(tot[0], tot[1] + 2 * K_EPSILON)
    best = None
    for f in range(cols):
        nb, mt = int(num_bin[f]), int(missing_type[f])
        missing_bin = {NAN: nb - 1, ZERO: int(zero_bin[f])}.get(mt)
        real = np.array([b != missing_bin for b in range(nb)])
        own = hist[f, :nb] * real[:, None]
        missing = (hist[f, missing_bin] if missing_bin is not None
                   else np.zeros(3))
        at_or_under = np.cumsum(own, axis=0)                 # bins <= t
        from_top = np.cumsum(own[::-1], axis=0)[::-1]        # bins >= t
        above = np.vstack([from_top[1:], np.zeros((1, 3))])  # bins > t
        # the reference scans a column that has no missing type in
        # direction -1 alone; run both anyway, they agree and -1 keeps ties
        for direction in (-1, +1):
            last = nb - 2 - (1 if mt == NAN and direction == -1 else 0)
            thresholds = [t for t in range(last + 1) if t != missing_bin]
            if direction == -1:
                thresholds = thresholds[::-1]
            for t in thresholds:
                left, right = at_or_under[t], above[t]
                if direction == -1:
                    left = left + missing
                else:
                    right = right + missing
                if (left[2] < min_data_in_leaf or right[2] < min_data_in_leaf
                        or left[1] < min_sum_hessian
                        or right[1] < min_sum_hessian):
                    continue
                gain = _leaf_gain(left[0], left[1]) \
                    + _leaf_gain(right[0], right[1])
                if gain <= shift:
                    continue
                if best is None or gain - shift > best["gain"] * (1 + TIE):
                    best = dict(feature=f, threshold=t,
                                default_left=direction == -1,
                                gain=gain - shift, left=left, right=right)
    if best is not None and (num_bin[best["feature"]] <= 2
                             and missing_type[best["feature"]] == NAN):
        # feature_histogram.hpp:105-108: a column of one real bin and the
        # NaN bin states default_left=False whichever direction found it
        best["default_left"] = False
    return best


# ---- (d) the decision rule on bins, and the tree ------------------------------------------
def goes_left(bins_col, threshold, default_left, num_bin, missing_type,
              zero_bin):
    missing = {NAN: bins_col == num_bin - 1,
               ZERO: bins_col == zero_bin}.get(int(missing_type),
                                               np.zeros(len(bins_col), bool))
    return np.where(missing, default_left, bins_col <= threshold)


def grow_tree(bins, grad, hess, num_bin, missing_type, zero_bin, num_leaves,
              **limits):
    """Leaf-wise growth, one split at a time, always the leaf whose best
    split gains most (the lower leaf on a tie).  Returns (splits in the
    order made, each with its `leaf` and the new `right_leaf`; the leaf of
    every row; [leaves, 3] float64 sums of (g, h, 1) per leaf)."""
    n, num_bins = len(bins), int(max(num_bin))
    leaf_of_row = np.zeros(n, np.int64)

    def search(leaf):
        h = histogram(bins, grad, hess, leaf_of_row, [leaf], num_bins)[0]
        return best_split(h, num_bin, missing_type, zero_bin, **limits)

    candidates = {0: search(0)}
    splits = []
    while len(splits) < num_leaves - 1:
        live = {leaf: s for leaf, s in candidates.items() if s is not None}
        if not live:
            break
        leaf = min(live)
        for k in sorted(live):
            if live[k]["gain"] > live[leaf]["gain"] * (1 + TIE):
                leaf = k
        s = dict(live[leaf], leaf=leaf, right_leaf=len(splits) + 1)
        f = s["feature"]
        rows = np.flatnonzero(leaf_of_row == leaf)
        left = goes_left(bins[rows, f], s["threshold"], s["default_left"],
                         num_bin[f], missing_type[f], zero_bin[f])
        leaf_of_row[rows[~left]] = s["right_leaf"]
        splits.append(s)
        candidates[leaf] = search(leaf)
        candidates[s["right_leaf"]] = search(s["right_leaf"])
    stats = np.stack([grad, hess, np.ones(n)], axis=1).astype(np.float64)
    sums = np.zeros((len(splits) + 1, 3))
    np.add.at(sums, leaf_of_row, stats)
    return splits, leaf_of_row, sums
