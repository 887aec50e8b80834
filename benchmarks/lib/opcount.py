"""Operations and bytes of the device kernels' work, from shapes and from
the trees that were grown.

These are the yardstick of the roofline shares, so they count what the
algorithm has to do, not what one implementation happens to do on top of
it (padding rows, recomputation, a one-hot's zeros).

Histogram work of a tree (`tree_histogram_work`, what `hist_kernel_roofline`
and `train_step_mfu` stand on): a tree of `splits` splits needs the root's
histogram over every row and, for each split, the histogram of the smaller
child (the larger one is the parent's less the smaller's: LightGBM's
`serial_tree_learner.cpp:428-437`).  Over the `hist_rows` rows those
histograms hold between them (`lib/reference.histogrammed_rows`, from the
public model text), `features` columns, `bins` bins and `histograms`
histograms built,

    operations = 3 * hist_rows * features
    bytes      = hist_rows * (features * bin_bytes + 8)
                 + histograms * features * bins * 12

(one addition each to a bin's gradient, hessian and count; a histogrammed
row's bins and its float32 gradient and hessian read once; each histogram
written once as three float32 planes).  It is the same work whatever
implements it, so a kernel that stops contracting rows no histogram needs,
a pass beside it, or another kernel altogether is read on the same scale,
and no implementation passes 100 % while it reads every histogrammed row
once.

Forest walk (`ops/predict.py`): every row descends every tree, one node per
level; a level reads the node's five table entries and the row's bin of the
split feature, compares, and picks a child.

    operations = rows * trees * depth * 4      (compare, two selects, step)
    bytes      = rows * trees * depth * 6 * 4  (five node entries + one bin)
                 + rows * features * 4 + rows * 4
"""


def tree_histogram_work(hist_rows: float, features: int, bins: int,
                        histograms: int, bin_bytes: int = 1):
    ops = 3 * hist_rows * features
    byts = (hist_rows * (features * bin_bytes + 8)
            + histograms * features * bins * 12)
    return ops, byts


def window_histogram_work(facts: dict):
    """(operations, bytes) one chip has to do for the histograms of the
    window's trees, from what a training job states of them: the rows each
    tree histograms, dealt over the job's row shards (every chip builds
    every histogram whole, over its own rows); None where the job states
    no trees or none fell in the window."""
    rows_by_tree = facts.get("hist_rows_by_tree")
    if not rows_by_tree:
        return None
    first = int(facts["first_window_tree"])
    last = first + int(facts["iterations"])
    hist_rows = sum(rows_by_tree[first:last])
    if not hist_rows:
        return None
    return tree_histogram_work(
        hist_rows / (facts.get("data_shards") or 1), facts["features"],
        facts["bins"], sum(facts["histograms_by_tree"][first:last]))


def forest_walk(rows: int, trees: int, depth: int, features: int):
    steps = rows * trees * depth
    return steps * 4, steps * 6 * 4 + rows * features * 4 + rows * 4


def roofline(ops: int, byts: int, seconds: float, peak_ops: float,
             peak_bytes_per_s: float):
    """(share of the roofline in %, which bound holds) for one kernel that
    took `seconds` on a chip with the given peaks."""
    t_ops, t_bytes = ops / peak_ops, byts / peak_bytes_per_s
    least = max(t_ops, t_bytes)
    return 100.0 * least / seconds, "compute" if t_ops >= t_bytes else "memory"
