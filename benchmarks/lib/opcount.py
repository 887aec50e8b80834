"""Operations and bytes of the two device kernels, from their shapes.

These are the yardstick of the roofline shares, so they count what the
algorithm as formulated has to do, not what one implementation happens to
do on top of it (padding rows, recomputation).

Histogram contraction (`ops/histogram.py`, the `pallas2` kernel): for every
row block the kernel multiplies a one-hot [bins, rows] matrix per feature
into the [slots x stat planes, rows] matrix of per-leaf-slot statistics on
the MXU.  One call over `rows` rows, `features` feature columns, `bins`
histogram bins, `slots` leaf slots and `planes` statistic planes (5 for the
hi/lo split of gradient and hessian plus the count, 3 otherwise) is

    operations = 2 * rows * features * bins * slots * planes
    bytes      = rows * (features * bin_bytes + planes * stat_bytes + 4)
                 + features * bins * slots * planes * 4

(the binned columns, the statistic planes and the int32 leaf id of every
row read once; the float32 accumulator written once).  The scatter-add
the contraction stands for needs only 3 * rows * features additions; the
one-hot formulation trades those for dense MXU work, and the share reported
is that of the formulation the kernel runs.

Forest walk (`ops/predict.py`): every row descends every tree, one node per
level; a level reads the node's five table entries and the row's bin of the
split feature, compares, and picks a child.

    operations = rows * trees * depth * 4      (compare, two selects, step)
    bytes      = rows * trees * depth * 6 * 4  (five node entries + one bin)
                 + rows * features * 4 + rows * 4
"""


def hist_contraction(rows: int, features: int, bins: int, slots: int,
                     planes: int, bin_bytes: int = 1, stat_bytes: int = 2):
    ops = 2 * rows * features * bins * slots * planes
    byts = (rows * (features * bin_bytes + planes * stat_bytes + 4)
            + features * bins * slots * planes * 4)
    return ops, byts


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
