"""The plain reference: host numpy code of the benchmark's own that decides
`correct`.  Nothing here imports the program.

* `parse_model` / `walk`: the trees as the public model text states them,
  walked row by row in float64 by the published decision rule
  (LightGBM `tree.h` NumericalDecision: missing routing first, then
  `value <= threshold` goes left).
* `histogrammed_rows`: the rows a tree's histograms hold between them, from
  its stated leaf counts: the work of `lib/opcount.tree_histogram_work`.
* `recount_first_tree`: tree 0 against the rows it was grown from — every
  leaf's row count, and its value from the labels alone.
* `auc`: rank AUC with ties at their mean rank.
"""

from concurrent.futures import ThreadPoolExecutor

import numpy as np

K_ZERO = 1e-35  # the reference's kZeroThreshold
_INT_FIELDS = ("split_feature", "decision_type", "left_child", "right_child",
               "leaf_count")
_FLOAT_FIELDS = ("threshold", "leaf_value")


class ModelTextError(ValueError):
    pass


def split_model_text(text: str):
    """(header, [tree block bodies without their `Tree=i` line], tail).

    `tail` starts at the `end of trees` line and carries whatever trailers
    the program appends (parameters, bin mappers), untouched.
    """
    first = text.find("\nTree=")
    end = text.find("\nend of trees")
    if first < 0 or end < 0:
        raise ModelTextError("model text has no tree section")
    blocks = []
    for chunk in text[first + 1:end].split("\nTree="):
        # the chunk's first line is what is left of its `Tree=i` line
        body = chunk.split("\n", 1)[1] if "\n" in chunk else ""
        blocks.append(body.strip("\n"))
    return text[:first + 1], blocks, text[end + 1:]


def parse_tree(body: str) -> dict:
    kv = dict(line.split("=", 1) for line in body.split("\n") if "=" in line)
    tree = {"num_leaves": int(kv["num_leaves"]),
            "num_cat": int(kv.get("num_cat", 0))}
    for key in _INT_FIELDS:
        tree[key] = np.array(kv.get(key, "").split(), np.int64)
    for key in _FLOAT_FIELDS:
        tree[key] = np.array(kv.get(key, "").split(), np.float64)
    return tree


def parse_model(text: str) -> list:
    _, blocks, _ = split_model_text(text)
    trees = [parse_tree(b) for b in blocks]
    if any(t["num_cat"] for t in trees):
        raise ModelTextError("the reference walker has no categorical rule")
    return trees


def child_counts(tree: dict) -> np.ndarray:
    """[2, splits]: the rows under the left and under the right child of
    every split, a leaf's from `leaf_count`, a split's the sum of its two.
    The model text numbers a split's children after it (LightGBM numbers
    splits in the order it makes them), which one pass from the last split
    to the root leans on."""
    splits = tree["num_leaves"] - 1
    kids = np.stack([tree["left_child"][:splits],
                     tree["right_child"][:splits]])
    if (kids >= 0).any() and (kids <= np.arange(splits))[kids >= 0].any():
        raise ModelTextError("a split's child is numbered before the split")
    under = np.zeros((2, splits), np.int64)
    for node in range(splits - 1, -1, -1):
        for side in (0, 1):
            child = kids[side, node]
            under[side, node] = (tree["leaf_count"][~child] if child < 0
                                 else under[:, child].sum())
    return under


def histogrammed_rows(tree: dict):
    """(rows, histograms): what growing `tree` has to histogram, whatever
    grew it.  The root's histogram holds every row; of a split's two
    children only the smaller one's is built from rows, the other is the
    parent's less that (LightGBM `serial_tree_learner.cpp:428-437`).  So

        rows = count(root) + sum over splits of min(count(left), count(right))

    with a histogram per split and one for the root.  A level's smaller
    children hold at most half the table between them, so `rows` is at
    most count(root) * (1 + depth / 2)."""
    if tree["num_leaves"] <= 1:
        counted = tree.get("leaf_count", ())
        return (int(counted[0]) if len(counted) else 0), 1
    under = child_counts(tree)
    return (int(under[:, 0].sum() + under.min(axis=0).sum()),
            int(tree["num_leaves"]))


def window_histogram_facts(trees: list, first_window_tree: int) -> dict:
    """What a training job puts into its `facts` for the readers of the
    histogram work: each tree's `histogrammed_rows`, and which tree the
    window began with."""
    rows, histograms = zip(*map(histogrammed_rows, trees)) if trees else ((), ())
    return {"hist_rows_by_tree": list(rows),
            "histograms_by_tree": list(histograms),
            "first_window_tree": int(first_window_tree)}


def leaf_index(tree: dict, X: np.ndarray) -> np.ndarray:
    """The leaf each row of float64 `X` ends in."""
    n = len(X)
    if tree["num_leaves"] <= 1:
        return np.zeros(n, np.int64)
    out = np.empty(n, np.int64)
    rows = np.arange(n)
    node = np.zeros(n, np.int64)
    while len(rows):
        v = X[rows, tree["split_feature"][node]]
        dt = tree["decision_type"][node]
        missing_type = (dt >> 2) & 3
        nan = np.isnan(v)
        v = np.where(nan & (missing_type != 2), 0.0, v)
        as_missing = (((missing_type == 1) & (np.abs(v) <= K_ZERO))
                      | ((missing_type == 2) & nan))
        left = np.where(as_missing, (dt & 2) != 0,
                        v <= tree["threshold"][node])
        node = np.where(left, tree["left_child"][node],
                        tree["right_child"][node])
        at_leaf = node < 0
        out[rows[at_leaf]] = ~node[at_leaf]
        rows, node = rows[~at_leaf], node[~at_leaf]
    return out


def walk(trees: list, X: np.ndarray) -> np.ndarray:
    """Raw score of each row: the sum of its leaf's value over all trees."""
    X = np.asarray(X, np.float64)
    score = np.zeros(len(X), np.float64)
    for t in trees:
        score += t["leaf_value"][leaf_index(t, X)]
    return score


def depth(tree: dict) -> int:
    """Levels from the root to the deepest leaf."""
    if tree["num_leaves"] <= 1:
        return 0
    deepest, todo = 0, [(0, 1)]
    while todo:
        node, d = todo.pop()
        for child in (tree["left_child"][node], tree["right_child"][node]):
            if child < 0:
                deepest = max(deepest, d)
            else:
                todo.append((int(child), d + 1))
    return deepest


def leaf_index_threaded(tree: dict, X: np.ndarray, threads: int = 4):
    """`leaf_index` over row slabs on a few threads, for tables of millions
    of rows (numpy's gathers release the GIL)."""
    cuts = np.linspace(0, len(X), threads + 1).astype(np.int64)
    with ThreadPoolExecutor(threads) as pool:
        parts = pool.map(lambda i: leaf_index(tree, X[cuts[i]:cuts[i + 1]]),
                         range(threads))
        return np.concatenate(list(parts))


def recount_first_tree(tree: dict, leaf: np.ndarray, y, learning_rate: float):
    """Tree 0 of a binary-logloss model against a recount from the labels.

    With no weights and no regularisation the first tree is grown at the
    constant score logit(p), p the label mean, so a leaf holding n rows of
    which `pos` are positive has value
    logit(p) - lr * (n*p - pos) / (n*p*(1-p)), whatever produced it.
    Returns (most rows any leaf's stated count is off by, worst absolute
    value error, its leaf).
    """
    nl = tree["num_leaves"]
    n = np.bincount(leaf, minlength=nl)
    count_off = int(np.abs(n - tree["leaf_count"][:nl]).max())
    p = float(np.mean(y))
    pos = np.bincount(leaf, weights=y, minlength=nl)
    with np.errstate(divide="ignore", invalid="ignore"):
        want = (np.log(p / (1.0 - p))
                - learning_rate * (n * p - pos) / (n * p * (1.0 - p)))
    err = np.abs(tree["leaf_value"][:nl] - want)
    err = np.where(n > 0, err, np.inf)  # an empty leaf is itself the fault
    worst = int(np.argmax(err))
    return count_off, float(err[worst]), worst


def auc(score, y) -> float:
    """Area under the ROC curve by ranks, ties at their mean rank."""
    score = np.asarray(score, np.float64)
    y = np.asarray(y) > 0
    order = np.argsort(score, kind="stable")
    s = score[order]
    # mean rank of each run of equal scores
    start = np.flatnonzero(np.r_[True, s[1:] != s[:-1]])
    end = np.r_[start[1:], len(s)]
    mean_rank = (start + end + 1) / 2.0
    ranks = np.empty(len(s), np.float64)
    ranks[order] = np.repeat(mean_rank, end - start)
    n_pos = int(y.sum())
    n_neg = len(y) - n_pos
    if n_pos == 0 or n_neg == 0:
        raise ValueError("AUC needs both classes")
    return float((ranks[y].sum() - n_pos * (n_pos + 1) / 2.0)
                 / (n_pos * n_neg))
