"""The plain reference of a ranking job: LambdaRank's gradients and NDCG in
float64 numpy, written from the equations and importing nothing of the
program.  With `lib/reference.py` (the trees as the model text states them)
it decides `correct` for job `train_rank`.

Per query with rows a..b, scores s, integer labels l, gains g = gain[l] and
c = 1 / maxDCG@max_position (0 where the query has no positive label):

    rank r_i   the row's place in the STABLE descending sort of s
    d_i        1 / log2(2 + r_i)
    for every ordered pair with l_i > l_j:
        D = s_i - s_j
        delta = (g_i - g_j) * |d_i - d_j| * c
        if norm and the query's best score != its worst:
            delta /= 0.01 + |D|
        p = 1 / (1 + exp(sigma * D))          (argument clipped to +-88)
        lambda_i -= sigma * delta * p         lambda_j += sigma * delta * p
        h_i += sigma^2 * delta * p * (1 - p)  h_j += the same
    if norm and S = 2 * sum_pairs sigma * delta * p > 0:
        every lambda and h of the query *= log2(1 + S) / S

Queries of one length are taken together as one [Q, L, L] block (a few
hundred numpy calls for a log of tens of thousands of queries, where a
loop over queries is tens of seconds), the blocks on a few threads.
"""

from concurrent.futures import ThreadPoolExecutor

import numpy as np

BLOCK_SLOTS = 1 << 22   # pair slots of one block: 32 MiB a float64 temporary
THREADS = 8


def default_label_gain(levels: int = 31) -> np.ndarray:
    """2^l - 1, the library's documented default."""
    return np.array([float((1 << i) - 1) for i in range(levels)])


def _blocks(group):
    """[(rows [Q, L] int64)] : the queries of each length, cut into blocks
    of at most BLOCK_SLOTS pair slots."""
    group = np.asarray(group, np.int64)
    starts = np.concatenate([[0], np.cumsum(group)[:-1]])
    out = []
    for L in np.unique(group):
        if L == 0:
            continue
        rows = starts[group == L][:, None] + np.arange(L)[None, :]
        per = max(1, BLOCK_SLOTS // int(L * L))
        out += [rows[i:i + per] for i in range(0, len(rows), per)]
    return out


def _threaded(fn, blocks, threads):
    with ThreadPoolExecutor(threads) as pool:
        return list(pool.map(fn, blocks))


def inverse_max_dcg(labels, gain, max_position: int):
    """[Q] from the labels [Q, L] of queries of one length."""
    k = min(max_position, labels.shape[1])
    top = -np.sort(-labels, axis=1)[:, :k]
    mdcg = (gain[top] / np.log2(2.0 + np.arange(k))).sum(axis=1)
    return np.where(mdcg > 0, 1.0 / np.where(mdcg > 0, mdcg, 1.0), 0.0)


def lambdas(score, label, group, *, sigmoid: float = 1.0, norm: bool = True,
            max_position: int = 20, label_gain=None, threads: int = THREADS):
    """(lambda, hessian), float64 [n], of every row at `score`."""
    score = np.asarray(score, np.float64)
    label = np.asarray(label).astype(np.int64)
    gain = default_label_gain() if label_gain is None else \
        np.asarray(label_gain, np.float64)
    lam = np.zeros(len(score))
    hes = np.zeros(len(score))

    def block(rows):
        L = rows.shape[1]
        if L < 2:
            return
        s, l = score[rows], label[rows]
        c = inverse_max_dcg(l, gain, max_position)
        order = np.argsort(-s, axis=1, kind="stable")
        s = np.take_along_axis(s, order, axis=1)
        l = np.take_along_axis(l, order, axis=1)
        g = gain[l]
        d = 1.0 / np.log2(2.0 + np.arange(L))
        valid = l[:, :, None] > l[:, None, :]
        D = s[:, :, None] - s[:, None, :]
        delta = ((g[:, :, None] - g[:, None, :])
                 * np.abs(d[:, None] - d[None, :])[None] * c[:, None, None])
        if norm:
            moving = (s[:, 0] != s[:, -1])[:, None, None]
            delta = np.where(moving, delta / (0.01 + np.abs(D)), delta)
        p = 1.0 / (1.0 + np.exp(np.clip(sigmoid * D, -88.0, 88.0)))
        pl = np.where(valid, sigmoid * delta * p, 0.0)
        ph = sigmoid * pl * (1.0 - p)
        lam_s = pl.sum(axis=1) - pl.sum(axis=2)
        hes_s = ph.sum(axis=1) + ph.sum(axis=2)
        if norm:
            S = 2.0 * pl.sum(axis=(1, 2))
            safe = np.where(S > 0, S, 1.0)
            factor = np.where(S > 0, np.log2(1.0 + safe) / safe, 1.0)
            lam_s, hes_s = lam_s * factor[:, None], hes_s * factor[:, None]
        back = np.take_along_axis(rows, order, axis=1)
        lam[back] = lam_s
        hes[back] = hes_s

    _threaded(block, _blocks(group), threads)
    return lam, hes


def lambdas_pair_by_pair(score, label, group, *, sigmoid=1.0, norm=True,
                         max_position=20, label_gain=None):
    """The same by a double loop over the pairs of every query: the
    equations read off line by line, for the tests of `lambdas`."""
    score = np.asarray(score, np.float64)
    label = np.asarray(label).astype(np.int64)
    gain = default_label_gain() if label_gain is None else \
        np.asarray(label_gain, np.float64)
    lam, hes = np.zeros(len(score)), np.zeros(len(score))
    a = 0
    for n in np.asarray(group, np.int64):
        rows = np.arange(a, a + n)
        a += n
        s, l = score[rows], label[rows]
        top = np.sort(l)[::-1][:max_position]
        mdcg = sum(gain[t] / np.log2(2.0 + i) for i, t in enumerate(top))
        if n < 2 or mdcg <= 0:
            continue
        order = sorted(range(n), key=lambda i: -s[i])   # sorted() is stable
        rank = np.empty(n, np.int64)
        rank[order] = np.arange(n)
        d = 1.0 / np.log2(2.0 + rank)
        ql, qh, S = np.zeros(n), np.zeros(n), 0.0
        for i in range(n):
            for j in range(n):
                if l[i] <= l[j]:
                    continue
                D = s[i] - s[j]
                delta = (gain[l[i]] - gain[l[j]]) * abs(d[i] - d[j]) / mdcg
                if norm and s.max() != s.min():
                    delta /= 0.01 + abs(D)
                p = 1.0 / (1.0 + np.exp(np.clip(sigmoid * D, -88.0, 88.0)))
                ql[i] -= sigmoid * delta * p
                ql[j] += sigmoid * delta * p
                qh[i] += sigmoid * sigmoid * delta * p * (1.0 - p)
                qh[j] += sigmoid * sigmoid * delta * p * (1.0 - p)
                S += 2.0 * sigmoid * delta * p
        if norm and S > 0:
            ql, qh = ql * np.log2(1.0 + S) / S, qh * np.log2(1.0 + S) / S
        lam[rows], hes[rows] = ql, qh
    return lam, hes


def ndcg_at_k(score, label, group, k: int, label_gain=None,
              threads: int = THREADS) -> float:
    """Mean NDCG@k over the queries, a query with no positive label
    counting 1 (the library's convention); ties by the stable sort."""
    score = np.asarray(score, np.float64)
    label = np.asarray(label).astype(np.int64)
    gain = default_label_gain() if label_gain is None else \
        np.asarray(label_gain, np.float64)

    def block(rows):
        kk = min(k, rows.shape[1])
        s, l = score[rows], label[rows]
        order = np.argsort(-s, axis=1, kind="stable")[:, :kk]
        disc = 1.0 / np.log2(2.0 + np.arange(kk))
        dcg = (gain[np.take_along_axis(l, order, axis=1)] * disc).sum(axis=1)
        inv = inverse_max_dcg(l, gain, k)
        return np.where(inv > 0, dcg * inv, 1.0)

    parts = _threaded(block, _blocks(group), threads)
    return float(np.concatenate(parts).mean())


def leaf_values_from(lam, hes, leaf, num_leaves: int, learning_rate: float):
    """What a tree grown on (lam, hes) states for each of its leaves, with
    no regularisation: -lr * sum(lam) / sum(hes) over the leaf's rows."""
    G = np.bincount(leaf, weights=lam, minlength=num_leaves)
    H = np.bincount(leaf, weights=hes, minlength=num_leaves)
    with np.errstate(divide="ignore", invalid="ignore"):
        return -learning_rate * G / H


def worst_leaf_value_error(tree: dict, leaf, lam, hes, learning_rate: float):
    """(worst absolute error of the tree's stated leaf values against
    `leaf_values_from`, its leaf); an empty or weightless leaf is itself
    the fault."""
    nl = tree["num_leaves"]
    want = leaf_values_from(lam, hes, leaf, nl, learning_rate)
    err = np.abs(tree["leaf_value"][:nl] - want)
    err = np.where(np.isfinite(err), err, np.inf)
    worst = int(np.argmax(err))
    return float(err[worst]), worst
