"""LambdaRank's pairwise gradients as one device function of the scores
(reference src/objective/rank_objective.hpp:23-254; the float64 oracle of
the same equations is `models/objectives_ext.LambdarankNDCG._one_query`).

Queries are ragged (one row to thousands), a device program is not, so the
queries are grouped by PADDED LENGTH into buckets, each a dense slab:

    layout, inverse_max_dcgs, stats = query_layout(
        boundaries, labels, label_gain, max_position)
    lam, hes = gradients(scores, layout, sigmoid=, norm=)

Everything that differs by data set is an array of `layout` (an ARGUMENT
of the training step, never a constant of its program); the shapes are a
function of the multiset of query lengths alone, through two rules that no
option touches: `padded_length` and `padded_count`.

Inside a bucket every row meets every other row of its query once, as
`self` and as `other`:

* rank: a row's place in the stable descending sort of its query's scores
  is the count of rows that beat it (higher score, or the same score and
  an earlier row), so the sort never moves a value: the pair pass works in
  row order and the sums come back where the rows are;
* pair pass: for the ordered pair (hi, lo) of two rows with different
  labels, both rows see the same `sigma * delta * p`; `self` takes it with
  the sign of its side, so lambda, hessian and the query's sum of lambdas
  are three sums over `other` of one pass, and no pair writes to two rows.

Short buckets keep the queries on the minor (lane) axis, [L, L, Q]; from
`LANE` rows on a query's own rows fill it, [Q, L, L].

Between the row vectors and the slabs nothing is gathered row by row (XLA's
gather costs a TPU 7.5 ns an element: 165 ms an iteration for 6.8M rows,
six times the pair pass; PERF.md §5).  A query is a contiguous range of
rows, so its slab row is cut from the whole `LANE`-row tiles that hold the
range (a gather of tiles, 0.4 ms for 170,000 of them) and moved left by the
range's offset in its first tile, in seven conditional static shifts (one
per bit of the offset); the sums go back the same way, moved right, and the
tiles of all queries are added into the row vector's tiles, where each row
gets its own query's sum and exact zeros from its neighbours'.
"""

from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

LANE = 128        # the minor axis of a TPU's registers
MIN_LENGTH = 8    # and the one above it


def padded_length(length: int) -> int:
    """The rule for a query's slab: the next of 8, 16, 24, 32, 48, 64, 96,
    128, ... (2^k and 3 * 2^(k-1)), so a pair matrix wastes under 1.8x."""
    p = MIN_LENGTH
    while length > p:
        if length <= p + p // 2 and p > MIN_LENGTH:
            return p + p // 2
        p *= 2
    return p


def padded_count(count: int, short: bool) -> int:
    """The rule for a bucket's queries: up to the next m * 2^k with m in
    8..15 (under an eighth more); a short bucket, whose queries lie along
    the lanes, to whole registers."""
    floor = LANE if short else MIN_LENGTH
    count = max(count, floor)
    step = max(1 << max(count.bit_length() - 4, 0), floor if short else 1)
    return -(-count // step) * step


def is_short(length: int) -> bool:
    return length < LANE


def window_tiles(length: int) -> int:
    """Tiles of `LANE` rows that a run of `length` rows can touch, wherever
    it starts."""
    return (length + LANE - 2) // LANE + 1


def query_layout(boundaries: np.ndarray, labels: np.ndarray,
                 label_gain: np.ndarray, max_position: int
                 ) -> Tuple[Dict[str, Dict[str, np.ndarray]], np.ndarray,
                            Dict[str, float]]:
    """(buckets, inverse_max_dcgs, stats).  `buckets["len<L>"]` holds one
    padded length's arrays (host numpy), Q its queries by `padded_count`:

        tile   int32 [Q, window_tiles(L)]  the `LANE`-row tiles of the row
                      vector that hold the query's rows, and the ones after
        off    int32 [Q]  the query's first row within its first tile
        label  f32    the slab (short: [L, Q], long: [Q, L]): each row's
                      label, -1 at padding
        gain   f32    the same of label_gain[label], 0 at padding
        c      f32 [Q] 1 / maxDCG@max_position of the query, 0 for a
                      padding query and for one with no positive label

    `inverse_max_dcgs` [queries] float64 is `c` in query order, as the host
    oracle takes it.  `stats` counts what the gauges state."""
    boundaries = np.asarray(boundaries, np.int64)
    lens = np.diff(boundaries)
    distinct = np.unique(lens)
    pads = np.array([padded_length(int(v)) for v in distinct], np.int64)
    pad_of = pads[np.searchsorted(distinct, lens)]
    labels = np.asarray(labels, np.int64)
    label_gain = np.asarray(label_gain, np.float64)
    discount = 1.0 / np.log2(2.0 + np.arange(max(int(lens.max()), 1)))
    inverse_max_dcgs = np.zeros(len(lens))
    buckets: Dict[str, Dict[str, np.ndarray]] = {}
    pair_slots = 0
    for L in sorted(set(pads.tolist())):
        qs = np.nonzero(pad_of == L)[0]
        short = is_short(L)
        Q = padded_count(len(qs), short)
        pos = np.arange(L)
        # [queries, L]: the query's rows, then padding
        inside = pos[None, :] < lens[qs][:, None]
        row_q = np.where(inside, boundaries[qs][:, None] + pos[None, :], 0)
        lab_q = np.where(inside, labels[row_q], -1)
        # maxDCG@k: the k largest labels' gains against the discounts,
        # summed query by query as the oracle's loop sums them (rows of
        # one k together, so numpy adds each in the loop's own order)
        top = -np.sort(-lab_q, axis=1)
        k_q = np.minimum(max_position, lens[qs])
        for k in np.unique(k_q):
            sel = k_q == k
            mdcg = (label_gain[top[sel, :k]] * discount[:k]).sum(axis=1)
            inverse_max_dcgs[qs[sel]] = np.where(
                mdcg > 0, 1.0 / np.where(mdcg > 0, mdcg, 1.0), 0.0)
        tile = np.zeros((Q, window_tiles(L)), np.int32)
        tile[:len(qs)] = (boundaries[qs] // LANE)[:, None]
        tile += np.arange(tile.shape[1], dtype=np.int32)
        off = np.zeros(Q, np.int32)
        off[:len(qs)] = boundaries[qs] % LANE
        lab = np.full((Q, L), -1.0, np.float32)
        gain = np.zeros((Q, L), np.float32)
        lab[:len(qs)] = lab_q
        gain[:len(qs)] = np.where(inside, label_gain[np.maximum(lab_q, 0)],
                                  0.0)
        c = np.zeros(Q, np.float32)
        c[:len(qs)] = inverse_max_dcgs[qs]
        if short:
            lab, gain = lab.T.copy(), gain.T.copy()
        pair_slots += Q * L * L
        buckets[f"len{L:05d}"] = {"tile": tile, "off": off, "label": lab,
                                  "gain": gain, "c": c}
    stats = {"queries": len(lens), "buckets": len(buckets),
             "max_len": int(lens.max()), "mean_len": float(lens.mean()),
             # pairs the equations visit, seen from both rows as the pair
             # pass sees them, over the slots it computes
             "pairs_valid": 2 * _valid_pairs(boundaries, labels),
             "pairs_slots": pair_slots}
    return buckets, inverse_max_dcgs, stats


def _valid_pairs(boundaries: np.ndarray, labels: np.ndarray) -> int:
    """Unordered pairs of rows of one query whose labels differ: all pairs
    less the pairs inside each (query, label) group."""
    lens = np.diff(boundaries)
    q_of = np.repeat(np.arange(len(lens)), lens)
    lab = np.asarray(labels, np.int64)
    key = q_of * (int(lab.max()) + 1) + lab
    same = np.unique(key, return_counts=True)[1].astype(np.int64)
    lens = lens.astype(np.int64)
    return int((lens * (lens - 1) // 2).sum() - (same * (same - 1) // 2).sum())


def _beats(s_self, s_other, pos_self, pos_other):
    """Whether `other` stands before `self` in the stable descending sort:
    a higher score, or the same score and an earlier row."""
    return (s_other > s_self) | ((s_other == s_self) & (pos_other < pos_self))


def _norm_factor(S):
    """log2(1 + S) / S per query, 1 where the query's lambdas sum to 0."""
    safe = jnp.where(S > 0, S, 1.0)
    return jnp.where(S > 0, jnp.log2(1.0 + safe) / safe, 1.0)


def _bucket(s, label, gain, c, *, short: bool, sigmoid: float, norm: bool):
    """One bucket's (lambda, hessian) in the slab's own shape; `s` the
    scores at the slab's slots."""
    r_axis = 0 if short else 1      # the rows' axis of the 2-D slab

    def as_self(x):                 # 2-D slab -> broadcast over `other`
        return x[:, None, :]

    def as_other(x):
        return x[None, :, :] if short else x[:, :, None]

    def per_query(x):               # [Q] -> 3-D
        return x[None, None, :] if short else x[:, None, None]

    def sum_other(*xs):
        # one reduce of several operands: the pair matrix feeds every sum
        # and is never written out
        zeros = tuple(jnp.zeros((), x.dtype) for x in xs)
        return lax.reduce(xs, zeros,
                          lambda a, b: tuple(u + v for u, v in zip(a, b)),
                          dimensions=(1,))

    live = label >= 0.0
    s = jnp.where(live, s, 0.0)
    shape3 = ((s.shape[0], s.shape[0], s.shape[1]) if short
              else (s.shape[0], s.shape[1], s.shape[1]))
    pos_self = lax.broadcasted_iota(jnp.int32, shape3, 0 if short else 2)
    pos_other = lax.broadcasted_iota(jnp.int32, shape3, 1)
    with jax.named_scope("rank_sort"):
        s_s, s_o = as_self(s), as_other(s)
        beats = as_other(live) & _beats(s_s, s_o, pos_self, pos_other)
        (rank,) = sum_other(beats.astype(jnp.float32))
        disc = 1.0 / jnp.log2(2.0 + rank)
        big = jnp.float32(np.finfo(np.float32).max)
        best = jnp.max(jnp.where(live, s, -big), axis=r_axis)
        worst = jnp.min(jnp.where(live, s, big), axis=r_axis)
    with jax.named_scope("pair_pass"):
        l_s, l_o = as_self(label), as_other(label)
        pair = (l_s != l_o) & as_self(live) & as_other(live)
        sign = jnp.where(l_s > l_o, 1.0, -1.0)   # +1: self is the pair's hi
        ds = sign * (as_self(s) - as_other(s))   # s_hi - s_lo
        delta = (sign * (as_self(gain) - as_other(gain))
                 * jnp.abs(as_self(disc) - as_other(disc)) * per_query(c))
        if norm:
            delta = jnp.where(per_query(best != worst),
                              delta / (0.01 + jnp.abs(ds)), delta)
        p = 1.0 / (1.0 + jnp.exp(jnp.clip(sigmoid * ds, -88.0, 88.0)))
        w = jnp.where(pair, sigmoid * delta * p, 0.0)
        lam, hes, tot = sum_other(-sign * w, sigmoid * w * (1.0 - p), w)
        if norm:
            # sum of the query's lambdas: every pair is seen from both rows
            factor = _norm_factor(jnp.sum(tot, axis=r_axis))
            factor = factor[None, :] if short else factor[:, None]
            lam, hes = lam * factor, hes * factor
    return lam, hes


def _shifted(x, off, toward_start: bool):
    """Each row of `x` [Q, W] moved by its own `off` [Q] in 0..LANE-1
    places (toward column 0, or away from it), zeros moving in: one
    conditional static shift per bit of the offset."""
    for bit in range(LANE.bit_length() - 1):
        k = 1 << bit
        if toward_start:
            moved = jnp.pad(x[:, k:], ((0, 0), (0, k)))
        else:
            moved = jnp.pad(x[:, :-k], ((0, 0), (k, 0)))
        x = jnp.where(((off >> bit) & 1)[:, None] == 1, moved, x)
    return x


def gradients(score, layout: Dict[str, Dict[str, jnp.ndarray]],
              *, sigmoid: float, norm: bool):
    """score [n_any] f32 (the data set's rows first) -> (lambda, hessian)
    [n_any] f32; rows past the data set's get zeros."""
    n_any = score.shape[0]
    tiles = -(-n_any // LANE)
    by_tile = jnp.pad(score, (0, tiles * LANE - n_any)).reshape(tiles, LANE)
    # buckets whose windows are equally many tiles move together
    groups: Dict[int, List[str]] = {}
    for name in sorted(layout):
        groups.setdefault(layout[name]["tile"].shape[1], []).append(name)
    dest, lam_t, hes_t = [], [], []
    for nt, names in groups.items():
        # a window past the vector's end reads (and later adds zeros to)
        # the last tile; its slots are padding by their labels
        tile = jnp.minimum(
            jnp.concatenate([layout[n]["tile"] for n in names]), tiles - 1)
        off = jnp.concatenate([layout[n]["off"] for n in names])
        with jax.named_scope("to_queries"):
            window = _shifted(by_tile[tile].reshape(-1, nt * LANE), off,
                              toward_start=True)
        lams, hess, first = [], [], 0
        for name in names:
            b, L = layout[name], int(name[3:])
            short, Q = is_short(L), layout[name]["off"].shape[0]
            s = window[first:first + Q, :L]
            first += Q
            lam, hes = _bucket(s.T if short else s, b["label"], b["gain"],
                               b["c"], short=short, sigmoid=sigmoid,
                               norm=norm)
            for out, x in ((lams, lam), (hess, hes)):
                out.append(jnp.pad(x.T if short else x,
                                   ((0, 0), (0, nt * LANE - L))))
        with jax.named_scope("to_rows"):
            for out, xs in ((lam_t, lams), (hes_t, hess)):
                out.append(_shifted(jnp.concatenate(xs), off,
                                    toward_start=False).reshape(-1, LANE))
            dest.append(tile.reshape(-1))
    with jax.named_scope("to_rows"):
        # every row's own query brings its sum, the queries that share its
        # tile bring exact zeros: the order of the additions changes nothing
        dest = jnp.concatenate(dest)
        lam, hes = (jnp.zeros((tiles, LANE), jnp.float32)
                    .at[dest].add(jnp.concatenate(x)).reshape(-1)[:n_any]
                    for x in (lam_t, hes_t))
    return lam, hes
