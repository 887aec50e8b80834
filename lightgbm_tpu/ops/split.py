"""Best-split search over histograms as masked cumulative sums + argmax.

Re-expresses the reference's sequential two-direction threshold scans
(reference src/treelearner/feature_histogram.hpp:508-644
FindBestThresholdSequence) as vectorized [F, B] tensor ops:

* direction +1 ("missing right"): left stats = prefix sums over bins in
  ascending order, excluding the zero bin for MissingType.Zero features and
  the NaN bin for MissingType.NaN features; right = parent - left, so the
  excluded (missing) mass falls to the right.  default_left = False.
* direction -1 ("missing left"): right stats = suffix sums with the same
  exclusions; left = parent - right, missing mass falls left.
  default_left = True.

Gain math matches feature_histogram.hpp:444-506: L1 soft-thresholded leaf
outputs, L2, max_delta_step clamp, optional monotone-constraint veto; the
reported gain is (left+right gain) - (parent gain + min_gain_to_split),
scaled by the per-feature penalty (CEGB / feature_contri hook).

Tie-breaking mirrors the reference scan order: dir=-1 is scanned first and
keeps the LARGEST threshold among equal gains; dir=+1 replaces only on
strictly greater gain and keeps the smallest threshold.  Across features the
lowest feature index wins ties (ArrayArgs::ArgMax semantics).
"""

from __future__ import annotations

from typing import Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp

K_MIN_SCORE = -1e30
K_EPSILON = 1e-15

MISSING_NONE = 0
MISSING_ZERO = 1
MISSING_NAN = 2


def numeric_go_left(col, mt, nbf, db, thr, dleft):
    """Numerical split decision incl. missing-value routing (reference
    dense_bin.hpp Split semantics); elementwise, the single source of
    truth for every partition lowering — the grower's select passes route
    rows through this one function, and the partition kernel through its
    scalar restatement, `go_right_scalars`."""
    is_miss = jnp.where(
        mt == MISSING_NAN, col == nbf - 1,
        jnp.where(mt == MISSING_ZERO, col == db, False))
    return jnp.where(is_miss, dleft, col <= thr)


def go_right_scalars(mt, nbf, db, thr, dleft):
    """`numeric_go_left` folded for a pass that has only scalars per split:
    a row goes RIGHT iff `(col > thr) ^ (col == flip_bin)`.  flip_bin is the
    split's missing bin (the last bin under NaN routing, the default bin
    under zero routing) where the threshold alone would send it the wrong
    way, else -1, which no bin equals.  Elementwise over the splits; the
    partition kernel (ops/partition.py) compares a row's bin against these
    two numbers."""
    miss = jnp.where(mt == MISSING_NAN, nbf - 1,
                     jnp.where(mt == MISSING_ZERO, db, -1))
    wrong = (miss >= 0) & ((miss > thr) == dleft)
    return jnp.where(wrong, miss, -1).astype(jnp.int32)


def argbest(gain: jnp.ndarray, feature: jnp.ndarray,
            threshold: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Winner index among candidate splits with the SHARED deterministic
    tie-break: highest gain, ties by lowest global feature id, then by
    lowest threshold bin.

    This is the one rule every cross-candidate winner selection uses —
    the serial/psum per-leaf argmax (features ascending, so plain
    first-max argmax already implements it), the feature-parallel and
    scatter-mode all_gather-of-per-shard-bests syncs, and the voting
    top-k search (whose candidates arrive in VOTE order, where a plain
    argmax would inherit the vote ranking and make equal-gain decisions
    depend on the shard count).  Mirrors the reference's
    ArrayArgs::ArgMax lowest-index semantics lifted to (feature, bin)
    keys.  All comparisons are exact (f32 equality on identically
    computed gains; int keys), so the winner is invariant to the lane
    order of the gathered candidates."""
    elig = gain >= jnp.max(gain)
    big = jnp.int32(2 ** 31 - 1)
    f = jnp.where(elig, feature.astype(jnp.int32), big)
    elig = elig & (feature == jnp.min(f))
    if threshold is not None:
        t = jnp.where(elig, threshold.astype(jnp.int32), big)
        elig = elig & (threshold == jnp.min(t))
    return jnp.argmax(elig).astype(jnp.int32)


def _threshold_l1(s, l1):
    reg = jnp.maximum(0.0, jnp.abs(s) - l1)
    return jnp.sign(s) * reg


def leaf_output(sum_g, sum_h, l1, l2, max_delta_step):
    """CalculateSplittedLeafOutput (feature_histogram.hpp:449-456)."""
    out = -_threshold_l1(sum_g, l1) / (sum_h + l2)
    if_clip = (max_delta_step > 0.0)
    clipped = jnp.clip(out, -max_delta_step, max_delta_step)
    return jnp.where(if_clip, clipped, out)


def leaf_split_gain(sum_g, sum_h, l1, l2, max_delta_step):
    """GetLeafSplitGain (feature_histogram.hpp:497-506)."""
    output = leaf_output(sum_g, sum_h, l1, l2, max_delta_step)
    sg_l1 = _threshold_l1(sum_g, l1)
    return -(2.0 * sg_l1 * output + (sum_h + l2) * output * output)


class SplitResult(NamedTuple):
    gain: jnp.ndarray          # scalar f32; <=0 means no valid split
    feature: jnp.ndarray       # i32 index into used features
    threshold: jnp.ndarray     # i32 bin threshold
    default_left: jnp.ndarray  # bool
    left_sum_g: jnp.ndarray
    left_sum_h: jnp.ndarray
    left_count: jnp.ndarray    # f32
    right_sum_g: jnp.ndarray   # summed from the right side's own bins,
    right_sum_h: jnp.ndarray   # never parent minus left (see the scan)
    left_output: jnp.ndarray
    right_output: jnp.ndarray
    is_cat: Optional[jnp.ndarray] = None    # categorical split? (None = no)
    cat_mask: Optional[jnp.ndarray] = None  # [B] f32: bins going LEFT


class PerFeatureBest(NamedTuple):
    """Per-feature best-split candidates (pre cross-feature argmax)."""
    gain: jnp.ndarray        # [F] net gain (min_gain_shift subtracted, penalized)
    threshold: jnp.ndarray   # [F] i32
    default_left: jnp.ndarray  # [F] bool
    left_sum_g: jnp.ndarray  # [F]
    left_sum_h: jnp.ndarray  # [F]
    left_count: jnp.ndarray  # [F]
    right_sum_g: jnp.ndarray  # [F]
    right_sum_h: jnp.ndarray  # [F]


def per_feature_best_split(
        hist: jnp.ndarray,        # [F, B, 3] (g, h, cnt)
        sum_g, sum_h, num_data,   # parent totals (scalars, f32)
        num_bin: jnp.ndarray,     # [F] i32 bins per feature
        missing_type: jnp.ndarray,  # [F] i32
        default_bin: jnp.ndarray,   # [F] i32
        monotone: jnp.ndarray,      # [F] i32 in {-1,0,1}
        penalty: jnp.ndarray,       # [F] f32
        feature_mask: jnp.ndarray,  # [F] f32/bool (feature_fraction)
        *, l1: float, l2: float, max_delta_step: float,
        min_data_in_leaf: float, min_sum_hessian: float,
        min_gain_to_split: float,
        min_constraint=-1e30, max_constraint=1e30,
        acc_scale=None) -> PerFeatureBest:
    """Best candidate per feature (the voting-parallel building block,
    reference voting_parallel_tree_learner.cpp:327-337 local candidates).

    min/max_constraint are the leaf's monotone value bounds, propagated down
    the tree by the grower (reference serial_tree_learner.cpp:840-851).

    acc_scale (quantized precisions): hist arrives in its int32
    accumulation dtype and the bin cumsums run in int32 — exact and
    reassociation-proof — before the [3] dequantization scales apply.
    Running the scan on pre-dequantized f32 instead would let XLA's
    per-program scan decomposition reassociate the adds, and a last-ulp
    difference in a left sum amplifies through the gain cancellation
    into a visible cross-topology model diff (ROADMAP item 7's residue
    after the bagging-RNG fix)."""
    F, B, _ = hist.shape
    bin_iota = jnp.arange(B, dtype=jnp.int32)[None, :]          # [1, B]
    nb = num_bin[:, None]                                        # [F, 1]

    hg, hh, hc = hist[..., 0], hist[..., 1], hist[..., 2]

    is_zero_missing = (missing_type[:, None] == MISSING_ZERO)
    is_nan_missing = (missing_type[:, None] == MISSING_NAN)
    skip_bin = is_zero_missing & (bin_iota == default_bin[:, None])
    na_bin = is_nan_missing & (bin_iota == nb - 1)
    acc_mask = (~skip_bin) & (~na_bin) & (bin_iota < nb)

    zero = jnp.zeros((), hist.dtype)
    ag = jnp.where(acc_mask, hg, zero)
    ah = jnp.where(acc_mask, hh, zero)
    ac = jnp.where(acc_mask, hc, zero)

    # Each side of a threshold is summed from its OWN bins: the prefix
    # over the bins at or under it, the suffix over the bins above it, and
    # the missing mass (the NaN bin, or the zero bin under zero_as_missing)
    # added to whichever side the direction sends it.  "Right = the leaf's
    # total minus left" is the reference's rule, in double.  In f32 it
    # loses a small child of a large leaf: a cumulative sum near the total
    # rounds by as much as the child holds (13M rows at hessian 0.034: an
    # ulp of 0.03 against a 20-row child's 0.68), and the leaf's total
    # comes from the rows' f32 values while the bins hold their hi + lo
    # bf16 halves, which on a first tree (one hessian for every row) are
    # off from them by one systematic 2^-17.  A hessian that comes out too
    # small inflates the gain, so the search picked just those candidates
    # (PR 27, on the chip: a leaf value off by 7.3, its count exact).  The
    # leaf's totals now enter `gain_shift` only.
    def suffix_after(a):
        """out[:, t] = sum of a[:, t+1:], accumulated from the top bin."""
        rev = jnp.cumsum(a[:, ::-1], axis=1)[:, ::-1]
        return jnp.concatenate([rev[:, 1:], jnp.zeros_like(rev[:, :1])],
                               axis=1)

    def missing_mass(h):
        return jnp.sum(jnp.where(acc_mask, zero, h), axis=1, keepdims=True)

    cg = jnp.cumsum(ag, axis=1)                                  # [F, B]
    ch = jnp.cumsum(ah, axis=1)
    cc = jnp.cumsum(ac, axis=1)
    rg, rh = suffix_after(ag), suffix_after(ah)
    mg, mh, mc = missing_mass(hg), missing_mass(hh), missing_mass(hc)
    # direction +1 sends the missing mass right, direction -1 left; added
    # in the histogram's dtype, so exactly under the int precisions
    right_g_p1, right_h_p1 = rg + mg, rh + mh
    left_g_m1, left_h_m1, left_c_m1 = cg + mg, ch + mh, cc + mc
    if acc_scale is not None:
        # int32 sums are exact; dequantize at the scan boundary
        def dequantized(plane, *sums):
            return (x.astype(jnp.float32) * acc_scale[plane] for x in sums)

        cg, rg, right_g_p1, left_g_m1 = dequantized(
            0, cg, rg, right_g_p1, left_g_m1)
        ch, rh, right_h_p1, left_h_m1 = dequantized(
            1, ch, rh, right_h_p1, left_h_m1)
        cc, left_c_m1 = dequantized(2, cc, left_c_m1)

    gain_shift = leaf_split_gain(sum_g, sum_h + 2 * K_EPSILON,
                                 l1, l2, max_delta_step)
    min_gain_shift = gain_shift + min_gain_to_split

    def eval_dir(left_g, left_h, left_c, right_g, right_h, thr_valid):
        right_c = num_data - left_c
        ok = (thr_valid
              & (left_c >= min_data_in_leaf) & (right_c >= min_data_in_leaf)
              & (left_h >= min_sum_hessian) & (right_h >= min_sum_hessian))
        lo = jnp.clip(leaf_output(left_g, left_h, l1, l2, max_delta_step),
                      min_constraint, max_constraint)
        ro = jnp.clip(leaf_output(right_g, right_h, l1, l2, max_delta_step),
                      min_constraint, max_constraint)
        mono = monotone[:, None]
        mono_bad = ((mono > 0) & (lo > ro)) | ((mono < 0) & (lo < ro))
        sg_l1_l = _threshold_l1(left_g, l1)
        sg_l1_r = _threshold_l1(right_g, l1)
        g_l = -(2.0 * sg_l1_l * lo + (left_h + l2) * lo * lo)
        g_r = -(2.0 * sg_l1_r * ro + (right_h + l2) * ro * ro)
        gain = jnp.where(mono_bad, 0.0, g_l + g_r)
        gain = jnp.where(ok & (gain > min_gain_shift), gain, K_MIN_SCORE)
        return gain, lo, ro

    # ---- direction +1: left = prefix, missing goes right ----------------
    thr_ok_p1 = (bin_iota <= nb - 2) & (~skip_bin) & \
        jnp.where(is_nan_missing, bin_iota <= nb - 2, True)
    gain_p1, lo_p1, ro_p1 = eval_dir(cg, ch, cc, right_g_p1, right_h_p1,
                                     thr_ok_p1)

    # ---- direction -1: right = suffix, missing goes left ----------------
    # A feature with no missing mass in this leaf gets the same sums, so
    # the same gains, in both directions, and the strict `>` below keeps
    # direction -1 (default_left) as the reference does, which runs only
    # that direction for such a feature.
    thr_ok_m1 = (bin_iota <= nb - 2 - is_nan_missing.astype(jnp.int32)) & (~skip_bin)
    gain_m1, lo_m1, ro_m1 = eval_dir(left_g_m1, left_h_m1, left_c_m1, rg, rh,
                                     thr_ok_m1)

    # ---- per-feature best with reference tie-breaking -------------------
    # dir=-1: largest threshold wins ties -> argmax over reversed bins
    rev = gain_m1[:, ::-1]
    idx_m1 = (B - 1) - jnp.argmax(rev, axis=1)                   # [F]
    best_m1 = jnp.take_along_axis(gain_m1, idx_m1[:, None], axis=1)[:, 0]
    # dir=+1: smallest threshold wins ties -> plain argmax
    idx_p1 = jnp.argmax(gain_p1, axis=1)
    best_p1 = jnp.take_along_axis(gain_p1, idx_p1[:, None], axis=1)[:, 0]

    use_p1 = best_p1 > best_m1                                   # strict >
    feat_gain = jnp.where(use_p1, best_p1, best_m1)
    feat_thr = jnp.where(use_p1, idx_p1, idx_m1).astype(jnp.int32)
    feat_dleft = ~use_p1

    # only-2-bin NaN features get default_left=False in the reference
    # (feature_histogram.hpp:105-108); with a full scan this is cosmetic but
    # keeps model files identical
    two_bin_nan = (num_bin <= 2) & (missing_type == MISSING_NAN)
    feat_dleft = jnp.where(two_bin_nan, False, feat_dleft)

    feat_gain = jnp.where(feature_mask > 0, feat_gain, K_MIN_SCORE)
    out_gain = jnp.where(feat_gain > K_MIN_SCORE / 2,
                         (feat_gain - min_gain_shift) * penalty,
                         K_MIN_SCORE)

    # per-feature left stats at the chosen (threshold, direction)
    f_iota = jnp.arange(F)
    lg = jnp.where(feat_dleft, left_g_m1[f_iota, feat_thr],
                   cg[f_iota, feat_thr])
    lh = jnp.where(feat_dleft, left_h_m1[f_iota, feat_thr],
                   ch[f_iota, feat_thr])
    lc = jnp.where(feat_dleft, left_c_m1[f_iota, feat_thr],
                   cc[f_iota, feat_thr])
    right_g = jnp.where(feat_dleft, rg[f_iota, feat_thr],
                        right_g_p1[f_iota, feat_thr])
    right_h = jnp.where(feat_dleft, rh[f_iota, feat_thr],
                        right_h_p1[f_iota, feat_thr])
    return PerFeatureBest(gain=out_gain, threshold=feat_thr,
                          default_left=feat_dleft,
                          left_sum_g=lg, left_sum_h=lh, left_count=lc,
                          right_sum_g=right_g, right_sum_h=right_h)


def finalize_split(pf: PerFeatureBest, best_f,
                   *, l1: float, l2: float, max_delta_step: float,
                   min_constraint=-1e30, max_constraint=1e30) -> SplitResult:
    """SplitResult for the chosen feature index (post argmax/vote/gather)."""
    g = pf.gain[best_f]
    thr = pf.threshold[best_f]
    dleft = pf.default_left[best_f]
    lg = pf.left_sum_g[best_f]
    lh = pf.left_sum_h[best_f]
    lc = pf.left_count[best_f]
    rg = pf.right_sum_g[best_f]
    rh = pf.right_sum_h[best_f]
    lo = jnp.clip(leaf_output(lg, lh, l1, l2, max_delta_step),
                  min_constraint, max_constraint)
    ro = jnp.clip(leaf_output(rg, rh, l1, l2, max_delta_step),
                  min_constraint, max_constraint)
    # the grower's stored-split state is f32; under deterministic f64 the
    # candidate math above runs in f64 and must downcast HERE, at the one
    # boundary, or every .at[].set into the state becomes a mixed-dtype
    # scatter (a future-jax error)
    f32 = lambda x: jnp.asarray(x).astype(jnp.float32)  # noqa: E731
    return SplitResult(
        gain=f32(g),
        feature=best_f.astype(jnp.int32),
        threshold=thr,
        default_left=dleft,
        left_sum_g=f32(lg), left_sum_h=f32(lh), left_count=f32(lc),
        right_sum_g=f32(rg), right_sum_h=f32(rh),
        left_output=f32(lo), right_output=f32(ro))


class PerFeatureCatBest(NamedTuple):
    """Per-feature best CATEGORICAL split candidates."""
    gain: jnp.ndarray        # [F] net gain (min_gain_shift subtracted, penalized)
    cat_mask: jnp.ndarray    # [F, B] f32: 1.0 for bins going LEFT
    left_sum_g: jnp.ndarray  # [F]
    left_sum_h: jnp.ndarray  # [F]
    left_count: jnp.ndarray  # [F]
    left_output: jnp.ndarray   # [F] (computed with the categorical l2)
    right_output: jnp.ndarray  # [F]


def _gain_given_outputs(gl, hl, gr, hr, l1, l2, mds, min_c, max_c):
    """GetSplitGains (feature_histogram.hpp:432-447): gain of the two leaf
    outputs after monotone clipping."""
    lo = jnp.clip(leaf_output(gl, hl, l1, l2, mds), min_c, max_c)
    ro = jnp.clip(leaf_output(gr, hr, l1, l2, mds), min_c, max_c)
    g_l = -(2.0 * _threshold_l1(gl, l1) * lo + (hl + l2) * lo * lo)
    g_r = -(2.0 * _threshold_l1(gr, l1) * ro + (hr + l2) * ro * ro)
    return g_l + g_r, lo, ro


def per_feature_best_split_categorical(
        hist: jnp.ndarray,        # [F, B, 3]
        sum_g, sum_h, num_data,
        num_bin: jnp.ndarray,     # [F] i32
        missing_type: jnp.ndarray,  # [F] i32
        penalty: jnp.ndarray,     # [F] f32
        feature_mask: jnp.ndarray,  # [F]
        *, l1: float, l2: float, max_delta_step: float,
        min_data_in_leaf: float, min_sum_hessian: float,
        min_gain_to_split: float,
        cat_l2: float, cat_smooth: float, max_cat_threshold: int,
        max_cat_to_onehot: int, min_data_per_group: float,
        min_constraint=-1e30, max_constraint=1e30) -> PerFeatureCatBest:
    """Categorical best-split search (FindBestThresholdCategorical,
    reference feature_histogram.hpp:118-279).

    Two modes per feature, selected by num_bin <= max_cat_to_onehot:
    * one-hot: each category bin vs the rest, vectorized over bins;
    * sorted-CTR subset: bins with count >= cat_smooth sorted by
      sum_g/(sum_h + cat_smooth), prefix-scanned from both ends with the
      reference's min_data_per_group grouping and early-break rules —
      a lax.scan of <=B steps per direction, vmapped over features.

    Returns per-feature candidates whose cat_mask marks the bins (i.e.
    categories) routed LEFT; the grower turns the winning mask into
    Tree.split_categorical bitsets.
    """
    F, B, _ = hist.shape
    hg, hh, hc = hist[..., 0], hist[..., 1], hist[..., 2]
    bin_iota = jnp.arange(B, dtype=jnp.int32)[None, :]

    # used_bin = num_bin - 1 + (missing_type == None)  (hpp:130-131)
    is_full = (missing_type == MISSING_NONE)
    used_bin = num_bin - 1 + is_full.astype(jnp.int32)          # [F]

    gain_shift = leaf_split_gain(sum_g, sum_h, l1, l2, max_delta_step)
    min_gain_shift = gain_shift + min_gain_to_split

    # ---- one-hot mode (hpp:137-169) ------------------------------------
    in_range = bin_iota < used_bin[:, None]
    oh_hl = hh + K_EPSILON
    oh_hr = sum_h - hh - K_EPSILON
    ok = (in_range
          & (hc >= min_data_in_leaf) & (hh >= min_sum_hessian)
          & ((num_data - hc) >= min_data_in_leaf)
          & (oh_hr >= min_sum_hessian))
    oh_gain, _, _ = _gain_given_outputs(
        sum_g - hg, oh_hr, hg, oh_hl, l1, l2, max_delta_step,
        min_constraint, max_constraint)
    oh_gain = jnp.where(ok & (oh_gain > min_gain_shift), oh_gain, K_MIN_SCORE)
    oh_best_t = jnp.argmax(oh_gain, axis=1)                     # [F]
    f_iota = jnp.arange(F)
    oh_best_gain = oh_gain[f_iota, oh_best_t]
    oh_mask = (bin_iota == oh_best_t[:, None]).astype(jnp.float32)
    oh_lg = hg[f_iota, oh_best_t]
    oh_lh = hh[f_iota, oh_best_t] + K_EPSILON
    oh_lc = hc[f_iota, oh_best_t]

    # ---- sorted-CTR subset mode (hpp:170-243) --------------------------
    l2c = l2 + cat_l2
    valid = in_range & (hc >= cat_smooth)                       # [F, B]
    ctr = hg / (hh + cat_smooth)
    sort_key = jnp.where(valid, ctr, jnp.inf)
    order = jnp.argsort(sort_key, axis=1).astype(jnp.int32)     # [F, B]
    used_cnt = jnp.sum(valid, axis=1).astype(jnp.int32)         # [F]
    max_cat = jnp.minimum(max_cat_threshold, (used_cnt + 1) // 2)

    def scan_dir(order_f, used_f, limit_f, hg_f, hh_f, hc_f, ascending):
        def body(carry, i):
            slg, slh, slc, grp, dead, bg, bi, blg, blh, blc = carry
            pos = jnp.where(ascending, i, used_f - 1 - i)
            t = order_f[jnp.clip(pos, 0, B - 1)]
            active = (i < limit_f) & (~dead)
            slg = slg + jnp.where(active, hg_f[t], 0.0)
            slh = slh + jnp.where(active, hh_f[t], 0.0)
            slc = slc + jnp.where(active, hc_f[t], 0.0)
            grp = grp + jnp.where(active, hc_f[t], 0.0)
            cont1 = (slc < min_data_in_leaf) | (slh < min_sum_hessian)
            rc = num_data - slc
            srh = sum_h - slh
            brk = ((rc < min_data_in_leaf) | (rc < min_data_per_group)
                   | (srh < min_sum_hessian))
            cont2 = grp < min_data_per_group
            evaluate = active & (~cont1) & (~brk) & (~cont2)
            gain, _, _ = _gain_given_outputs(
                slg, slh, sum_g - slg, srh, l1, l2c, max_delta_step,
                min_constraint, max_constraint)
            good = evaluate & (gain > min_gain_shift) & (gain > bg)
            grp = jnp.where(evaluate, 0.0, grp)
            bg = jnp.where(good, gain, bg)
            bi = jnp.where(good, i, bi)
            blg = jnp.where(good, slg, blg)
            blh = jnp.where(good, slh, blh)
            blc = jnp.where(good, slc, blc)
            dead = dead | (active & (~cont1) & brk)
            return (slg, slh, slc, grp, dead, bg, bi, blg, blh, blc), None

        init = (jnp.float32(0.0), jnp.float32(K_EPSILON), jnp.float32(0.0),
                jnp.float32(0.0), jnp.asarray(False),
                jnp.float32(K_MIN_SCORE), jnp.int32(-1),
                jnp.float32(0.0), jnp.float32(0.0), jnp.float32(0.0))
        carry, _ = jax.lax.scan(body, init, jnp.arange(B, dtype=jnp.int32))
        _, _, _, _, _, bg, bi, blg, blh, blc = carry
        return bg, bi, blg, blh, blc

    def per_feature(order_f, used_f, limit_f, hg_f, hh_f, hc_f):
        g1, i1, lg1, lh1, lc1 = scan_dir(order_f, used_f, limit_f,
                                         hg_f, hh_f, hc_f, True)
        g2, i2, lg2, lh2, lc2 = scan_dir(order_f, used_f, limit_f,
                                         hg_f, hh_f, hc_f, False)
        use2 = g2 > g1                    # dir=+1 scanned first keeps ties
        bg = jnp.where(use2, g2, g1)
        bi = jnp.where(use2, i2, i1)
        lg = jnp.where(use2, lg2, lg1)
        lh = jnp.where(use2, lh2, lh1)
        lc = jnp.where(use2, lc2, lc1)
        # bins routed left: sorted positions 0..bi (asc) / last bi+1 (desc)
        inv = jnp.zeros(B, jnp.int32).at[order_f].set(
            jnp.arange(B, dtype=jnp.int32))
        asc_mask = inv <= bi
        desc_mask = (inv >= used_f - 1 - bi) & (inv < used_f)
        mask = jnp.where(use2, desc_mask, asc_mask) & (bi >= 0)
        return bg, mask.astype(jnp.float32), lg, lh, lc

    so_gain, so_mask, so_lg, so_lh, so_lc = jax.vmap(per_feature)(
        order, used_cnt, max_cat, hg, hh, hc)

    # ---- merge modes per feature (hpp:136 use_onehot) ------------------
    use_oh = num_bin <= max_cat_to_onehot
    gain = jnp.where(use_oh, oh_best_gain, so_gain)
    mask = jnp.where(use_oh[:, None], oh_mask, so_mask)
    lg = jnp.where(use_oh, oh_lg, so_lg)
    lh = jnp.where(use_oh, oh_lh, so_lh)
    lc = jnp.where(use_oh, oh_lc, so_lc)
    l2_out = jnp.where(use_oh, l2, l2c)

    # leaf outputs with the mode's l2 (hpp:244-258)
    lo = jnp.clip(-_threshold_l1(lg, l1) / (lh + l2_out),
                  min_constraint, max_constraint)
    ro = jnp.clip(-_threshold_l1(sum_g - lg, l1) / (sum_h - lh + l2_out),
                  min_constraint, max_constraint)
    if max_delta_step > 0.0:
        lo = jnp.clip(lo, -max_delta_step, max_delta_step)
        ro = jnp.clip(ro, -max_delta_step, max_delta_step)

    gain = jnp.where(feature_mask > 0, gain, K_MIN_SCORE)
    out_gain = jnp.where(gain > K_MIN_SCORE / 2,
                         (gain - min_gain_shift) * penalty,
                         K_MIN_SCORE)
    return PerFeatureCatBest(gain=out_gain, cat_mask=mask,
                             left_sum_g=lg, left_sum_h=lh, left_count=lc,
                             left_output=lo, right_output=ro)


def find_best_split_all_features(
        hist: jnp.ndarray, sum_g, sum_h, num_data,
        num_bin, missing_type, default_bin, monotone, penalty, feature_mask,
        *, l1: float, l2: float, max_delta_step: float,
        min_data_in_leaf: float, min_sum_hessian: float,
        min_gain_to_split: float,
        min_constraint=-1e30, max_constraint=1e30) -> SplitResult:
    """Best split for one leaf across all features: per-feature candidates +
    first-max-wins argmax (ArrayArgs::ArgMax semantics)."""
    pf = per_feature_best_split(
        hist, sum_g, sum_h, num_data, num_bin, missing_type, default_bin,
        monotone, penalty, feature_mask,
        l1=l1, l2=l2, max_delta_step=max_delta_step,
        min_data_in_leaf=min_data_in_leaf, min_sum_hessian=min_sum_hessian,
        min_gain_to_split=min_gain_to_split,
        min_constraint=min_constraint, max_constraint=max_constraint)
    best_f = jnp.argmax(pf.gain, axis=0).astype(jnp.int32)
    return finalize_split(pf, best_f,
                          l1=l1, l2=l2, max_delta_step=max_delta_step,
                          min_constraint=min_constraint,
                          max_constraint=max_constraint)
