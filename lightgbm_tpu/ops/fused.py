"""Fused frontier growth: the grow megakernel and the partition kernel.

The per-iteration critical path used to be several XLA ops with HBM
round-trips between them: the batched histogram contraction writes the
[K, F, B, 3] smaller-child histograms to HBM, the sibling subtraction
reads them back next to the pool, and the split scan reads the children
again to run its bin cumsums.  This module fuses the frontier step into
ONE Pallas kernel (`fused_hist_scan`):

* the per-feature one-hot MXU accumulation of ops/histogram.py's
  "perfeature" kernel runs unchanged over the row-block grid, its
  accumulator resident in VMEM;
* at the LAST row block — while the finished accumulator is still in
  VMEM — the kernel subtracts each slot's block from the parent's pooled
  histogram (sibling subtraction) and runs the split gain scan
  (ops/split.py per_feature_best_split, pure jnp, traced into the kernel
  body) over every child's bins, emitting per-feature best
  `(gain, threshold, default_left, left stats)` records directly;
* the grower's `select()` consumes those flat f32 records
  (split.pack_pf_records layout) instead of dequantized histograms, so
  split search never leaves the device and the full child histograms
  never round-trip to HBM for the scan.

The in-kernel scan is restricted to the QUANTIZED precisions (int8 /
int16) on the serial learner: int32 bin cumsums are exact and
reassociation-proof, and the f32 gain math after the dequantize boundary
is the same exactly-rounded elementwise code the XLA path runs — so
fused and unfused model files are byte-identical (the acceptance gate
tests/test_fused_grow.py enforces).  Float precisions and sharded
learners fall back to the plain perfeature histogram kernel + the
existing device-side `select()` (still one compiled grow program; only
the scan fusion is forgone).

`partition_rows` is the row→leaf scatter kernel (tpu_partition_impl=
"kernel"): the K-way frontier partition as one VMEM pass over the row
blocks, mirroring the "vselect" lowering's integer math bit-for-bit
(split.numeric_go_left is the shared decision function).

Mosaic does not lower the in-kernel scan on a TPU (`cumsum` has no Pallas
TPU lowering, and behind it sit a reversed argmax and integer gathers), so
`tpu_hist_impl=auto` never picks "fused": it is explicit-only, raises the
compiler's own error on a TPU and runs in interpret mode (plain jnp) on
CPU, where the parity tests exercise it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .histogram import (_INT_STAT_DTYPES, _dot_spec, _unpack_hist,
                        pallas_interpret)
from .split import (PF_RECORD_WIDTH, pack_pf_records, numeric_go_left,
                    per_feature_best_split, unpack_pf_records)

# VMEM budget for the fused kernel's resident blocks (accumulator +
# parent histograms + records); smaller than the plain perfeature
# kernel's budget because the parent block doubles the residency
_FUSED_OUT_BUDGET = 4 * 1024 * 1024

# ctx-row column layout (see `fused_hist_scan` child_ctx)
CTX_SUM_G, CTX_SUM_H, CTX_COUNT, CTX_MIN_C, CTX_MAX_C, CTX_USE_SMALL = \
    range(6)


def fused_supported(precision: str, *, data_axis=None, feature_axis=None,
                    voting_k: int = 0, bynode: bool = False,
                    has_cat: bool = False, has_bundles: bool = False,
                    has_sparse: bool = False, has_cegb: bool = False,
                    forced: bool = False, packed_bins: bool = False):
    """Reason the in-kernel split scan cannot engage, or None if it can.

    The grower computes the same predicate structurally; this helper
    exists so the learner/autotuner can explain a fallback to the user
    instead of silently degrading."""
    if precision not in _INT_STAT_DTYPES:
        return (f"precision={precision!r} (the in-kernel scan needs the "
                "exact int32 accumulation of int8/int16)")
    if data_axis is not None or feature_axis is not None or voting_k:
        return "sharded learner (aggregation must precede the scan)"
    if bynode:
        return "feature_fraction_bynode (per-node masks)"
    for flag, name in ((has_cat, "categorical splits"),
                       (has_bundles, "EFB bundling"),
                       (has_sparse, "sparse storage"),
                       (has_cegb, "CEGB"),
                       (forced, "forced splits"),
                       (packed_bins, "packed 4-bit bins")):
        if flag:
            return name
    return None


def fused_hist_scan(bins_t_blocks, stats_blocks, leaf_blocks,
                    slot_leaf_ids, parent_hist, child_ctx, meta_i, meta_f,
                    num_bins: int, precision: str, *, split_kw: dict):
    """The grow megakernel: histograms + sibling subtraction + split scan.

    bins_t_blocks: [nb, F, block] integer bins
    stats_blocks:  [S, nb, block] packed int stats (S == 3)
    leaf_blocks:   [nb, block] int32 current leaf per row
    slot_leaf_ids: [K] int32 smaller-child leaf per slot (-1 = dead)
    parent_hist:   [K, F, B, 3] int32 pooled parent histograms
    child_ctx:     [2K+1, 8] f32 — row j < 2K is child j's
        (sum_g, sum_h, count, min_constraint, max_constraint, use_small)
        where children are ordered [left 0..K-1, right 0..K-1] like the
        grower's vselect concatenation and use_small > 0 means the child
        is the freshly-histogrammed (smaller) sibling; row 2K carries the
        dequantization scales (g_scale, h_scale, 1.0).
    meta_i: [F, 8] int32 — cols (num_bin, missing_type, default_bin,
        monotone); meta_f: [F, 8] f32 — cols (penalty, feature_mask).
    split_kw: the six static split scalars for per_feature_best_split.

    Returns (hist [K, F, B, 3] int32 smaller-child histograms — identical
    to the perfeature kernel's output, for the pool update — and records
    [2K, F, PF_RECORD_WIDTH] f32 per-child per-feature best splits).
    """
    from jax.experimental import pallas as pl

    nb, F, block = bins_t_blocks.shape
    S = stats_blocks.shape[0]
    K = slot_leaf_ids.shape[0]
    B = num_bins
    C = 2 * K
    if S != 3 or precision not in _INT_STAT_DTYPES:
        raise ValueError("the fused scan requires quantized [3, n] stats")
    Bp = -(-B // 8) * 8
    dot_dtype, acc_dtype, dot_prec = _dot_spec(precision)
    RW = PF_RECORD_WIDTH

    # parent histograms pre-shaped to the kernel's flat accumulator
    # layout [F*Bp, K*3] so the in-VMEM subtraction is a plain slice
    par = jnp.transpose(parent_hist.astype(acc_dtype), (1, 2, 0, 3))
    if Bp != B:
        par = jnp.pad(par, ((0, 0), (0, Bp - B), (0, 0), (0, 0)))
    par_flat = par.reshape(F * Bp, K * 3)

    # feature chunking mirrors the perfeature kernel: largest divisor of
    # F whose resident blocks (accumulator + parent) fit the budget
    ks_pad = -(-(K * S) // 128) * 128
    step = {1: 32, 2: 16, 4: 8}[bins_t_blocks.dtype.itemsize]

    def fits(c):
        return c * Bp * (ks_pad + K * 3) * 4 <= _FUSED_OUT_BUDGET

    fblk = F
    if not fits(F):
        cands = [c for c in range(step, F, step)
                 if F % c == 0 and fits(c)]
        if cands:
            fblk = max(cands)
    nf = F // fblk
    kw = dict(split_kw)

    def kernel(bins_ref, stats_ref, leaf_ref, slots_ref, par_ref,
               ctx_ref, mi_ref, mf_ref, out_ref, rec_ref):
        i = pl.program_id(1)  # row-block axis (innermost)
        # ---- accumulate: identical math to the perfeature kernel ----
        s = stats_ref[0]                            # [S, blk]
        l = leaf_ref[0]                             # [1, blk] i32
        slots = slots_ref[:]                        # [K, 1] i32
        slot_oh = (slots == l).astype(dot_dtype)
        sexp = (slot_oh[:, None, :] * s[None, :, :].astype(dot_dtype))
        sexp = sexp.reshape(K * S, block)
        iota_b = jax.lax.broadcasted_iota(jnp.int32, (Bp, block), 0)
        for f in range(fblk):
            b_f = bins_ref[0, f].astype(jnp.int32)
            onehot = (b_f[None, :] == iota_b).astype(dot_dtype)
            acc = jax.lax.dot_general(
                onehot, sexp, (((1,), (1,)), ((), ())),
                precision=dot_prec, preferred_element_type=acc_dtype)

            @pl.when(i == 0)
            def _(f=f, acc=acc):
                out_ref[f * Bp:(f + 1) * Bp, :] = acc

            @pl.when(i > 0)
            def _(f=f, acc=acc):
                out_ref[f * Bp:(f + 1) * Bp, :] += acc

        @pl.when(i == 0)
        def _():
            rec_ref[...] = jnp.zeros_like(rec_ref[...])

        # ---- device-resident split search at the final row block ----
        # (the accumulator just completed and is still in VMEM: sibling
        # subtraction + the bin gain scan run here, never touching HBM)
        @pl.when(i == nb - 1)
        def _():
            accs = out_ref[...].reshape(fblk, Bp, K * S)
            parb = par_ref[...].reshape(fblk, Bp, K, 3)
            qs = jnp.stack([ctx_ref[C, 0], ctx_ref[C, 1], ctx_ref[C, 2]])
            nbin = mi_ref[:, 0]
            mtyp = mi_ref[:, 1]
            dbin = mi_ref[:, 2]
            mono = mi_ref[:, 3]
            pen = mf_ref[:, 0]
            fmask = mf_ref[:, 1]
            for j in range(C):
                k = j % K
                small = accs[:, :B, k * S:(k + 1) * S]   # [fblk, B, 3]
                large = parb[:, :B, k, :] - small
                hs = jnp.where(ctx_ref[j, CTX_USE_SMALL] > 0, small, large)
                pf = per_feature_best_split(
                    hs, ctx_ref[j, CTX_SUM_G], ctx_ref[j, CTX_SUM_H],
                    ctx_ref[j, CTX_COUNT], nbin, mtyp, dbin, mono, pen,
                    fmask, min_constraint=ctx_ref[j, CTX_MIN_C],
                    max_constraint=ctx_ref[j, CTX_MAX_C],
                    acc_scale=qs, **kw)
                rec_ref[:, j * RW:(j + 1) * RW] = pack_pf_records(pf)

    stats_nb = jnp.moveaxis(stats_blocks, 1, 0)
    raw, recs = pl.pallas_call(
        kernel,
        grid=(nf, nb),
        in_specs=[
            pl.BlockSpec((1, fblk, block), lambda fi, i: (i, fi, 0)),
            pl.BlockSpec((1, S, block), lambda fi, i: (i, 0, 0)),
            pl.BlockSpec((1, 1, block), lambda fi, i: (i, 0, 0)),
            pl.BlockSpec((K, 1), lambda fi, i: (0, 0)),
            pl.BlockSpec((fblk * Bp, K * 3), lambda fi, i: (fi, 0)),
            pl.BlockSpec((C + 1, 8), lambda fi, i: (0, 0)),
            pl.BlockSpec((fblk, 8), lambda fi, i: (fi, 0)),
            pl.BlockSpec((fblk, 8), lambda fi, i: (fi, 0)),
        ],
        out_specs=[
            pl.BlockSpec((fblk * Bp, K * S), lambda fi, i: (fi, 0)),
            pl.BlockSpec((fblk, C * RW), lambda fi, i: (fi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((F * Bp, K * S), acc_dtype),
            jax.ShapeDtypeStruct((F, C * RW), jnp.float32),
        ],
        interpret=pallas_interpret(),
    )(bins_t_blocks, stats_nb, leaf_blocks.reshape(nb, 1, block),
      slot_leaf_ids.reshape(K, 1), par_flat, child_ctx,
      meta_i, meta_f)
    raw = jnp.transpose(raw.reshape(F, Bp, K, S)[:, :B], (2, 3, 0, 1))
    raw = raw.reshape(K, S, F * B)
    hist = jax.vmap(lambda r: _unpack_hist(r, precision))(raw)
    hist = hist.reshape(K, F, B, 3)
    records = jnp.transpose(recs.reshape(F, C, RW), (1, 0, 2))
    return hist, records


def partition_rows(cols, leaf_ids, sel, new_ids, thr, dleft, mt, nbf, db,
                   do_k, nb: int, block: int):
    """Row→leaf partition kernel (tpu_partition_impl="kernel").

    One VMEM pass over the row blocks replaces the partition's separate
    XLA program points: each block evaluates all K split decisions
    vectorized ([K, blk] broadcast of the per-slot scalars) and resolves
    each row's unique destination with a max-reduce — the exact integer
    math of the "vselect" lowering, so the two are bit-identical.

    cols:     [K, n_pad] int32 — the chosen features' bin columns
              (gathered by the caller; plain dense storage only)
    leaf_ids: [n_pad] int32 current assignment
    sel/new_ids/thr: [K] i32; dleft/do_k: [K] bool; mt/nbf/db: [K] i32
    Returns the updated [n_pad] int32 leaf ids.
    """
    from jax.experimental import pallas as pl

    K = cols.shape[0]
    n_pad = leaf_ids.shape[0]
    ints = jnp.stack(
        [sel, new_ids, thr, dleft.astype(jnp.int32), mt, nbf, db,
         do_k.astype(jnp.int32)], axis=1).astype(jnp.int32)  # [K, 8]

    def kernel(cols_ref, ints_ref, leaf_ref, out_ref):
        cb = cols_ref[...]                       # [K, blk]
        li = leaf_ref[...]                       # [1, blk]
        p_sel = ints_ref[:, 0:1]
        p_new = ints_ref[:, 1:2]
        p_thr = ints_ref[:, 2:3]
        p_dl = ints_ref[:, 3:4] > 0
        p_mt = ints_ref[:, 4:5]
        p_nb = ints_ref[:, 5:6]
        p_db = ints_ref[:, 6:7]
        p_do = ints_ref[:, 7:8] > 0
        go_left = numeric_go_left(cb, p_mt, p_nb, p_db, p_thr, p_dl)
        move = (li == p_sel) & p_do & (~go_left)          # [K, blk]
        moved = jnp.max(jnp.where(move, p_new, -1), axis=0,
                        keepdims=True)                    # [1, blk]
        out_ref[...] = jnp.where(moved >= 0, moved, li)

    out = pl.pallas_call(
        kernel,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((K, block), lambda i: (0, i)),
            pl.BlockSpec((K, 8), lambda i: (0, 0)),
            pl.BlockSpec((1, block), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((1, block), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, n_pad), jnp.int32),
        interpret=pallas_interpret(),
    )(cols.astype(jnp.int32), ints, leaf_ids.reshape(1, n_pad))
    return out.reshape(n_pad)


def children_from_records(records, finalize):
    """[2K, F, RW] records → batched SplitResult via the caller-supplied
    per-child finalizer (the grower binds its static split scalars and
    constraint bounds there).  Split out for the oracle test's reuse."""
    return jax.vmap(finalize)(records)


__all__ = [
    "CTX_SUM_G", "CTX_SUM_H", "CTX_COUNT", "CTX_MIN_C", "CTX_MAX_C",
    "CTX_USE_SMALL", "children_from_records", "fused_hist_scan",
    "fused_supported", "partition_rows", "unpack_pf_records",
]
