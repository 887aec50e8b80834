"""Out-of-core streamed tree growth: host-resident bins, blocked H2D.

The resident grower (ops/grower.py) assumes the transposed [G, n_pad]
bin matrix lives in HBM for the whole run — dataset size is capped by
device memory.  This module removes that cap (ROADMAP: rows x features
stops being a refusal): the binned matrix stays HOST-resident, rows are
partitioned into fixed-size stream blocks, and each grower round streams
the blocks through two device slots so block i+1's H2D copy overlaps
block i's histogram contraction (the out-of-core GBDT scheme of
arXiv 2005.09148, with the per-block histogram work kept device-shaped
as in arXiv 1706.08359).

Structure — the resident grower's ONE `lax.while_loop` program becomes a
small, BOUNDED family of jitted programs driven by a host loop (one
host sync per round, on a single `cont` scalar):

* ``prep``        — gradient quantization, packed stats, scalar sums
                    (the resident root preamble, verbatim math);
* ``root_block``  / ``block_step`` — per-stream-block histogram
                    accumulation (+ the round's row partition), donated
                    accumulators, one compiled shape per block width
                    (full R and the final partial block — no per-block
                    retrace);
* ``root_finish`` / ``round_head`` / ``round_update`` — the resident
    round body split at the histogram seam: everything except the
    contraction runs on [L]/[K]-sized state, device-resident between
    programs;
* ``finish``      — quantized leaf refit + the out dict;
* ``replay_block``— recover leaf ids for GOSS-skipped blocks by
    replaying the split records (one partition-only pass per skipped
    block at tree end);
* ``goss_plan``   — per-block sum|g*h| scores + PCG uniforms keyed on
    each block's first GLOBAL row index (graftlint D101: invariant to
    padding and topology).

Bitwise contract: the histogram accumulator is block-partitioned in the
ACCUMULATION dtype.  For int8/int16 precisions every sum is int32 and
therefore associative, the row padding, quantization grid (same n_pad
as the resident layout) and stochastic-rounding hash (GLOBAL row
indices, row0=0) are identical — so streamed model files are
BYTE-IDENTICAL to resident ones.  Float precisions (f32/f64/hilo/bf16)
reassociate across the stream-block seam and are numerically close but
not bitwise.  GOSS block sampling changes which rows build each tree,
so it deliberately trades the bitwise-vs-resident guarantee for fewer
H2D copies per iteration.

Restrictions (validated by the streamed learner): serial tree_learner,
numerical features only, no EFB bundling, no sparse COO storage, no
CEGB, no forced splits, no per-node feature sampling, no 4-bit packing.
"""

from __future__ import annotations

import functools
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from ..utils.compile_ledger import ledger_jit
from .grower import (GrowerParams, K_MIN_SCORE, MF_QUANT_REFIT,
                     MF_STOCHASTIC, pool_dtype)
from .histogram import (build_histogram_batched_t, build_histogram_t,
                        hashed_uniform, key_words, pack_stats, quant_limit,
                        quantize_values)
from .split import (MISSING_NAN, MISSING_ZERO, finalize_split, leaf_output,
                    per_feature_best_split)

# record-row indices mirrored from ops/grower.py (REC_*): replay_block
# reads the same packed layout the round body writes
from .grower import (REC_DEFAULT_LEFT, REC_DID_SPLIT, REC_FEATURE,
                     REC_LEAF, REC_THRESHOLD, REC_WIDTH)


def stream_supported(params: GrowerParams) -> Optional[str]:
    """None when the streamed layout can serve these grower params, else
    a human-readable reason it cannot (the learner raises / the planner
    refuses to auto-select on it)."""
    if params.has_cat:
        return "categorical features"
    if params.has_bundles:
        return "EFB bundling (enable_bundle)"
    if params.has_sparse:
        return "sparse COO storage (tpu_sparse_threshold)"
    if params.has_cegb or params.has_cegb_lazy:
        return "CEGB penalties"
    if params.forced:
        return "forced splits"
    if params.feature_fraction_bynode < 1.0:
        return "feature_fraction_bynode"
    if params.packed_bins:
        return "packed 4-bit bins (tpu_pack_bins)"
    return None


def _numeric_go_left(col, mt, nbf, db, thr, dleft):
    """Numerical split decision incl. missing routing — the resident
    grower's `numeric_go_left`, duplicated (it is nested inside
    make_grower) so the streamed partition and replay use the SAME
    elementwise math bit for bit."""
    is_miss = jnp.where(
        mt == MISSING_NAN, col == nbf - 1,
        jnp.where(mt == MISSING_ZERO, col == db, False))
    return jnp.where(is_miss, dleft, col <= thr)


def _scatter_set(arr, idx, val, valid):
    # invalid slots write out of bounds -> dropped (resident scatter_set)
    safe = jnp.where(valid, idx, arr.shape[0])
    return arr.at[safe].set(val, mode="drop")


def _hist_geometry(params: GrowerParams, rows: int):
    """Inner histogram-scan blocking for a stream block of `rows` rows —
    the resident grower's block derivation applied to the block width
    (int32 accumulation makes the decomposition value-invariant)."""
    block = min(params.block_rows, rows)
    nbi = max(rows // block, 1)
    return nbi, rows // nbi


@functools.lru_cache(maxsize=16)
def _build_stream_programs(params: GrowerParams, G: int, n_pad: int,
                           live_columns: Optional[int] = None):
    """The bounded jitted-program family for one (params, shape) pair.

    Memoized like `_build_grower` so a ladder rebuild at the same shape
    reuses the compiled executables.  Every program's shapes are fixed
    except the stream-block width of `root_block` / `block_step` /
    `replay_block`, which admits exactly two values (the full block R
    and the final partial block) — the compile-ledger gate in
    tests/test_stream.py pins the total program count.
    """
    L = params.num_leaves
    B = params.num_bins
    K = max(1, min(int(params.split_batch), L - 1))
    precision = params.precision
    quantized = precision in ("int8", "int16")
    hist_t = pool_dtype(precision)
    big = jnp.float32(1e30)

    split_kw = dict(l1=params.l1, l2=params.l2,
                    max_delta_step=params.max_delta_step,
                    min_data_in_leaf=params.min_data_in_leaf,
                    min_sum_hessian=params.min_sum_hessian,
                    min_gain_to_split=params.min_gain_to_split)

    def select_one(hist, sg, sh, cnt, min_c, max_c, fmask, qscale, meta):
        """The resident select() restricted to the streamed feature set
        (serial, numerical, no bundles/sparse/cat/CEGB): identical ops
        in identical order, so split decisions match bit for bit."""
        acc = qscale if quantized else None
        if not quantized and hist.dtype != jnp.float32:
            # f64 deterministic pool: the search consumes the
            # accumulation dtype directly (resident dequant is identity)
            pass
        pf = per_feature_best_split(
            hist, sg, sh, cnt,
            meta["num_bin"], meta["missing_type"], meta["default_bin"],
            meta["monotone"], meta["penalty"], fmask,
            min_constraint=min_c, max_constraint=max_c,
            acc_scale=acc, **split_kw)
        bf = jnp.argmax(pf.gain).astype(jnp.int32)
        res = finalize_split(pf, bf,
                             l1=params.l1, l2=params.l2,
                             max_delta_step=params.max_delta_step,
                             min_constraint=min_c, max_constraint=max_c)
        return res._replace(is_cat=jnp.asarray(False),
                            cat_mask=jnp.zeros(1, jnp.float32))

    vselect = jax.vmap(select_one,
                       in_axes=(0, 0, 0, 0, 0, 0, None, None, None))

    # ---- prep: quantization + packed stats + scalar sums --------------
    def prep(grad, hess, row_mask, w_blocks, key, mf, block_width):
        # per-row GOSS block weight: w_blocks[nbs] expanded by global
        # row -> block index (all-ones when GOSS is off, making every
        # product exact and the path bit-identical to resident)
        nbs = w_blocks.shape[0]
        w_row = w_blocks[jnp.minimum(
            jax.lax.iota(jnp.int32, n_pad) // jnp.int32(block_width),
            jnp.int32(nbs - 1))]
        mask = row_mask * (w_row > 0).astype(jnp.float32)
        g = grad * w_row * mask
        h = hess * w_row * mask
        if quantized:
            qmax = quant_limit(precision, n_pad)
            amax_g = jnp.max(jnp.abs(g))
            amax_h = jnp.max(jnp.abs(h))
            g_scale = jnp.maximum(amax_g, jnp.float32(1e-30)) / qmax
            h_scale = jnp.maximum(amax_h, jnp.float32(1e-30)) / qmax
            seed_a, seed_b = key_words(jax.random.fold_in(key, 0x5154))
            sto = mf[MF_STOCHASTIC]
            g_q = quantize_values(g, g_scale, qmax, "stochastic",
                                  seed_a, seed_b, 0, salt=0x9E3779B9,
                                  stochastic=sto)
            h_q = quantize_values(h, h_scale, qmax, "stochastic",
                                  seed_a, seed_b, 0, salt=0x85EBCA6B,
                                  stochastic=sto)
            qscale = jnp.stack([g_scale, h_scale, jnp.float32(1.0)])
            sum_g = (jnp.sum(g_q, dtype=jnp.int32).astype(jnp.float32)
                     * g_scale)
            sum_h = (jnp.sum(h_q, dtype=jnp.int32).astype(jnp.float32)
                     * h_scale)
            cnt = (jnp.sum(mask.astype(jnp.int32), dtype=jnp.int32)
                   .astype(jnp.float32))
            stats = pack_stats(g_q, h_q, mask, precision)
        else:
            sum_t = jnp.float64 if precision == "f64" else jnp.float32
            sum_g = jnp.sum(g, dtype=sum_t).astype(jnp.float32)
            sum_h = jnp.sum(h, dtype=sum_t).astype(jnp.float32)
            cnt = jnp.sum(mask, dtype=sum_t).astype(jnp.float32)
            qscale = jnp.ones(3, jnp.float32)  # unused placeholder
            stats = pack_stats(g, h, mask, precision)
        return stats, g, h, sum_g, sum_h, cnt, qscale

    # ---- per-block histogram programs ---------------------------------
    def root_block(acc, bins_blk, stats, row0):
        rows = bins_blk.shape[1]
        nbi, block = _hist_geometry(params, rows)
        S = stats.shape[0]
        bins_blocks = jnp.moveaxis(bins_blk.reshape(G, nbi, block), 1, 0)
        stats_blk = jax.lax.dynamic_slice(stats, (0, row0), (S, rows))
        stats_blocks = stats_blk.reshape(S, nbi, block)
        with jax.named_scope("hist_build"):
            if params.hist_impl == "pallas2":
                part = build_histogram_batched_t(
                    bins_blocks, stats_blocks,
                    jnp.zeros((nbi, block), jnp.int32),
                    jnp.zeros(1, jnp.int32), B,
                    precision, impl=params.hist_impl,
                    packed_rows=False, live_columns=live_columns)[0]
            else:
                part = build_histogram_t(bins_blocks, stats_blocks, B,
                                         precision)
        return acc + part

    def block_step(acc, leaf_ids, bins_blk, stats, row0,
                   sel, do_k, new_ids, smaller_ids,
                   sel_feat, sel_thr, sel_dleft, meta):
        """Partition this block's rows for the round's K splits, then
        accumulate their contribution to the K smaller-child histograms
        — the per-row math of the resident exec_round 'select' lowering,
        applied to the [rows] slice at row0."""
        rows = bins_blk.shape[1]
        nbi, block = _hist_geometry(params, rows)
        S = stats.shape[0]
        leaf_blk = jax.lax.dynamic_slice(leaf_ids, (row0,), (rows,))
        new_leaf = leaf_blk
        for k in range(K):
            f_k = sel_feat[k]
            col_k = jax.lax.dynamic_index_in_dim(bins_blk, f_k, 0,
                                                 keepdims=False)
            go_left_k = _numeric_go_left(
                col_k, meta["missing_type"][f_k],
                meta["num_bin"][f_k], meta["default_bin"][f_k],
                sel_thr[k], sel_dleft[k])
            in_k = (leaf_blk == sel[k]) & do_k[k]
            new_leaf = jnp.where(in_k & (~go_left_k), new_ids[k],
                                 new_leaf)
        leaf_ids = jax.lax.dynamic_update_slice(leaf_ids, new_leaf,
                                                (row0,))
        bins_blocks = jnp.moveaxis(bins_blk.reshape(G, nbi, block), 1, 0)
        stats_blk = jax.lax.dynamic_slice(stats, (0, row0), (S, rows))
        stats_blocks = stats_blk.reshape(S, nbi, block)
        with jax.named_scope("hist_build"):
            part = build_histogram_batched_t(
                bins_blocks, stats_blocks, new_leaf.reshape(nbi, block),
                smaller_ids, B, precision, impl=params.hist_impl,
                packed_rows=False, live_columns=live_columns)
        return acc + part, leaf_ids

    # ---- root finish: state init from the accumulated root hist -------
    def root_finish(acc, sum_g, sum_h, cnt, qscale, fmask, meta):
        root_hist = acc
        with jax.named_scope("split_search"):
            root_split = select_one(root_hist, sum_g, sum_h, cnt,
                                    -big, big, fmask, qscale, meta)
        state = {
            "pool": jnp.zeros((L, G, B, 3), hist_t).at[0].set(root_hist),
            "leaf_sum_g": jnp.zeros(L, jnp.float32).at[0].set(sum_g),
            "leaf_sum_h": jnp.zeros(L, jnp.float32).at[0].set(sum_h),
            "leaf_cnt": jnp.zeros(L, jnp.float32).at[0].set(cnt),
            "leaf_depth": jnp.zeros(L, jnp.int32),
            "leaf_output": jnp.zeros(L, jnp.float32).at[0].set(
                leaf_output(sum_g, sum_h, params.l1, params.l2,
                            params.max_delta_step)),
            "bs_gain": jnp.full(L, K_MIN_SCORE, jnp.float32).at[0].set(
                root_split.gain),
            "bs_feat": jnp.zeros(L, jnp.int32).at[0].set(
                root_split.feature),
            "bs_thr": jnp.zeros(L, jnp.int32).at[0].set(
                root_split.threshold),
            "bs_dleft": jnp.zeros(L, jnp.bool_).at[0].set(
                root_split.default_left),
            "bs_lg": jnp.zeros(L, jnp.float32).at[0].set(
                root_split.left_sum_g),
            "bs_lh": jnp.zeros(L, jnp.float32).at[0].set(
                root_split.left_sum_h),
            "bs_lc": jnp.zeros(L, jnp.float32).at[0].set(
                root_split.left_count),
            "bs_rg": jnp.zeros(L, jnp.float32).at[0].set(
                root_split.right_sum_g),
            "bs_rh": jnp.zeros(L, jnp.float32).at[0].set(
                root_split.right_sum_h),
            "bs_lo": jnp.zeros(L, jnp.float32).at[0].set(
                root_split.left_output),
            "bs_ro": jnp.zeros(L, jnp.float32).at[0].set(
                root_split.right_output),
            "leaf_min": jnp.full(L, -1e30, jnp.float32),
            "leaf_max": jnp.full(L, 1e30, jnp.float32),
            "records": jnp.zeros((L - 1 + K, REC_WIDTH), jnp.float32),
            "n_splits": jnp.int32(0),
        }
        return state

    # ---- round head: top-K slot selection (pre-histogram) -------------
    def round_head(state):
        depth_ok = jnp.logical_or(
            params.max_depth <= 0,
            state["leaf_depth"] < params.max_depth)
        cand = jnp.where(depth_ok, state["bs_gain"], K_MIN_SCORE)
        cont = ((state["n_splits"] < L - 1) & (jnp.max(cand) > 0.0))
        vals, sel = jax.lax.top_k(cand, K)
        sel = sel.astype(jnp.int32)
        kar = jnp.arange(K, dtype=jnp.int32)
        budget = (L - 1) - state["n_splits"]
        do_k = (vals > 0.0) & (kar < budget)
        if params.split_batch_alpha > 0.0 and K > 1:
            alpha = min(params.split_batch_alpha, 0.999)
            do_k &= vals >= alpha * vals[0]
        new_ids = state["n_splits"] + 1 + kar
        lc = state["bs_lc"][sel]
        rc = state["leaf_cnt"][sel] - lc
        smaller_is_left = lc <= rc
        smaller_ids = jnp.where(
            do_k, jnp.where(smaller_is_left, sel, new_ids), -1)
        head = dict(
            cont=cont, sel=sel, vals=vals, do_k=do_k, new_ids=new_ids,
            smaller_ids=smaller_ids,
            sel_feat=state["bs_feat"][sel], sel_thr=state["bs_thr"][sel],
            sel_dleft=state["bs_dleft"][sel],
            lg=state["bs_lg"][sel], lh=state["bs_lh"][sel], lc=lc,
            rg=state["bs_rg"][sel], rh=state["bs_rh"][sel],
            lo=state["bs_lo"][sel], ro=state["bs_ro"][sel])
        acc0 = jnp.zeros((K, G, B, 3), hist_t)
        return head, acc0

    # ---- round update: everything after the histogram seam ------------
    def round_update(state, acc, sel, vals, do_k, new_ids,
                     sel_feat, sel_thr, sel_dleft,
                     lg, lh, lc, rg, rh, lo, ro, fmask, qscale, meta):
        num_do = jnp.sum(do_k, dtype=jnp.int32)
        ph = state["leaf_sum_h"][sel]
        pc = state["leaf_cnt"][sel]
        rc = pc - lc
        smaller_is_left = lc <= rc
        hist_small = acc                              # [K, G, B, 3]
        parent_hist = state["pool"][sel]
        hist_large = parent_hist - hist_small
        sl = smaller_is_left[:, None, None, None]
        hist_left = jnp.where(sl, hist_small, hist_large)
        hist_right = jnp.where(sl, hist_large, hist_small)
        pool = _scatter_set(state["pool"], sel, hist_left, do_k)
        pool = _scatter_set(pool, new_ids, hist_right, do_k)

        p_min = state["leaf_min"][sel]
        p_max = state["leaf_max"][sel]
        mono_k = meta["monotone"][sel_feat]
        mid = (lo + ro) / 2.0
        l_min = jnp.where(mono_k < 0, mid, p_min)
        l_max = jnp.where(mono_k > 0, mid, p_max)
        r_min = jnp.where(mono_k > 0, mid, p_min)
        r_max = jnp.where(mono_k < 0, mid, p_max)

        new_state = dict(state)
        with jax.named_scope("split_search"):
            ch = vselect(
                jnp.concatenate([hist_left, hist_right], axis=0),
                jnp.concatenate([lg, rg]), jnp.concatenate([lh, rh]),
                jnp.concatenate([lc, rc]),
                jnp.concatenate([l_min, r_min]),
                jnp.concatenate([l_max, r_max]),
                fmask, qscale, meta)

        new_state["pool"] = pool
        for key_, li, ri in (("leaf_sum_g", lg, rg),
                             ("leaf_sum_h", lh, rh),
                             ("leaf_cnt", lc, rc), ("leaf_output", lo, ro),
                             ("leaf_min", l_min, r_min),
                             ("leaf_max", l_max, r_max)):
            arr = _scatter_set(new_state[key_], sel, li, do_k)
            new_state[key_] = _scatter_set(arr, new_ids, ri, do_k)
        d_child = state["leaf_depth"][sel] + 1
        d = _scatter_set(state["leaf_depth"], sel, d_child, do_k)
        new_state["leaf_depth"] = _scatter_set(d, new_ids, d_child, do_k)
        for key_, cv in (("bs_gain", ch.gain), ("bs_feat", ch.feature),
                         ("bs_thr", ch.threshold),
                         ("bs_dleft", ch.default_left),
                         ("bs_lg", ch.left_sum_g),
                         ("bs_lh", ch.left_sum_h),
                         ("bs_lc", ch.left_count),
                         ("bs_rg", ch.right_sum_g),
                         ("bs_rh", ch.right_sum_h),
                         ("bs_lo", ch.left_output),
                         ("bs_ro", ch.right_output)):
            arr = _scatter_set(new_state[key_], sel, cv[:K], do_k)
            new_state[key_] = _scatter_set(arr, new_ids, cv[K:], do_k)

        rec = jnp.stack([
            sel.astype(jnp.float32), sel_feat.astype(jnp.float32),
            sel_thr.astype(jnp.float32), sel_dleft.astype(jnp.float32),
            vals, lo, ro, lc, rc, lh, rh,
            state["leaf_output"][sel], ph, pc,
            do_k.astype(jnp.float32),
            jnp.zeros(K, jnp.float32)],                # REC_IS_CAT
            axis=1)                                    # [K, 16]
        new_state["records"] = jax.lax.dynamic_update_slice(
            state["records"], rec, (state["n_splits"], jnp.int32(0)))
        new_state["n_splits"] = state["n_splits"] + num_do
        return new_state

    # ---- finish: quantized leaf refit + out dict ----------------------
    def finish(state, leaf_ids, g, h, mf):
        leaf_out = state["leaf_output"]
        if quantized:
            refit_on = mf[MF_QUANT_REFIT]
            rg = jnp.zeros(L, jnp.float32).at[leaf_ids].add(g)
            rh = jnp.zeros(L, jnp.float32).at[leaf_ids].add(h)
            refit = jnp.clip(
                leaf_output(rg, rh + jnp.float32(2e-15), params.l1,
                            params.l2, params.max_delta_step),
                state["leaf_min"], state["leaf_max"])
            leaf_out = jnp.where(
                (state["leaf_cnt"] > 0) & (refit_on > 0),
                refit, leaf_out)
        return {
            "records": state["records"][:L - 1],
            "leaf_output": leaf_out,
            "leaf_cnt": state["leaf_cnt"],
            "leaf_sum_h": state["leaf_sum_h"],
        }

    # ---- replay: leaf ids for GOSS-skipped blocks ---------------------
    def replay_block(leaf_ids, bins_blk, records, row0, meta):
        rows = bins_blk.shape[1]
        leaf_blk = jax.lax.dynamic_slice(leaf_ids, (row0,), (rows,))

        def body(j, lb):
            rec = records[j]
            did = rec[REC_DID_SPLIT] > 0.5
            parent = rec[REC_LEAF].astype(jnp.int32)
            feat = rec[REC_FEATURE].astype(jnp.int32)
            thr = rec[REC_THRESHOLD].astype(jnp.int32)
            dleft = rec[REC_DEFAULT_LEFT] > 0.5
            col = jax.lax.dynamic_index_in_dim(bins_blk, feat, 0,
                                               keepdims=False)
            go_left = _numeric_go_left(
                col, meta["missing_type"][feat], meta["num_bin"][feat],
                meta["default_bin"][feat], thr, dleft)
            # record row j created leaf id j+1 (do_k is a prefix mask,
            # so records are contiguous and new_ids = n_splits + 1 + k)
            move = did & (lb == parent) & (~go_left)
            return jnp.where(move, jnp.int32(j) + 1, lb)

        lb = jax.lax.fori_loop(0, L - 1, body, leaf_blk)
        return jax.lax.dynamic_update_slice(leaf_ids, lb, (row0,))

    # ---- GOSS plan: block scores + uniforms ---------------------------
    def goss_plan(grad, hess, row_mask, key, w_len, block_width):
        # w_len/block_width are static (closure-free ints via
        # static_argnames): [nbs] per-block sum|g*h| over real rows, and
        # one PCG uniform per block keyed on its first GLOBAL row index
        v = jnp.abs(grad * hess) * row_mask
        bidx = jnp.minimum(
            jax.lax.iota(jnp.int32, n_pad) // jnp.int32(block_width),
            jnp.int32(w_len - 1))
        scores = jnp.zeros(w_len, jnp.float32).at[bidx].add(v)
        seed_a, seed_b = key_words(jax.random.fold_in(key, 0x51B5))
        starts = (jnp.arange(w_len, dtype=jnp.uint32)
                  * jnp.uint32(block_width))
        u = hashed_uniform(starts, seed_a, seed_b, 0x60553)
        return scores, u

    class _P:
        pass

    p = _P()
    p.prep = ledger_jit(prep, site="stream.prep",
                        static_argnames=("block_width",))
    p.root_block = ledger_jit(root_block, site="stream.root_block",
                              donate_argnums=(0,))
    p.block_step = ledger_jit(block_step, site="stream.block_step",
                              donate_argnums=(0, 1))
    p.root_finish = ledger_jit(root_finish, site="stream.root_finish")
    p.round_head = ledger_jit(round_head, site="stream.round_head")
    p.round_update = ledger_jit(round_update, site="stream.round_update",
                                donate_argnums=(0,))
    p.finish = ledger_jit(finish, site="stream.finish")
    p.replay_block = ledger_jit(replay_block, site="stream.replay_block",
                                donate_argnums=(0,))
    p.goss_plan = ledger_jit(goss_plan, site="stream.goss_plan",
                             static_argnames=("w_len", "block_width"))
    return p


class StreamGrower:
    """Host-loop driver for the streamed tree growth.

    Owns the per-block H2D schedule (double-buffered device slots), the
    GOSS block-sampling plan, and the per-tree overlap telemetry.  The
    compiled programs come from `_build_stream_programs` (memoized), so
    a ladder rebuild at the same shapes reuses the executables.
    """

    def __init__(self, params: GrowerParams, num_columns: int,
                 n_pad: int, stream_rows: int,
                 double_buffer: bool = True,
                 goss_top: float = 0.0, goss_other: float = 0.0,
                 live_columns: Optional[int] = None):
        reason = stream_supported(params)
        if reason is not None:
            raise NotImplementedError(
                f"streamed training layout does not support {reason}; "
                "set tpu_stream_mode=resident")
        if stream_rows <= 0:
            raise ValueError(f"stream_rows={stream_rows} must be positive")
        self.params = params
        self.G = int(num_columns)
        self.n_pad = int(n_pad)
        self.R = min(int(stream_rows), self.n_pad)
        self.nbs = -(-self.n_pad // self.R)
        tail = self.n_pad - (self.nbs - 1) * self.R
        for rows in sorted({self.R, tail}):
            nbi, blk = _hist_geometry(params, rows)
            if nbi * blk != rows:
                raise ValueError(
                    f"stream block of {rows} rows does not decompose "
                    f"into whole histogram scan blocks "
                    f"(block_rows={params.block_rows}); use "
                    "resolve_stream_rows() to size tpu_stream_block_rows")
        self.double_buffer = bool(double_buffer)
        self.goss_top = float(goss_top)
        self.goss_other = float(goss_other)
        self.goss_on = self.goss_top > 0.0 or self.goss_other > 0.0
        self._progs = _build_stream_programs(params, self.G, self.n_pad,
                                             live_columns)
        # per-tree telemetry, harvested by the learner / bench / probes
        self.last_stats: Dict[str, float] = {}
        self._h2d_rate: Optional[float] = None  # seconds per byte

    # ------------------------------------------------------------------
    def _block_bounds(self, i: int):
        row0 = i * self.R
        return row0, min(self.R, self.n_pad - row0)

    def _goss_weights(self, grad, hess, row_mask, key):
        """Host-side GOSS block plan from device scores/uniforms:
        weights [nbs] (0 = skipped), deterministic given the key (the
        uniforms hash each block's first GLOBAL row index, the ordering
        tie-break is the stable block index)."""
        scores, u = self._progs.goss_plan(grad, hess, row_mask, key,
                                          w_len=self.nbs,
                                          block_width=self.R)
        scores = np.asarray(scores)
        u = np.asarray(u)
        nbs = self.nbs
        top_k = int(np.ceil(self.goss_top * nbs)) if self.goss_top > 0 \
            else 0
        order = np.argsort(-scores, kind="stable")
        w = np.zeros(nbs, np.float32)
        top = order[:top_k]
        w[top] = 1.0
        rest = order[top_k:]
        if self.goss_other > 0 and len(rest):
            amp = (1.0 - self.goss_top) / self.goss_other
            picked = rest[u[rest] < self.goss_other]
            w[picked] = np.float32(amp)
        if not (w > 0).any():
            # degenerate fractions: always stream at least the
            # highest-scored block, or the tree would see zero rows
            w[order[0]] = 1.0
        return w

    def _stream_blocks(self, host_blocks: List[np.ndarray], indices,
                       consume):
        """Drive `consume(i, dev_block, row0)` over the selected blocks
        with (optionally) double-buffered H2D: block i+1's device_put is
        issued before block i's result is consumed, so on accelerators
        with async transfers the copy rides under the previous block's
        histogram contraction.  Records per-block copy/stall walls for
        the overlap estimate."""
        indices = list(indices)
        if not indices:
            return
        puts = {}

        def _put(i):
            t0 = time.perf_counter()
            dev = jax.device_put(host_blocks[i])
            if not self.double_buffer:
                dev.block_until_ready()
            return dev, time.perf_counter() - t0, host_blocks[i].nbytes

        # calibrate the copy wall on the first block (nothing to overlap
        # with there anyway): a synchronous timed put
        i0 = indices[0]
        t0 = time.perf_counter()
        with obs.span("stream_h2d", block=i0,
                      bytes=int(host_blocks[i0].nbytes)):
            dev0 = jax.device_put(host_blocks[i0])
            dev0.block_until_ready()
        wall0 = time.perf_counter() - t0
        if host_blocks[i0].nbytes:
            self._h2d_rate = wall0 / host_blocks[i0].nbytes
        puts[i0] = (dev0, wall0, host_blocks[i0].nbytes)
        self._t_h2d += wall0
        self._copy_est += wall0

        for pos, i in enumerate(indices):
            if self.double_buffer and pos + 1 < len(indices):
                nxt = indices[pos + 1]
                if nxt not in puts:
                    with obs.span("stream_h2d", block=nxt,
                                  bytes=int(host_blocks[nxt].nbytes)):
                        puts[nxt] = _put(nxt)
            if i not in puts:
                with obs.span("stream_h2d", block=i,
                              bytes=int(host_blocks[i].nbytes)):
                    puts[i] = _put(i)
            dev, issue_wall, nbytes = puts.pop(i)
            if pos > 0:
                est = (nbytes * self._h2d_rate if self._h2d_rate
                       else issue_wall)
                t_w = time.perf_counter()
                dev.block_until_ready()
                stall = time.perf_counter() - t_w
                if not self.double_buffer:
                    # serial copies: the full copy wall was paid at the
                    # put — nothing was hidden by construction
                    stall = est
                self._copy_est += est
                self._hidden += max(0.0, est - stall)
                self._t_h2d += stall + issue_wall
            row0, _rows = self._block_bounds(i)
            consume(i, dev, row0)

    # ------------------------------------------------------------------
    def grow(self, host_blocks: List[np.ndarray], grad, hess, row_mask,
             feature_mask, meta, key):
        """Grow one tree over the host-resident blocked bin matrix.

        host_blocks: [G, rows_i] C-contiguous host arrays (rows_i = R
        except the final partial block).  Returns the resident grower's
        out dict (records / leaf_ids / leaf_output / leaf_cnt /
        leaf_sum_h)."""
        P = self._progs
        t_tree = time.perf_counter()
        self._t_h2d = 0.0
        self._copy_est = 0.0
        self._hidden = 0.0

        if self.goss_on:
            w = self._goss_weights(grad, hess, row_mask, key)
        else:
            w = np.ones(self.nbs, np.float32)
        sampled = [i for i in range(self.nbs) if w[i] > 0]
        skipped = [i for i in range(self.nbs) if w[i] <= 0]

        stats, g, h, sum_g, sum_h, cnt, qscale = P.prep(
            grad, hess, row_mask, jnp.asarray(w), key,
            meta["mode_flags"], block_width=self.R)

        # ---- root histogram over the sampled blocks ----
        acc = jnp.zeros((self.G, self.params.num_bins, 3),
                        pool_dtype(self.params.precision))
        t_hist = time.perf_counter()
        with obs.span("hist_build", streamed=True, phase="root"):
            box = {"acc": acc}

            def root_consume(i, dev, row0):
                with obs.span("stream_block", block=i):
                    box["acc"] = P.root_block(box["acc"], dev, stats,
                                              jnp.int32(row0))

            self._stream_blocks(host_blocks, sampled, root_consume)
            acc = box["acc"]
        state = P.root_finish(acc, sum_g, sum_h, cnt, qscale,
                              feature_mask, meta)
        leaf_ids = jnp.zeros(self.n_pad, jnp.int32)

        # ---- rounds: one host sync per round on the cont scalar ----
        rounds = 0
        while True:
            head, acc_k = P.round_head(state)
            if not bool(head["cont"]):
                break
            rounds += 1
            with obs.span("hist_build", streamed=True, round=rounds):
                box = {"acc": acc_k, "leaf_ids": leaf_ids}

                def round_consume(i, dev, row0):
                    with obs.span("stream_block", block=i):
                        box["acc"], box["leaf_ids"] = P.block_step(
                            box["acc"], box["leaf_ids"], dev, stats,
                            jnp.int32(row0), head["sel"], head["do_k"],
                            head["new_ids"], head["smaller_ids"],
                            head["sel_feat"], head["sel_thr"],
                            head["sel_dleft"], meta)

                self._stream_blocks(host_blocks, sampled, round_consume)
                acc_k, leaf_ids = box["acc"], box["leaf_ids"]
            state = P.round_update(
                state, acc_k, head["sel"], head["vals"], head["do_k"],
                head["new_ids"], head["sel_feat"], head["sel_thr"],
                head["sel_dleft"], head["lg"], head["lh"], head["lc"],
                head["rg"], head["rh"], head["lo"], head["ro"],
                feature_mask, qscale, meta)
        t_hist = time.perf_counter() - t_hist

        out = dict(P.finish(state, leaf_ids, g, h, meta["mode_flags"]))

        # ---- GOSS-skipped blocks: one replay partition pass each ----
        if skipped:
            box = {"leaf_ids": leaf_ids}

            def replay_consume(i, dev, row0):
                with obs.span("stream_block", block=i, replay=True):
                    box["leaf_ids"] = P.replay_block(
                        box["leaf_ids"], dev, out["records"],
                        jnp.int32(row0), meta)

            with obs.span("hist_build", streamed=True, phase="replay"):
                self._stream_blocks(host_blocks, skipped, replay_consume)
            leaf_ids = box["leaf_ids"]
        out["leaf_ids"] = leaf_ids

        wall = time.perf_counter() - t_tree
        overlap = (100.0 * self._hidden / self._copy_est
                   if self._copy_est > 0 else 0.0)
        self.last_stats = {
            "tree_wall_s": wall,
            "h2d_wall_s": self._t_h2d,
            "hist_wall_s": max(t_hist - self._t_h2d, 0.0),
            "copy_est_s": self._copy_est,
            "overlap_pct": overlap,
            "rounds": float(rounds),
            "blocks_streamed": float(len(sampled)),
            "blocks_skipped": float(len(skipped)),
            "rows_per_sec": (self.n_pad * max(rounds, 1)) / max(wall,
                                                                1e-9),
        }
        obs.event("stream_tree", **self.last_stats)
        return out


def resolve_stream_rows(cfg_rows: int, n_pad: int, bytes_per_row: int,
                        inner_block: int,
                        budget_bytes: Optional[int] = None) -> int:
    """Resolve tpu_stream_block_rows to the actual stream-block width.

    The width is a multiple of the grower's inner histogram scan block
    (so per-block programs reuse the resident contraction geometry and
    the tail block stays a whole number of scan blocks), clamped to
    [inner_block, n_pad].  cfg_rows=0 = auto: two device slots sized to
    fit under ~1/8 of the HBM budget, floored at 64k rows.
    """
    b0 = max(1, min(int(inner_block), int(n_pad)))
    if cfg_rows > 0:
        r = int(cfg_rows)
    else:
        r = 65536
        if budget_bytes and bytes_per_row > 0:
            r = max(r, int((budget_bytes // 8) // (2 * bytes_per_row)))
    r = min(max(r, b0), int(n_pad))
    return max(r // b0, 1) * b0


def make_host_blocks(bins_t: np.ndarray, stream_rows: int
                     ) -> List[np.ndarray]:
    """Partition a host [G, n_pad] transposed bin matrix into
    C-contiguous per-block [G, rows_i] arrays (the H2D unit: contiguous
    blocks device_put without a host-side gather).  Works for plain
    ndarrays and np.memmap sources (the PR-3 chunked-ingest layout) —
    each block materializes at most G * stream_rows bytes."""
    G, n_pad = bins_t.shape
    out = []
    for row0 in range(0, n_pad, stream_rows):
        out.append(np.ascontiguousarray(
            bins_t[:, row0:row0 + stream_rows]))
    return out
