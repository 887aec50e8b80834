"""Histogram construction on the MXU: the framework's hottest op.

The reference builds per-(leaf,feature) histograms with 4-way unrolled gather
loops on CPU (reference src/io/dense_bin.hpp:71-132) and with per-workgroup
local-memory atomic adds on GPU (reference src/treelearner/ocl/
histogram256.cl:78-120).  TPUs have neither fast random scatter nor atomics —
the idiomatic formulation is a ONE-HOT CONTRACTION:

    hist[s, f*B + b] = sum_r stats[s, r] * (bins[r, f] == b)

i.e. a [S, n] x [n, F*B] matmul whose RHS is a one-hot encoding of the bin
matrix, generated on the fly block-by-block.  The MXU reduces over rows; the
one-hot is exact in bf16, so all precision lies in the stats operand.

Precision modes (`tpu_hist_precision`):
  * "hilo" (default): each f32 stat row is split into bf16 hi + lo rows
    (hi = bf16(x), lo = bf16(x - hi)).  The MXU accumulates in f32, so the
    result carries ~16 mantissa bits of the inputs at full bf16 speed —
    the moral equivalent of the reference GPU's `gpu_use_dp` toggle
    (reference gpu_tree_learner.cpp:306).  The stats matrix is [5, n]:
    rows (g_hi, g_lo, h_hi, h_lo, cnt); the batched kernel packs K leaf
    slots x 5 rows onto the 128-lane axis, so a lean S means more leaves
    per pass (K=25 -> N=125, one 128-lane MXU tile).
  * "f32": full f32 matmul with HIGHEST precision (slowest, exact).
  * "bf16": single bf16 pass (fastest, ~8 mantissa bits).
  * "int16" / "int8": QUANTIZED gradients (the Booster-accelerator /
    LightGBM-quantized-training idea): grad/hess are stochastically
    rounded per iteration onto a fixed-point grid (`quantize_values`,
    scales = per-class max-abs / `quant_limit`), the stats matrix is a
    [3, n] int8/int16 plane, and the MXU contracts narrow-int operands
    with EXACT int32 accumulation (`preferred_element_type=int32`).
    Integer sums are associative, so data-parallel psum'd histograms are
    bit-identical for any shard count — the fast deterministic mode —
    and the stats operand is 2-4x narrower than hilo's [5, n] bf16.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..utils.compile_ledger import ledger_jit

# --------------------------------------------------------------------------
# Quantized-gradient support (tpu_hist_precision=int16|int8)
# --------------------------------------------------------------------------

_INT_STAT_DTYPES = {"int8": jnp.int8, "int16": jnp.int16}
_INT_TYPE_MAX = {"int8": 127, "int16": 32767}


def _dot_spec(precision: str):
    """(operand dtype, accumulator dtype, lax precision) for a histogram
    contraction — the ONE table every builder below reads, so the xla and
    pallas backends can never disagree on the int32-exact contract."""
    if precision in _INT_STAT_DTYPES:
        # integer dots ignore lax.Precision; int32 accumulation is exact
        return (_INT_STAT_DTYPES[precision], jnp.int32,
                jax.lax.Precision.DEFAULT)
    if precision == "f64":
        return jnp.float64, jnp.float64, jax.lax.Precision.HIGHEST
    if precision == "f32":
        return jnp.float32, jnp.float32, jax.lax.Precision.HIGHEST
    return jnp.bfloat16, jnp.float32, jax.lax.Precision.DEFAULT


def quant_limit(precision: str, total_rows: int) -> int:
    """Largest |quantized| stat value such that a worst-case histogram bin
    (every row landing in it at max magnitude) still fits int32.

    The grid narrows below the dtype's own range once total_rows exceeds
    2^31 / type_max (~65k rows for int16, ~16.9M for int8): the stats
    still ship/contract at the narrow dtype's width, only the effective
    mantissa shrinks — overflow is impossible by construction, on one
    shard or across any psum of shards (the bound is on GLOBAL rows)."""
    cap = (2 ** 31 - 1) // max(int(total_rows), 1)
    q = min(_INT_TYPE_MAX[precision], cap)
    if q < 1:
        raise ValueError(
            f"{total_rows} rows overflow int32 histogram accumulation even "
            "at 1-bit quantization; use a float tpu_hist_precision")
    return q


def _hash_u32(x: jnp.ndarray) -> jnp.ndarray:
    """Stateless PCG-style avalanche over uint32 counters (wrapping
    arithmetic): the per-row randomness source for stochastic rounding.
    Keyed on the GLOBAL row index so the draw is invariant to how rows
    are sharded — a requirement for bit-identical data-parallel
    quantization, which jax.random's shape-keyed streams cannot give
    under shard_map."""
    x = x * jnp.uint32(747796405) + jnp.uint32(2891336453)
    w = ((x >> ((x >> jnp.uint32(28)) + jnp.uint32(4))) ^ x) \
        * jnp.uint32(277803737)
    return (w >> jnp.uint32(22)) ^ w


def hashed_uniform(idx: jnp.ndarray, seed_a, seed_b, salt: int
                   ) -> jnp.ndarray:
    """[n] uniforms in [0, 1) from uint32 row counters + two key words."""
    h = _hash_u32(idx.astype(jnp.uint32)
                  ^ (jnp.asarray(seed_a, jnp.uint32) ^ jnp.uint32(salt)))
    h = _hash_u32(h + jnp.asarray(seed_b, jnp.uint32))
    return (h >> jnp.uint32(8)).astype(jnp.float32) * jnp.float32(2.0 ** -24)


def key_words(key: jnp.ndarray):
    """Two uint32 words from a PRNG key (raw uint32[2] or typed)."""
    if jnp.issubdtype(key.dtype, jax.dtypes.prng_key):
        key = jax.random.key_data(key)
    kw = jnp.ravel(key).astype(jnp.uint32)
    return kw[0], kw[-1]


def quantize_values(x: jnp.ndarray, scale, qmax: int, mode: str,
                    seed_a=0, seed_b=0, row_offset=0, salt: int = 0,
                    stochastic=None) -> jnp.ndarray:
    """f32 [n] -> int32 grid values in [-qmax, qmax]: x ~= result * scale.

    mode="stochastic" rounds floor(q) up with probability frac(q) —
    unbiased (E[result] * scale == x on-grid) and deterministic given the
    seed words; the randomness comes from `hashed_uniform` over GLOBAL
    row indices (row_offset = this shard's first global row), so the
    rounded values are identical under any row sharding.
    mode="nearest" is plain round-half-to-even.

    `stochastic` (optional TRACED scalar, >0 = stochastic) folds the
    rounding-mode switch into the program instead of keying a distinct
    compile on `mode`: both roundings are elementwise-cheap, so ONE
    program serves either value (the grower passes its traced mode flag
    here; `mode` is ignored then).  Each selected branch is bit-identical
    to the corresponding static `mode`."""
    q = jnp.clip(x / scale, -float(qmax), float(qmax))
    if stochastic is None and mode == "nearest":
        return jnp.rint(q).astype(jnp.int32)
    fl = jnp.floor(q)
    idx = (jnp.arange(x.shape[0], dtype=jnp.uint32)
           + jnp.asarray(row_offset).astype(jnp.uint32))
    r = hashed_uniform(idx, seed_a, seed_b, salt)
    sto = (fl + (r < (q - fl))).astype(jnp.int32)
    if stochastic is None:
        return sto
    return jnp.where(stochastic > 0, sto, jnp.rint(q).astype(jnp.int32))


def bench_hist_operands(bins_np: np.ndarray, precision: str, block: int,
                        seed: int = 0):
    """Blocked operands for histogram micro-benchmarks (bench.py's
    hist_rows_per_sec and tools/perf_probe.py's hist sweep — ONE
    implementation so the stats layout and quantization call can't
    drift between them): slice to whole blocks, transpose to the
    [nb, F, block] layout, draw synthetic grad/hess, quantize for int
    precisions.  Returns (bins_t_blocks, stats_blocks, n_use)."""
    n, F = bins_np.shape
    nb = n // block
    if nb < 1:
        raise ValueError(f"need >= {block} rows, have {n}")
    n_use = nb * block
    rng = np.random.default_rng(seed)
    g = jnp.asarray(rng.normal(size=n_use).astype(np.float32))
    h = jnp.asarray((np.abs(rng.normal(size=n_use)) + 0.1)
                    .astype(np.float32))
    ones = jnp.ones(n_use, jnp.float32)
    if precision in _INT_STAT_DTYPES:
        q = quant_limit(precision, n_use)
        g = quantize_values(g, jnp.max(jnp.abs(g)) / q, q, "nearest")
        h = quantize_values(h, jnp.max(jnp.abs(h)) / q, q, "nearest")
    stats = pack_stats(g, h, ones, precision)
    bins_tb = jnp.asarray(np.ascontiguousarray(bins_np[:n_use].T)
                          .reshape(F, nb, block).transpose(1, 0, 2))
    return bins_tb, stats.reshape(-1, nb, block), n_use


def pack_stats(grad: jnp.ndarray, hess: jnp.ndarray, mask: jnp.ndarray,
               precision: str = "hilo") -> jnp.ndarray:
    """Pack per-row gradient/hessian/count-mask into histogram stat rows.

    grad/hess must already be multiplied by `mask` by the caller if masking
    is intended (mask also serves as the count row).
    Returns [5, n] bf16 for "hilo", [3, n] bf16/f32/f64 otherwise.

    "f64" is the deterministic-parity mode (requires jax_enable_x64): all
    accumulation runs in doubles like the reference's HistogramBinEntry
    (reference include/LightGBM/bin.h:33-40), so serial and data-parallel
    split decisions agree bit-for-bit on real data regardless of psum
    reduction order.

    "int8"/"int16": grad/hess must ALREADY be quantized int values from
    `quantize_values` (within +-quant_limit); the return is the narrow
    [3, n] integer stats plane the int32-accumulating contraction reads.
    """
    if precision in _INT_STAT_DTYPES:
        dt = _INT_STAT_DTYPES[precision]
        return jnp.stack([grad.astype(dt), hess.astype(dt),
                          mask.astype(dt)])
    if precision == "f64":
        return jnp.stack([grad, hess, mask]).astype(jnp.float64)
    if precision == "f32":
        return jnp.stack([grad, hess, mask]).astype(jnp.float32)
    if precision == "bf16":
        return jnp.stack([grad, hess, mask]).astype(jnp.bfloat16)
    # hilo.  The hi half is rounded with reduce_precision, which XLA never
    # elides: written as x.astype(bf16).astype(f32), the TPU compiler keeps
    # the f32 value through the round trip (excess precision is allowed by
    # default), x - hi is then 0 and the lo half is lost — histograms at
    # bf16 accuracy against exact f32 leaf totals (first chip run, PR 21: a
    # 149-row leaf of the first Higgs-1M tree came out at -5359)
    def split(x):
        hi = jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
        return hi.astype(jnp.bfloat16), (x - hi).astype(jnp.bfloat16)

    g_hi, g_lo = split(grad)
    h_hi, h_lo = split(hess)
    cnt = mask.astype(jnp.bfloat16)  # exact: 0.0 or 1.0
    return jnp.stack([g_hi, g_lo, h_hi, h_lo, cnt])


def _unpack_hist(raw: jnp.ndarray, precision: str) -> jnp.ndarray:
    """[S, F*B] accumulated rows -> [F*B, 3] (g, h, cnt).

    Int precisions stay int32 here: the grower's pool, psum, and sibling
    subtraction all run on exact integers; rescaling to f32 happens once
    per leaf at the split-search boundary (ops/grower.py select)."""
    if precision in ("f32", "f64", "bf16", "int8", "int16"):
        g, h, c = raw[0], raw[1], raw[2]
    else:
        g = raw[0] + raw[1]
        h = raw[2] + raw[3]
        c = raw[4]
    return jnp.stack([g, h, c], axis=-1)


@ledger_jit(site="histogram.build",
            static_argnames=("num_bins", "block_rows", "precision"))
def build_histogram(bins: jnp.ndarray, stats: jnp.ndarray, num_bins: int,
                    block_rows: int = 16384, precision: str = "hilo"
                    ) -> jnp.ndarray:
    """hist[f, b, (g,h,cnt)] over all rows.

    bins:  [n, F] int (bin index per row/feature, 0 <= bin < num_bins)
    stats: packed rows from `pack_stats` ([S, n])
    Returns [F, B, 3] f32.

    Rows are processed in blocks via lax.scan so the materialized one-hot is
    [block, F*B] (bf16) rather than [n, F*B]; XLA fuses the compare+select
    into the matmul operand.
    """
    n, num_features = bins.shape
    dot_dtype, acc_dtype, prec = _dot_spec(precision)

    block = min(block_rows, max(n, 1))
    num_blocks = (n + block - 1) // block
    pad = num_blocks * block - n
    if pad:
        bins = jnp.pad(bins, ((0, pad), (0, 0)))
        stats = jnp.pad(stats, ((0, 0), (0, pad)))  # zero stats: no contribution

    bins_blocks = bins.reshape(num_blocks, block, num_features)
    stats_blocks = stats.reshape(stats.shape[0], num_blocks, block)
    iota = jnp.arange(num_bins, dtype=jnp.int32)

    def body(acc, xs):
        b_blk, s_blk = xs  # [block, F], [S, block]
        onehot = (b_blk[:, :, None] == iota).astype(dot_dtype)
        onehot = onehot.reshape(block, num_features * num_bins)
        acc = acc + jnp.dot(s_blk.astype(dot_dtype), onehot,
                            precision=prec,
                            preferred_element_type=acc_dtype)
        return acc, None

    init = jnp.zeros((stats.shape[0], num_features * num_bins), acc_dtype)
    raw, _ = jax.lax.scan(
        body, init, (bins_blocks, jnp.moveaxis(stats_blocks, 1, 0)))
    hist = _unpack_hist(raw, precision)
    return hist.reshape(num_features, num_bins, 3)


def build_histogram_batched_t(bins_t_blocks, stats_blocks, leaf_blocks,
                              slot_leaf_ids, num_bins: int,
                              precision: str = "hilo",
                              impl: str = "xla",
                              packed_rows: bool = False,
                              live_columns: Optional[int] = None,
                              with_rows: bool = False):
    """Transposed-layout batched histogram: rows on the lane axis.

    Histograms of K leaves in ONE contraction: the single-leaf formulation
    ([S, n] x [n, F*B]) is an M=8 matmul that lights at most 8/128 of the
    MXU's rows; batching K leaf slots widens the small axis to K*S lanes,

        hist[(f,b), (k,s)] = sum_r onehot[r, (f,b)] * stats[s, r]
                                    * (leaf_ids[r] == slot_leaf_ids[k])

    and the tree takes ~254/K passes instead of 254 (the TPU analog of the
    reference GPU kernel histogramming many features per workgroup,
    reference src/treelearner/ocl/histogram256.cl:78-120).  The bin matrix
    is stored [F, n] so every operand keeps rows in the 128-lane minor
    dimension (bins [F, blk], stats [S, blk], leaf [1, blk]) — no 28-lane
    padding waste and no layout changes between the one-hot generation and
    the MXU feed.

    bins_t_blocks: [nb, F, block] integer bins (uint8 when
        bins fit — the narrow dense storage — else int32)
    stats_blocks:  [S, nb, block]
    leaf_blocks:   [nb, block] int32
    slot_leaf_ids: [K] int32 (-1 = dead slot)
    impl: "xla" (lax.scan + dot_general) or "pallas2" (the perfeature
        VMEM kernel, `_hist_pallas`)
    live_columns: STATIC count of leading columns that carry data (default:
        all F).  The rest are the learner's alignment padding, whose
        histograms nothing reads: "pallas2" contracts only the live ones
        and returns exact zeros for the padding; "xla" contracts every
        column, so padding comes back as whatever its bins say (all rows in
        bin 0).
    with_rows: also return what the call did with the table's rows, [3]
        uint32 (`_call_rows`): 1, the call, which swept every row; the
        sub-blocks of `perfeature_dot_lanes(block)` rows it contracted; the
        rows it found live (their leaf one of the slots).  "xla" contracts
        every row; "pallas2" says (`_hist_pallas`).
    Returns [K, F, B, 3] f32, with `with_rows` a pair of that and the rows.
    """
    if impl == "pallas2":
        hist, rows = _hist_pallas(
            bins_t_blocks, stats_blocks, leaf_blocks, slot_leaf_ids,
            num_bins, precision,
            packed_rows=packed_rows, live_columns=live_columns)
        return (hist, rows) if with_rows else hist
    if impl != "xla":
        raise ValueError(f"unknown histogram impl {impl!r}")
    if packed_rows:
        raise ValueError("packed (4-bit) bins require impl pallas2")
    nb, num_features, block = bins_t_blocks.shape
    S = stats_blocks.shape[0]
    K = slot_leaf_ids.shape[0]
    dot_dtype, acc_dtype, prec = _dot_spec(precision)

    def body(acc, xs):
        b_t, s_blk, l_blk = xs  # [F, blk], [S, blk], [blk]
        iota = jax.lax.broadcasted_iota(jnp.int32,
                                        (num_features, num_bins, block), 1)
        onehot = (b_t[:, None, :] == iota).astype(dot_dtype)
        onehot = onehot.reshape(num_features * num_bins, block)
        slot_oh = (slot_leaf_ids[:, None] == l_blk[None, :]).astype(dot_dtype)
        sexp = (slot_oh[:, None, :] * s_blk[None, :, :].astype(dot_dtype))
        sexp = sexp.reshape(K * S, block)
        acc = acc + jax.lax.dot_general(
            onehot, sexp, (((1,), (1,)), ((), ())),
            precision=prec, preferred_element_type=acc_dtype)
        return acc, None

    init = jnp.zeros((num_features * num_bins, K * S), acc_dtype)
    raw, _ = jax.lax.scan(
        body, init, (bins_t_blocks, jnp.moveaxis(stats_blocks, 1, 0),
                     leaf_blocks))
    raw = jnp.transpose(
        raw.reshape(num_features * num_bins, K, S), (1, 2, 0))
    hist = jax.vmap(lambda r: _unpack_hist(r, precision))(raw)
    hist = hist.reshape(K, num_features, num_bins, 3)
    if not with_rows:
        return hist
    live = jnp.any(slot_leaf_ids[:, None, None] == leaf_blocks[None], axis=0)
    return hist, _call_rows(nb * (block // perfeature_dot_lanes(block)), live)


def _call_rows(contracted, live) -> jnp.ndarray:
    """[3] uint32 of one histogram call, which a tree's calls sum to its
    `ops/grower.py` HIST_ROWS_* columns: 1 (the call), the sub-blocks it
    contracted and its live rows, each a count or per-block counts."""
    return jnp.stack([1, jnp.sum(contracted),
                      jnp.sum(live, dtype=jnp.int32)]).astype(jnp.uint32)


def build_histogram_sparse(sidx: jnp.ndarray, sbin: jnp.ndarray,
                           stats: jnp.ndarray, leaf_ids: jnp.ndarray,
                           slot_leaf_ids: jnp.ndarray, num_bins: int,
                           precision: str = "hilo",
                           block_entries: int = 2048) -> jnp.ndarray:
    """Batched histograms for COO-stored sparse feature groups.

    The dense contraction sweeps every row per group; sparse groups store
    only their nonzero-bin entries (reference OrderedSparseBin,
    src/io/ordered_sparse_bin.hpp — delta-encoded there, padded COO
    here), so the sweep is O(nnz) per group: gather the stats and leaf
    ids at the stored row ids, then run the SAME one-hot x slot-one-hot
    contraction per group over the entry axis.

    sidx: [Gs, M] int32 stored row ids; padding entries may hold any
        value (e.g. n_pad) — their sbin must be num_bins, whose one-hot
        row is all-zero, so they contribute nothing regardless of what
        the (clipped) gather returns.
    sbin: [Gs, M] int32 stored bins in [0, B); padding = num_bins.
    stats: [S, n_pad] packed rows from `pack_stats`.
    leaf_ids: [n_pad] int32 current leaf per row.
    slot_leaf_ids: [K] int32 (-1 = dead slot).
    Returns [K, Gs, B, 3] f32/f64 — WITHOUT the implicit zero-bin mass
    (every unstored row); the grower reconstructs it from leaf totals
    exactly like FixHistogram (reference dataset.cpp:1044-1063).
    """
    Gs, M = sidx.shape
    S = stats.shape[0]
    K = slot_leaf_ids.shape[0]
    dot_dtype, acc_dtype, prec = _dot_spec(precision)

    mb = min(block_entries, M)
    nmb = (M + mb - 1) // mb
    if nmb * mb != M:  # static pad to whole blocks; pads contribute 0
        padw = nmb * mb - M
        sidx = jnp.pad(sidx, ((0, 0), (0, padw)))
        sbin = jnp.pad(sbin, ((0, 0), (0, padw)),
                       constant_values=num_bins)
    sidx_b = jnp.moveaxis(sidx.reshape(Gs, nmb, mb), 1, 0)  # [nmb, Gs, mb]
    sbin_b = jnp.moveaxis(sbin.reshape(Gs, nmb, mb), 1, 0)
    iota_b = jnp.arange(num_bins, dtype=jnp.int32)

    def body(acc, xs):
        si, sb = xs                              # [Gs, mb] each
        safe = jnp.clip(si, 0, stats.shape[1] - 1)
        st = stats[:, safe]                      # [S, Gs, mb] gather
        lf = leaf_ids[safe]                      # [Gs, mb]
        slot_oh = (slot_leaf_ids[:, None, None] == lf[None]).astype(dot_dtype)
        onehot = (sb[:, None, :] == iota_b[None, :, None]).astype(dot_dtype)
        sexp = (slot_oh[:, None, :, :]                    # [K, 1, Gs, mb]
                * st[None, :, :, :].astype(dot_dtype))    # [1, S, Gs, mb]
        sexp = jnp.moveaxis(sexp.reshape(K * S, Gs, mb), 1, 0)  # [Gs, KS, mb]
        acc = acc + jax.lax.dot_general(
            onehot, sexp, (((2,), (2,)), ((0,), (0,))),
            precision=prec, preferred_element_type=acc_dtype)  # [Gs, B, KS]
        return acc, None

    init = jnp.zeros((Gs, num_bins, K * S), acc_dtype)
    raw, _ = jax.lax.scan(body, init, (sidx_b, sbin_b))
    raw = jnp.transpose(raw.reshape(Gs, num_bins, K, S),
                        (2, 3, 0, 1))            # [K, S, Gs, B]
    raw = raw.reshape(K, S, Gs * num_bins)
    hist = jax.vmap(lambda r: _unpack_hist(r, precision))(raw)
    return hist.reshape(K, Gs, num_bins, 3)


# VMEM budget for one feature chunk's accumulator block in the perfeature
# pallas kernel; the remaining ~10 MB of VMEM holds the [Bp, blk] one-hot,
# the [K*S, blk] expanded stats, and the double-buffered input DMAs
_PERFEATURE_OUT_BUDGET = 6 * 1024 * 1024
# a group of the perfeature kernel (`perfeature_columns_per_dot`): the lanes
# (table rows) one dot of a group contracts, the most columns a group stacks,
# and the VMEM budget of its stacked [G * Bp, lanes] one-hot.  Measured on a
# v5e (PERF.md §5, PR 28): 1024 lanes beat 512 and tie 2048 and 4096 at a
# third of the compile time; 2 to 4 columns beat 7 and more at 255 bins
_PERFEATURE_GROUP_LANES = 1024
_PERFEATURE_GROUP_COLUMNS = 4
_PERFEATURE_GROUP_BUDGET = 2 * 1024 * 1024
# the precisions `tpu_hist_impl=auto` may hand to the perfeature kernel: each compiled and ran at full Higgs width on a v5e,
# at 8192- and 16384-row blocks, equal to the xla contraction (PR 21).
# f32 runs too but took 157 s to compile; Mosaic refuses int16 dots
PERFEATURE_AUTO_PRECISIONS = ("hilo", "bf16", "int8")


def perfeature_chunk_fits(columns: int, num_bins: int, slots: int,
                          planes: int) -> bool:
    """Whether a [columns * Bp, K*S] f32 accumulator block of the perfeature
    kernel (bins padded to 8 rows, slots x planes to 128 lanes) fits its
    VMEM budget."""
    bp = -(-num_bins // 8) * 8
    ks_pad = -(-(slots * planes) // 128) * 128
    return columns * bp * ks_pad * 4 <= _PERFEATURE_OUT_BUDGET


def perfeature_chunks(columns: int, num_bins: int, slots: int, planes: int,
                      bins_itemsize: int = 1) -> Tuple[int, int]:
    """(columns per chunk, chunks) of the perfeature kernel's feature grid.

    Feature chunking: the largest divisor of `columns` whose accumulator
    block fits the VMEM budget.  Mosaic block-shape rules constrain the
    candidates: the bins block's second-minor dim (the chunk width) must be
    sublane-tile-aligned for the bins dtype unless it equals the array dim
    (32 rows for uint8, 16 for 2-byte, 8 for int32).  When the width has no
    aligned divisor that fits (e.g. 2000 = 2^4 * 5^3 for uint8 bins), the
    kernel stays single-chunk; the learner pads the column axis to a
    32-multiple for pallas2 precisely to unlock chunking, and says how many
    of the columns are real (`live_columns`).  The one place this is
    decided: `_hist_pallas` runs the grid it returns and the learner's
    `lgbm_hist_grid` gauge reports it.
    """
    step = {1: 32, 2: 16, 4: 8}[bins_itemsize]
    fblk = columns
    if not perfeature_chunk_fits(columns, num_bins, slots, planes):
        cands = [c for c in range(step, columns, step)
                 if columns % c == 0
                 and perfeature_chunk_fits(c, num_bins, slots, planes)]
        if cands:
            fblk = max(cands)
    return fblk, columns // fblk


def perfeature_dot_lanes(block: int) -> int:
    """Lanes (table rows) one dot of a group contracts: a lane sub-block of
    the row block where the block is a whole number of them, else the block."""
    return (_PERFEATURE_GROUP_LANES if block % _PERFEATURE_GROUP_LANES == 0
            else block)


def perfeature_columns_per_dot(num_bins: int, block: int, precision: str,
                               columns: int, live: int) -> int:
    """G, the adjacent live columns whose one-hots one dot of the perfeature
    kernel stacks into a [G * Bp, lanes] operand (a group).

    Every dot hands the MXU the [K*S, lanes] slot operand anew, and a
    column's own dot streams only its Bp one-hot rows past it; a group
    streams G * Bp rows per load (PERF.md §5).  G is the most columns whose
    stacked one-hot fits its VMEM budget, at most `_PERFEATURE_GROUP_COLUMNS`
    and the columns a chunk can hold live; 1, the ungrouped kernel, where
    the budget holds one column only (bins in the thousands) or where Bp is
    not a whole number of the dot dtype's sublane tiles (a column's one-hot
    could not be stored at its row offset of the group).  The one place
    this is decided: `_hist_pallas` forms its groups by it and the learner's
    `lgbm_hist_grid{axis="columns_per_dot"}` gauge reports it.
    """
    bp = -(-num_bins // 8) * 8
    itemsize = jnp.dtype(_dot_spec(precision)[0]).itemsize
    if bp % (32 // itemsize):  # sublane tile: 8 rows of f32, 16 bf16, 32 int8
        return 1
    fits = _PERFEATURE_GROUP_BUDGET // (
        bp * perfeature_dot_lanes(block) * itemsize)
    return max(1, min(fits, _PERFEATURE_GROUP_COLUMNS, columns, live))


# the leaf id of a lane that holds no row, in a block the perfeature kernel
# has packed: no slot's, a dead one's (-1) included
_NO_SLOT = -2


def pallas_interpret() -> bool:
    """Whether `pallas_call` runs its kernels in interpret mode — the ONE
    place that is decided: compiled by Mosaic whenever the platform is
    `tpu`, interpreted (plain jnp, for tests) everywhere else."""
    return jax.devices()[0].platform != "tpu"


def unpack2d(b2):
    """[.., blk/2] packed two-rows-per-byte uint8 -> [.., blk] int32.

    The SINGLE definition of the 4-bit stride layout (low nibbles are a
    block's first half of rows, high nibbles the second): the pallas
    kernels and the grower's partition unpack must agree or packed
    histograms and packed partitions silently diverge."""
    # widen FIRST: the VPU has no 8-bit shift (Mosaic on a v5e: "failed to
    # legalize operation 'arith.shrui'" on i8 vectors)
    b = b2.astype(jnp.int32)
    return jnp.concatenate([b & 0xF, b >> 4], axis=-1)


def _hist_pallas(bins_t_blocks, stats_blocks, leaf_blocks, slot_leaf_ids,
                 num_bins: int, precision: str,
                 packed_rows: bool = False,
                 live_columns: Optional[int] = None) -> jnp.ndarray:
    """Pallas kernel: fused one-hot + slot-expansion + MXU contraction.

    The TPU answer to the reference GPU kernel's workgroup-local
    sub-histograms (reference src/treelearner/ocl/histogram256.cl:78-120):
    the accumulator stays resident in VMEM across the row-block grid, and
    neither the one-hot nor the expanded stats ever round-trip to HBM.

    This is impl "pallas2", the auto default on a TPU at 8192-row blocks
    (PERF.md §5 has its times on a v5e).  The one-hot is generated per
    feature ([Bp, blk] at most, statically-unrolled dots), so the largest
    temporary is [Bp, blk] and blocks of 2-8k rows fit.  Each feature's
    bin rows live at a sublane-aligned Bp = ceil(B/8)*8 offset in the
    accumulator.  When the full [F*Bp, K*S] accumulator would overflow
    VMEM (wide data: Epsilon/Bosch F*B shapes), the grid gains a FEATURE
    axis: features are processed in the largest divisor-of-F chunk whose
    [fblk*Bp, K*S] out block fits, and the row-block axis iterates
    innermost so each feature chunk's accumulator stays VMEM-resident
    across its row sweep.

    Only the first `live_columns` columns get a one-hot and a dot; the
    accumulator rows of the rest (the learner's padding to the bins
    dtype's sublane tile) are written as zeros once, so the output keeps
    its [K, F, B, 3] shape.  The count is static because the column loop
    is unrolled: it is part of the program's key.

    The dots are per GROUP: G adjacent live columns (of those live in the
    same feature chunks) write their one-hots to Bp-aligned rows of one
    [G*Bp, lanes] VMEM scratch and are contracted against the slot operand
    in one dot, whose [G*Bp, K*S] result lands on the group's rows of the
    accumulator, contiguous as they are.  A group's dots run over
    `perfeature_dot_lanes` rows of the block at a time (a `fori_loop` over
    lane sub-blocks; the 4-bit stride layout takes the block whole); a
    block's sub-blocks are summed in a second scratch and added once to
    the accumulator, which the first row block zeroes, so the f32
    accumulator rounds once per block as it did when a dot spanned the
    block.  G comes from `perfeature_columns_per_dot`, a function of the
    shapes alone (Bp, block, dot dtype, columns of a chunk, live count)
    that the learner's gauge calls too; where it answers 1 the kernel is
    the ungrouped one: a [Bp, blk] one-hot and a dot per column over the
    whole block, the first block's dot storing.

    Rows: a row carries anything into the call only where its leaf is one
    of the K slots (a live row); after the root call a fifth of a tree's
    rows are.  The grouped kernel over lane sub-blocks therefore copies a
    block to VMEM scratch, left-packs its live rows there (bins as 32-bit
    words, the stat planes' bit patterns and the leaf ids, moved together
    and in order: `left_pack`) and runs its dots only over the sub-blocks
    that then are full.  The last, part-filled one is not contracted with
    its block: its rows join a carry, one more sub-block of VMEM that
    holds up to `lanes - 1` rows of earlier blocks, and the sub-block that
    fills is contracted in the block that fills it (`merge`, one roll of a
    sub-block's tile).  A feature chunk's last block contracts what is left
    in the carry, and its first starts with none, so a chunk contracts its
    packed blocks' live rows in whole sub-blocks but one.  A block with no
    live row does no dot; a block that packing would not shorten by a
    sub-block (every block of the root call) is swept as it came and
    leaves the carry as it was.  A dead row added an exact zero, so the
    sums are the same; only which rows share a sub-block's f32 partial sum
    moves, and a block's partial sums still meet the accumulator once.
    The other two arms (G = 1, 4-bit rows) sweep every row.  The kernel
    also writes out, one int32 a block each, the block's live rows and,
    packing, the sub-blocks it contracted.

    Returns the [K, F, B, 3] histograms and the call's `_call_rows`: the
    call, the sub-blocks (of `perfeature_dot_lanes(block)` rows) it
    contracted, and its live rows.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    nb, F, bins_block = bins_t_blocks.shape
    live = F if live_columns is None else int(live_columns)
    if not 0 < live <= F:
        raise ValueError(f"live_columns={live_columns} outside 1..{F}")
    # packed 4-bit storage (the reference dense_nbits_bin.hpp analog,
    # max_bin<=16): each uint8 byte holds TWO rows of one block — row j in
    # the low nibble, row j + block/2 in the high nibble — so the kernel's
    # row-sweep DMA traffic halves.  Unpacking is a nibble mask/shift plus
    # a lane-axis concat of two half-blocks (the stride layout exists so
    # the concat IS the row order).
    block = bins_block * 2 if packed_rows else bins_block
    S = stats_blocks.shape[0]
    K = slot_leaf_ids.shape[0]
    B = num_bins
    Bp = -(-B // 8) * 8  # sublane-aligned per-feature row offset
    # int accumulator twins: narrow-int operands, exact int32 VMEM
    # accumulator — the [3, n] int8 stats plane is 2-4x leaner than
    # hilo's [5, n] bf16, so larger row blocks fit the same VMEM budget
    if precision in _INT_STAT_DTYPES:
        dot_dtype, acc_dtype, dot_prec = _dot_spec(precision)
    else:
        dot_dtype = jnp.float32 if precision == "f32" else jnp.bfloat16
        acc_dtype = jnp.float32
        dot_prec = (jax.lax.Precision.HIGHEST if precision == "f32"
                    else jax.lax.Precision.DEFAULT)

    def expand_slots(s, l, slots_ref):
        """[K*S, lanes] per-slot stats of rows with packed stat rows `s`
        [S, lanes] and leaf ids `l` [1, lanes]: slot one-hot x stats."""
        slots = slots_ref[:]                    # [K, 1] i32
        hit = slots == l                                    # [K, lanes]
        if precision in _INT_STAT_DTYPES:
            # the VPU has no narrow-int multiply (Mosaic on a v5e: "failed
            # to legalize operation 'arith.muli'" on i8 vectors): select
            # at 32 bits, narrow once for the MXU
            sexp = jnp.where(hit[:, None, :],
                             s.astype(jnp.int32)[None, :, :],
                             0).astype(dot_dtype)
        else:
            sexp = (hit.astype(dot_dtype)[:, None, :]
                    * s[None, :, :].astype(dot_dtype))
        return sexp.reshape(K * S, s.shape[-1])

    def live_lanes(leaf_ref, slots_ref):
        """[1, block] int32, 1 where a row's leaf is one of the call's
        slots: the rows that carry anything into a histogram."""
        hit = slots_ref[:] == leaf_ref[0]                   # [K, block]
        return jnp.max(hit.astype(jnp.int32), axis=0, keepdims=True)

    def accumulate(i, out_ref, rows, acc):
        @pl.when(i == 0)
        def _():
            out_ref[rows, :] = acc

        @pl.when(i > 0)
        def _():
            out_ref[rows, :] += acc

    def kernel_perfeature_chunk(fblk, nf, G, lanes, compacts, narrow, meta):
        # position f holds a live column in chunks 0..last(f), which only
        # falls as f rises: a run is the adjacent positions live in the same
        # chunks, under one guard, a group up to G adjacent positions of a run
        positions = {}
        for f in range(min(fblk, live)):
            positions.setdefault((live - 1 - f) // fblk, []).append(f)
        # (last chunk, [(first position, columns) of each group])
        runs = [(last, [(f, min(G, fs[-1] + 1 - f))
                        for f in range(fs[0], fs[-1] + 1, G)])
                for last, fs in positions.items()]

        def kernel(bins_ref, stats_ref, leaf_ref, slots_ref, out_ref,
                   live_ref, *scratch):
            # the packing arm also writes the sub-blocks a block contracted
            sweeps_ref, *scratch = scratch if compacts else (None, *scratch)
            fi = pl.program_id(0)  # feature-chunk axis
            i = pl.program_id(1)   # row-block axis (innermost)
            live_row = live_lanes(leaf_ref, slots_ref)
            n_live = jnp.sum(live_row)
            live_ref[i] = n_live

            def each_run(do):
                """`do(groups)` for every run live in this chunk."""
                for last, groups in runs:
                    if last >= nf - 1:
                        do(groups)
                    else:
                        pl.when(fi <= last)(functools.partial(do, groups))

            def sweep(column, s, l, land):
                """Every live column's dot over `lanes` rows: `column(f)`
                their bins in column f, `s` their stat planes, `l` their
                leaf ids; `land(rows, acc)` takes a dot's result for those
                rows of the accumulator."""
                sexp = expand_slots(s, l, slots_ref)
                iota_b = jax.lax.broadcasted_iota(jnp.int32, (Bp, lanes), 0)

                def onehot_of(f):
                    return (column(f)[None, :] == iota_b).astype(dot_dtype)

                def contract(f, g):
                    if G == 1:
                        onehot = onehot_of(f)
                    else:
                        # built in dot layout: each column's narrowed
                        # one-hot goes to its Bp-aligned rows of the
                        # scratch, so the 32-bit iota and compare stay one
                        # column wide
                        stack = scratch[0]
                        for j in range(g):
                            stack[j * Bp:(j + 1) * Bp, :] = onehot_of(f + j)
                        onehot = stack[:g * Bp, :]
                    land(slice(f * Bp, (f + g) * Bp), jax.lax.dot_general(
                        onehot, sexp, (((1,), (1,)), ((), ())),
                        precision=dot_prec,
                        preferred_element_type=acc_dtype))

                def run(groups):
                    for f, g in groups:
                        contract(f, g)
                each_run(run)

            def sweep_block(land):
                """The block as it came, every row of it."""
                if packed_rows:
                    def column(f):
                        return unpack2d(bins_ref[0, f])
                else:
                    def column(f):
                        return bins_ref[0, f, :].astype(jnp.int32)
                sweep(column, stats_ref[0], leaf_ref[0], land)

            def zero_from_first_block(cond, rows):
                @pl.when(cond & (i == 0))
                def _():
                    out_ref[rows, :] = jnp.zeros(
                        (rows.stop - rows.start, K * S), acc_dtype)

            if G == 1:
                # the ungrouped kernel: a column's first dot stores, dead
                # positions are zeroed where they are dead
                for last, fs in positions.items():
                    if last < nf - 1:
                        zero_from_first_block(
                            fi > last, slice(fs[0] * Bp, (fs[-1] + 1) * Bp))
                if live < fblk:  # positions that are padding in every chunk
                    zero_from_first_block(True, slice(live * Bp, fblk * Bp))
                sweep_block(lambda rows, acc: accumulate(
                    i, out_ref, rows, acc))
                return
            # every dot adds: dead and padding rows stay as zeroed
            zero_from_first_block(True, slice(0, fblk * Bp))
            if not compacts:
                def add(rows, acc):
                    out_ref[rows, :] += acc
                sweep_block(add)
                return
            part, packed, carried = scratch[1], scratch[4], scratch[5]
            as_stored = packed.bitcast(bins_t_blocks.dtype) if narrow \
                else packed
            stats_rows, leaf_row = slice(meta, meta + S), slice(
                meta + S, meta + S + 1)
            # `packed` is the block's sub-blocks and one more, the carry:
            # rows of earlier blocks at its first `carried` lanes, no
            # slot's leaf id past them
            rows_of_block = slice(0, block)
            carry_sb = block // lanes
            carry_at = slice(block, block + lanes)

            def stage():
                """The block's rows into `packed`, as they came."""
                if narrow:
                    packed[0:meta, rows_of_block] = bins_ref.bitcast(
                        jnp.int32)[0]
                else:
                    packed[0:fblk, rows_of_block] = bins_ref[0].astype(
                        jnp.int32)
                s = stats_ref[0]
                if not jnp.issubdtype(s.dtype, jnp.integer):
                    s = jax.lax.bitcast_convert_type(
                        s.astype(jnp.float32), jnp.int32)
                packed[stats_rows, rows_of_block] = s.astype(jnp.int32)
                packed[leaf_row, rows_of_block] = leaf_ref[0]

            def left_pack():
                """`packed`'s live rows moved to its first `n_live` lanes,
                in their order, every tile of it alike.  A live row moves
                left by the dead rows before it: that count by log-steps
                of roll-and-add (Mosaic lowers no cumsum), then one
                conditional shift per bit of it, lowest first, which never
                lands two rows on a lane; the count travels with its row
                and a lane a row has left counts as dead.  The counts are
                kept as `[8, block / 8]`, lane p of the block at
                `[p // (block / 8), p % (block / 8)]`: an eighth of the
                registers a `[1, block]` row takes.  The lanes past the
                live rows get a leaf id no slot has (-1 is a dead
                slot's)."""
                sub, bits = block // 8, (block - 1).bit_length()
                lane = jax.lax.broadcasted_iota(jnp.int32, (8, sub), 1)
                place = lane + sub * jax.lax.broadcasted_iota(
                    jnp.int32, (8, sub), 0)
                comes_to, comes_at = scratch[2], scratch[3]

                def ahead(x, step):
                    """The counts `step` lanes of the block further on,
                    around its end."""
                    rows, lanes_on = divmod(step, sub)
                    if lanes_on:
                        x = pltpu.roll(x, sub - lanes_on, 1)
                    here = pltpu.roll(x, 8 - rows, 0) if rows else x
                    if not lanes_on:
                        return here
                    below = pltpu.roll(x, 7 - rows, 0) if rows < 7 else x
                    return jnp.where(lane < sub - lanes_on, here, below)

                alive = jnp.concatenate(
                    [live_row[:, r * sub:(r + 1) * sub] for r in range(8)],
                    axis=0)
                move = 1 - alive
                for bit in range(bits):
                    move = move + jnp.where(
                        place >= 1 << bit, ahead(move, block - (1 << bit)), 0)
                move = move * alive
                for bit in range(bits):
                    coming = ahead(move, 1 << bit)
                    comes = (coming >> bit) & 1
                    comes_at[bit * 8:bit * 8 + 8, :] = comes
                    move = jnp.where(
                        comes != 0, coming,
                        jnp.where(((move >> bit) & 1) != 0, 0, move))

                def widen(bit, carry):
                    # whether a row comes to a lane at a step, for every
                    # sublane of a tile
                    tile = pl.ds(pl.multiple_of(bit * 8, 8), 8)
                    comes = comes_at[tile, :]
                    for r in range(8):
                        comes_to[tile, r * sub:(r + 1) * sub] = (
                            jnp.broadcast_to(comes[r:r + 1, :], (8, sub)))
                    return carry

                jax.lax.fori_loop(0, bits, widen, 0)

                def shifts(j, carry):
                    tile = pl.ds(pl.multiple_of(j * 8, 8), 8)
                    x = packed[tile, rows_of_block]
                    for bit in range(bits):
                        x = jnp.where(
                            comes_to[bit * 8:bit * 8 + 8, :] != 0,
                            pltpu.roll(x, block - (1 << bit), 1), x)
                    packed[tile, rows_of_block] = x
                    return carry

                jax.lax.fori_loop(0, meta // 8 + 1, shifts, 0)
                packed[leaf_row, rows_of_block] = jnp.where(
                    jax.lax.broadcasted_iota(jnp.int32, (1, block), 1)
                    < n_live, packed[leaf_row, rows_of_block], _NO_SLOT)

            # a block's sub-blocks are summed apart, in `part`, and meet the
            # accumulator once, as a whole block's dot does: its sums run
            # into the hundreds of thousands, where every f32 add rounds
            def rows_of(groups):
                return slice(groups[0][0] * Bp, sum(groups[-1]) * Bp)

            def clear(groups):
                rows = rows_of(groups)
                part[rows, :] = jnp.zeros((rows.stop - rows.start, K * S),
                                          acc_dtype)

            def add(rows, acc):
                part[rows, :] += acc

            def meet(groups):
                rows = rows_of(groups)
                out_ref[rows, :] += part[rows, :]

            @pl.when(i == 0)
            def _():
                # each feature chunk's row sweep starts with no carry; its
                # lanes hold zeros, not stale VMEM, since the flush sweeps
                # them too (a dead lane's stats times a zero)
                carried[0] = 0
                packed[:, carry_at] = jnp.zeros((meta + 8, lanes), jnp.int32)
                packed[leaf_row, carry_at] = jnp.full((1, lanes), _NO_SLOT,
                                                   jnp.int32)

            # where packing frees no sub-block the rows stay where they
            # are (a dead row's leaf is no slot's either way), all of them
            # contracted; a packed block's full sub-blocks are, and its
            # part-filled one joins the carry: `q` full, `m` rows over
            packs = (n_live > 0) & (n_live <= block - lanes)
            r = carried[0]
            q, m = n_live // lanes, n_live % lanes
            full = r + m >= lanes
            held = jnp.where(packs, jnp.where(full, r + m - lanes, r + m), r)
            carried[0] = held
            # the contracted sub-blocks: the block's, then the carry's at
            # the chunk's last block
            own = jnp.where(packs, jnp.where(full, q + 1, q),
                            (n_live + lanes - 1) // lanes)
            trips = jnp.where((i == nb - 1) & (held > 0), own + 1, own)
            sweeps_ref[i] = trips

            def merge():
                """The carry's `r` rows and sub-block `q`'s `m`, turned
                right by `r`, into sub-block `q` and the carry.  Where
                they fill it, sub-block `q` is contracted and the carry is
                the rows that turned round (the lanes past them are dead
                rows of `q` or the carry's own); else the carry is the
                two."""
                at = pl.ds(pl.multiple_of(q * lanes, lanes), lanes)
                x, c = packed[:, at], packed[:, carry_at]
                turned = pltpu.roll(x, r, 1)
                lane = jax.lax.broadcasted_iota(jnp.int32, (meta + 8, lanes),
                                                1)
                packed[:, at] = jnp.where(lane < r, c, turned)
                # the lanes the carry takes from `turned`
                lo, hi = jnp.where(full, 0, r), jnp.where(full, r, lanes)
                packed[:, carry_at] = jnp.where((lane >= lo) & (lane < hi),
                                             turned, c)

            pl.when(n_live > 0)(stage)

            @pl.when(packs)
            def _():
                left_pack()
                merge()

            def sub_block(t, state):
                # the block's sub-blocks in order, then the carry
                sb = jnp.where(t < own, t, carry_sb)
                at = pl.ds(pl.multiple_of(sb * lanes, lanes), lanes)
                stats = packed[stats_rows, at]
                if not jnp.issubdtype(stats_ref.dtype, jnp.integer):
                    stats = jax.lax.bitcast_convert_type(stats, jnp.float32)
                sweep(lambda f: as_stored[f, at].astype(jnp.int32),
                      stats, packed[leaf_row, at], add)
                return state

            pl.when(trips > 0)(functools.partial(each_run, clear))
            jax.lax.fori_loop(0, trips, sub_block, 0)
            pl.when(trips > 0)(functools.partial(each_run, meet))
        return kernel

    # Mosaic block-shape rule: the last two dims of every block must be
    # (8k, 128k)-aligned or equal the array's dims.  All operands are laid
    # out [nb, ..., block] so each grid step's block matches the trailing
    # dims exactly; the S/leaf axes ride along whole.
    stats_nb = jnp.moveaxis(stats_blocks, 1, 0)             # [nb, S, blk]
    interpret = pallas_interpret()
    ks_pad = -(-(K * S) // 128) * 128
    fblk, nf = perfeature_chunks(F, B, K, S, bins_t_blocks.dtype.itemsize)
    G = perfeature_columns_per_dot(B, block, precision, fblk, live)
    # a group's dots run over lane sub-blocks of the row block; the
    # 4-bit stride layout spans the block, and one column at a time is
    # the kernel as it was
    lanes = block if G == 1 or packed_rows else perfeature_dot_lanes(block)
    # the grouped kernel over lane sub-blocks packs a block's live rows
    # first and contracts only the sub-blocks that then hold one; the other
    # two arms sweep every row of every block
    compacts = G > 1 and lanes < block
    # the packed copy of a block: the bins as 32-bit words (`narrow`: four
    # uint8 columns a word, as Mosaic stores them) or widened, a column a
    # row, up to whole sublane tiles (`meta` rows); then one more tile, the
    # S stat planes as their 32-bit patterns and the leaf ids
    narrow = bins_t_blocks.dtype.itemsize == 1 and fblk % 32 == 0
    meta = -(-(fblk // 4 if narrow else fblk) // 8) * 8
    pack_bits = (block - 1).bit_length()
    dot_bytes = jnp.dtype(dot_dtype).itemsize
    # scoped-VMEM ceiling, from the shapes: the compiler's default
    # (16 MiB on a v5e) is under what the block-scaled temporaries
    # need at 16384 rows (int8 there: "Scoped allocation with size
    # 18.45M and limit 16.00M exceeded scoped vmem limit").  An upper
    # bound, not a reservation: double-buffered in/out blocks (the
    # [S, blk] stats and [1, blk] leaf ids each pad to one 32-byte
    # sublane tile per row) plus the [Bp, lanes] iota, compare and
    # one-hot and the [K*S, lanes] slot expansion at 32 bits and
    # narrowed, all live at once; a group adds its stacked one-hot
    # (the scratch and the dot's read of it) and its [G*Bp, K*S]
    # result, lane sub-blocks their partial accumulator, the pack its
    # copy of the block, a [8, blk] tile of 32-bit masks per step and
    # ten more of temporaries
    pipelined = 2 * (fblk * Bp * ks_pad * 4
                     + fblk * bins_block * bins_t_blocks.dtype.itemsize
                     + (32 + 32) * block)
    temporaries = lanes * (Bp * (4 + 4 + dot_bytes) + ks_pad * (4 + 4))
    stacked = (G > 1) * G * Bp * (2 * lanes * dot_bytes + ks_pad * 4)
    part = compacts * fblk * Bp * ks_pad * 4
    packing = compacts * ((meta + 8) * (block + lanes)
                          + 8 * (10 + pack_bits) * block) * 4
    vmem_limit = pipelined + temporaries + stacked + part + packing
    # grid order: the row-block axis is LAST (innermost), so each
    # feature chunk's accumulator block stays resident while the row
    # sweep accumulates into it, and in order (`arbitrary`): the carry
    # goes from one row block to the next.  The second output is a
    # block's live rows, the packing arm's third the sub-blocks it
    # contracted, a scalar a grid step each (every feature chunk writes
    # the same)
    counts = pl.BlockSpec(memory_space=pltpu.SMEM)
    raw, live_rows, *sweeps = pl.pallas_call(
        kernel_perfeature_chunk(fblk, nf, G, lanes, compacts, narrow, meta),
        grid=(nf, nb),
        in_specs=[
            pl.BlockSpec((1, fblk, bins_block), lambda fi, i: (i, fi, 0)),
            pl.BlockSpec((1, S, block), lambda fi, i: (i, 0, 0)),
            pl.BlockSpec((1, 1, block), lambda fi, i: (i, 0, 0)),
            pl.BlockSpec((K, 1), lambda fi, i: (0, 0)),
        ],
        out_specs=(
            pl.BlockSpec((fblk * Bp, K * S), lambda fi, i: (fi, 0)),
            counts, *[counts] * compacts),
        out_shape=(jax.ShapeDtypeStruct((F * Bp, K * S), acc_dtype),
                   *[jax.ShapeDtypeStruct((nb,), jnp.int32)] * (1 + compacts)),
        scratch_shapes=(
            [pltpu.VMEM((G * Bp, lanes), dot_dtype)] * (G > 1)
            + [pltpu.VMEM((fblk * Bp, K * S), acc_dtype),
               pltpu.VMEM((8 * pack_bits, block), jnp.int32),
               pltpu.VMEM((8 * pack_bits, block // 8), jnp.int32),
               pltpu.VMEM((meta + 8, block + lanes), jnp.int32),
               pltpu.SMEM((1,), jnp.int32)] * compacts),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=vmem_limit),
        interpret=interpret,
    )(bins_t_blocks, stats_nb, leaf_blocks.reshape(nb, 1, block),
      slot_leaf_ids.reshape(K, 1))
    raw = jnp.transpose(raw.reshape(F, Bp, K, S)[:, :B], (2, 3, 0, 1))
    raw = raw.reshape(K, S, F * B)
    hist = jax.vmap(lambda r: _unpack_hist(r.reshape(S, F * B), precision))(
        raw)
    contracted = (sweeps[0] if compacts
                  else nb * (block // perfeature_dot_lanes(block)))
    return hist.reshape(K, F, B, 3), _call_rows(contracted, live_rows)


def build_histogram_t(bins_t_blocks, stats_blocks, num_bins: int,
                      precision: str = "hilo") -> jnp.ndarray:
    """Single-histogram (root) pass in the transposed layout.

    bins_t_blocks: [nb, F, block]; stats_blocks: [S, nb, block].
    Returns [F, B, 3] f32.
    """
    nb, num_features, block = bins_t_blocks.shape
    dot_dtype, acc_dtype, prec = _dot_spec(precision)

    def body(acc, xs):
        b_t, s_blk = xs
        iota = jax.lax.broadcasted_iota(jnp.int32,
                                        (num_features, num_bins, block), 1)
        onehot = (b_t[:, None, :] == iota).astype(dot_dtype)
        onehot = onehot.reshape(num_features * num_bins, block)
        acc = acc + jax.lax.dot_general(
            onehot, s_blk.astype(dot_dtype), (((1,), (1,)), ((), ())),
            precision=prec, preferred_element_type=acc_dtype)
        return acc, None

    init = jnp.zeros((num_features * num_bins, stats_blocks.shape[0]),
                     acc_dtype)
    raw, _ = jax.lax.scan(
        body, init, (bins_t_blocks, jnp.moveaxis(stats_blocks, 1, 0)))
    hist = _unpack_hist(raw.T, precision)
    return hist.reshape(num_features, num_bins, 3)
