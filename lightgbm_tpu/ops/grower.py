"""Leaf-wise tree growth as a single compiled device program.

The reference grows best-first one split at a time with pointer-chasing state
(reference src/treelearner/serial_tree_learner.cpp:173-237): an LRU histogram
pool, permuted row-index partitions, and per-leaf OrderedBin re-sorts.  None
of that maps to XLA.  Here the whole tree is ONE `lax.while_loop` over
BATCHED ROUNDS, each splitting up to `split_batch` leaves at once:

* leaf assignment is an [n] int32 vector (splits become `where` updates, the
  analog of DataPartition::Split, data_partition.hpp:111-163);
* each round picks the top-K leaves by stored best gain (`lax.top_k` over
  the per-leaf candidate table — K-wide best-first, degenerating to the
  reference's strict best-first order at split_batch=1), partitions all K
  leaves' rows in one vectorized pass, and histograms all K smaller
  children in ONE [F*B, n] x [n, K*S] MXU contraction
  (ops/histogram.py build_histogram_batched_t).  Batching exists for
  the MXU: a single-leaf histogram is an M=8 matmul (~3% MFU measured);
  K leaves widen the small axis to K*S >= 128 lanes, the whole systolic
  array lights up, and a tree takes ~254/K passes instead of 254;
* the smaller/larger-leaf trick + histogram subtraction carries over
  verbatim as tensor subtraction (serial_tree_learner.cpp:428-437,566-572):
  each round histograms only the smaller child of every split and derives
  the sibling from the parent's pooled histogram;
* the histogram pool is a dense [num_leaves, F, B, 3] tensor (the analog of
  HistogramPool, feature_histogram.hpp:654-831, without the LRU since HBM
  holds it whole);
* best-split search for all 2K children is the vectorized cumsum+argmax of
  ops/split.py, vmapped over children;
* step records are written into a fixed [L-1, W] buffer at a dynamic
  offset; the host assembles the Tree model from ONE fetch afterwards.

The `while_loop` trip count is data-dependent (ceil(254/K) rounds when
gains stay positive, up to 254 for pathological chain trees), which XLA
supports natively — no wasted full-data passes on no-op steps.

Distribution — the same round body runs under shard_map in three sharded
modes, mirroring the reference's parallel tree learners (SURVEY.md §2.3):

* `data_axis` (DataParallelTreeLearner, data_parallel_tree_learner.cpp:
  149-163): rows sharded; the [K, F, B, 3] smaller-child histograms
  aggregate over ICI in one of two modes (GrowerParams.hist_agg):
  - "psum": every shard receives the full GLOBAL histograms and makes
    identical split decisions while partitioning only its local rows.
    XLA lowers the psum to reduce-scatter + all-gather — but the
    all-gather half replicates the whole [K, F, B, 3] aggregate to
    every shard, the pool stores all F features P times across the
    mesh, and the split search repeats P times.
  - "scatter": stop after the reduce-scatter (`lax.psum_scatter`) —
    each shard keeps only its CONTIGUOUS F/P feature slice of the
    aggregated histograms, exactly the reference's
    Network::ReduceScatter leaving worker i its own feature block
    (data_parallel_tree_learner.cpp:149-163).  The pool, sibling
    subtraction, EFB expansion, sparse zero-bin fixes, and CEGB
    charges all operate on the slice; the split search runs only over
    it; and the global winner is ONE tiny best-split record: an
    all_gather of per-shard bests + the shared deterministic tie-break
    (the SyncUpGlobalBestSplit analog, parallel_tree_learner.h:
    190-213).  Per-shard pool HBM and psum receive volume both drop
    ~P×.  Integer (int8/int16) psum_scatter sums stay associative, so
    scatter decisions are BIT-IDENTICAL to psum at any shard count.
* `feature_axis` (FeatureParallelTreeLearner, feature_parallel_tree_
  learner.cpp:23-75): BINS REPLICATED (like the reference's all-data-on-
  all-machines feature mode), search sharded; each shard histograms +
  searches only its own feature slice, then the global best split is an
  all_gather of per-shard best gains + argmax (replacing
  SyncUpGlobalBestSplit's allreduce-by-max, parallel_tree_learner.h:
  190-213).  Every shard partitions identically from its full local
  matrix — no per-split column movement at all.
* `data_axis` + `voting_k` (VotingParallelTreeLearner, voting_parallel_
  tree_learner.cpp:170-471 / PV-Tree): rows sharded, but only the top-k
  VOTED features' histograms are aggregated per leaf.  Each shard proposes
  its local top-2k features by gain (computed against LOCAL leaf sums with
  1/p-scaled minimum-data thresholds, :58-59); gains are psum-summed per
  feature (the weighted-gain vote of GlobalVoting, :170-200); the global
  top-k features' histograms are psum'ed ([k, B, 3] instead of [F, B, 3] —
  top-k gradient compression on the data axis) and the final search runs
  on those.

Cost model: each round is one O(n) batched contraction covering up to K
splits, so a 255-leaf tree costs ~ (log2(K) + 254/K) full-data passes at
MXU-shaped operand sizes — versus 254 passes at M=8 shapes before.

Quantized precisions ("int8"/"int16", GrowerParams.precision): grad/hess
discretize per tree onto an integer grid (stochastic rounding hashed on
GLOBAL row indices — sharding-invariant, deterministic given the seed),
the histogram pool/psum/subtraction stay in exact int32, and the scales
rescale (g, h) to f32 once per leaf inside select().  Because integer
sums are associative, the `data_axis` mode's split decisions are
bit-identical for ANY shard count — the fast deterministic mode.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..utils.compile_ledger import ledger_jit
from .histogram import (build_histogram_batched_t, build_histogram_sparse,
                        build_histogram_t, key_words, pack_stats,
                        perfeature_dot_lanes, quant_limit, quantize_values,
                        unpack2d)
from .partition import partition_rows
from .split import (K_MIN_SCORE, SplitResult, argbest, finalize_split,
                    go_right_scalars, leaf_output, leaf_split_gain,
                    numeric_go_left,
                    per_feature_best_split,
                    per_feature_best_split_categorical,
                    MISSING_NAN, MISSING_ZERO)


class GrowerParams(NamedTuple):
    """Static (compile-time) grower configuration.

    Shape-stability discipline (ROADMAP item 3): every field here keys a
    DISTINCT compiled program, so only genuinely structural axes belong —
    operand shapes/dtypes (num_bins, precision, split_batch, sparse/EFB
    storage), kernel choice (hist_impl, partition_impl), and collective
    topology (hist_agg).  Branchless-free boolean switches ride the
    traced `meta["mode_flags"]` vector instead (quantized rounding mode,
    leaf refit, CEGB penalty scalars): one `grow` program serves every
    value of those, bit-identically to the old per-mode closures."""
    num_leaves: int
    num_bins: int          # padded bin-axis size B
    block_rows: int
    precision: str
    l1: float
    l2: float
    max_delta_step: float
    min_data_in_leaf: float
    min_sum_hessian: float
    min_gain_to_split: float
    max_depth: int
    # categorical split search (feature_histogram.hpp:118-279); has_cat
    # statically disables the whole categorical path for numerical data
    has_cat: bool = False
    max_cat_threshold: int = 32
    cat_l2: float = 10.0
    cat_smooth: float = 10.0
    max_cat_to_onehot: int = 4
    min_data_per_group: float = 100.0
    # leaves split per round; 1 = strict reference best-first order
    split_batch: int = 16
    # batch only leaves whose gain >= split_batch_alpha * round-max gain:
    # batching near-ties keeps the split order close to strict best-first
    # (a child's gain rarely exceeds a near-tie of its parent's round)
    split_batch_alpha: float = 0.0
    # per-NODE feature sampling (reference GetUsedFeatures with
    # is_tree_level=false, serial_tree_learner.cpp:271-319); Bernoulli
    # form of the reference's exact-count sample, like the GOSS sampler
    feature_fraction_bynode: float = 1.0
    # bins stored packed two-rows-per-byte (reference dense_nbits_bin.hpp,
    # max_bin<=16): halves the histogram row sweep's DMA traffic
    packed_bins: bool = False
    # very-sparse features stored as padded COO (row-id, bin) pairs in
    # meta["sparse_idx"/"sparse_bin"] instead of dense bins_t columns
    # (reference OrderedSparseBin, src/io/ordered_sparse_bin.hpp):
    # histograms come from an O(nnz) gather contraction, the zero bin is
    # reconstructed from leaf totals (FixHistogram, dataset.cpp:1044),
    # and partitions materialize the chosen column on the fly.
    # meta["hist_perm"] maps feature f to its slot in
    # concat(dense columns, sparse groups).
    has_sparse: bool = False
    has_cegb: bool = False
    # lazy per-row acquisition costs: meta carries a [FG, n_pad] paid
    # matrix threaded across trees (feature_used_in_data_ bitset,
    # cost_effective_gradient_boosting.hpp:46-48,88-107)
    has_cegb_lazy: bool = False
    cegb_tradeoff: float = 1.0
    cegb_penalty_split: float = 0.0
    # forced splits (reference ForceSplits, serial_tree_learner.cpp:
    # 607-769): static BFS-ordered tuple of (parent_leaf, feature, thr_bin)
    # applied as unrolled rounds before best-gain growth
    forced: tuple = ()
    # batched-histogram backend: "xla" (scan + dot_general) or "pallas2"
    # (the perfeature VMEM kernel — ops/histogram.py _hist_pallas)
    hist_impl: str = "xla"
    # row-partition lowering: "kernel" is ONE Pallas pass over the leaf
    # ids per round (ops/partition.py; dense numerical unpacked bins
    # only); "select" unrolls K scalar-broadcast passes (one dynamic row
    # slice + elementwise compare per split — no per-row table gathers,
    # which XLA serializes on TPU) and takes every storage.  The learner
    # resolves tpu_partition_impl=auto to one of the two
    partition_impl: str = "select"
    # EFB (reference FindGroups/FastFeatureBundling, dataset.cpp:91-263):
    # bins_t holds G <= F bundle columns; meta carries bundle_idx /
    # bin_offset / needs_fix per feature and the search expands bundle
    # histograms back to feature space, reconstructing each bundled
    # feature's bin 0 from leaf totals (FixHistogram, dataset.cpp:1044)
    has_bundles: bool = False
    # frontier ramp: statically-unrolled pre-rounds at K' = 1, 2, 4, ...
    # before the full-K while_loop.  After r rounds the frontier holds at
    # most 2^r leaves, so each pre-round's K' covers every possible
    # positive-gain leaf and the grown tree is BIT-IDENTICAL to the plain
    # loop — the ramp only removes the dead-slot contraction work of the
    # first log2(K) rounds (at K=84 that waste is ~half the tree's MXU
    # time).  Disabled automatically when forced splits pre-grow the
    # frontier beyond the 2^r bound.
    ramp: bool = False
    # quantized precisions (int16/int8) only: grad/hess rounding onto the
    # integer grid — "stochastic" (unbiased, hashed global-row-index
    # randomness, shard-count invariant) or "nearest"
    quant_round: str = "stochastic"
    # recompute final leaf outputs from the TRUE f32 grad/hess sums over
    # each leaf's rows (LightGBM quantized training's renew-leaf): split
    # DECISIONS stay integer-exact, leaf values regain float precision
    quant_refit: bool = False
    # frontier-ramp growth factor for the K' pre-round widths (1, s,
    # s^2, ...): any s >= 2 keeps s^(i-1) >= 2^(i-1) (the frontier bound
    # after i-1 rounds), so the tree stays BIT-IDENTICAL to the plain
    # loop at any step.  s=4 halves the unrolled pre-round count — the
    # "wide" bucket policy's compile-time lever for the grow program
    ramp_step: int = 2
    # data-axis histogram aggregation (see the module docstring):
    # "psum" replicates the full aggregate on every shard; "scatter"
    # reduce-scatters (lax.psum_scatter) so each shard keeps only its
    # F/P feature slice of the pool and search, syncing the winner as
    # one best-split record.  In voting mode "scatter" applies to the
    # voted [k, B, 3] aggregation instead (the pool is local anyway).
    hist_agg: str = "psum"


def resolve_split_batch(split_batch: int, num_leaves: int) -> int:
    """Auto-pick the per-round split batch K.

    K trades MXU utilization (bigger contraction N axis) against split-order
    fidelity: each round splits the top-K frontier leaves at once, so
    keeping K a small fraction of num_leaves means only the very top of the
    frontier is batched and the order stays close to strict best-first.
    Measured anchors: K=3 at 31 leaves already costs ~0.05 multiclass
    logloss (small trees cannot absorb batching), while at 255 leaves K=15
    and K=25 train to identical Higgs AUC (0.8268/0.8269,
    docs/PERF_NOTES.md) and K=25 is 1.3x faster — so small trees stay
    strictly sequential and only wide trees ride the full 128-lane MXU
    tile (25 slots x 5 hilo stat rows = 125).
    """
    if split_batch > 0:
        return split_batch
    return max(1, num_leaves // 16) if num_leaves < 192 else 25


# ---- traced mode switches (meta["mode_flags"]) ---------------------------
# Layout of the f32 [MF_WIDTH] vector: boolean mode switches and penalty
# scalars whose branches are branchless-cheap ride the TRACED program
# instead of keying distinct compiled closures.  Callers that omit the
# vector (direct grower tests) fall back to the static GrowerParams fields
# as trace-time constants — the selected values are bit-identical either
# way, so one `grow` program serves every combination.
MF_STOCHASTIC, MF_QUANT_REFIT, MF_CEGB_TRADEOFF, MF_CEGB_SPLIT = range(4)
MF_WIDTH = 4

# the folded fields and their canonical (cache-key) values
_FOLDED_FIELDS = dict(quant_round="stochastic", quant_refit=False,
                      cegb_tradeoff=1.0, cegb_penalty_split=0.0)


def canonical_params(params: GrowerParams) -> GrowerParams:
    """Normalize the mode-flag-folded fields so every structurally
    identical configuration maps onto ONE cached grower program.  Only
    for callers that supply meta["mode_flags"] (the learner does): the
    grower never reads the folded fields then."""
    return params._replace(**_FOLDED_FIELDS)


def mode_flags_np(quant_round: str = "stochastic",
                  quant_refit: bool = False,
                  cegb_tradeoff: float = 1.0,
                  cegb_penalty_split: float = 0.0) -> np.ndarray:
    """Build the meta["mode_flags"] vector for the given mode values."""
    return np.asarray(
        [1.0 if str(quant_round) == "stochastic" else 0.0,
         1.0 if quant_refit else 0.0,
         float(cegb_tradeoff), float(cegb_penalty_split)], np.float32)


def pool_dtype(precision: str):
    """Histogram pool / accumulation dtype for `precision` — the single
    definition shared with the learner's donated-pool allocation."""
    return (jnp.float64 if precision == "f64"
            else jnp.int32 if precision in ("int8", "int16")
            else jnp.float32)


# meta entries that are NOT per-feature [F'] vectors and must be skipped
# by feature-axis slicing and by search-slice meta gathers
NONFEAT_META = ("sparse_idx", "sparse_bin", "hist_perm",
                "scatter_feat", "cegb_paid", "mode_flags")


def make_grower(params: GrowerParams, num_features: int,
                data_axis: Optional[str] = None,
                feature_axis: Optional[str] = None,
                voting_k: int = 0, num_shards: int = 1, jit: bool = True,
                num_columns: Optional[int] = None,
                debug_hist: bool = False, external_pool: bool = False,
                live_columns: Optional[int] = None):
    """Build the whole-tree grower for fixed shapes/params.

    num_features is the LOCAL feature count: with `feature_axis` set it is
    the per-shard shard width and the passed meta/feature_mask arrays are
    the GLOBAL [F_local * num_shards] versions (sliced per shard inside).
    num_columns is the bin-matrix column count: G < F when EFB bundling is
    active (has_bundles), otherwise F.
    live_columns is how many leading columns of the bin matrix this grower
    histograms carry data (the rest are the learner's alignment padding);
    the perfeature kernel contracts only those (ops/histogram.py).  None =
    all of them, and the only value a feature axis takes: its shards run
    one program and their live counts differ.

    `data_axis` and `feature_axis` COMPOSE (the reference's parallel
    learners are templates over the device learner so device x
    {feature,data} compose, parallel_tree_learner.h:25-187): rows shard
    over `data`, the histogram/search feature slice over `feature`;
    histograms psum over `data`, per-shard bests all_gather+argmax over
    `feature`, and the scalar leaf sums reduce over `data` only (rows are
    replicated across feature shards).

    Growers are MEMOIZED on every argument: two calls with identical
    configuration return the SAME (jitted) callable, so a second learner
    of the same shape reuses the first one's compiled executables instead
    of re-tracing a fresh closure — the retrace-elimination half of
    ROADMAP item 3 (the zoo was never the one big program, but every
    Booster construction silently re-compiling it).

    external_pool=True adds an 8th `pool` argument (the [L, G/P, B, 3]
    histogram pool in `pool_dtype(precision)`, donated when jit=True):
    the grower zeroes and refills it IN PLACE and returns it as
    out["pool"], so XLA aliases one pool allocation across iterations
    instead of allocating a fresh pool per tree."""
    return _build_grower(params, num_features, data_axis, feature_axis,
                         voting_k, num_shards, jit, num_columns,
                         debug_hist, external_pool, live_columns)


# bounded: the key includes dataset-shape-derived fields (block_rows,
# num_features), so an unbounded cache would pin one compiled grower per
# distinct shape for the process lifetime in long-lived sweep/serving
# processes.  64 spans any realistic concurrent working set; eviction
# only costs a re-trace on the next same-shaped construction.
@functools.lru_cache(maxsize=64)
def _build_grower(params, num_features, data_axis, feature_axis,
                  voting_k, num_shards, jit, num_columns, debug_hist,
                  external_pool, live_columns):
    # the axis-addressed collective vocabulary (the ONLY sanctioned
    # spelling of cross-shard ops — graftlint T5xx).  Imported at build
    # time: parallel/strategies.py imports this module, so a module-level
    # import back into parallel/ would cycle.
    from ..parallel.topology import (axis_all_gather, axis_best_split_sync,
                                     axis_index, axis_pmax, axis_psum,
                                     axis_psum_scatter)

    if voting_k and not data_axis:
        raise ValueError("voting requires a data axis")
    if live_columns is not None and feature_axis:
        raise ValueError("live_columns does not compose with a feature "
                         "axis: the shards' live counts differ")
    if voting_k and feature_axis:
        # the reference's voting learner is a data-parallel variant
        # (voting_parallel_tree_learner.cpp); it does not compose with
        # feature sharding there either
        raise ValueError("voting does not compose with a feature axis")
    L = params.num_leaves
    B = params.num_bins
    F = num_features
    G = num_columns if num_columns is not None else F
    if params.has_bundles and (feature_axis or voting_k):
        raise ValueError("EFB bundling composes with serial/data learners "
                         "only")
    if params.has_bundles and params.forced:
        raise ValueError("EFB bundling does not compose with forced splits; "
                         "set enable_bundle=false")
    if params.packed_bins and (
            params.has_bundles or params.hist_impl != "pallas2"):
        raise ValueError(
            "packed 4-bit bins require the pallas2 histogram impl and no "
            "EFB bundling")
    if params.has_sparse and (
            feature_axis or params.has_bundles or params.packed_bins):
        # EFB/packing already reshape the dense matrix the sparse split
        # composes with; feature sharding replicates rows — serial,
        # data-parallel, and voting only
        raise ValueError(
            "sparse train-time storage (tpu_sparse_threshold) requires "
            "tree_learner=serial/data/voting and no EFB bundling / 4-bit "
            "packing")
    if params.partition_impl not in ("select", "kernel"):
        raise ValueError(f"partition_impl={params.partition_impl!r}; "
                         "expected select or kernel (the learner resolves "
                         "'auto' upstream)")
    if params.partition_impl == "kernel" and (
            params.has_cat or params.has_bundles or params.has_sparse
            or params.packed_bins):
        raise ValueError(
            "partition_impl=kernel takes dense numerical unpacked bins "
            "only (no categorical feature, EFB bundle, sparse column or "
            "4-bit packing); use select")
    precision = params.precision
    # quantized-gradient mode (tpu_hist_precision=int16|int8): stats ride
    # the MXU as narrow ints, histograms/pool/psum/subtraction stay in
    # exact int32, and the per-iteration scales rescale (g, h) back to
    # floats once per leaf at the split-search boundary (select)
    quantized = precision in ("int8", "int16")
    if quantized:
        if params.forced:
            raise ValueError("quantized histogram precisions do not "
                             "compose with forced splits")
        if params.has_sparse:
            raise ValueError(
                "quantized histogram precisions do not compose with "
                "sparse train-time storage (tpu_sparse_threshold)")
        if params.quant_round not in ("stochastic", "nearest"):
            raise ValueError(
                f"tpu_quant_round={params.quant_round!r}; expected "
                "stochastic or nearest")
    K = max(1, min(int(params.split_batch), L - 1))

    if params.hist_agg not in ("psum", "scatter"):
        raise ValueError(f"hist_agg={params.hist_agg!r}; expected psum or "
                         "scatter (the learner resolves 'auto' upstream)")
    if external_pool and voting_k:
        raise ValueError("external (donated) histogram pools do not "
                         "compose with voting (its pool is shard-LOCAL "
                         "by design and cannot be a global array)")
    if params.ramp_step < 2:
        raise ValueError(f"ramp_step={params.ramp_step}; the frontier "
                         "bound needs a growth factor >= 2")
    # scatter aggregation: active only with a real (>1) data axis.  In
    # plain data / data_feature modes the POOL is scattered (each shard
    # holds its G/P column slice); voting keeps the pool local and
    # scatters only the voted [k, B, 3] aggregation inside select()
    scatter_on = (params.hist_agg == "scatter" and data_axis is not None
                  and num_shards > 1)
    pool_scatter = scatter_on and not voting_k
    vote_scatter = scatter_on and bool(voting_k)
    if pool_scatter and G % num_shards != 0:
        raise ValueError(
            f"hist_agg=scatter needs the histogram column count {G} padded "
            f"to a multiple of the data-shard count {num_shards}")
    # per-shard column slice and (non-bundle) feature slice widths; with
    # EFB the features of a column slice are resolved through the static
    # meta["scatter_feat"] table instead (columns != features there)
    SG = G // num_shards if pool_scatter else G
    SF = F // num_shards if (pool_scatter and not params.has_bundles) else F
    # the one sparse reconstruction input the scattered slice cannot
    # derive locally: dense_ref's histogram (the leaf-total source) may
    # live on another shard, so exact per-leaf totals are carried in
    # state and threaded into select explicitly
    sparse_tot = pool_scatter and params.has_sparse

    def preduce_scalar(x):
        return axis_psum(x, data_axis) if data_axis else x

    def agg_hist(x):
        """Aggregate LOCAL (per-shard) histograms over the row axes.
        x's feature/column axis is axis -3 ([..., G, B, 3]).  psum
        replicates the full aggregate; scatter (reduce-scatter) leaves
        this shard only its contiguous G/P column slice — shard d holds
        columns [d*SG, (d+1)*SG).  Voting keeps the pool LOCAL and
        aggregates only voted features inside select()."""
        if not data_axis or voting_k:
            return x
        if pool_scatter:
            return axis_psum_scatter(x, data_axis,
                                     scatter_dimension=x.ndim - 3,
                                     tiled=True)
        return axis_psum(x, data_axis)

    split_kw = dict(l1=params.l1, l2=params.l2,
                    max_delta_step=params.max_delta_step,
                    min_data_in_leaf=params.min_data_in_leaf,
                    min_sum_hessian=params.min_sum_hessian,
                    min_gain_to_split=params.min_gain_to_split)
    # local-vote thresholds scaled by 1/p (voting_parallel_tree_learner.
    # cpp:58-59: local min_data/min_hessian are divided by num_machines)
    local_kw = dict(split_kw)
    if voting_k:
        local_kw["min_data_in_leaf"] = params.min_data_in_leaf / num_shards
        local_kw["min_sum_hessian"] = params.min_sum_hessian / num_shards

    # width of the carried categorical bin mask; 1 when the categorical
    # path is statically disabled (numerical-only data)
    CB = B if params.has_cat else 1

    def pf_search(hist, sg, sh, cnt, meta, fmask, kw, min_c, max_c,
                  acc_scale=None):
        return per_feature_best_split(
            hist, sg, sh, cnt,
            meta["num_bin"], meta["missing_type"], meta["default_bin"],
            meta["monotone"], meta["penalty"], fmask,
            min_constraint=min_c, max_constraint=max_c,
            acc_scale=acc_scale, **kw)

    def combined_search(hist, sg, sh, cnt, meta, fmask, kw, min_c, max_c,
                        acc_scale=None):
        """Per-feature bests merging numerical and categorical searches.

        Returns (gain_vec [F'], finalize(best_idx) -> SplitResult) so the
        callers (serial argmax, voting top-k, feature-parallel all-gather)
        can each apply their own winner selection.
        """
        if not params.has_cat:
            pf = pf_search(hist, sg, sh, cnt, meta, fmask, kw, min_c, max_c,
                           acc_scale=acc_scale)

            def fin_plain(bi):
                res = finalize_split(pf, bi,
                                     l1=params.l1, l2=params.l2,
                                     max_delta_step=params.max_delta_step,
                                     min_constraint=min_c,
                                     max_constraint=max_c)
                return res._replace(is_cat=jnp.asarray(False),
                                    cat_mask=jnp.zeros(CB, jnp.float32))
            return pf.gain, fin_plain

        is_cat = meta["is_categorical"] > 0
        catf = is_cat.astype(jnp.float32)
        pf = pf_search(hist, sg, sh, cnt, meta, fmask * (1.0 - catf),
                       kw, min_c, max_c)
        pfc = per_feature_best_split_categorical(
            hist, sg, sh, cnt, meta["num_bin"], meta["missing_type"],
            meta["penalty"], fmask * catf,
            cat_l2=params.cat_l2, cat_smooth=params.cat_smooth,
            max_cat_threshold=params.max_cat_threshold,
            max_cat_to_onehot=params.max_cat_to_onehot,
            min_data_per_group=params.min_data_per_group,
            min_constraint=min_c, max_constraint=max_c, **kw)
        gain = jnp.where(is_cat, pfc.gain, pf.gain)

        def fin(bi):
            resn = finalize_split(pf, bi,
                                  l1=params.l1, l2=params.l2,
                                  max_delta_step=params.max_delta_step,
                                  min_constraint=min_c, max_constraint=max_c)
            c = is_cat[bi]
            return SplitResult(
                gain=gain[bi], feature=bi.astype(jnp.int32),
                threshold=jnp.where(c, 0, resn.threshold).astype(jnp.int32),
                default_left=jnp.where(c, False, resn.default_left),
                left_sum_g=jnp.where(c, pfc.left_sum_g[bi], resn.left_sum_g),
                left_sum_h=jnp.where(c, pfc.left_sum_h[bi], resn.left_sum_h),
                left_count=jnp.where(c, pfc.left_count[bi], resn.left_count),
                # a categorical split's left bins are no prefix: its
                # right side stays the leaf's total less the left
                right_sum_g=jnp.where(c, sg - pfc.left_sum_g[bi],
                                      resn.right_sum_g),
                right_sum_h=jnp.where(c, sh - pfc.left_sum_h[bi],
                                      resn.right_sum_h),
                left_output=jnp.where(c, pfc.left_output[bi],
                                      resn.left_output),
                right_output=jnp.where(c, pfc.right_output[bi],
                                       resn.right_output),
                is_cat=c,
                cat_mask=pfc.cat_mask[bi] * c.astype(jnp.float32))
        return gain, fin

    bynode = params.feature_fraction_bynode < 1.0

    def grow(bins_t: jnp.ndarray,       # [G, n_pad] uint8/int32 (rows on
             #                            lanes; cols >= n zero-filled)
             grad: jnp.ndarray,         # [n_pad] f32 (padding rows zero)
             hess: jnp.ndarray,         # [n_pad] f32
             row_mask: jnp.ndarray,     # [n_pad] f32 (bagging x padding)
             feature_mask: jnp.ndarray,  # [F] f32 ([F_global] w/ feature_axis)
             meta: Dict[str, jnp.ndarray],
             key: jnp.ndarray,          # PRNG key (per-node sampling)
             pool_buf: Optional[jnp.ndarray] = None):  # donated pool
        #                                 (external_pool only; see above)
        # traced mode switches: present whenever the learner built the
        # meta (one program serves every value); direct callers without
        # the vector fall back to the static params fields as trace-time
        # constants — bit-identical selected values either way
        mf = meta.get("mode_flags")

        def mode_flag(idx: int, static_val: float) -> jnp.ndarray:
            if mf is not None:
                return mf[idx]
            return jnp.float32(static_val)

        # rows come from grad, NOT bins_t: with packed (4-bit) storage the
        # bin matrix holds two rows per byte
        n_pad = grad.shape[0]
        block, nb = row_blocks(n_pad, params.block_rows)
        bcols = block // 2 if params.packed_bins else block

        if feature_axis:
            ax = axis_index(feature_axis)

            def fslice(a):
                return jax.lax.dynamic_slice_in_dim(a, ax * F, F)

            meta_local = {k: (v if k in NONFEAT_META else fslice(v))
                          for k, v in meta.items()}
            # bins arrive REPLICATED [F_global, n] (the reference's
            # all-data-on-all-machines feature mode): histogram only this
            # shard's feature slice; the partition reads the full matrix
            bins_hist_t = fslice(bins_t)
        else:
            ax = None
            meta_local = meta
            bins_hist_t = bins_t
        # this shard's LINEARIZED position on the row axes: under scatter
        # it owns histogram columns [dax*SG, (dax+1)*SG) after the
        # reduce-scatter
        dax = axis_index(data_axis) if scatter_on else None

        FG = feature_mask.shape[0]  # global feature width

        def bynode_masks(k, shape_prefix):
            """Per-node feature masks: Bernoulli(frac) over the tree-level
            mask, falling back to the full mask for empty draws."""
            r = jax.random.uniform(k, shape_prefix + (FG,))
            samp = ((r < params.feature_fraction_bynode)
                    & (feature_mask > 0)).astype(jnp.float32)
            nonempty = jnp.sum(samp, axis=-1, keepdims=True) > 0
            return jnp.where(nonempty, samp, feature_mask)

        def expand_bundles(hist_g, sg, sh, cnt, fmeta=None, col_base=0):
            """[G', B, 3] bundle histograms -> [F', B, 3] feature
            histograms for the features described by `fmeta` (the full
            meta_local by default; a scatter_feat-gathered slice under
            scatter aggregation, where hist_g holds only this shard's
            column slice and col_base is its first global column).

            Each bundled feature's bins live at bin_offset+1..+num_bin-1 of
            its bundle column; its bin 0 (the shared all-default bin) is
            reconstructed from the leaf totals minus the other bins — the
            FixHistogram trick (reference src/io/dataset.cpp:1044-1063)."""
            if not params.has_bundles:
                return hist_g
            if fmeta is None:
                fmeta = meta_local
            bi = jnp.clip(fmeta["bundle_idx"] - col_base, 0,
                          hist_g.shape[0] - 1)             # [F'] local col
            off = fmeta["bin_offset"]                      # [F']
            fix = fmeta["needs_fix"] > 0                   # [F']
            iota_b = jnp.arange(B, dtype=jnp.int32)
            src = jnp.clip(off[:, None] + iota_b[None, :], 0, B - 1)
            hist_f = hist_g[bi[:, None], src]              # [F', B, 3]
            # bundled features: mask bins outside their range, then
            # reconstruct bin 0 from totals
            nbv = fmeta["num_bin"][:, None]
            in_range = (iota_b[None, :] >= 1) & (iota_b[None, :] < nbv)
            keep = jnp.where(fix[:, None], in_range,
                             jnp.ones_like(in_range))
            hist_f = jnp.where(keep[:, :, None], hist_f, 0.0)
            totals = jnp.stack([sg, sh, cnt])             # [3]
            rest = jnp.sum(hist_f, axis=1)                # [F, 3]
            bin0 = totals[None, :] - rest                 # [F, 3]
            hist_f = hist_f.at[:, 0, :].set(
                jnp.where(fix[:, None], bin0, hist_f[:, 0, :]))
            return hist_f

        def fix_sparse_bins(hist, isp, db, totals):
            """hist[f, default_bin] = totals - sum(other bins) where isp:
            the FixHistogram identity (reference dataset.cpp:1044-1063)
            over [F', B, 3] rows with caller-supplied leaf totals."""
            iota_b = jnp.arange(B, dtype=jnp.int32)
            at_db = isp[:, None] & (iota_b[None, :] == db[:, None])
            zeroed = jnp.where(at_db[:, :, None], 0.0, hist)
            bin0 = totals[None, :] - jnp.sum(zeroed, axis=1)
            return jnp.where(at_db[:, :, None], bin0[:, None, :], zeroed)

        def expand_sparse(hist):
            """Reconstruct each sparse feature's zero bin from the leaf
            totals: the stored COO entries cover only nonzero bins.
            [F, B, 3] in and out.

            The totals come from a known-DENSE feature's own histogram
            (every row lands in exactly one bin per feature), not from
            the f32 scalar leaf sums: the reconstruction then stays
            entirely in the histogram accumulation dtype, so
            deterministic f64 sparse storage bit-matches dense — and in
            voting mode, where hist is the shard-LOCAL pool, the derived
            totals are automatically the LOCAL ones the vote needs."""
            if not params.has_sparse:
                return hist
            totals = jnp.sum(hist[meta_local["dense_ref"][0]], axis=0)
            return fix_sparse_bins(hist, meta_local["is_sparse"] > 0,
                                   meta_local["default_bin"], totals)

        # CEGB penalty scalars ride the traced mode-flag vector: changing
        # cegb_tradeoff / cegb_penalty_split between runs no longer keys
        # a fresh compiled program (the per-feature penalties were always
        # traced via meta["cegb_coupled"/"cegb_lazy"])
        cegb_tradeoff = mode_flag(MF_CEGB_TRADEOFF, params.cegb_tradeoff)
        cegb_split_pen = mode_flag(MF_CEGB_SPLIT, params.cegb_penalty_split)

        def cegb_delta(used, cnt, unpaid=None):
            """[M, FG] per-leaf gain charge (DetlaGain,
            cost_effective_gradient_boosting.hpp:50-62): the split
            penalty scaled by the leaf's row count, the coupled
            acquisition penalty for features the model has not used yet,
            and (lazy mode) the per-row on-demand cost for rows that
            have not paid for the feature."""
            d = (cegb_split_pen * cnt[:, None]
                 + meta["cegb_coupled"][None, :] * (1.0 - used)[None, :])
            if unpaid is not None:
                d = d + meta["cegb_lazy"][None, :] * unpaid
            return cegb_tradeoff * d

        def apply_delta(gain_vec, delta):
            return jnp.where(gain_vec > K_MIN_SCORE / 2, gain_vec - delta,
                             gain_vec)

        def sync_best(res: SplitResult, gfeat, axis) -> SplitResult:
            """Global best split from per-shard bests: all_gather ONE tiny
            best-split record per shard over `axis` and pick the winner
            with the shared deterministic tie-break (split.argbest:
            highest gain, then lowest feature id, then lowest threshold
            bin) — the SyncUpGlobalBestSplit analog
            (parallel_tree_learner.h:190-213).  `gfeat` is this shard's
            winning feature id in the frame common to all shards on
            `axis`, and becomes the returned feature."""
            payload = dict(
                default_left=res.default_left.astype(jnp.int32),
                left_sum_g=res.left_sum_g,
                left_sum_h=res.left_sum_h,
                left_count=res.left_count,
                right_sum_g=res.right_sum_g,
                right_sum_h=res.right_sum_h,
                left_output=res.left_output,
                right_output=res.right_output,
                is_cat=res.is_cat.astype(jnp.int32),
                cat_mask=res.cat_mask)
            gain, feat, thr, w = axis_best_split_sync(
                axis, res.gain, gfeat, res.threshold, payload)
            return SplitResult(
                gain=gain,
                feature=feat,
                threshold=thr.astype(jnp.int32),
                default_left=w["default_left"] > 0,
                left_sum_g=w["left_sum_g"],
                left_sum_h=w["left_sum_h"],
                left_count=w["left_count"],
                right_sum_g=w["right_sum_g"],
                right_sum_h=w["right_sum_h"],
                left_output=w["left_output"],
                right_output=w["right_output"],
                is_cat=w["is_cat"] > 0,
                cat_mask=w["cat_mask"])

        def select(hist, sg, sh, cnt, min_c, max_c, fmask,
                   delta, sp_tot=None) -> SplitResult:
            """Best split across all (global) features for one leaf; the
            returned feature index is GLOBAL in every mode.  vmapped over
            children by the round body.  fmask/delta are global-width.
            sp_tot is the leaf's exact [3] histogram-dtype totals, threaded
            in only under scatter aggregation with sparse storage (the
            slice cannot derive them from dense_ref locally)."""
            fmask_local = fslice(fmask) if feature_axis else fmask
            delta_local = (fslice(delta) if feature_axis else delta) \
                if params.has_cegb else None
            if voting_k:
                # local leaf totals from any one DENSE feature's bins
                # (every row lands in exactly one bin per feature; a
                # sparse column is missing its zero-bin mass)
                dref = (meta_local["dense_ref"][0] if params.has_sparse
                        else 0)
                loc = dequant(jnp.sum(hist[dref], axis=0))
                # sparse features need their LOCAL zero bin before the
                # local gain vote — reconstructed from the SAME `loc`
                # totals that (psum'd) later fix the voted aggregation
                hist_loc = (fix_sparse_bins(hist,
                                            meta_local["is_sparse"] > 0,
                                            meta_local["default_bin"],
                                            loc)
                            if params.has_sparse else hist)
                gain_loc, _ = combined_search(
                    dequant(hist_loc), loc[0], loc[1], loc[2], meta_local,
                    fmask_local, local_kw, min_c, max_c)
                k2 = min(2 * voting_k, F)
                vals, idx = jax.lax.top_k(gain_loc, k2)
                # weighted-gain vote across shards (GlobalVoting :170-200)
                contrib = jnp.zeros(F, jnp.float32).at[idx].add(
                    jnp.where(vals > K_MIN_SCORE / 2, vals, 0.0))
                score = axis_psum(contrib, data_axis)
                kk = min(voting_k, F)
                _, sel = jax.lax.top_k(score, kk)
                sel = sel.astype(jnp.int32)
                if vote_scatter:
                    # reduce-scatter the voted aggregation: pad the voted
                    # set to a shard multiple (extras duplicate sel[0]
                    # with a zeroed mask, so the searched candidate set
                    # is unchanged), psum_scatter the [kp, B, 3] block so
                    # each shard receives only its kp/P slice, search it,
                    # and sync the winner as one best-split record
                    kp = -(-kk // num_shards) * num_shards
                    if kp > kk:
                        sel_p = jnp.concatenate(
                            [sel, jnp.broadcast_to(sel[:1], (kp - kk,))])
                        vmask = jnp.zeros(kp, jnp.float32).at[:kk].set(1.0)
                    else:
                        sel_p, vmask = sel, jnp.ones(kk, jnp.float32)
                    sel_hist = axis_psum_scatter(
                        hist[sel_p], data_axis, scatter_dimension=0,
                        tiled=True)                        # [kp/P, B, 3]
                    W = kp // num_shards
                    sel_loc = jax.lax.dynamic_slice_in_dim(sel_p,
                                                           dax * W, W)
                    fmask_sel = (fmask_local[sel_loc]
                                 * jax.lax.dynamic_slice_in_dim(
                                     vmask, dax * W, W))
                else:
                    sel_loc, sel_hist = sel, None
                    fmask_sel = fmask_local[sel]
                # aggregate ONLY the voted features' histograms — RAW
                # (zero bins reconstructed after the psum from GLOBAL
                # totals); the 2-D COO tables are not per-feature rows
                sel_meta = {k: v[sel_loc] for k, v in meta_local.items()
                            if k not in NONFEAT_META}
                if sel_hist is None:
                    sel_hist = axis_psum(hist[sel], data_axis)
                if params.has_sparse:
                    sel_hist = fix_sparse_bins(
                        sel_hist, sel_meta["is_sparse"] > 0,
                        sel_meta["default_bin"],
                        axis_psum(loc, data_axis))
                gain_sel, fin = combined_search(dequant(sel_hist), sg, sh,
                                                cnt, sel_meta,
                                                fmask_sel,
                                                split_kw, min_c, max_c)
                if params.has_cegb:
                    gain_sel = apply_delta(gain_sel, delta_local[sel_loc])
                # shared tie-break: lowest GLOBAL feature id among equal
                # gains (a plain argmax would inherit the vote ranking)
                bi = argbest(gain_sel, sel_loc)
                res = fin(bi)
                # f32 downcast at the state boundary, like finalize_split
                res = res._replace(feature=sel_loc[bi],
                                   gain=gain_sel[bi].astype(jnp.float32))
                if vote_scatter:
                    res = sync_best(res, sel_loc[bi], data_axis)
                return res

            # the leaf-cost boundary: integer histograms rescale to f32
            # stats HERE, once per leaf — everything upstream (psum or
            # psum_scatter, pool, sibling subtraction) was exact int32.
            # On the plain numerical path the int32 tensor travels one
            # stage further: per_feature_best_split runs its bin cumsums
            # in int32 (exact, reassociation-proof) and dequantizes at
            # the scan boundary — bundle/sparse/categorical expansion
            # needs f32 up front, so those paths rescale here as before
            int_scan = (quantized and not params.has_bundles
                        and not params.has_sparse and not params.has_cat)
            acc = qscale if int_scan else None
            if not int_scan:
                hist = dequant(hist)
            if pool_scatter:
                # scattered slice: this shard holds only the aggregated
                # histogram columns [dax*SG, (dax+1)*SG) — search the
                # features living there against the GLOBAL leaf totals,
                # then sync the winner as one tiny best-split record
                if params.has_bundles:
                    # the features of this shard's column slice, via the
                    # static assignment table (bundle columns != features;
                    # entries sorted ascending, -1 = padding)
                    sfeat = jax.lax.dynamic_index_in_dim(
                        meta["scatter_feat"], dax, 0, keepdims=False)
                    sidx = jnp.maximum(sfeat, 0)
                    fmask_s = (fmask_local[sidx]
                               * (sfeat >= 0).astype(jnp.float32))
                    meta_s = {k: v[sidx] for k, v in meta_local.items()
                              if k not in NONFEAT_META}
                    delta_s = (delta_local[sidx] if params.has_cegb
                               else None)
                    hist = expand_bundles(hist, sg, sh, cnt, meta_s,
                                          col_base=dax * SG)
                else:
                    def dslice(a):
                        return jax.lax.dynamic_slice_in_dim(
                            a, dax * SF, SF)

                    sfeat = dax * SF + jnp.arange(SF, dtype=jnp.int32)
                    fmask_s = dslice(fmask_local)
                    meta_s = {k: dslice(v) for k, v in meta_local.items()
                              if k not in NONFEAT_META}
                    delta_s = (dslice(delta_local) if params.has_cegb
                               else None)
                    if params.has_sparse:
                        # zero-bin reconstruction on the slice from the
                        # threaded exact leaf totals (dense_ref's column
                        # may live on another shard)
                        hist = fix_sparse_bins(hist,
                                               meta_s["is_sparse"] > 0,
                                               meta_s["default_bin"],
                                               sp_tot)
                gain_vec, fin = combined_search(hist, sg, sh, cnt, meta_s,
                                                fmask_s, split_kw,
                                                min_c, max_c, acc_scale=acc)
                if params.has_cegb:
                    gain_vec = apply_delta(gain_vec, delta_s)
                # per-shard best: slice entries ascend in feature id, so
                # first-max argmax = lowest feature id within the shard
                bf = jnp.argmax(gain_vec).astype(jnp.int32)
                res = fin(bf)
                # f32 downcast at the state boundary, like finalize_split
                res = res._replace(gain=gain_vec[bf].astype(jnp.float32))
                # cross-shard winner in the feature-frame-LOCAL id space
                # (global when no feature axis; the feature sync below
                # lifts it to global otherwise)
                res = sync_best(res, sfeat[bf], data_axis)
            else:
                hist = expand_bundles(hist, sg, sh, cnt)
                hist = expand_sparse(hist)
                gain_vec, fin = combined_search(hist, sg, sh, cnt,
                                                meta_local, fmask_local,
                                                split_kw, min_c, max_c,
                                                acc_scale=acc)
                if params.has_cegb:
                    gain_vec = apply_delta(gain_vec, delta_local)
                bf = jnp.argmax(gain_vec).astype(jnp.int32)
                res = fin(bf)
                if params.has_cegb:
                    res = res._replace(gain=gain_vec[bf])
            if feature_axis:
                # global best over feature shards (replaces
                # SyncUpGlobalBestSplit, parallel_tree_learner.h:190-213)
                # with the same shared tie-break; contiguous feature
                # sharding keeps ax*F + local ids ascending, so the
                # winner matches the serial lowest-feature rule exactly
                res = sync_best(res, ax * F + res.feature, feature_axis)
            return res

        vselect = jax.vmap(select,
                           in_axes=(0, 0, 0, 0, 0, 0,
                                    0 if bynode else None,
                                    0 if params.has_cegb else None,
                                    0 if sparse_tot else None))

        # ---- root ----------------------------------------------------
        g = grad * row_mask
        h = hess * row_mask
        if quantized:
            # per-iteration gradient discretization: symmetric max-abs
            # scales per class (max is associative, so pmax makes them
            # bit-identical on every shard), stochastic rounding keyed on
            # GLOBAL row indices (invariant to row sharding), and a grid
            # capped by quant_limit so a worst-case int32 bin can never
            # overflow across the GLOBAL row count
            total_rows = n_pad * (num_shards if data_axis else 1)
            qmax = quant_limit(precision, total_rows)
            amax_g = jnp.max(jnp.abs(g))
            amax_h = jnp.max(jnp.abs(h))
            if data_axis:
                amax_g = axis_pmax(amax_g, data_axis)
                amax_h = axis_pmax(amax_h, data_axis)
            g_scale = jnp.maximum(amax_g, jnp.float32(1e-30)) / qmax
            h_scale = jnp.maximum(amax_h, jnp.float32(1e-30)) / qmax
            # fold_in leaves the caller's split stream untouched, so the
            # bynode draws below stay on their usual sequence
            seed_a, seed_b = key_words(jax.random.fold_in(key, 0x5154))
            row0 = (axis_index(data_axis) * n_pad if data_axis
                    else 0)
            # rounding mode as a traced flag: stochastic and nearest are
            # both elementwise-cheap, so ONE program serves either (the
            # old static `mode` keyed a distinct compile per value)
            sto = mode_flag(MF_STOCHASTIC,
                            1.0 if params.quant_round == "stochastic"
                            else 0.0)
            g_q = quantize_values(g, g_scale, qmax, "stochastic",
                                  seed_a, seed_b, row0, salt=0x9E3779B9,
                                  stochastic=sto)
            h_q = quantize_values(h, h_scale, qmax, "stochastic",
                                  seed_a, seed_b, row0, salt=0x85EBCA6B,
                                  stochastic=sto)
            qscale = jnp.stack([g_scale, h_scale, jnp.float32(1.0)])

            def dequant(hh):
                return hh.astype(jnp.float32) * qscale

            # scalar leaf totals from the SAME quantized values the
            # histograms accumulate (int32 sums, psum-exact), rescaled
            sum_g = (preduce_scalar(jnp.sum(g_q, dtype=jnp.int32))
                     .astype(jnp.float32) * g_scale)
            sum_h = (preduce_scalar(jnp.sum(h_q, dtype=jnp.int32))
                     .astype(jnp.float32) * h_scale)
            cnt = (preduce_scalar(
                jnp.sum(row_mask.astype(jnp.int32), dtype=jnp.int32))
                .astype(jnp.float32))
            stats = pack_stats(g_q, h_q, row_mask, precision)  # [3, n_pad]
        else:
            def dequant(hh):  # identity: floats never rescale
                return hh

            # deterministic (f64) mode: the scalar leaf sums must be
            # reduced in f64 too, or psum reassociation of f32 partials
            # re-enters by the back door
            sum_t = jnp.float64 if precision == "f64" else jnp.float32
            sum_g = preduce_scalar(
                jnp.sum(g, dtype=sum_t)).astype(jnp.float32)
            sum_h = preduce_scalar(
                jnp.sum(h, dtype=sum_t)).astype(jnp.float32)
            cnt = preduce_scalar(
                jnp.sum(row_mask, dtype=sum_t)).astype(jnp.float32)
            # per-tree packed stats, reused by every round's contraction
            stats = pack_stats(g, h, row_mask, precision)     # [S, n_pad]
        S = stats.shape[0]
        # dense column count from the matrix itself: with sparse storage
        # bins_t holds only the dense groups (Gd < G = feature width)
        Gd = bins_hist_t.shape[0]
        bins_blocks = jnp.moveaxis(bins_hist_t.reshape(Gd, nb, bcols), 1, 0)
        stats_blocks = stats.reshape(S, nb, block)

        if params.has_sparse:
            sp_idx_t = meta["sparse_idx"]
            sp_bin_t = meta["sparse_bin"]
            if data_axis:
                # the [d_shards, Gs, M] per-shard tables (rows
                # re-indexed shard-local by the learner) shard their
                # leading axis over 'data': this shard sees its own
                # [1, Gs, M] block
                sp_idx_t = sp_idx_t[0]
                sp_bin_t = sp_bin_t[0]
        else:
            sp_idx_t = sp_bin_t = None

        def merge_sparse_hist(dense_h, leaf_vec, slot_ids):
            """[.., Gd, B, 3] LOCAL dense hist -> [.., G, B, 3] LOCAL
            feature hist: append the sparse groups' O(nnz) gather
            contraction and reorder by the static feature->slot
            permutation.  The caller aggregates the MERGED tensor over
            the data axis (psum is elementwise, so aggregating after the
            merge is value-identical to the old per-part psums — and
            scatter needs the full feature-ordered axis to slice);
            zero-bin reconstruction happens AFTER the aggregation, in
            select, from global totals."""
            if not params.has_sparse:
                return dense_h
            sp = build_histogram_sparse(
                sp_idx_t, sp_bin_t, stats, leaf_vec,
                slot_ids, B, precision)           # [k, Gs, B, 3]
            merged = jnp.concatenate([dense_h, sp], axis=-3)
            return jnp.take(merged, meta["hist_perm"], axis=-3)
        with jax.named_scope("hist_build"):
            if params.hist_impl == "pallas2":
                # reuse the batched VMEM kernel at ONE slot (the all-zero
                # root leaf ids), the shape the ramp's first pre-round
                # compiles anyway: the xla scan at pallas-sized short
                # blocks would round-trip a materialized one-hot per
                # block through HBM
                root_local, root_rows = build_histogram_batched_t(
                    bins_blocks, stats_blocks,
                    jnp.zeros((nb, block), jnp.int32),
                    jnp.zeros(1, jnp.int32), B,
                    precision, impl=params.hist_impl,
                    packed_rows=params.packed_bins,
                    live_columns=live_columns, with_rows=True)
                root_local = root_local[0]
            else:
                root_local = build_histogram_t(bins_blocks, stats_blocks,
                                               B, precision)
                root_rows = jnp.array(
                    [1, n_pad // perfeature_dot_lanes(block), n_pad],
                    jnp.uint32)
        if params.has_sparse:
            root_local = merge_sparse_hist(
                root_local[None], jnp.zeros(n_pad, jnp.int32),
                jnp.zeros(1, jnp.int32))[0]
        if sparse_tot:
            # exact per-leaf totals in the ACCUMULATION dtype, reduced
            # from the pre-scatter local histograms (dense_ref's column
            # slice may land on another shard): sum over bins locally,
            # psum the [3] vector — associative for int, exact-in-
            # practice for f64 like every other histogram reduction
            tot_root = preduce_scalar(
                jnp.sum(root_local[meta["dense_ref"][0]], axis=0))
        root_hist = agg_hist(root_local)
        big = jnp.float32(1e30)
        if bynode:
            key, k_root = jax.random.split(key)
            root_fmask = bynode_masks(k_root, ())
        else:
            root_fmask = feature_mask
        # CEGB state persists ACROSS trees (the reference's
        # is_feature_used_in_split_ / feature_used_in_data_ live on the
        # learner, cost_effective_gradient_boosting.hpp:33-48): seeded
        # from meta, returned in the out dict
        if params.has_cegb:
            used0 = meta["cegb_used"]
            if params.has_cegb_lazy:
                paid0 = meta["cegb_paid"]                     # [FG, n_pad] bool
                unpaid_root = jnp.maximum(
                    cnt - jnp.einsum(
                        "fn,n->f", paid0.astype(jnp.float32), row_mask,
                        precision=jax.lax.Precision.HIGHEST),
                    0.0)[None, :]                             # [1, FG]
            else:
                unpaid_root = None
            delta0 = cegb_delta(used0, jnp.reshape(cnt, (1,)),
                                unpaid_root)[0]
        else:
            used0 = jnp.zeros(FG, jnp.float32)
            delta0 = None
        with jax.named_scope("split_search"):
            root_split = select(root_hist, sum_g, sum_h, cnt, -big, big,
                                root_fmask, delta0,
                                tot_root if sparse_tot else None)

        RW = REC_WIDTH + (CB if params.has_cat else 0)
        # the pool stores histograms in the ACCUMULATION dtype: an f32
        # pool under deterministic f64 would silently round every stored
        # leaf histogram back to f32 (and mixed-dtype scatters become
        # errors in future jax) — the reference's deterministic analog
        # keeps f64 HistogramBinEntry end to end (bin.h:33-40).  Int
        # precisions keep the pool in int32 so sibling subtraction stays
        # EXACT (and reduction-order invariant) until select() rescales.
        hist_t = pool_dtype(precision)
        if external_pool:
            # donated scratch: the buffer arrives holding the PREVIOUS
            # iteration's pool, so zero it in place before seeding the
            # root slot — XLA aliases the donated input buffer, so one
            # pool allocation serves every iteration
            if pool_buf.shape != (L, SG, B, 3) or pool_buf.dtype != hist_t:
                raise ValueError(
                    f"external pool must be {(L, SG, B, 3)} {hist_t}; got "
                    f"{pool_buf.shape} {pool_buf.dtype}")
            pool0 = pool_buf.at[:].set(0).at[0].set(root_hist)
        else:
            pool0 = jnp.zeros((L, SG, B, 3), hist_t).at[0].set(root_hist)
        state = {
            "leaf_ids": jnp.zeros(n_pad, jnp.int32),
            # under scatter aggregation the pool holds ONLY this shard's
            # G/P column slice — the P× per-shard HBM saving
            "pool": pool0,
            "leaf_sum_g": jnp.zeros(L, jnp.float32).at[0].set(sum_g),
            "leaf_sum_h": jnp.zeros(L, jnp.float32).at[0].set(sum_h),
            "leaf_cnt": jnp.zeros(L, jnp.float32).at[0].set(cnt),
            "leaf_depth": jnp.zeros(L, jnp.int32),
            "leaf_output": jnp.zeros(L, jnp.float32).at[0].set(
                leaf_output(sum_g, sum_h, params.l1, params.l2,
                            params.max_delta_step)),
            # stored best split per leaf
            "bs_gain": jnp.full(L, K_MIN_SCORE, jnp.float32).at[0].set(root_split.gain),
            "bs_feat": jnp.zeros(L, jnp.int32).at[0].set(root_split.feature),
            "bs_thr": jnp.zeros(L, jnp.int32).at[0].set(root_split.threshold),
            "bs_dleft": jnp.zeros(L, jnp.bool_).at[0].set(root_split.default_left),
            "bs_lg": jnp.zeros(L, jnp.float32).at[0].set(root_split.left_sum_g),
            "bs_lh": jnp.zeros(L, jnp.float32).at[0].set(root_split.left_sum_h),
            "bs_lc": jnp.zeros(L, jnp.float32).at[0].set(root_split.left_count),
            "bs_rg": jnp.zeros(L, jnp.float32).at[0].set(root_split.right_sum_g),
            "bs_rh": jnp.zeros(L, jnp.float32).at[0].set(root_split.right_sum_h),
            "bs_lo": jnp.zeros(L, jnp.float32).at[0].set(root_split.left_output),
            "bs_ro": jnp.zeros(L, jnp.float32).at[0].set(root_split.right_output),
            # categorical best-split carry: flag + bins-going-left mask
            "bs_iscat": jnp.zeros(L, jnp.bool_).at[0].set(root_split.is_cat),
            "bs_catmask": jnp.zeros((L, CB), jnp.float32).at[0].set(
                root_split.cat_mask),
            # monotone value constraints per leaf (propagated on split)
            "leaf_min": jnp.full(L, -1e30, jnp.float32),
            "leaf_max": jnp.full(L, 1e30, jnp.float32),
            # records buffer: K slack rows so the last round's full-width
            # write stays in bounds; trimmed to [L-1] on return
            "records": jnp.zeros((L - 1 + K, RW), jnp.float32),
            "n_splits": jnp.int32(0),
            # what the tree's histogram calls did with this shard's rows
            # (HIST_ROWS_*): calls, sub-blocks contracted, live rows
            "hist_rows": root_rows,
        }
        if bynode:
            state["key"] = key
        if params.has_cegb:
            state["used"] = used0
            if params.has_cegb_lazy:
                state["paid"] = paid0
        if sparse_tot:
            # exact [L, 3] per-leaf totals in the accumulation dtype: the
            # sparse zero-bin source the scattered slices cannot derive
            # from dense_ref locally; maintained like the pool (smaller
            # child summed+psum'd, sibling by subtraction)
            state["leaf_tot"] = jnp.zeros((L, 3), hist_t).at[0].set(tot_root)

        def cand_gains(state):
            depth_ok = jnp.logical_or(
                params.max_depth <= 0,
                state["leaf_depth"] < params.max_depth)
            return jnp.where(depth_ok, state["bs_gain"], K_MIN_SCORE)

        def cond(state):
            return ((state["n_splits"] < L - 1)
                    & (jnp.max(cand_gains(state)) > 0.0))

        def scatter_set(arr, idx, val, valid):
            # invalid slots write out of bounds -> dropped
            safe = jnp.where(valid, idx, arr.shape[0])
            return arr.at[safe].set(val, mode="drop")

        def fix_bundle_col(raw, off, nbf, fixed):
            """Bundle column -> feature-space bin (elementwise; scalars or
            [n] vectors broadcast).  Bins outside the feature's
            [offset+1, offset+num_bin-1] range are some OTHER bundle
            member's value, i.e. this feature sits at its all-default
            bin 0 (reference src/io/dataset.cpp:91-263)."""
            rel = raw - off
            in_rng = (rel >= 1) & (rel < nbf)
            return jnp.where(fixed, jnp.where(in_rng, rel, 0), raw)

        def exec_round(state, sel, vals, do_k, sel_feat, sel_thr, sel_dleft,
                       sel_iscat, cmask_sel, lg, lh, lc, rg, rh, lo, ro):
            """Execute up to Kr splits (slot k: leaf sel[k] on feature
            sel_feat[k]) — partition, batched child histograms, child
            search, state/record updates.  Shared by the best-gain round
            body (Kr=K), the ramp pre-rounds (Kr = 1, 2, 4, ...) and the
            unrolled forced-split rounds; the round width is the static
            shape of the slot operands."""
            leaf_ids = state["leaf_ids"]
            Kr = sel.shape[0]
            kar = jnp.arange(Kr, dtype=jnp.int32)
            # dtype pinned: under x64 (deterministic mode) jnp.sum would
            # promote to int64 and break the while_loop carry contract
            num_do = jnp.sum(do_k, dtype=jnp.int32)
            new_ids = state["n_splits"] + 1 + kar
            ph = state["leaf_sum_h"][sel]
            pc = state["leaf_cnt"][sel]
            # a child's totals are what the scan summed for it on its own
            # side (split.per_feature_best_split), not parent minus left
            rc = pc - lc

            # ---- partition all K splits at once (reference dense_bin.hpp
            # Split / SplitCategorical semantics).  With feature sharding
            # the bins are replicated, so sel_feat's GLOBAL ids index
            # bins_t/meta directly in both lowerings — no column
            # broadcast ----
            if params.partition_impl == "kernel":
                # ONE pass over the ids: every slot's split applied to a
                # block of rows in registers (ops/partition.py)
                leaf_ids = partition_rows(
                    bins_t, leaf_ids, jnp.where(do_k, sel, -1), new_ids,
                    sel_feat, sel_thr,
                    go_right_scalars(meta["missing_type"][sel_feat],
                                     meta["num_bin"][sel_feat],
                                     meta["default_bin"][sel_feat],
                                     sel_thr, sel_dleft))
            else:
                # "select": K unrolled scalar-broadcast passes (the form
                # that takes every storage: bundles, sparse columns,
                # packed rows, categorical masks): each split reads ONE
                # bin row (dynamic slice) and updates its own rows with
                # elementwise compares.  No per-row table gathers — XLA's
                # TPU gather for tiny tables serializes per element, and at
                # ~8 gathers/round x ~20 rounds it dominated tree time.
                def unpack_feature_row(pr):
                    # packed 4-bit row [n_pad/2] -> [n_pad]; unpack2d is
                    # the single definition of the stride layout
                    return unpack2d(pr.reshape(nb, bcols)).reshape(-1)

                new_leaf = leaf_ids
                for k in range(Kr):
                    f_k = sel_feat[k]
                    if params.has_bundles:
                        raw_k = jax.lax.dynamic_index_in_dim(
                            bins_t, meta["bundle_idx"][f_k], 0,
                            keepdims=False)
                        col_k = fix_bundle_col(
                            raw_k, meta["bin_offset"][f_k],
                            meta["num_bin"][f_k],
                            meta["needs_fix"][f_k] > 0)
                    elif params.has_sparse:
                        # dense read via the feature->column map; sparse
                        # features materialize their column on the fly:
                        # every unstored row sits at the zero bin, the
                        # O(nnz) stored entries scatter over it (pad
                        # entries index n_pad -> dropped)
                        col_k = jax.lax.dynamic_index_in_dim(
                            bins_t, meta["dense_col"][f_k], 0,
                            keepdims=False)
                        slot_k = meta["sparse_slot"][f_k]
                        si_k = jax.lax.dynamic_index_in_dim(
                            sp_idx_t, slot_k, 0, keepdims=False)
                        sb_k = jax.lax.dynamic_index_in_dim(
                            sp_bin_t, slot_k, 0, keepdims=False)
                        scol_k = jnp.full(
                            n_pad, meta["default_bin"][f_k],
                            col_k.dtype).at[si_k].set(
                                sb_k.astype(col_k.dtype), mode="drop")
                        col_k = jnp.where(meta["is_sparse"][f_k] > 0,
                                          scol_k, col_k)
                    else:
                        col_k = jax.lax.dynamic_index_in_dim(
                            bins_t, f_k, 0, keepdims=False)
                        if params.packed_bins:
                            col_k = unpack_feature_row(col_k)
                    go_left_k = numeric_go_left(
                        col_k, meta["missing_type"][f_k],
                        meta["num_bin"][f_k], meta["default_bin"][f_k],
                        sel_thr[k], sel_dleft[k])
                    if params.has_cat:
                        cm_r = jnp.take(cmask_sel[k], col_k)
                        go_left_k = jnp.where(sel_iscat[k], cm_r > 0.5,
                                              go_left_k)
                    in_k = (leaf_ids == sel[k]) & do_k[k]
                    new_leaf = jnp.where(in_k & (~go_left_k),
                                         new_ids[k], new_leaf)
                leaf_ids = new_leaf

            # ---- monotone constraint propagation -----------------------
            # (reference serial_tree_learner.cpp:840-851)
            p_min = state["leaf_min"][sel]
            p_max = state["leaf_max"][sel]
            mono_k = meta["monotone"][sel_feat]
            mid = (lo + ro) / 2.0
            l_min = jnp.where(mono_k < 0, mid, p_min)
            l_max = jnp.where(mono_k > 0, mid, p_max)
            r_min = jnp.where(mono_k > 0, mid, p_min)
            r_max = jnp.where(mono_k < 0, mid, p_max)

            # ---- histograms: all K smaller children in one contraction,
            # siblings by subtraction (on the aggregated slice) ----
            smaller_is_left = lc <= rc
            smaller_ids = jnp.where(
                do_k, jnp.where(smaller_is_left, sel, new_ids), -1)
            parent_hist = state["pool"][sel]             # [K, F/P, B, 3]
            # named_scope: the telemetry span names (hist_build /
            # split_search) appear inside xprof device traces too —
            # trace-time metadata, zero runtime cost
            with jax.named_scope("hist_build"):
                h_local, call_rows = build_histogram_batched_t(
                    bins_blocks, stats_blocks,
                    leaf_ids.reshape(nb, block),
                    smaller_ids, B, precision,
                    impl=params.hist_impl,
                    packed_rows=params.packed_bins,
                    live_columns=live_columns,
                    with_rows=True)                      # [K, F, B, 3]
                h_local = merge_sparse_hist(h_local, leaf_ids,
                                            smaller_ids)
                if sparse_tot:
                    tot_small = preduce_scalar(jnp.sum(
                        h_local[:, meta["dense_ref"][0]],
                        axis=1))                         # [K, 3]
                hist_small = agg_hist(h_local)       # [K, F/P, B, 3]
            hist_large = parent_hist - hist_small
            sl = smaller_is_left[:, None, None, None]
            hist_left = jnp.where(sl, hist_small, hist_large)
            hist_right = jnp.where(sl, hist_large, hist_small)

            pool = scatter_set(state["pool"], sel, hist_left, do_k)
            pool = scatter_set(pool, new_ids, hist_right, do_k)

            # ---- best splits for all 2K children -----------------------
            new_state = dict(state)
            if sparse_tot:
                tot_parent = state["leaf_tot"][sel]          # [K, 3]
                tot_large = tot_parent - tot_small
                sl3 = smaller_is_left[:, None]
                tot_left = jnp.where(sl3, tot_small, tot_large)
                tot_right = jnp.where(sl3, tot_large, tot_small)
                lt = scatter_set(state["leaf_tot"], sel, tot_left, do_k)
                new_state["leaf_tot"] = scatter_set(lt, new_ids, tot_right,
                                                    do_k)
                tot_children = jnp.concatenate([tot_left, tot_right])
            else:
                tot_children = None
            if bynode:
                nkey, k_nodes = jax.random.split(state["key"])
                child_masks = bynode_masks(k_nodes, (2 * Kr,))
                new_state["key"] = nkey
            else:
                child_masks = feature_mask
            if params.has_cegb:
                prev_used = state["used"]
                used = scatter_set(prev_used, sel_feat,
                                   jnp.ones(Kr, jnp.float32), do_k)
                new_state["used"] = used
                cnt_children = jnp.concatenate([lc, rc])      # [2K]
                unpaid = None
                if params.has_cegb_lazy:
                    paid = state["paid"]                  # [FG, n_pad] bool
                    # pay the applied splits' costs FIRST: all parent-leaf
                    # rows (pre-partition membership, like the reference
                    # marking bits before DataPartition::Split,
                    # serial_tree_learner.cpp:775-797)
                    pre_memb = ((state["leaf_ids"][None, :] == sel[:, None])
                                & (row_mask[None, :] > 0)
                                & do_k[:, None])
                    pay = jnp.zeros_like(paid).at[sel_feat].max(
                        pre_memb, mode="drop")
                    paid = paid | pay
                    new_state["paid"] = paid
                    # per-child unpaid-row counts for the lazy charge
                    child_ids = jnp.concatenate([sel, new_ids])
                    memb = ((leaf_ids[None, :] == child_ids[:, None])
                            .astype(jnp.float32) * row_mask[None, :])
                    paid_sum = jnp.einsum("kn,fn->kf", memb,
                                          paid.astype(jnp.float32),
                                          precision=jax.lax.Precision.HIGHEST)
                    unpaid = jnp.maximum(
                        cnt_children[:, None] - paid_sum, 0.0)
                delta = cegb_delta(used, cnt_children, unpaid)  # [2K, FG]
                # newly-used features re-credit other leaves' STORED best
                # gains (UpdateLeafBestSplits,
                # cost_effective_gradient_boosting.hpp:64-77); children
                # slots are overwritten by the fresh uncharged search
                # below.  Known bounded approximation vs the reference:
                # only the stored BEST split per leaf is re-credited — a
                # runner-up split on the newly-freed feature cannot be
                # promoted, because per-(leaf, feature) candidate storage
                # ([L, F] SplitInfo, splits_per_leaf_) does not exist in
                # the batched-frontier design
                newly = used - prev_used
                credit = (cegb_tradeoff
                          * meta["cegb_coupled"][state["bs_feat"]]
                          * newly[state["bs_feat"]])
                live = state["bs_gain"] > K_MIN_SCORE / 2
                new_state["bs_gain"] = state["bs_gain"] + \
                    jnp.where(live, credit, 0.0)
            else:
                delta = None
            with jax.named_scope("split_search"):
                ch = vselect(
                    jnp.concatenate([hist_left, hist_right], axis=0),
                    jnp.concatenate([lg, rg]),
                    jnp.concatenate([lh, rh]),
                    jnp.concatenate([lc, rc]),
                    jnp.concatenate([l_min, r_min]),
                    jnp.concatenate([l_max, r_max]),
                    child_masks, delta, tot_children)

            new_state["leaf_ids"] = leaf_ids
            new_state["pool"] = pool
            for key, li, ri in (("leaf_sum_g", lg, rg), ("leaf_sum_h", lh, rh),
                                ("leaf_cnt", lc, rc), ("leaf_output", lo, ro),
                                ("leaf_min", l_min, r_min),
                                ("leaf_max", l_max, r_max)):
                arr = scatter_set(new_state[key], sel, li, do_k)
                new_state[key] = scatter_set(arr, new_ids, ri, do_k)
            d_child = state["leaf_depth"][sel] + 1
            d = scatter_set(state["leaf_depth"], sel, d_child, do_k)
            new_state["leaf_depth"] = scatter_set(d, new_ids, d_child, do_k)
            for key, cv in (("bs_gain", ch.gain), ("bs_feat", ch.feature),
                            ("bs_thr", ch.threshold),
                            ("bs_dleft", ch.default_left),
                            ("bs_lg", ch.left_sum_g), ("bs_lh", ch.left_sum_h),
                            ("bs_lc", ch.left_count),
                            ("bs_rg", ch.right_sum_g),
                            ("bs_rh", ch.right_sum_h),
                            ("bs_lo", ch.left_output),
                            ("bs_ro", ch.right_output),
                            ("bs_iscat", ch.is_cat),
                            ("bs_catmask", ch.cat_mask)):
                arr = scatter_set(new_state[key], sel, cv[:Kr], do_k)
                new_state[key] = scatter_set(arr, new_ids, cv[Kr:], do_k)

            # ---- records: contiguous [K, W] block at row n_splits -------
            rec = jnp.stack([
                sel.astype(jnp.float32), sel_feat.astype(jnp.float32),
                sel_thr.astype(jnp.float32), sel_dleft.astype(jnp.float32),
                vals, lo, ro, lc, rc, lh, rh,
                state["leaf_output"][sel], ph, pc,
                do_k.astype(jnp.float32), sel_iscat.astype(jnp.float32)],
                axis=1)                                      # [K, 16]
            if params.has_cat:
                rec = jnp.concatenate([rec, cmask_sel], axis=1)
            new_state["records"] = jax.lax.dynamic_update_slice(
                state["records"], rec, (state["n_splits"], jnp.int32(0)))
            new_state["n_splits"] = state["n_splits"] + num_do
            new_state["hist_rows"] = state["hist_rows"] + call_rows
            return new_state

        def body(state, round_k=None):
            Kr = K if round_k is None else round_k
            vals, sel = jax.lax.top_k(cand_gains(state), Kr)
            sel = sel.astype(jnp.int32)
            kar = jnp.arange(Kr, dtype=jnp.int32)
            budget = (L - 1) - state["n_splits"]
            # vals is sorted descending, so do_k is a prefix mask: records
            # written this round are contiguous
            do_k = (vals > 0.0) & (kar < budget)
            if params.split_batch_alpha > 0.0 and K > 1:
                # near-tie guard (still a prefix: vals descending); alpha
                # is clamped below 1 so slot 0 always qualifies and the
                # while_loop is guaranteed to make progress
                alpha = min(params.split_batch_alpha, 0.999)
                do_k &= vals >= alpha * vals[0]
            return exec_round(
                state, sel, vals, do_k,
                state["bs_feat"][sel], state["bs_thr"][sel],
                state["bs_dleft"][sel], state["bs_iscat"][sel],
                state["bs_catmask"][sel],
                state["bs_lg"][sel], state["bs_lh"][sel],
                state["bs_lc"][sel], state["bs_rg"][sel],
                state["bs_rh"][sel], state["bs_lo"][sel],
                state["bs_ro"][sel])

        def forced_round(state, ok, parent, feat, thr):
            """One forced split (reference ForceSplits, serial_tree_
            learner.cpp:607-769): leaf `parent` splits on static (feat,
            thr) regardless of best gain; left stats come from the pooled
            histogram at the threshold (GatherInfoForThreshold,
            feature_histogram.hpp:281-419).  A negative forced gain aborts
            this and all remaining forced splits, like the reference's
            aborted_last_force_split."""
            p = jnp.int32(parent)
            iota_b = jnp.arange(B, dtype=jnp.int32)
            mt = meta["missing_type"][feat]
            nb_f = meta["num_bin"][feat]
            db_f = meta["default_bin"][feat]
            nan_excl = (mt == MISSING_NAN) & (iota_b == nb_f - 1)
            mask_b = ((iota_b <= thr) & (iota_b < nb_f)
                      & (~nan_excl)).astype(jnp.float32)
            # the forced feature's pooled column may live on another
            # shard: feature sharding slices the pool by F, scatter
            # aggregation further by SG — the owning shard contributes
            # its sums, everyone else zeros, one psum over the sliced
            # axes broadcasts the result (feat is compile-time constant,
            # so the slice indices stay static)
            f_loc = feat
            own = None
            axes = ()
            if feature_axis:
                own = (f_loc // F) == ax
                f_loc = f_loc % F
                axes += (feature_axis,)
            if pool_scatter:
                own_d = (f_loc // SG) == dax
                f_loc = f_loc % SG
                own = own_d if own is None else (own & own_d)
                # data_axis may itself be an axis TUPLE (hosts, data) —
                # splice its members so the psum sees flat names
                axes += (data_axis if isinstance(data_axis, tuple)
                         else (data_axis,))
            col_hist = state["pool"][p, f_loc]               # [B, 3]
            sums = jnp.sum(col_hist * mask_b[:, None], axis=0)
            if axes:
                sums = axis_psum(
                    jnp.where(own, sums, jnp.zeros_like(sums)), axes)
            if data_axis and voting_k:
                # voting keeps the pool local: forced stats need the
                # global sums
                sums = axis_psum(sums, data_axis)
            lg0, lh0, lc0 = sums[0], sums[1], sums[2]
            pg0 = state["leaf_sum_g"][p]
            ph0 = state["leaf_sum_h"][p]
            pc0 = state["leaf_cnt"][p]
            rg0, rh0, rc0 = pg0 - lg0, ph0 - lh0, pc0 - lc0
            min_c = state["leaf_min"][p]
            max_c = state["leaf_max"][p]
            lo0 = jnp.clip(leaf_output(lg0, lh0, params.l1, params.l2,
                                       params.max_delta_step), min_c, max_c)
            ro0 = jnp.clip(leaf_output(rg0, rh0, params.l1, params.l2,
                                       params.max_delta_step), min_c, max_c)
            shift = leaf_split_gain(pg0, ph0 + 2e-15, params.l1, params.l2,
                                    params.max_delta_step)
            gain0 = (leaf_split_gain(lg0, lh0, params.l1, params.l2,
                                     params.max_delta_step)
                     + leaf_split_gain(rg0, rh0, params.l1, params.l2,
                                       params.max_delta_step)
                     - shift - params.min_gain_to_split)
            do0 = ok & (gain0 >= 0.0) & (lc0 > 0) & (rc0 > 0)
            kar = jnp.arange(K, dtype=jnp.int32)
            first = kar == 0

            def bcast(v, fill=0):
                return jnp.where(first, v, fill)

            dleft0 = (mt == MISSING_ZERO) & (db_f <= thr)
            new_state = exec_round(
                state,
                jnp.full(K, p, jnp.int32),
                bcast(gain0, K_MIN_SCORE),
                first & do0,
                jnp.full(K, feat, jnp.int32),
                jnp.full(K, thr, jnp.int32),
                jnp.broadcast_to(dleft0, (K,)),
                jnp.zeros(K, jnp.bool_),
                jnp.zeros((K, CB), jnp.float32),
                bcast(lg0), bcast(lh0), bcast(lc0), bcast(rg0), bcast(rh0),
                bcast(lo0), bcast(ro0))
            return new_state, do0

        # forced splits run first as statically-unrolled rounds (the
        # forced table is compile-time constant for a training run)
        forced_ok = jnp.asarray(True)
        for parent, feat, thr in params.forced:
            state, forced_ok = forced_round(state, forced_ok,
                                            int(parent), int(feat), int(thr))

        if params.ramp and not params.forced and not bynode and K > 1:
            # frontier ramp (see GrowerParams.ramp): after r rounds the
            # frontier holds <= 2^r leaves, so pre-rounds at K' = 2^r
            # split exactly the leaves the full-K loop would and the tree
            # is bit-identical — only the dead-slot contraction work goes.
            # bynode is excluded: its per-child RNG draw shapes follow the
            # round width, which would change the sampled masks.
            # ramp_step > 2 (the "wide" bucket policy) still covers the
            # frontier (s^i >= 2^i) with fewer unrolled pre-rounds — the
            # grow program's own compile-time lever.
            kr = 1
            while kr < K:
                state = body(state, round_k=kr)
                kr *= int(params.ramp_step)

        state = jax.lax.while_loop(cond, body, state)
        if quantized:
            # leaf-value refit: the tree STRUCTURE came from integer
            # histograms; the final outputs come from the true f32
            # grad/hess sums over each leaf's rows, so leaf values carry
            # no quantization error (LightGBM quantized training's
            # renew-leaf).  f32 psum here is the one reduction whose
            # shard-order ulps can reach the model — turn refit off for
            # strictly bitwise cross-shard model files.  The on/off
            # switch is a TRACED flag (two [L] scatters + a psum are
            # branchless-cheap), so refit on/off shares one program.
            refit_on = mode_flag(MF_QUANT_REFIT,
                                 1.0 if params.quant_refit else 0.0)
            rg = preduce_scalar(
                jnp.zeros(L, jnp.float32).at[state["leaf_ids"]].add(g))
            rh = preduce_scalar(
                jnp.zeros(L, jnp.float32).at[state["leaf_ids"]].add(h))
            refit = jnp.clip(
                leaf_output(rg, rh + jnp.float32(2e-15), params.l1,
                            params.l2, params.max_delta_step),
                state["leaf_min"], state["leaf_max"])
            state["leaf_output"] = jnp.where(
                (state["leaf_cnt"] > 0) & (refit_on > 0),
                refit, state["leaf_output"])
        out = {
            "records": state["records"][:L - 1],  # [L-1, W], REC_* indices
            "leaf_ids": state["leaf_ids"],
            "leaf_output": state["leaf_output"],
            "leaf_cnt": state["leaf_cnt"],
            "leaf_sum_h": state["leaf_sum_h"],
            # [1, 3]: a row per shard once shard_map stacks them
            "hist_rows": state["hist_rows"][None],
        }
        if external_pool:
            # the (donated, in-place) pool rides back to the caller so
            # the next iteration rewrites the same allocation
            out["pool"] = state["pool"]
        if params.has_cegb:
            # cross-tree CEGB state (the learner threads it into the next
            # tree's meta, matching the reference's learner-lifetime
            # is_feature_used_in_split_ / feature_used_in_data_)
            out["cegb_used"] = state["used"]
            if params.has_cegb_lazy:
                out["cegb_paid"] = state["paid"]
        if debug_hist:
            # the GPU_DEBUG_COMPARE analog (reference gpu_tree_learner.
            # cpp:995-1020): expose the pre-aggregation root histogram so
            # callers can assert the collective math against an
            # independently computed full histogram.  In voting mode this
            # is the LOCAL shard histogram (the pool is local by design);
            # in data mode the psum'd one; in feature mode the shard's
            # feature slice.
            out["root_hist"] = root_hist
        return out

    if not jit:
        return grow
    # the grower's own jit site rides the compile ledger so
    # `tools/perf_probe.py retrace` can attribute every compiled program;
    # with an external pool the 8th arg is donated (in-place reuse)
    jit_kw = {"donate_argnums": (7,)} if external_pool else {}
    return ledger_jit(grow, site="grower.grow", **jit_kw)


# record-row field indices (see `rec` stack in make_grower.body); rows are
# 16 wide, plus a trailing [B] categorical bin mask when has_cat
REC_LEAF, REC_FEATURE, REC_THRESHOLD, REC_DEFAULT_LEFT, REC_GAIN, \
    REC_LEFT_OUTPUT, REC_RIGHT_OUTPUT, REC_LEFT_COUNT, REC_RIGHT_COUNT, \
    REC_LEFT_WEIGHT, REC_RIGHT_WEIGHT, REC_INTERNAL_VALUE, \
    REC_INTERNAL_WEIGHT, REC_INTERNAL_COUNT, REC_DID_SPLIT, \
    REC_IS_CAT = range(16)
REC_WIDTH = 16  # categorical mask starts at REC_WIDTH
# columns of the grower's `hist_rows` output, uint32 per shard: histogram
# calls of the tree (each sweeps the shard's padded rows), the sub-blocks of
# `histogram.perfeature_dot_lanes(block)` rows they contracted, and the rows
# they found live (leaf one of the call's slots)
HIST_ROWS_CALLS, HIST_ROWS_CONTRACTED, HIST_ROWS_LIVE = range(3)


def row_blocks(n_pad: int, block_rows: int) -> Tuple[int, int]:
    """(rows of a histogram block, blocks) the grower cuts `n_pad` padded
    rows of a shard into."""
    nb = max(n_pad // min(block_rows, n_pad), 1)
    return n_pad // nb, nb


def pad_rows(n: int, block_rows: int) -> int:
    """Rows padded up to a whole number of histogram blocks."""
    block = min(block_rows, max(n, 1))
    return ((n + block - 1) // block) * block
