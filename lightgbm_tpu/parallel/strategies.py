"""Parallel tree-learner strategies over a device mesh.

The reference selects its learner in a factory keyed on (tree_learner,
device_type) (reference src/treelearner/tree_learner.cpp:13-36) and the
parallel learners are templates over the base learner (parallel_tree_
learner.h:25-187) so device x {feature,data,voting} compose.  Here the
device learner IS the base grower and each strategy is a shard_map wrapping
of the same grower body over a `jax.sharding.Mesh` axis:

  serial   — plain jit, one device
  data     — rows sharded over 'data'; histogram aggregation per
             GrowerParams.hist_agg: full psum, or reduce-scattered
             feature slices + best-split sync
             (DataParallelTreeLearner, data_parallel_tree_learner.cpp:149)
  feature  — features sharded over 'feature'; all_gather + shared
             tie-break of per-shard bests (FeatureParallelTreeLearner,
             feature_parallel_tree_learner.cpp:23-75)
  voting   — rows sharded; top-k voted features' histograms psum'ed (or
             psum_scatter'ed under hist_agg=scatter)
             (VotingParallelTreeLearner, voting_parallel_tree_learner.cpp)

All four present the SAME call signature
    grow(bins_t, grad, hess, row_mask, feature_mask, meta, key) -> out dict
so the driver/learner code is strategy-agnostic.

Collectives dtype note: under the quantized histogram precisions
(tpu_hist_precision=int16|int8) the `data` axis psums int32 histograms.
Integer psum is associative, so data-parallel split decisions are
bit-identical across any shard count (the f32/hilo modes only promise
~ulp agreement); the per-shard contraction additionally reads a stats
operand 2-4x narrower than hilo's — see ops/histogram.py and
docs/USAGE.md "Quantized training".
"""

from __future__ import annotations

from typing import Optional

import jax
from jax import shard_map

import functools

from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops.grower import GrowerParams, make_grower
from ..utils.compile_ledger import ledger_jit
from .topology import FEATURE, ROW_AXES

META_KEYS = ("num_bin", "missing_type", "default_bin", "monotone", "penalty",
             "is_categorical", "cegb_coupled", "cegb_lazy", "bundle_idx",
             "bin_offset", "needs_fix", "mode_flags")

_CANON = {
    "serial": "serial",
    "data": "data", "data_parallel": "data",
    "feature": "feature", "feature_parallel": "feature",
    "voting": "voting", "voting_parallel": "voting",
    # 2-D composition (the reference's device x parallel template nesting,
    # parallel_tree_learner.h:25-187): rows on 'data' x features on
    # 'feature' in one mesh
    "data_feature": "data_feature", "feature_data": "data_feature",
    "data_feature_parallel": "data_feature",
}


def resolve_tree_learner(name: str) -> str:
    """Canonical strategy name (reference tree_learner config aliases,
    src/io/config.cpp ParseTreeLearnerType)."""
    try:
        return _CANON[str(name).strip().lower()]
    except KeyError:
        raise ValueError(f"unknown tree_learner {name!r}") from None


def pool_partition_spec(strategy: str, scatter: bool) -> P:
    """Partition spec of the GLOBAL [L, G, B, 3] histogram pool under
    `strategy` — the donated external pool's placement.  The column axis
    shards exactly like the slices the grower keeps per shard: the full
    width under psum (replicated), the contiguous G/P slice under
    scatter, the feature slice under feature sharding (feature-major /
    data-minor in the 2-D mesh).  Row shards address the (hosts, data)
    axis PRODUCT — the linearized index equals the old flat data-axis
    index, so placement is unchanged on a 1-host mesh."""
    if strategy in ("data", "voting"):
        return P(None, ROW_AXES) if scatter else P()
    if strategy == "feature":
        return P(None, FEATURE)
    if strategy == "data_feature":
        return (P(None, (FEATURE,) + ROW_AXES) if scatter
                else P(None, FEATURE))
    return P()


def make_strategy_grower(params: GrowerParams, num_features: int,
                         strategy: str, mesh: Optional[Mesh] = None,
                         voting_k: int = 20,
                         num_columns: Optional[int] = None,
                         debug_hist: bool = False,
                         external_pool: bool = False,
                         live_columns: Optional[int] = None):
    """Grower for `strategy`; num_features is the GLOBAL (padded) count;
    num_columns the bin-matrix column count (< num_features under EFB);
    live_columns how many of the bin matrix's leading columns carry data
    (ops/grower.py make_grower; None under a feature axis).

    debug_hist adds a "root_hist" output (the GPU_DEBUG_COMPARE analog,
    reference gpu_tree_learner.cpp:995-1020): per-shard LOCAL in voting
    mode (out axis 0 stacks shards), psum'd/replicated in data mode, the
    feature slice stacked to global width in feature modes.

    external_pool adds the donated 8th `pool` argument (ops/grower.py
    make_grower) — the global [L, G, B, 3] pool placed per
    `pool_partition_spec` and rewritten in place every call.  Strategy
    growers are memoized like the base grower: an identical configuration
    returns the SAME jitted callable, so repeat Booster constructions
    reuse compiled executables instead of re-tracing."""
    return _build_strategy_grower(params, num_features, strategy, mesh,
                                  voting_k, num_columns, debug_hist,
                                  external_pool, live_columns)


def _strategy_jit(fn, strategy: str, external_pool: bool):
    """The ledgered jit site for one sharded strategy (donating the
    external pool when present)."""
    kw = {"donate_argnums": (7,)} if external_pool else {}
    return ledger_jit(fn, site=f"grower.{strategy}", **kw)


# bounded like ops/grower.py:_build_grower: the key pins Mesh/device
# objects and shape-derived params, so cap retention instead of growing
# one compiled strategy grower per distinct shape forever
@functools.lru_cache(maxsize=64)
def _build_strategy_grower(params, num_features, strategy, mesh,
                           voting_k, num_columns, debug_hist,
                           external_pool, live_columns):
    if strategy == "serial" or mesh is None:
        return make_grower(params, num_features, num_columns=num_columns,
                           debug_hist=debug_hist,
                           external_pool=external_pool,
                           live_columns=live_columns)

    meta_spec = {k: P() for k in META_KEYS}
    base_out = {"records": P(), "leaf_output": P(), "leaf_cnt": P(),
                "leaf_sum_h": P()}
    if params.has_cegb:
        # coupled CEGB composes with the parallel learners (the split
        # decisions are globally identical, so `used` stays replicated);
        # lazy CEGB is serial-only and never reaches here
        meta_spec["cegb_used"] = P()
        base_out["cegb_used"] = P()
    if params.has_sparse:
        # the per-shard COO tables shard their LEADING axis over 'data'
        # (each device holds only its own [1, Gs, M] block — replicating
        # a feature whose purpose is saving HBM would defeat it); the
        # small per-feature vectors replicate
        for k in ("is_sparse", "sparse_slot", "dense_col", "dense_ref",
                  "hist_perm"):
            meta_spec[k] = P()
        meta_spec["sparse_idx"] = P(ROW_AXES)
        meta_spec["sparse_bin"] = P(ROW_AXES)
    scatter = params.hist_agg == "scatter"
    if scatter and params.has_bundles:
        # static shard -> feature-ids table for the scattered EFB search
        # (bundle columns != features); tiny, replicated
        meta_spec["scatter_feat"] = P()
    pool_spec = pool_partition_spec(strategy, scatter)
    if strategy in ("data", "voting"):
        nshards = mesh.shape["hosts"] * mesh.shape["data"]
        grow = make_grower(
            params, num_features, data_axis=ROW_AXES,
            voting_k=(voting_k if strategy == "voting" else 0),
            num_shards=nshards, jit=False, num_columns=num_columns,
            debug_hist=debug_hist, external_pool=external_pool,
            live_columns=live_columns)
        out_specs = {**base_out, "leaf_ids": P(ROW_AXES),
                     "hist_rows": P(ROW_AXES)}
        if external_pool:
            out_specs["pool"] = pool_spec
        if debug_hist:
            # voting keeps pools local -> stack shards on axis 0; data
            # mode under psum replicates the full histogram on every
            # shard, under scatter each shard holds its contiguous
            # feature slice (stacking over 'data' reassembles the global
            # histogram — and the per-shard slice width IS the
            # no-global-histogram assertion hook for tests)
            out_specs["root_hist"] = (P(ROW_AXES)
                                      if strategy == "voting" or scatter
                                      else P())
        in_specs = (P(None, ROW_AXES), P(ROW_AXES), P(ROW_AXES),
                    P(ROW_AXES), P(), meta_spec, P())
        if external_pool:
            in_specs = in_specs + (pool_spec,)
        fn = shard_map(
            grow, mesh=mesh,
            in_specs=in_specs,
            out_specs=out_specs,
            check_vma=False)
        return _strategy_jit(fn, strategy, external_pool)
    if strategy == "feature":
        nshards = mesh.shape["feature"]
        if num_features % nshards != 0:
            raise ValueError(
                f"feature count {num_features} must be padded to a multiple "
                f"of the feature-shard count {nshards}")
        f_local = num_features // nshards
        grow = make_grower(params, f_local, feature_axis=FEATURE,
                           jit=False, debug_hist=debug_hist,
                           external_pool=external_pool,
                           live_columns=live_columns)
        # bins REPLICATED (P()), like the reference feature-parallel mode
        # where every machine holds all data (feature_parallel_tree_
        # learner.cpp:55-71): each shard histograms only its own feature
        # slice but partitions rows from the full local matrix, so no
        # per-split column broadcast is needed — the only collective left
        # is the all_gather of per-shard best gains
        out_specs = {**base_out, "leaf_ids": P(), "hist_rows": P(FEATURE)}
        if external_pool:
            out_specs["pool"] = pool_spec
        if debug_hist:
            out_specs["root_hist"] = P(FEATURE)
        in_specs = (P(), P(), P(), P(), P(), meta_spec, P())
        if external_pool:
            in_specs = in_specs + (pool_spec,)
        fn = shard_map(
            grow, mesh=mesh,
            in_specs=in_specs,
            out_specs=out_specs,
            check_vma=False)
        return _strategy_jit(fn, strategy, external_pool)
    if strategy == "data_feature":
        f_shards = mesh.shape["feature"]
        d_shards = mesh.shape["hosts"] * mesh.shape["data"]
        if num_features % f_shards != 0:
            raise ValueError(
                f"feature count {num_features} must be padded to a multiple "
                f"of the feature-shard count {f_shards}")
        f_local = num_features // f_shards
        grow = make_grower(params, f_local, data_axis=ROW_AXES,
                           feature_axis=FEATURE, num_shards=d_shards,
                           jit=False, debug_hist=debug_hist,
                           external_pool=external_pool,
                           live_columns=live_columns)
        # rows shard over (hosts, data); the bin matrix is [F_global,
        # n_local] per device (features replicated within a row shard so
        # the partition reads the full matrix, like the 1-D feature
        # mode); histograms psum over the row axes, bests all_gather
        # over 'feature'
        out_specs = {**base_out, "leaf_ids": P(ROW_AXES),
                     "hist_rows": P((FEATURE,) + ROW_AXES)}
        if external_pool:
            out_specs["pool"] = pool_spec
        if debug_hist:
            # stack feature slices to global; under scatter each feature
            # shard's slice is further scattered over the row axes
            # (feature-major, row-minor — exactly the global feature
            # order)
            out_specs["root_hist"] = (P((FEATURE,) + ROW_AXES) if scatter
                                      else P(FEATURE))
        in_specs = (P(None, ROW_AXES), P(ROW_AXES), P(ROW_AXES),
                    P(ROW_AXES), P(), meta_spec, P())
        if external_pool:
            in_specs = in_specs + (pool_spec,)
        fn = shard_map(
            grow, mesh=mesh,
            in_specs=in_specs,
            out_specs=out_specs,
            check_vma=False)
        return _strategy_jit(fn, strategy, external_pool)
    raise ValueError(f"unknown strategy {strategy!r}")


def bins_sharding(mesh: Mesh, strategy: str) -> NamedSharding:
    """Sharding for the transposed [F, n_pad] bin matrix under `strategy`."""
    if strategy in ("data", "voting", "data_feature"):
        return NamedSharding(mesh, P(None, ROW_AXES))
    if strategy == "feature":
        # replicated: every shard partitions rows from the full matrix
        # (the reference's all-data-on-all-machines feature mode)
        return NamedSharding(mesh, P())
    raise ValueError(strategy)


def rows_sharding(mesh: Mesh, strategy: str) -> NamedSharding:
    """Sharding for [n_pad] per-row vectors under `strategy`."""
    if strategy in ("data", "voting", "data_feature"):
        return NamedSharding(mesh, P(ROW_AXES))
    return NamedSharding(mesh, P())
