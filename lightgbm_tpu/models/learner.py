"""TPU tree learner: wraps the device grower, assembles host Tree models.

The analog of the reference's learner factory slot (reference
src/treelearner/tree_learner.cpp:13-36): the serial learner here IS the
device learner (device offload is the default, like `device_type=gpu`
composing with the serial learner, gpu_tree_learner.cpp:739-750).  Parallel
variants wrap the same grower with mesh shardings (lightgbm_tpu.parallel).
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import obs
from ..config import Config
from ..io.bin_mapper import MissingType
from ..io.dataset import TrainingData
from ..ops.grower import (HIST_ROWS_CALLS, HIST_ROWS_CONTRACTED,
                          HIST_ROWS_LIVE, GrowerParams, canonical_params,
                          mode_flags_np, pad_rows, pool_dtype,
                          resolve_split_batch, row_blocks)
from ..ops.histogram import (hashed_uniform, key_words, perfeature_chunks,
                             perfeature_columns_per_dot, perfeature_dot_lanes)
from ..ops.lookup import lookup
from ..ops.partition import partition_kernel_fits
from ..parallel.mesh import (exchange_bytes_per_tree, put_global, put_local,
                             tree_hist_slots)
from ..parallel.strategies import (bins_sharding, make_strategy_grower,
                                   pool_partition_spec,
                                   resolve_tree_learner, rows_sharding)
from ..parallel.topology import ROW_AXES
from ..utils import timer
from ..utils.compile_ledger import closed_over_bytes, ledger_jit
from ..utils.log import Log
from .tree import Tree


def _to_bitset(values) -> list:
    """Int values -> uint32 bitset words (reference Common::ConstructBitset,
    include/LightGBM/utils/common.h)."""
    vals = [int(v) for v in values if int(v) >= 0]
    if not vals:
        return [0]
    words = [0] * (max(vals) // 32 + 1)
    for v in vals:
        words[v // 32] |= 1 << (v % 32)
    return words


class TPUTreeLearner:
    # True on StreamedTreeLearner (ops/stream.py): the binned matrix
    # stays HOST-resident and serial placement routes through the
    # _place_serial_bins hook instead of a device transpose/pack
    stream_layout = False

    def __init__(self, config: Config, train_data: TrainingData):
        self.config = config
        self.td = train_data
        # persistent XLA compilation cache (tpu_compile_cache_dir): every
        # program of the run is cached there so repeat runs of the same
        # shapes skip the cold compile tail.  JAX_COMPILATION_CACHE_DIR,
        # where set, wins over this option (utils/backend.py)
        cache_dir = str(config.tpu_compile_cache_dir or "")
        if cache_dir:
            from ..utils.backend import enable_compilation_cache

            enable_compilation_cache(cache_dir, min_compile_time_secs=0.0)
        n = train_data.num_data
        self.num_features = train_data.num_features
        if self.num_features == 0:
            raise ValueError("no usable features in training data")

        meta_np = dict(train_data.feature_arrays())
        # CEGB feature-acquisition penalties, mapped onto used features
        # (reference config.h cegb_penalty_feature_coupled/_lazy)
        def _per_feature(raw):
            vals = np.zeros(train_data.num_features, np.float32)
            for j, col in enumerate(train_data.used_feature_idx):
                if col < len(raw):
                    vals[j] = raw[col]
            return vals

        coupled_raw = [float(v) for v in config.cegb_penalty_feature_coupled]
        lazy_raw = [float(v) for v in config.cegb_penalty_feature_lazy]
        meta_np["cegb_coupled"] = _per_feature(coupled_raw)
        meta_np["cegb_lazy"] = _per_feature(lazy_raw)
        # all-zero penalty lists are no-ops in the reference (IsEnable,
        # cost_effective_gradient_boosting.hpp:25-31 checks emptiness, but
        # zeros charge nothing) — don't pay for the machinery
        has_cegb_lazy = any(v != 0.0 for v in lazy_raw)
        has_cegb = (any(v != 0.0 for v in coupled_raw) or has_cegb_lazy
                    or float(config.cegb_penalty_split) != 0.0)
        self.meta_np = meta_np
        forced = self._parse_forced_splits(config, train_data)
        B = int(meta_np["num_bin"].max())
        self.num_bins = B

        # ---- strategy resolution (the factory slot,
        # reference tree_learner.cpp:13-36 + CheckParamConflict which
        # degrades parallel learners to serial when num_machines==1) ----
        strategy = resolve_tree_learner(config.tree_learner)
        n_shards = int(config.num_machines)
        if strategy != "serial":
            if str(config.machines):
                # multi-host: machine list -> jax.distributed global mesh
                # (the Linkers-socket rendezvous role,
                # linkers_socket.cpp:165-220); single-process runs skip it
                from ..parallel.mesh import init_multihost

                init_multihost(str(config.machines),
                               int(config.local_listen_port), n_shards)
            ndev = len(jax.devices())
            if n_shards <= 1:
                Log.warning(f"tree_learner={strategy} needs num_machines>1; "
                            "falling back to serial")
                strategy = "serial"
            elif n_shards > ndev:
                raise ValueError(
                    f"num_machines={n_shards} exceeds the {ndev} available "
                    f"devices ({jax.devices()[0].platform})")
        self.n_shards = n_shards if strategy != "serial" else 1
        # hosts axis of the (hosts, data, feature) topology — the
        # process/DCN tier.  tpu_topology_hosts>0 pins it (simulated
        # multi-host grids on one process); 0 follows the live process
        # count.  Live multi-process runs must agree with reality: the
        # put_local/put_global placement contracts key on it.
        from ..parallel.topology import resolve_hosts

        self.hosts = (resolve_hosts(int(config.tpu_topology_hosts))
                      if strategy != "serial" else 1)
        if (strategy != "serial" and jax.process_count() > 1
                and self.hosts != jax.process_count()):
            raise ValueError(
                f"tpu_topology_hosts={self.hosts} disagrees with the live "
                f"process count {jax.process_count()}; leave it 0 (auto) "
                "on real multi-host meshes")
        # 2-D factorization: rows on (hosts, data) x features on
        # 'feature' (reference parallel_tree_learner.h:25-187 template
        # nesting)
        if strategy == "data_feature":
            fs = int(config.tpu_feature_shards)
            if fs <= 0:
                # auto: 2 feature shards when the device count factors,
                # else degrade to a (n, 1) mesh — 1-sized axes are valid
                # (the collectives become no-ops) so odd/prime counts
                # still train instead of crashing on a value the user
                # never set
                fs = 2 if (self.n_shards % 2 == 0 and self.n_shards > 2) \
                    else 1
            if self.n_shards % fs != 0:
                raise ValueError(
                    f"tpu_feature_shards={fs} must divide "
                    f"num_machines={self.n_shards}")
            self.f_shards = fs
            self.d_shards = self.n_shards // fs
        elif strategy == "feature":
            if self.hosts > 1:
                # feature sharding across hosts: no host holds every row
                # once the hosts axis is real, so rows ride the hosts
                # axis (one row shard per host) and each host's devices
                # shard the features — the data_feature composition with
                # d_shards == hosts.  Split decisions are gain-identical
                # to 1-host feature sharding: histograms psum exactly
                # over the row axes and the best-split sync shares the
                # deterministic tie-break.
                if self.n_shards % self.hosts != 0:
                    raise ValueError(
                        f"num_machines={self.n_shards} must split evenly "
                        f"across {self.hosts} hosts for tree_learner="
                        "feature")
                strategy = "data_feature"
                self.f_shards = self.n_shards // self.hosts
                self.d_shards = self.hosts
            else:
                self.f_shards, self.d_shards = self.n_shards, 1
        else:
            self.f_shards, self.d_shards = 1, self.n_shards
        self.strategy = strategy

        # ---- pre-partitioned training rows (reference loader
        # pre_partition, dataset_loader.cpp row distribution): each
        # PROCESS holds only its local row shard, so the row geometry
        # and device placement below become process-local and metrics
        # reduce globally (parallel/metric_sync).  DERIVED, not gated on
        # strategy: every parallel learner rides the same (hosts, data,
        # feature) mesh, so the old feature/EFB refusals are gone.
        self._partitioned = (bool(config.pre_partition)
                             and strategy != "serial"
                             and jax.process_count() > 1)
        if self._partitioned:
            if self.n_shards != len(jax.devices()):
                raise ValueError(
                    "pre_partition requires num_machines == the total "
                    f"device count ({len(jax.devices())}); got "
                    f"{self.n_shards}")
            if self.d_shards % jax.process_count() != 0:
                raise ValueError("row shards must split evenly across "
                                 "processes for pre_partition")

        # values whose implementations were deleted (the chip's compiler
        # refused them, or they won nowhere) and what serves in their place
        removed = {("tpu_hist_impl", "pallas"): "pallas2",
                   ("tpu_hist_impl", "fused"): "pallas2",
                   ("tpu_partition_impl", "gather"): "select",
                   ("tpu_partition_impl", "vselect"): "auto"}
        for key, allowed in (("tpu_partition_impl",
                              ("auto", "select", "kernel")),
                             ("tpu_hist_impl", ("auto", "xla", "pallas2")),
                             ("tpu_hist_precision", ("hilo", "bf16", "f32",
                                                     "f64", "int8", "int16")),
                             ("tpu_quant_round", ("stochastic", "nearest")),
                             ("tpu_hist_agg", ("auto", "psum", "scatter")),
                             ("tpu_bucket_policy", ("fine", "wide"))):
            value = str(getattr(config, key))
            if (key, value) in removed:
                raise ValueError(
                    f"{key}={value} was removed; use {key}="
                    f"{removed[key, value]} (or leave the default)")
            if value not in allowed:
                raise ValueError(f"{key}={getattr(config, key)!r}; "
                                 f"expected one of {allowed}")
        self.hist_agg = self._resolve_hist_agg(config, strategy,
                                               self.d_shards)

        precision = self._resolve_precision(config)
        quantized = precision in ("int8", "int16")

        # feature axis padded to a multiple of the shard count; padding
        # features are trivial (num_bin=1) and can never split
        self.f_pad = self.num_features
        if self.f_shards > 1:
            self.f_pad = (-(-self.num_features // self.f_shards)
                          * self.f_shards)

        # layout phase timer (bench.py splits ingest into sketch / bin /
        # layout): everything from EFB planning to the placed device
        # arrays below counts as layout
        with timer.PHASE("layout"):
            # ---- EFB bundling (reference FindGroups/
            # FastFeatureBundling, dataset.cpp:91-263): sparse
            # zero-default features share columns, shrinking the
            # histogram matrix's feature axis ----
            plan = None
            if (bool(config.enable_bundle)
                    and strategy not in ("serial", "data")
                    and self.num_features > 1):
                # voting/feature learners train unbundled (the grower's
                # bundle expansion composes with serial/data only) — say so
                # instead of silently dropping the requested EFB
                Log.info(f"EFB bundling is inactive under tree_learner="
                         f"{strategy}; training on plain columns")
            if (bool(config.enable_bundle) and strategy in ("serial", "data")
                    and not forced and self.num_features > 1
                    and not self.stream_layout):
                from ..io.bundling import (EFB_SAMPLE_ROWS, find_bundles,
                                           find_bundles_multihost)

                zero_frac = train_data.column_zero_fraction()
                if self._partitioned:
                    # every rank must greedy-group the SAME plan or the
                    # global arrays' num_columns/meta diverge; all plan-
                    # determining statistics reduce inside the helper
                    cand_plan = find_bundles_multihost(
                        train_data.bins, meta_np["num_bin"], zero_frac, n,
                        float(config.sparse_threshold),
                        float(config.max_conflict_rate), B)
                else:
                    # the greedy only ever reads the strided row sample;
                    # hand it exactly that sample (a bounded device fetch
                    # when the matrix is device-resident) instead of the
                    # full host matrix — identical rows, identical plan
                    cand_plan = find_bundles(
                        train_data.strided_row_sample(EFB_SAMPLE_ROWS),
                        meta_np["num_bin"],
                        zero_frac >= float(config.sparse_threshold),
                        float(config.max_conflict_rate), B,
                        sample_rows=EFB_SAMPLE_ROWS)
                obs.REGISTRY.set_gauge(
                    "lgbm_efb_candidates", cand_plan.candidates,
                    help="features the EFB greedy tried to bundle (mostly "
                         "zero, few enough bins)")
                obs.REGISTRY.set_gauge(
                    "lgbm_efb_bundles",
                    sum(len(g) > 1 for g in cand_plan.groups),
                    help="bundles of two or more features the EFB greedy "
                         "formed")
                if not cand_plan.is_trivial:
                    plan = cand_plan
                    B = max(B, int(plan.num_bin.max()))
                    self.num_bins = B
                    Log.info(
                        f"EFB: bundled {self.num_features} features into "
                        f"{plan.num_columns} columns")
            self.bundle_plan = plan

            if plan is not None:
                from ..io.bundling import apply_bundles

                cols_src = apply_bundles(train_data.bins, plan)
                dev_src = None
                meta_np["bundle_idx"] = plan.bundle_idx.astype(np.int32)
                meta_np["bin_offset"] = plan.bin_offset.astype(np.int32)
                meta_np["needs_fix"] = plan.needs_fix.astype(np.int32)
                self.num_columns = cols_src.shape[1]
            else:
                # device-resident ingest keeps the host matrix lazy: the
                # plain-column layout below can transpose on device, so
                # cols_src stays unmaterialized until a host-only path
                # (sparse COO packing, parallel placement) asks for it
                dev_src = train_data.device_ingest_bins()
                cols_src = None if dev_src is not None else train_data.bins
                F_ = self.num_features
                meta_np["bundle_idx"] = np.arange(F_, dtype=np.int32)
                meta_np["bin_offset"] = np.zeros(F_, np.int32)
                meta_np["needs_fix"] = np.zeros(F_, np.int32)
                self.num_columns = F_
            self.g_pad = (self.f_pad if self.f_shards > 1
                          else self.num_columns)

            # ---- sparse train-time storage (reference OrderedSparseBin,
            # src/io/ordered_sparse_bin.hpp / sparse_bin.hpp:73): features
            # whose nonzero-bin fraction is <= tpu_sparse_threshold keep only
            # their O(nnz) (row, bin) pairs; the dense [Gd, n] matrix holds
            # the rest.  Wide very-sparse data (Bosch-shaped 1M x 968 @ ~2%)
            # stops paying dense HBM for rows sitting at the zero bin. ----
            self._sparse_mask = None
            sth = float(config.tpu_sparse_threshold)
            if sth > 0.0:
                if quantized:
                    # the sparse zero-bin reconstruction mixes histogram rows
                    # with scalar leaf totals; keeping that exact in the
                    # integer domain is future work — reject loudly
                    raise ValueError(
                        "tpu_sparse_threshold does not compose with quantized "
                        "histogram precisions (tpu_hist_precision=int8|int16)")
                if bool(config.enable_bundle):
                    # deterministic gate on the FLAG, not on whether a plan
                    # happened to form for this data — the error must not
                    # depend on bundle-ability
                    raise ValueError(
                        "tpu_sparse_threshold requires enable_bundle=false "
                        "(EFB already re-columns sparse features; pick one)")
                if strategy not in ("serial", "data", "voting"):
                    raise NotImplementedError(
                        "tpu_sparse_threshold requires tree_learner=serial, "
                        "data, or voting (feature sharding replicates rows)")
                if forced:
                    raise ValueError("tpu_sparse_threshold does not compose "
                                     "with forced splits")
                zb_f = meta_np["default_bin"]
                # one vectorized (bins != zero_bin).sum(axis=0) pass — the
                # sparse gate implies enable_bundle=false, so the columns
                # are the plain training bins; the helper row-chunks the
                # boolean temporary (Bosch scale) and reduces on device
                # when the matrix is device-resident
                nz_counts = train_data.column_nonzero_counts(zb_f)
                denom = n
                if self._partitioned:
                    # every rank must agree on WHICH features are sparse, or
                    # Gs/perm diverge and the global tables are inconsistent
                    # — decide from the GLOBAL nonzero fractions
                    from ..parallel.topology import host_allgather

                    g = host_allgather(
                        np.concatenate([nz_counts, [n]]).astype(np.int32),
                        name="sparse_global_fractions")
                    tot = g.sum(axis=0)
                    nz_counts, denom = tot[:-1], int(tot[-1])
                nz_frac = nz_counts / max(denom, 1)
                sp_mask = nz_frac <= sth
                if sp_mask.all():
                    # the dense kernel needs a nonempty matrix; keep the
                    # densest feature dense
                    sp_mask[int(np.argmax(nz_frac))] = False
                if sp_mask.any():
                    self._sparse_mask = sp_mask

            # impl/block resolution happens HERE, once, with the final
            # histogram shape: bundling above only needs the host bin matrix,
            # while the padded row count below depends on the resolved block.
            # (The perfeature kernel chunks the feature axis itself, so the
            # VMEM fit depends only on the bin count, not the feature width.)
            hist_impl, block = self._resolve_hist_impl(config, B, precision)
            if hist_impl == "pallas2":
                # the perfeature kernel chunks its feature grid in
                # sublane-aligned (multiple-of-32) divisors (ops/histogram.py
                # _hist_pallas); pad the histogram column axis so every width
                # admits aligned chunks.  Padding columns are storage only:
                # they belong to num_bin=1 features that can never split, and
                # the kernel is told how many leading columns are live
                # (`live_columns` below) and contracts no others.  Feature-
                # parallel pads to 32 * n_shards so each shard's slice stays
                # aligned
                if self.f_shards > 1:
                    a = 32 * self.f_shards
                    self.f_pad = -(-self.f_pad // a) * a
                    self.g_pad = self.f_pad
                elif plan is None:
                    self.f_pad = -(-self.f_pad // 32) * 32
                    self.g_pad = self.f_pad
                else:
                    self.g_pad = -(-self.g_pad // 32) * 32
            # ---- shape bucketing (compile-cache policy): quantize the padded
            # axes so at most `tpu_shape_buckets` distinct shapes exist per
            # power-of-2 octave — a new dataset of similar size then hits the
            # persistent compilation cache instead of paying the 70-150 s
            # cold remote compile (SURVEY §7 "dispatch overhead is the #1
            # wall-clock risk").  Worst-case pad waste is 2/buckets (~6% at
            # the default 32); 0 disables (exact block-multiple padding,
            # maximum throughput — bench.py pins this).
            buckets = int(config.tpu_shape_buckets)

            def bucket_up(count: int, quantum: int) -> int:
                padded = -(-count // quantum) * quantum
                if buckets <= 0:
                    return padded
                q = quantum
                while q * buckets < padded:
                    q *= 2
                return -(-count // q) * q

            def bucket_rows(count: int) -> int:
                # supra-block: quantize the BLOCK COUNT (pad_rows clamps the
                # block to the row count, so derive the effective block the
                # same way).  Sub-block (count < tpu_block_rows, the common
                # case on TPU where the resolved block is 8-16k): quantize
                # the row count itself from the 128-lane tile upward, capped
                # at one block — without this, every sub-block n is its own
                # XLA program
                eff = min(block, max(count, 1))
                base = pad_rows(count, block)
                if buckets <= 0:
                    return base
                if base >= block:
                    return bucket_up(base // eff, 1) * eff
                return min(bucket_up(count, 128), block)

            if self._partitioned:
                # rows per shard must be UNIFORM across the whole mesh: size
                # from the largest process's share (short ranks pad with
                # masked rows); n here is only THIS process's row count
                from ..parallel.topology import host_allgather

                shards_local = self.d_shards // jax.process_count()
                ns = host_allgather(np.asarray([n], np.int32),
                                    name="shard_rows_sync")
                max_shard_rows = -(-int(ns.max()) // shards_local)
                self.n_pad = bucket_rows(max_shard_rows) * self.d_shards
                self._local_width = ((self.n_pad // self.d_shards)
                                     * shards_local)
            elif self.d_shards > 1:
                # every shard holds an equal, whole number of histogram blocks
                self.n_pad = bucket_rows(
                    (n + self.d_shards - 1) // self.d_shards) * self.d_shards
            else:
                self.n_pad = bucket_rows(n)
            # feature axis: bucket above the alignment the padding code above
            # already established (32-multiples for pallas2, shard-count
            # multiples for feature sharding); padding features are trivial
            # (num_bin=1) and can never split
            if buckets > 0:
                if hist_impl == "pallas2":
                    align = 32 * self.f_shards if self.f_shards > 1 else 32
                else:
                    align = self.f_shards if self.f_shards > 1 else 8
                if self.g_pad == self.f_pad:
                    self.f_pad = bucket_up(self.f_pad, align)
                    self.g_pad = self.f_pad
                else:
                    # EFB keeps g_pad (bundle columns) separate from f_pad
                    self.g_pad = bucket_up(self.g_pad, align)

            # ---- scatter-aggregation alignment (tpu_hist_agg=scatter): the
            # reduce-scatter hands shard d a contiguous 1/P slice of the
            # histogram column axis, so that axis must divide by the data-
            # shard count — on top of whatever alignment feature sharding /
            # the pallas2 kernel already demanded.  Padding columns/features
            # are trivial (num_bin=1) and can never split.  Voting scatters
            # only the voted [k, B, 3] block (padded inside the grower).
            if self.hist_agg == "scatter" and strategy != "voting":
                import math

                if plan is None:
                    a = self.f_shards * self.d_shards
                    if hist_impl == "pallas2":
                        a = math.lcm(a, 32 * max(self.f_shards, 1))
                    self.f_pad = -(-self.f_pad // a) * a
                    self.g_pad = self.f_pad
                else:
                    # EFB: only the bundle-column axis scatters; the shard ->
                    # feature assignment rides the scatter_feat table below
                    a = self.d_shards
                    if hist_impl == "pallas2":
                        a = math.lcm(a, 32)
                    self.g_pad = -(-self.g_pad // a) * a

            from ..parallel import topology as _topo

            if strategy == "serial":
                self.topology = None
            else:
                self.topology = _topo.make_topology(
                    num_data_shards=self.d_shards,
                    num_feature_shards=self.f_shards,
                    num_hosts=self.hosts,
                    partitioned_rows=self._partitioned)
            self.mesh = self.topology.mesh if self.topology else None
            _topo.activate(self.topology)

            # transposed [G, n] bin matrix: rows ride the 128-lane minor axis
            # for the histogram contraction (see ops/histogram.py).  Stored
            # uint8 when bins fit (the reference's narrow dense bins,
            # dense_bin.hpp / dense_nbits_bin.hpp): the matrix is re-read every
            # grower round, so width directly scales histogram HBM traffic;
            # the one-hot compare upcasts on the fly
            bin_dtype = np.uint8 if B <= 256 else np.int32
            if self._sparse_mask is not None:
                if cols_src is None:  # COO packing reads host columns
                    cols_src = train_data.bins
                    dev_src = None
                dense_idx = np.flatnonzero(~self._sparse_mask)
                sparse_idx_cols = np.flatnonzero(self._sparse_mask)
                gd = len(dense_idx)
                # the perfeature pallas kernel chunks its feature grid in
                # 32-multiples — align the DENSE matrix width; the sparse
                # groups never enter that kernel
                gd_pad = -(-gd // 32) * 32 if hist_impl == "pallas2" else gd
                width_sp = (self._local_width if self._partitioned
                            else self.n_pad)
                bins_t = np.zeros((gd_pad, width_sp), dtype=bin_dtype)
                bins_t[:gd, :n] = cols_src[:, dense_idx].T
                live = gd
                zb_np = meta_np["default_bin"]
                Gs = len(sparse_idx_cols)
                # ONE vectorized nonzero pass over the sparse columns,
                # column-blocked to bound the boolean temporary; entries
                # come out sorted by (slot, row), exactly the order the
                # per-column scans produced
                slot_parts, row_parts, bin_parts = [], [], []
                blk = max((1 << 28) // max(n, 1), 1)
                for lo_c in range(0, Gs, blk):
                    cols = sparse_idx_cols[lo_c:lo_c + blk]
                    sub = cols_src[:, cols]
                    g_i, r_i = np.nonzero((sub != zb_np[cols][None, :]).T)
                    slot_parts.append((g_i + lo_c).astype(np.int64))
                    row_parts.append(r_i.astype(np.int64))
                    bin_parts.append(sub[r_i, g_i].astype(np.int32))
                slot = (np.concatenate(slot_parts) if slot_parts
                        else np.zeros(0, np.int64))
                row_id = (np.concatenate(row_parts) if row_parts
                          else np.zeros(0, np.int64))
                binval = (np.concatenate(bin_parts) if bin_parts
                          else np.zeros(0, np.int32))
                # pad row-id = the (local) width (out of range: the
                # partition scatter drops it); pad bin = B (its one-hot row
                # is all-zero, so the clipped histogram gather contributes
                # nothing)
                if self.d_shards > 1:
                    # data sharding: per-SHARD tables with shard-local row
                    # ids — the leading axis shards over 'data' so each
                    # device holds only its block, and the sparse
                    # contraction psums like the dense one.  Partitioned
                    # ingest: this process's local rows cover exactly its
                    # own shards, so it builds [shards_local, Gs, M] and
                    # contributes them via put_local; the entry capacity M
                    # must still be the GLOBAL max.
                    rps = self.n_pad // self.d_shards
                    sl = (self.d_shards // jax.process_count()
                          if self._partitioned else self.d_shards)
                    shard = row_id // rps
                    key = shard * Gs + slot
                    counts = np.bincount(key, minlength=sl * Gs)
                    max_nnz = int(counts.max()) if counts.size else 0
                    if self._partitioned:
                        from ..parallel.topology import host_allgather

                        max_nnz = int(host_allgather(
                            np.asarray([max_nnz], np.int32),
                            name="sparse_table_width").max())
                    M = max(128, -(-max_nnz // 128) * 128)
                    sp_rows = np.full((sl, Gs, M), rps, np.int32)
                    sp_bins = np.full((sl, Gs, M), B, np.int32)
                    # stable sort by (shard, slot) keeps rows ascending
                    # within each table row, like the per-shard slices did
                    order = np.argsort(key, kind="stable")
                    k_s = key[order]
                    starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
                    pos = np.arange(len(k_s)) - starts[k_s]
                    sp_rows[shard[order], slot[order], pos] = \
                        row_id[order] - shard[order] * rps
                    sp_bins[shard[order], slot[order], pos] = binval[order]
                else:
                    counts = np.bincount(slot, minlength=Gs)
                    max_nnz = int(counts.max()) if counts.size else 0
                    M = max(128, -(-max_nnz // 128) * 128)
                    sp_rows = np.full((Gs, M), self.n_pad, np.int32)
                    sp_bins = np.full((Gs, M), B, np.int32)
                    starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
                    pos = np.arange(len(row_id)) - starts[slot]
                    sp_rows[slot, pos] = row_id
                    sp_bins[slot, pos] = binval
                F_ = self.num_features
                is_sparse = np.zeros(F_, np.int32)
                is_sparse[sparse_idx_cols] = 1
                sparse_slot = np.zeros(F_, np.int32)
                sparse_slot[sparse_idx_cols] = np.arange(Gs)
                dense_col = np.zeros(F_, np.int32)
                dense_col[dense_idx] = np.arange(gd)
                meta_np["is_sparse"] = is_sparse
                meta_np["sparse_slot"] = sparse_slot
                meta_np["dense_col"] = dense_col
                # a known-dense feature id: expand_sparse reads this
                # feature's histogram for exact leaf totals (padded by the
                # meta loop; only element 0 is read)
                meta_np["dense_ref"] = np.full(F_, dense_idx[0], np.int32)
                # feature -> slot in concat(dense columns, sparse groups);
                # padding features (g_pad > F) point at a dense padding
                # column — trivial (num_bin=1), never searched or split
                perm = np.full(self.g_pad, min(gd, gd_pad - 1), np.int32)
                perm[dense_idx] = np.arange(gd)
                perm[sparse_idx_cols] = gd_pad + np.arange(Gs)
                self._sparse_arrays = (sp_rows, sp_bins, perm)
                Log.info(f"sparse storage: {Gs} of {F_} features as COO "
                         f"({M} entry slots), dense matrix "
                         f"{gd_pad}x{self.n_pad}")
            else:
                self._sparse_arrays = None
                live = self.num_columns
                # partitioned: only this process's rows, at its local width
                width = self._local_width if self._partitioned else self.n_pad
                if (dev_src is not None and not self.stream_layout
                        and (strategy == "serial"
                             or jax.process_count() == 1)):
                    # device-side layout: transpose + pad the device-
                    # resident ingest matrix in HBM, on its shards under
                    # a parallel strategy — the host [n, F] matrix never
                    # exists on this path
                    bins_t = self._layout_device_bins(
                        dev_src, n, jnp.uint8 if B <= 256 else jnp.int32)
                else:
                    if cols_src is None:  # a mesh over processes ships host
                        cols_src = train_data.bins
                    bins_t = np.zeros((self.g_pad, width), dtype=bin_dtype)
                    bins_t[:self.num_columns, :n] = cols_src.T

            # what the histogram kernel is told to contract: the live
            # columns are the matrix's first `live`, the padding its tail.
            # Only the perfeature kernel reads the count; feature shards run
            # one program on slices whose live counts differ, so a grower
            # with a feature axis (of any size: data_feature on two devices
            # has one feature shard) keeps the full extent
            self.live_columns = (
                live if hist_impl == "pallas2"
                and self.strategy not in ("feature", "data_feature")
                else None)
            contracted = self.live_columns or bins_t.shape[0]
            for kind, count in (("live", contracted),
                                ("padding", bins_t.shape[0] - contracted)):
                obs.REGISTRY.set_gauge(
                    "lgbm_hist_columns", count, kind=kind,
                    help="bin-matrix columns the histogram kernel contracts "
                         "(live) and skips (padding)")
            obs.REGISTRY.set_gauge(
                "lgbm_hist_root_slots",
                1 if hist_impl == "pallas2" else 0,
                help="leaf slots of the root histogram pass (0: the xla "
                     "root scan has no slot axis)")

            # 4-bit packing (reference dense_nbits_bin.hpp): two rows per
            # byte in a per-block stride layout (row j low nibble, row
            # j + block/2 high nibble) so the pallas kernel unpacks with a
            # nibble mask + lane concat.  Halves the row sweep's DMA traffic.
            # the pack layout's blocks must coincide with the GROWER's blocks,
            # which are derived from the PER-SHARD row count under data
            # sharding — a global-block layout split across shards would
            # decode the wrong rows silently
            local_rows = self.n_pad // self.d_shards
            eff_block = min(block, local_rows)
            # the grid the perfeature kernel will run over this matrix at
            # the round loop's slot count, and how many of the one-hot rows
            # it builds any row can hit: the kernel's own arithmetic
            # (ops/histogram.perfeature_chunks, perfeature_columns_per_dot);
            # zeros off that kernel
            grid, hist_bins = (0, 0, 0, 0), (0, 0)
            if self.live_columns is not None:
                fblk, nf = perfeature_chunks(
                    bins_t.shape[0], B,
                    *self._kernel_slots_planes(config, precision),
                    bins_t.dtype.itemsize)
                grid = (nf, fblk, local_rows // eff_block,
                        perfeature_columns_per_dot(
                            B, eff_block, precision, fblk, self.live_columns))
                live_bins = (plan.num_bin if plan is not None
                             else meta_np["num_bin"]
                             if self._sparse_mask is None
                             else meta_np["num_bin"][~self._sparse_mask])
                hist_bins = (int(live_bins.sum()),
                             self.live_columns * (-(-B // 8) * 8))
            for axis, count in zip(("feature_chunks", "columns_per_chunk",
                                    "row_blocks", "columns_per_dot"), grid):
                obs.REGISTRY.set_gauge(
                    "lgbm_hist_grid", count, axis=axis,
                    help="grid of the perfeature histogram kernel: feature "
                         "chunks x row blocks, the columns in a chunk, and "
                         "the columns whose one-hots one dot stacks")
            for kind, count in zip(("live", "stored"), hist_bins):
                obs.REGISTRY.set_gauge(
                    "lgbm_hist_bins", count, kind=kind,
                    help="one-hot rows of the live columns: those a row "
                         "can hit (live, the columns' own bin counts) and "
                         "those the kernel builds (stored, bins padded to 8)")
            self.packed_bins = (
                bool(config.tpu_pack_bins) and B <= 16
                and not self.stream_layout
                and hist_impl == "pallas2" and plan is None
                and self._sparse_arrays is None and not self._partitioned
                and eff_block % 256 == 0 and local_rows % eff_block == 0)
            if self.packed_bins:
                x = bins_t.reshape(self.g_pad, self.n_pad // eff_block, 2,
                                   eff_block // 2)
                packed = (x[:, :, 0, :] | (x[:, :, 1, :] << 4)).reshape(
                    self.g_pad, self.n_pad // 2)
                # device-laid-out bins_t packs in HBM; host arrays keep the
                # contiguity the kernel's DMA expects
                bins_t = (np.ascontiguousarray(packed)
                          if isinstance(packed, np.ndarray) else packed)

            meta_host = {}
            for k, v in meta_np.items():
                pad_val = (1 if k == "num_bin"
                           else 1.0 if k == "penalty" else 0)
                if self.f_pad != self.num_features:
                    v = np.concatenate(
                        [v, np.full(self.f_pad - self.num_features, pad_val,
                                    dtype=v.dtype)])
                meta_host[k] = v

            if strategy == "serial":
                self._place_serial_bins(bins_t, n)
            else:
                if self._partitioned:
                    # each process contributes only ITS rows to the global
                    # arrays (reference pre_partition: rows never leave
                    # their machine)
                    self.bins_t = put_local(
                        bins_t, bins_sharding(self.mesh, strategy),
                        (bins_t.shape[0], self.n_pad))
                    ones = np.zeros(self._local_width, np.float32)
                    ones[:n] = 1.0
                    self._ones_host = ones
                    self._ones_mask = put_local(
                        ones, rows_sharding(self.mesh, strategy),
                        (self.n_pad,))
                else:
                    sharding = bins_sharding(self.mesh, strategy)
                    if isinstance(bins_t, np.ndarray):
                        with self._place_shards_span(bins_t, "host"):
                            self.bins_t = put_global(bins_t, sharding)
                    else:
                        # on its shards already (_layout_device_bins): a
                        # no-op unless the 4-bit packing reshaped it
                        self.bins_t = jax.device_put(bins_t, sharding)
                    ones = np.ones(self.n_pad, np.float32)
                    ones[n:] = 0.0
                    self._ones_host = ones
                    self._ones_mask = put_global(
                        ones, rows_sharding(self.mesh, strategy))
            self.n = n

            meta_cast = {k: (v.astype(np.int32) if v.dtype != np.float32
                             else v)
                         for k, v in meta_host.items()}
            # multi-host mesh: every array entering the sharded grower must be
            # a GLOBAL jax.Array; cache the shardings train() re-uses per tree
            self._multiproc = self.mesh is not None and jax.process_count() > 1
            # traced mode switches (ops/grower.py MF_*): the real boolean/
            # scalar mode values ride this meta vector so ONE compiled grow
            # program serves every combination; the GrowerParams fields they
            # replace are canonicalized out of the grower cache key below
            meta_cast["mode_flags"] = mode_flags_np(
                quant_round=str(config.tpu_quant_round),
                quant_refit=(quantized
                             and bool(config.tpu_quant_refit_leaves)),
                cegb_tradeoff=float(config.cegb_tradeoff),
                cegb_penalty_split=float(config.cegb_penalty_split))
            if self.mesh is not None:
                # replicated over the mesh once, here: an array left on
                # one device would be copied to the others at every call
                # of the sharded programs
                self._rep_sharding = NamedSharding(self.mesh, P())
                self._rows_shard = rows_sharding(self.mesh, strategy)
                self.meta = {k: put_global(v, self._rep_sharding)
                             for k, v in meta_cast.items()}
            else:
                self.meta = {k: jnp.asarray(v) for k, v in meta_cast.items()}
            if self._sparse_arrays is not None:
                # COO tables ride meta like the CEGB state does (the pad
                # loop above only handles per-feature vectors).  Data-
                # sharded learners shard the per-shard leading axis at
                # placement so no replicated->sharded reshard crosses the
                # program boundary (the CPU gloo backend aborts on those)
                sp_rows, sp_bins, perm = self._sparse_arrays
                if self.mesh is not None:
                    shard3 = NamedSharding(self.mesh, P(ROW_AXES))
                    if self._partitioned:
                        # this process built only ITS shards' tables
                        gshape = (self.d_shards,) + sp_rows.shape[1:]
                        self.meta["sparse_idx"] = put_local(sp_rows, shard3,
                                                            gshape)
                        self.meta["sparse_bin"] = put_local(sp_bins, shard3,
                                                            gshape)
                    else:
                        self.meta["sparse_idx"] = put_global(sp_rows, shard3)
                        self.meta["sparse_bin"] = put_global(sp_bins, shard3)
                    self.meta["hist_perm"] = put_global(perm,
                                                        self._rep_sharding)
                else:
                    self.meta["sparse_idx"] = jnp.asarray(sp_rows)
                    self.meta["sparse_bin"] = jnp.asarray(sp_bins)
                    self.meta["hist_perm"] = jnp.asarray(perm)
            if self.hist_agg == "scatter" and plan is not None:
                # static shard -> feature-ids table for the scattered EFB
                # search: shard d owns bundle columns [d*SGc, (d+1)*SGc) and
                # therefore exactly the features bundled into them.  Rows are
                # ascending (so the per-shard argmax keeps the lowest-feature
                # tie-break) and -1-padded to the widest shard's count.
                sgc = self.g_pad // self.d_shards
                bidx = meta_np["bundle_idx"][:self.num_features]
                by_shard = [np.sort(np.flatnonzero(bidx // sgc == d))
                            for d in range(self.d_shards)]
                sf = np.full((self.d_shards,
                              max(1, max(len(l) for l in by_shard))), -1,
                             np.int32)
                for d, l in enumerate(by_shard):
                    sf[d, :len(l)] = l
                self.meta["scatter_feat"] = (
                    put_global(sf, self._rep_sharding)
                    if self.mesh is not None else jnp.asarray(sf))

        self.params = GrowerParams(
            num_leaves=max(int(config.num_leaves), 2),
            num_bins=B,
            block_rows=min(block, self.n_pad // self.d_shards
                           if self.d_shards > 1 else self.n_pad),
            precision=precision,
            l1=float(config.lambda_l1),
            l2=float(config.lambda_l2),
            max_delta_step=float(config.max_delta_step),
            min_data_in_leaf=float(config.min_data_in_leaf),
            min_sum_hessian=float(config.min_sum_hessian_in_leaf),
            min_gain_to_split=float(config.min_gain_to_split),
            max_depth=int(config.max_depth),
            has_cat=bool(meta_np["is_categorical"].any()),
            max_cat_threshold=int(config.max_cat_threshold),
            cat_l2=float(config.cat_l2),
            cat_smooth=float(config.cat_smooth),
            max_cat_to_onehot=int(config.max_cat_to_onehot),
            min_data_per_group=float(config.min_data_per_group),
            split_batch=resolve_split_batch(int(config.tpu_split_batch),
                                            int(config.num_leaves)),
            split_batch_alpha=float(config.tpu_split_batch_alpha),
            feature_fraction_bynode=float(config.feature_fraction_bynode),
            has_cegb=has_cegb,
            has_cegb_lazy=has_cegb_lazy,
            cegb_tradeoff=float(config.cegb_tradeoff),
            cegb_penalty_split=float(config.cegb_penalty_split),
            forced=forced,
            hist_impl=hist_impl,
            partition_impl=self._resolve_partition_impl(
                config,
                dense_unpacked=not (meta_np["is_categorical"].any()
                                    or plan is not None
                                    or self._sparse_arrays is not None
                                    or self.packed_bins
                                    # the streamed grower has its own
                                    or self.stream_layout),
                shard_rows=self.n_pad // self.d_shards,
                columns=self.g_pad, itemsize=1 if B <= 256 else 4),
            has_bundles=plan is not None,
            has_sparse=self._sparse_arrays is not None,
            packed_bins=self.packed_bins,
            ramp=bool(config.tpu_ramp),
            quant_round=str(config.tpu_quant_round),
            quant_refit=(quantized
                         and bool(config.tpu_quant_refit_leaves)),
            # the bucket policy's compile-time lever on the grow program:
            # "wide" ramps the frontier pre-rounds x4 (half the unrolled
            # rounds, bit-identical trees)
            ramp_step=(4 if str(config.tpu_bucket_policy) == "wide"
                       else 2),
            hist_agg=self.hist_agg,
        )
        # quantized leaf refit: the driver must fetch out["leaf_output"]
        # and override the record-replayed leaf values at tree build
        self.refits_leaves = self.params.quant_refit
        if has_cegb_lazy and strategy != "serial":
            # the reference's lazy bitset is learner-local over the full
            # data; under row sharding the paid matrix would need its own
            # collective — reject loudly until that exists
            raise NotImplementedError(
                "cegb_penalty_feature_lazy requires tree_learner=serial")
        # cross-tree CEGB state (reference is_feature_used_in_split_ /
        # feature_used_in_data_ live for the learner's lifetime,
        # cost_effective_gradient_boosting.hpp:33-48)
        if has_cegb:
            zeros_f = np.zeros(self.f_pad, np.float32)
            self._cegb_used = (put_global(zeros_f, self._rep_sharding)
                               if self.mesh is not None
                               else jnp.asarray(zeros_f))
            self.meta["cegb_used"] = self._cegb_used
            if has_cegb_lazy:
                # bool storage: the reference's bitset is n*F/8 bytes;
                # bool is 8x that but 4x smaller than f32, and the einsum
                # casts per round transiently
                self._cegb_paid = jnp.zeros((self.f_pad, self.n_pad),
                                            jnp.bool_)
                self.meta["cegb_paid"] = self._cegb_paid
        # buffer donation (tpu_donate_buffers): the grower's histogram
        # pool and the step's score buffers are donated to XLA so they
        # are rewritten in place across iterations.  Multi-process runs
        # keep donation off (global-array donation across the gloo CPU
        # test backend is unvalidated); voting keeps its pool shard-LOCAL
        # so only the score buffers donate there.
        self._donate = (bool(config.tpu_donate_buffers)
                        and not self._multiproc)
        self._external_pool = self._donate and strategy != "voting"
        if self._external_pool:
            shape = (self.params.num_leaves, self.g_pad, B, 3)
            pdt = jnp.dtype(pool_dtype(precision))
            sharding = None
            if self.mesh is not None:
                sharding = NamedSharding(self.mesh, pool_partition_spec(
                    strategy, self.hist_agg == "scatter"))
            self._pool_spec = (shape, pdt, sharding)
        else:
            self._pool_spec = None
        self.reset_pool()
        # the grower cache key is the CANONICAL params (the mode-flag-
        # folded fields normalized away): every run whose structural axes
        # match reuses one grow program, whatever its mode values
        self.grow = make_strategy_grower(
            canonical_params(self.params), self.f_pad, strategy, self.mesh,
            voting_k=int(config.top_k), num_columns=self.g_pad,
            external_pool=self._external_pool,
            live_columns=self.live_columns)
        self._feature_rng = np.random.default_rng(int(config.feature_fraction_seed))
        self._note_exchange()
        self._note_partition()
        # running sums of the trees' `hist_rows` over the shards, and trees
        self._hist_rows = np.zeros(3, np.int64)
        self._hist_trees = 0

    def reset_pool(self) -> None:
        """(Re)create the donated histogram-pool buffer as zeros.

        The pool MUST be XLA-owned (jnp.zeros, never
        jnp.asarray(np.zeros(...))): on the CPU backend a device_put of
        aligned host memory is ZERO-COPY — the buffer aliases
        numpy-owned pages, and donating it lets XLA rewrite/free memory
        it does not own (intermittent, alignment-dependent heap
        corruption; reproduced on jaxlib 0.4.x).

        Also the recovery path after a failed DONATING dispatch consumed
        the threaded buffer (gbdt._iter_restore): the pool is
        per-iteration scratch that the grower rewrites wholesale, so a
        zeros replacement is bit-equivalent."""
        if self._pool_spec is None:
            self._pool = None
            return
        shape, pdt, sharding = self._pool_spec
        self._pool = (jnp.zeros(shape, pdt, device=sharding)
                      if sharding is not None else jnp.zeros(shape, pdt))

    def _place_serial_bins(self, bins_t, n: int) -> None:
        """Place the serial-layout transposed bin matrix.

        The resident default commits the whole [g_pad, n_pad] matrix to
        device memory; StreamedTreeLearner overrides this to keep it
        host-resident as fixed-size row blocks (ops/stream.py)."""
        self.bins_t = jnp.asarray(bins_t)
        self._ones_mask = jnp.ones(self.n_pad, jnp.float32).at[n:].set(0.0)

    def _place_shards_span(self, bins_t, source: str):
        """The span around the bin matrix's way onto its shards."""
        return obs.span("place_shards", source=source,
                        bytes=int(bins_t.size) * bins_t.dtype.itemsize,
                        devices=int(self.mesh.size))

    def _layout_device_bins(self, dev_src, n: int, dtype):
        """The transposed, padded [g_pad, n_pad] bin matrix from the
        device-resident [n, F] ingest matrix, built in HBM.  Under a
        parallel strategy it is built SHARD BY SHARD: each chip is handed
        the rows and columns of its own block (already there where ingest
        dealt the rows to the chips, ops/binning.RowParts; chip to chip
        otherwise) and pads and transposes that block alone, so no chip
        holds more of the table than it trains on."""
        from ..ops.binning import RowParts

        cols, shape = self.num_columns, (self.g_pad, self.n_pad)
        src = RowParts.of(dev_src)
        if self.mesh is None:
            return jnp.zeros(shape, dtype).at[:cols, :n].set(
                src.gathered().T.astype(dtype))
        sharding = bins_sharding(self.mesh, self.strategy)

        def lay(pieces, block):
            # a chip's block of the matrix from the pieces that hold its
            # rows and columns of the table
            x = jnp.concatenate(pieces, axis=0)
            return jnp.zeros(block, dtype).at[
                :x.shape[1], :x.shape[0]].set(x.T.astype(dtype))

        lay = ledger_jit(lay, site="learner.layout", static_argnums=1)

        with self._place_shards_span(jax.ShapeDtypeStruct(shape, dtype),
                                     "device"):
            shards = []
            for dev, (cs, rs) in sharding.addressable_devices_indices_map(
                    shape).items():
                (c0, c1, _), (r0, r1, _) = (cs.indices(shape[0]),
                                            rs.indices(shape[1]))
                pieces = (src.rows(r0, min(r1, n), dev,
                                   slice(c0, min(c1, cols)))
                          if c0 < cols else [])
                block = (c1 - c0, r1 - r0)
                shards.append(lay(pieces, block) if pieces else
                              jnp.zeros(block, dtype, device=dev))
            return jax.make_array_from_single_device_arrays(
                shape, sharding, shards)

    def place_rows(self, v) -> jnp.ndarray:
        """A per-row array ([..., n], or already [..., n_pad]) as the
        step's programs take it: the row axis padded with zeros to n_pad
        and sharded like the grower's row vectors."""
        v = jnp.asarray(v)
        pad = self.n_pad - v.shape[-1]
        if pad:
            v = jnp.pad(v, [(0, 0)] * (v.ndim - 1) + [(0, pad)])
        if self.mesh is None:
            return v
        return jax.device_put(v, self._rows_sharding(v.ndim))

    def _rows_sharding(self, ndim: int = 1) -> NamedSharding:
        """Sharding of an array whose LAST axis is the padded row axis."""
        return NamedSharding(self.mesh, P(
            *(None,) * (ndim - 1),
            *rows_sharding(self.mesh, self.strategy).spec))

    def _note_exchange(self) -> None:
        """Gauges of the data axis, from the shapes the programs run."""
        rps = self.n_pad // self.d_shards
        obs.REGISTRY.set_gauge(
            "lgbm_data_shards", self.d_shards,
            help="row shards of the binned table (1: no data axis)")
        for k in range(self.d_shards):
            obs.REGISTRY.set_gauge(
                "lgbm_shard_rows", rps, shard=str(k),
                help="rows of the padded row axis a shard's programs sweep")
            if not self._partitioned:
                # rows [0, n) are the table's and the padding is the tail,
                # so the last shards hold it all
                obs.REGISTRY.set_gauge(
                    "lgbm_shard_table_rows",
                    min(max(self.n - k * rps, 0), rps), shard=str(k),
                    help="of a shard's rows, those of the table")
        for mode in ("psum", "scatter"):
            obs.REGISTRY.set_gauge(
                "lgbm_hist_agg", int(self.d_shards > 1
                                     and self.hist_agg == mode), mode=mode,
                help="how histograms cross the data axis (1: the mode in "
                     "force; both 0 without a data axis)")
        p = self.params
        per_op = exchange_bytes_per_tree(
            tree_hist_slots(p.num_leaves, p.split_batch, p.ramp,
                            p.ramp_step),
            self.g_pad // self.f_shards, p.num_bins,
            jnp.dtype(pool_dtype(p.precision)).itemsize,
            self.hist_agg == "scatter", p.num_bins if p.has_cat else 1)
        for op, nbytes in per_op.items():
            obs.REGISTRY.set_gauge(
                "lgbm_exchange_bytes_per_tree",
                nbytes if self.d_shards > 1 and self.strategy != "voting"
                else 0, op=op,
                help="bytes a row shard hands to the data axis's "
                     "collectives per tree (parallel/mesh.py "
                     "exchange_bytes_per_tree; voting exchanges voted "
                     "columns only and is not modelled)")

    def _note_partition(self) -> None:
        """`lgbm_partition_passes_per_tree{impl=}`: sweeps of the leaf ids
        the grow program's row partition makes while it grows one tree,
        from the round widths it was built with: one per split slot under
        `select`, one per round under `kernel`; 0 on the lowering not in
        force."""
        p = self.params
        rounds = tree_hist_slots(p.num_leaves, p.split_batch, p.ramp,
                                 p.ramp_step)[1:]   # the root splits nothing
        for impl in ("select", "kernel"):
            passes = sum(rounds) if impl == "select" else len(rounds)
            obs.REGISTRY.set_gauge(
                "lgbm_partition_passes_per_tree",
                passes if impl == p.partition_impl else 0, impl=impl,
                help="sweeps of the leaf ids the row partition makes per "
                     "tree when every round splits all it can (0: the "
                     "lowering is not in force)")

    def note_hist_rows(self, hist_rows) -> None:
        """`lgbm_hist_rows_per_tree{kind=}` from one more tree's fetched
        `hist_rows` (a row a shard, `ops/grower.py` HIST_ROWS_*), the mean
        over the trees this learner has grown, summed over the shards:
        rows its histogram calls swept (calls x a shard's padded rows),
        rows they contracted (sub-blocks run x their rows) and rows that
        were live (their leaf one of a call's slots).  Set where the host
        reads a tree, from what came with its records."""
        self._hist_rows += np.asarray(hist_rows, np.int64).sum(axis=0)
        self._hist_trees += 1
        shard_rows = self.n_pad // self.d_shards
        block, _ = row_blocks(shard_rows, self.params.block_rows)
        per_unit = {"swept": (HIST_ROWS_CALLS, shard_rows),
                    "contracted": (HIST_ROWS_CONTRACTED,
                                   perfeature_dot_lanes(block)),
                    "live": (HIST_ROWS_LIVE, 1)}
        for kind, (column, rows) in per_unit.items():
            obs.REGISTRY.set_gauge(
                "lgbm_hist_rows_per_tree",
                int(self._hist_rows[column]) * rows / self._hist_trees,
                kind=kind,
                help="table rows per tree, all shards: swept by the "
                     "histogram calls, contracted by them, and live (in a "
                     "leaf the call histograms)")

    # ------------------------------------------------------------------
    @staticmethod
    def _resolve_partition_impl(config: Config, dense_unpacked: bool,
                                shard_rows: int, columns: int,
                                itemsize: int) -> str:
        """Resolve tpu_partition_impl, honoring "auto": a rule over what
        the code can observe, like `_resolve_hist_impl`'s.  The Pallas
        pass ("kernel", ops/partition.py: one sweep of the leaf ids a
        round) on a TPU when the table is dense numerical and unpacked (no
        categorical feature, EFB bundle, sparse column or 4-bit packing)
        and its shapes give the kernel whole registers and a narrow row
        (`partition_kernel_fits`), at any tree_learner: inside shard_map
        each shard partitions its own rows.  "select" (one XLA pass per
        split) everywhere else, CPU included."""
        impl = str(config.tpu_partition_impl)
        if impl != "auto":
            return impl
        on_tpu = jax.devices()[0].platform == "tpu"
        return ("kernel" if on_tpu and dense_unpacked
                and partition_kernel_fits(shard_rows, columns, itemsize)
                else "select")

    @staticmethod
    def _resolve_hist_agg(config: Config, strategy: str,
                          d_shards: int) -> str:
        """Effective data-axis histogram aggregation: 'psum' | 'scatter'.

        tpu_hist_agg=auto picks scatter whenever the data axis spans more
        than one device: the reduce-scatter moves half the psum's ICI
        receive bytes, the per-shard histogram pool shrinks by the data-
        shard factor, and the split search stops being repeated P times —
        with int8/int16 decisions bit-identical to psum (associative
        int32 sums + the shared tie-break).  Everywhere without a real
        data axis (serial, pure feature sharding, one data shard) the
        collective degenerates and psum is the plain path."""
        if strategy in ("data", "voting", "data_feature") and d_shards > 1:
            return ("scatter" if str(config.tpu_hist_agg)
                    in ("auto", "scatter") else "psum")
        return "psum"

    @staticmethod
    def _kernel_slots_planes(config: Config, precision: str) -> Tuple[int, int]:
        """(leaf slots of the round loop's histogram call, statistic planes):
        the lane axis of the perfeature kernel's accumulator."""
        leaves = max(int(config.num_leaves), 2)
        k = min(resolve_split_batch(int(config.tpu_split_batch), leaves),
                leaves - 1)  # the grower's own clamp (make_grower)
        return k, 5 if precision == "hilo" else 3

    @staticmethod
    def _resolve_hist_impl(config: Config, num_bins: int, precision: str
                           ) -> Tuple[str, int]:
        """Resolve (tpu_hist_impl, tpu_block_rows), honoring "auto"/0.

        Auto is a rule over what the code can observe, never the outcome
        of running a kernel: the perfeature pallas kernel ("pallas2") on a
        TPU at hilo/bf16/int8 — its largest VMEM temporary is a [Bp, block]
        one-hot, so multi-k-row blocks fit and the kernel self-chunks the
        feature axis when the accumulator would overflow — and the xla
        scan everywhere else: CPU, f32/f64, int16, bin counts too tall for
        even the minimum dtype-tile-wide feature chunk (32 features for
        uint8 bins, 8 for int32), or an explicit row block the kernel's
        grid cannot take.
        int16 "pallas2" is explicit-only.
        """
        impl = str(config.tpu_hist_impl)
        block = int(config.tpu_block_rows)
        if impl == "auto":
            from ..ops.histogram import (PERFEATURE_AUTO_PRECISIONS,
                                         perfeature_chunk_fits)

            # smallest feature chunk the kernel can retreat to: the
            # sublane tile of the bins dtype (uint8 for <=256 bins, else
            # int32 — learner.py bin_dtype / perfeature_chunks' step
            # table), whose accumulator block must fit the budget; the
            # learner's 32-multiple column pad keeps either divisible
            chunk_fits = perfeature_chunk_fits(
                32 if num_bins <= 256 else 8, num_bins,
                *TPUTreeLearner._kernel_slots_planes(config, precision))
            # an explicit row block must stay Mosaic-lane-aligned for the
            # kernel's [.., block] grid specs; the [Bp, block] one-hot and
            # [K*S, block] expanded stats scale with the block (the kernel
            # sizes its own vmem_limit_bytes from them), and 16384 is the
            # largest block compiled and run on a chip at every precision
            # this rule offers; larger blocks ride the xla scan
            block_ok = block <= 0 or (block % 128 == 0 and block <= 16384)
            on_tpu = jax.devices()[0].platform == "tpu"
            impl = ("pallas2" if on_tpu and chunk_fits and block_ok
                    and precision in PERFEATURE_AUTO_PRECISIONS else "xla")
        if block <= 0:
            block = 8192 if impl == "pallas2" else 16384
        return impl, block

    @staticmethod
    def _resolve_precision(config: Config) -> str:
        """Histogram precision, honoring deterministic mode.

        deterministic=true accumulates everything in f64 (the reference's
        HistogramBinEntry representation, bin.h:33-40) so serial and
        data-parallel decisions agree exactly; requires jax x64, which is
        enabled here process-wide.  The quantized precisions (int8/int16)
        are ALREADY reduction-order invariant — int32 sums are associative
        — so deterministic=true keeps them as-is at full speed instead of
        forcing the slow f64 path (the recommended deterministic mode)."""
        precision = str(config.tpu_hist_precision)
        if not bool(config.deterministic):
            return precision
        if precision in ("int8", "int16"):
            return precision
        jax.config.update("jax_enable_x64", True)
        if str(config.tpu_hist_impl) == "pallas2":
            raise ValueError(
                "deterministic=true requires tpu_hist_impl=xla")
        return "f64"

    @staticmethod
    def _parse_forced_splits(config: Config, train_data: TrainingData
                             ) -> tuple:
        """forcedsplits_filename JSON -> static BFS (parent_leaf, feature,
        thr_bin) triples for the grower (reference ForceSplits reads the
        same nested {feature, threshold, left, right} JSON,
        serial_tree_learner.cpp:617-669)."""
        path = str(config.forcedsplits_filename or "")
        if not path:
            return ()
        import json

        with open(path) as f:
            root = json.load(f)
        pos_of = {col: j for j, col in enumerate(train_data.used_feature_idx)}
        out = []
        queue = [(root, 0)]
        while queue and len(out) < max(int(config.num_leaves) - 1, 0):
            node, leaf = queue.pop(0)
            real_f = int(node["feature"])
            if real_f not in pos_of:
                raise ValueError(
                    f"forced split on unused/trivial feature {real_f}")
            inner = pos_of[real_f]
            mapper = train_data.mappers[real_f]
            from ..io.bin_mapper import BinType

            if mapper.bin_type != BinType.NUMERICAL:
                raise NotImplementedError(
                    "forced splits on categorical features are not "
                    "supported")
            thr_bin = int(mapper.value_to_bin(float(node["threshold"])))
            i = len(out)
            out.append((leaf, inner, thr_bin))
            # left child keeps the parent's leaf id; right child is the
            # (i+1)-th leaf created (the grower's record/new-leaf contract)
            if isinstance(node.get("left"), dict) and "feature" in node["left"]:
                queue.append((node["left"], leaf))
            if isinstance(node.get("right"), dict) and "feature" in node["right"]:
                queue.append((node["right"], i + 1))
        return tuple(out)

    def sample_features(self) -> jnp.ndarray:
        """Per-tree feature_fraction mask (reference GetUsedFeatures,
        serial_tree_learner.cpp:271-319).  Sized to the padded feature axis;
        padding features stay masked off."""
        frac = float(self.config.feature_fraction)
        F = self.num_features
        mask = np.zeros(self.f_pad, np.float32)
        if frac < 1.0:
            k = max(1, int(np.ceil(F * frac)))
            used = self._feature_rng.choice(F, size=k, replace=False)
            mask[used] = 1.0
        else:
            mask[:F] = 1.0
        return jnp.asarray(mask)

    def pad_vector(self, v: jnp.ndarray) -> jnp.ndarray:
        if v.shape[0] == self.n_pad:
            return v
        return jnp.zeros(self.n_pad, v.dtype).at[:v.shape[0]].set(v)

    # ------------------------------------------------------------------
    def make_train_step(self, objective, learning_rate: float,
                        bagging: Optional[Dict] = None,
                        goss: Optional[Dict] = None):
        """The device step of one iteration for EVERY tree_learner: three
        programs, gradients (`learner.pre`) -> the strategy's grower ->
        score update (`learner.post`), dispatched back to back; on a data
        axis a fourth between the last two, the all-gather of the leaf ids
        (`learner.gather`).

        A host<->device round trip per tree would leave the device idle,
        so the driver dispatches asynchronously and never syncs on the hot
        path: RNG keys thread through device state, bagging and
        feature-fraction masks are sampled on device, and the only per-tree
        artifact is the packed [L-1, 15] record array (fetched lazily).

        Nothing with a row axis and nothing that differs by data set is
        closed over: the bin matrix, the row mask, `meta` and the
        objective's per-row arrays (`objective.row_arrays()`: labels,
        weights) are ARGUMENTS, padded to n_pad and on the strategy's row
        sharding, and so is what else of the objective differs by data set
        (`objective.layout_arrays()`: a ranking objective's queries, under
        `rows["layout"]`), so a program's cache key holds shapes and not a table
        (`lgbm_step_row_constant_bytes` says so, program by program), and
        the grower stays the one bucketed program every Booster of a shape
        shares.  pre's outputs leave on the sharding the grower's inputs
        have, so nothing is resharded between programs.

        objective: supplies `gradients(scores [k, n_pad], rows) ->
        (grad, hess)`, a pure device function of its per-row arrays.
        Returns step(grad_scores, scores, key, bag_key, pool, class_id,
        refresh_bag, goss_on) -> (records, new_scores, leaf_ids [n_pad],
        leaf_output, new_key, new_bag_key, pool).
        """
        n, n_pad = self.n, self.n_pad
        frac = 1.0 if bagging is None else bagging.get("fraction", 1.0)
        pos_frac = 1.0 if bagging is None else bagging.get("pos_fraction", 1.0)
        neg_frac = 1.0 if bagging is None else bagging.get("neg_fraction", 1.0)
        rows = {k: self.place_rows(v)
                for k, v in objective.row_arrays().items()}
        layout = objective.layout_arrays()
        if layout:
            # what differs by data set and has no row axis (a ranking
            # objective's queries): whole on every device, an argument too
            rows["layout"] = jax.tree.map(
                jnp.asarray if self.mesh is None else
                lambda v: jax.device_put(v, self._rep_sharding), layout)
        if bagging is not None and (pos_frac < 1.0 or neg_frac < 1.0):
            rows["is_pos"] = self.place_rows(bagging["is_pos"])
        feature_frac = float(self.config.feature_fraction)
        F = self.num_features
        f_pad = self.f_pad

        goss_top_k = goss_other_k = 0
        if goss is not None:
            goss_top_k = max(1, int(n * float(goss["top_rate"])))
            goss_other_k = max(1, int(n * float(goss["other_rate"])))

        def _pre(grad_scores, rows, valid, key, bag_key, class_id,
                 refresh_bag, goss_on):
            # grad_scores = scores at ITERATION start: all classes' gradients
            # come from the same snapshot, like the reference's single
            # Boosting() call per iteration (gbdt.cpp:150-158); `scores`
            # accumulates the per-class deltas within the iteration.
            # class_id and refresh_bag are TRACED (shape-stability: one
            # compiled step serves every class and both sides of the
            # bagging_freq boundary — previously each was a static key
            # multiplying the program count)
            # named_scope: the host-span vocabulary (boost / bagging /
            # score_update) mirrored into xprof device traces
            rows = dict(rows)
            is_pos = rows.pop("is_pos", None)
            live = valid > 0
            with jax.named_scope("boost"):
                # the scores take the row vectors' padding (and, on a
                # mesh, their sharding: every shard computes its own
                # rows' gradients); a padding row's gradient is whatever
                # zeros give and is zeroed below
                padded = jnp.pad(grad_scores, ((0, 0), (0, n_pad - n)))
                if self.mesh is not None:
                    padded = jax.lax.with_sharding_constraint(
                        padded, self._rows_sharding(2))
                grad, hess = objective.gradients(padded, rows)
            g = grad[class_id] if grad.ndim == 2 else grad
            h = hess[class_id] if hess.ndim == 2 else hess
            g = jnp.where(live, g, 0.0).astype(jnp.float32)
            h = jnp.where(live, h, 0.0).astype(jnp.float32)

            key, kf = jax.random.split(key)
            bag_key = jnp.where(jnp.asarray(refresh_bag),
                                jax.random.split(bag_key)[0], bag_key)

            def bag_uniform(k, salt):
                # per-row uniforms keyed on the GLOBAL row index (PCG
                # hash, like the quantization rounding) — NOT
                # jax.random.uniform(k, (n_pad,)), whose threefry
                # counters pair across array halves so every value
                # changes with the total padded length.  n_pad differs
                # between serial and sharded layouts (per-shard padding),
                # which made bagging masks topology-dependent and broke
                # the cross-shard bitwise contract (ROADMAP item 7).
                # Precondition: iota == global row index, which holds
                # because this step only exists single-process
                # (_maybe_make_train_step gates on not _multiproc) and
                # the single-process layout is compact-at-front (rows
                # [0, n) contiguous, padding only at the tail) — the
                # partitioned multihost layout with interior per-host
                # padding rides the sync path's host-global numpy mask
                sa, sb = key_words(k)
                return hashed_uniform(
                    jax.lax.iota(jnp.uint32, n_pad), sa, sb, salt)

            mask = valid
            if goss_on:
                # GOSS on device (reference goss.hpp:91-139 BaggingHelper):
                # keep the top_rate rows by sum_k |g*h|, Bernoulli-sample
                # other_rate of the rest and upscale their grad/hess by
                # (n - top_k) / other_k.  The reference samples exactly
                # other_k without replacement; the Bernoulli form has the
                # same expectation and is XLA-friendly.
                if grad.ndim == 2:
                    gh_all = jnp.sum(jnp.abs(grad * hess), axis=0)
                else:
                    gh_all = jnp.abs(grad * hess)
                gh = jnp.where(live, gh_all, -1.0).astype(jnp.float32)
                thr = jnp.sort(gh)[n_pad - goss_top_k]
                keep_top = gh >= thr
                bag_key = jax.random.split(bag_key)[0]
                r = bag_uniform(bag_key, 0x60553)
                p_other = goss_other_k / max(n - goss_top_k, 1)
                keep_other = (~keep_top) & (r < p_other)
                multiply = (n - goss_top_k) / goss_other_k
                scale = jnp.where(keep_other, multiply, 1.0)
                g = g * scale
                h = h * scale
                mask = mask * (keep_top | keep_other).astype(jnp.float32)
            elif is_pos is not None:
                r = bag_uniform(bag_key, 0xBA66)
                keep = jnp.where(is_pos, r < pos_frac, r < neg_frac)
                mask = mask * keep.astype(jnp.float32)
            elif frac < 1.0:
                r = bag_uniform(bag_key, 0xBA66)
                mask = mask * (r < frac).astype(jnp.float32)
            fmask = jnp.zeros(f_pad, jnp.float32).at[:F].set(1.0)
            if feature_frac < 1.0:
                k_used = max(1, int(np.ceil(F * feature_frac)))
                perm = jax.random.permutation(kf, F)
                fmask = jnp.zeros(f_pad, jnp.float32).at[perm[:k_used]].set(1.0)

            key, k_node = jax.random.split(key)
            return g, h, mask, fmask, k_node, key, bag_key

        def _post(scores, records, leaf_ids, leaf_output, class_id):
            with jax.named_scope("score_update"):
                any_split = records[0, 14] > 0.5  # REC_DID_SPLIT
                # scale the [L] leaf vector FIRST, then look up: the
                # per-row path is lookup + ONE correctly-rounded add.
                # The per-row `leaf_output[ids] * lr + scores` form left
                # a mul+add chain that XLA/LLVM may (or may not)
                # contract into an FMA depending on the surrounding
                # program — serial and shard_map programs contracted
                # differently, drifting scores one ulp apart at the
                # SAME trees and breaking the cross-topology bitwise
                # contract (ROADMAP item 7's second root cause).  The
                # one-hot lookup (a TPU's) keeps that to the bit; under
                # the gather XLA:CPU fuses this multiply back into the
                # per-row loop and contracts it with the add, barrier or
                # not, alike in every topology (tests/test_score_lookup.py)
                scaled = jnp.where(any_split,
                                   leaf_output * learning_rate, 0.0)
                return scores.at[class_id, :].add(
                    lookup(scaled, leaf_ids[:n]))

        # the grower's own ledgered jit donates the pool, post the scores
        # buffer.  The scores are [k, n] with no row padding, so on a mesh
        # they are replicated (n need not divide by the shards) and post
        # takes the leaf ids gathered (`gather_j` below).  pre and post
        # are closures of this Booster (the objective's scalars, the
        # bagging rates), traced once per Booster; what they compile to
        # depends on shapes alone, so the persistent cache answers a new
        # data set of the same shape.  Only goss_on stays static (its
        # sort is structural work).
        shard_kw_pre, shard_kw_post = {}, {}
        if self.mesh is not None:
            row, rep = self._rows_sharding(), self._rep_sharding
            shard_kw_pre["out_shardings"] = (row, row, row, rep, rep, rep,
                                             rep)
            shard_kw_post["out_shardings"] = rep
        pre_j = ledger_jit(_pre, site="learner.pre",
                           static_argnames=("goss_on",), **shard_kw_pre)
        post_j = ledger_jit(_post, site="learner.post",
                            donate_argnums=((0,) if self._donate else ()),
                            **shard_kw_post)
        # row-sharded leaf ids reach post through an all-gather of their
        # own program: post then runs replicated, on replicated operands,
        # so every device runs the serial learner's post and rounds as it
        # does (a post partitioned over sharded ids fuses its multiply
        # and add otherwise: scores an ulp apart at the same trees).  A
        # `device_put` to the replicated sharding would do the same
        # through the host, and wait for the grower first
        gather_j = None
        if self.d_shards > 1:
            gather_j = ledger_jit(lambda ids: ids, site="learner.gather",
                                  out_shardings=self._rep_sharding)

        def step(grad_scores, scores, key, bag_key, pool, class_id,
                 refresh_bag, goss_on=False):
            # `pool` is the donated histogram-pool buffer (None when
            # donation is off): grow rewrites it in place and the caller
            # threads the returned buffer into the next call
            if self.mesh is not None:
                # the caller's first scores and keys sit on one device; the
                # programs hand them back replicated, after which these
                # puts move nothing, and every iteration runs the programs
                # the first one compiled
                grad_scores, scores, key, bag_key = (
                    jax.device_put(x, self._rep_sharding)
                    for x in (grad_scores, scores, key, bag_key))
            g, h, mask, fmask, k_node, key, bag_key = pre_j(
                grad_scores, rows, self._ones_mask, key, bag_key,
                class_id=class_id, refresh_bag=refresh_bag,
                goss_on=goss_on)
            if self._external_pool:
                out = self.grow(self.bins_t, g, h, mask, fmask, self.meta,
                                k_node, pool)
                pool = out["pool"]
            else:
                out = self.grow(self.bins_t, g, h, mask, fmask, self.meta,
                                k_node)
            ids = out["leaf_ids"]
            if gather_j is not None:
                ids = gather_j(ids)
            new_scores = post_j(scores, out["records"], ids,
                                out["leaf_output"], class_id=class_id)
            return (out["records"], new_scores, out["leaf_ids"],
                    out["leaf_output"], key, bag_key, pool,
                    out["hist_rows"])

        self._note_row_constants(pre_j, gather_j, post_j, rows,
                                 objective.num_model_per_iteration(),
                                 goss is not None)
        return step

    def _note_row_constants(self, pre_j, gather_j, post_j, rows,
                            classes: int, goss_on: bool) -> None:
        """`lgbm_step_row_constant_bytes{site=}`: for each program of the
        step, the bytes of the arrays it closes over that have a row axis
        (n, n_pad or a shard's rows long).  Read off the programs' traces
        at the shapes the step runs, the traces its first call uses."""
        spec = jax.ShapeDtypeStruct
        f32 = jnp.float32
        scores, key = spec((classes, self.n), f32), spec((2,), jnp.uint32)
        vec = spec((self.n_pad,), f32)
        pool = () if self._pool is None else (self._pool,)
        grow_args = (self.bins_t, vec, vec, vec, spec((self.f_pad,), f32),
                     self.meta, key) + pool
        out = self.grow.trace(*grow_args).out_info
        calls = [
            (pre_j, (scores, rows, self._ones_mask, key, key),
             dict(class_id=0, refresh_bag=False, goss_on=goss_on)),
            (self.grow, grow_args, {}),
            (post_j, (scores, out["records"], out["leaf_ids"],
                      out["leaf_output"]), dict(class_id=0))]
        if gather_j is not None:
            calls.append((gather_j, (out["leaf_ids"],), {}))
        lengths = {self.n, self.n_pad, self.n_pad // self.d_shards}
        # the gradient program may hold no array shaped like the
        # objective's layout either (a data set's queries)
        of_layout = {d for leaf in jax.tree.leaves(rows.get("layout", {}))
                     for d in leaf.shape}
        for fn, args, kwargs in calls:
            obs.REGISTRY.set_gauge(
                "lgbm_step_row_constant_bytes",
                closed_over_bytes(fn, args, kwargs,
                                  lengths | (of_layout if fn is pre_j
                                             else set())),
                site=fn.site,
                help="bytes of closed-over arrays with a row axis in a "
                     "program of the training step (0: the table, the "
                     "masks and the labels are arguments)")

    def train(self, grad: jnp.ndarray, hess: jnp.ndarray,
              row_mask: Optional[jnp.ndarray] = None
              ) -> Tuple[Tree, jnp.ndarray, Dict]:
        """Grow one tree. Returns (tree, leaf_ids[n] device, raw grower out)."""
        # RNG consumption order must stay sample_features() THEN the key
        # draw — the order the serial call has always used — or seeded
        # runs change trees
        fmask = self.sample_features()
        key = jax.random.PRNGKey(int(self._feature_rng.integers(2 ** 31)))
        if self.params.has_cegb:
            # thread the cross-tree CEGB state through this tree's meta
            self.meta = dict(self.meta)
            self.meta["cegb_used"] = self._cegb_used
            if self.params.has_cegb_lazy:
                self.meta["cegb_paid"] = self._cegb_paid
        if self._multiproc:
            # shard the per-row vectors globally, replicate the small
            # ones.  Partitioned: the row vectors are LOCAL (this
            # process's rows only) and placed as local shards.
            width = (self._local_width if self._partitioned
                     else self.n_pad)

            def pad_host(v):
                out_v = np.zeros(width, np.float32)
                out_v[:np.shape(v)[0]] = np.asarray(v, np.float32)
                return out_v

            def place_rows(v):
                if self._partitioned:
                    return put_local(v, self._rows_shard, (self.n_pad,))
                return put_global(v, self._rows_shard)

            mask_np = self._ones_host if row_mask is None else \
                self._ones_host * pad_host(row_mask)
            out = self.grow(self.bins_t,
                            place_rows(pad_host(grad)),
                            place_rows(pad_host(hess)),
                            place_rows(mask_np),
                            put_global(np.asarray(fmask),
                                       self._rep_sharding),
                            self.meta,
                            put_global(np.asarray(key), self._rep_sharding))
        else:
            mask = self._ones_mask if row_mask is None else \
                self.pad_vector(row_mask) * self._ones_mask
            if self._external_pool:
                out = self.grow(self.bins_t, self.pad_vector(grad),
                                self.pad_vector(hess), mask, fmask,
                                self.meta, key, self._pool)
                self._pool = out["pool"]
            else:
                out = self.grow(self.bins_t, self.pad_vector(grad),
                                self.pad_vector(hess), mask, fmask,
                                self.meta, key)
        if self.params.has_cegb:
            # harvest the updated state for the NEXT tree (async device
            # arrays; no host sync)
            self._cegb_used = out["cegb_used"]
            if self.params.has_cegb_lazy:
                self._cegb_paid = out["cegb_paid"]
        tree = self.build_tree(out)
        if self._multiproc:
            if self._partitioned:
                # each process keeps only ITS rows' leaf ids: the score
                # state is local, so pull the addressable shards in
                # global row order and trim the pad
                shards = sorted(out["leaf_ids"].addressable_shards,
                                key=lambda s: s.index[0].start or 0)
                lids = np.concatenate(
                    [np.asarray(jax.device_get(s.data)).ravel()
                     for s in shards])[:self.n]
                return tree, jnp.asarray(lids), out
            # reassemble the row-sharded leaf ids on every host: the GBDT
            # driver's score updates and renew paths operate on LOCAL
            # arrays (identical on all ranks), and a non-addressable
            # global array cannot be device_get there
            from ..parallel.topology import host_device_allgather

            # the per-iteration hot collective: a dead peer here is the
            # canonical distributed-GBDT hang, so the watchdog matters
            # most at this site
            lids = host_device_allgather(
                out["leaf_ids"], name="leaf_id_allgather")[:self.n]
            return tree, jnp.asarray(lids), out
        return tree, out["leaf_ids"][:self.n], out

    def build_tree(self, out: Dict) -> Tree:
        """Replay device split records into a reference-compatible Tree."""
        fetch = [out["records"], out.get("hist_rows")]
        if self.refits_leaves:
            fetch.append(out["leaf_output"])
        got = jax.device_get(fetch)  # one fetch
        rec = np.asarray(got[0])
        if got[1] is not None:
            self.note_hist_rows(got[1])
        leaf_out = np.asarray(got[2]) if self.refits_leaves else None
        return self.build_tree_from_records(rec, leaf_out)

    def build_tree_from_records(self, rec: np.ndarray,
                                leaf_output: Optional[np.ndarray] = None
                                ) -> Tree:
        from ..ops import grower as G
        L = self.params.num_leaves
        tree = Tree(L)
        used = self.td.used_feature_idx
        mappers = self.td.mappers
        missing = self.meta_np["missing_type"]
        for s in range(rec.shape[0]):
            row = rec[s]
            if row[G.REC_DID_SPLIT] < 0.5:
                break
            f = int(row[G.REC_FEATURE])
            thr_bin = int(row[G.REC_THRESHOLD])
            real_f = used[f]
            common = dict(
                leaf=int(row[G.REC_LEAF]),
                feature_inner=f,
                real_feature=real_f,
                left_value=float(row[G.REC_LEFT_OUTPUT]),
                right_value=float(row[G.REC_RIGHT_OUTPUT]),
                left_cnt=int(round(float(row[G.REC_LEFT_COUNT]))),
                right_cnt=int(round(float(row[G.REC_RIGHT_COUNT]))),
                left_weight=float(row[G.REC_LEFT_WEIGHT]),
                right_weight=float(row[G.REC_RIGHT_WEIGHT]),
                gain=float(row[G.REC_GAIN]),
                missing_type=int(missing[f]))
            if row[G.REC_IS_CAT] > 0.5:
                # bins routed left -> bin bitset + raw-category bitset
                # (Tree::SplitCategorical, reference tree.h:60-85)
                bins_left = np.nonzero(row[G.REC_WIDTH:] > 0.5)[0]
                cats_left = [mappers[real_f].bin_2_categorical[b]
                             for b in bins_left]
                tree.split_categorical(
                    threshold_bins=_to_bitset(bins_left),
                    thresholds=_to_bitset(cats_left),
                    **common)
            else:
                tree.split(
                    threshold_bin=thr_bin,
                    threshold_double=mappers[real_f].bin_to_value(thr_bin),
                    default_left=row[G.REC_DEFAULT_LEFT] > 0.5,
                    **common)
        if leaf_output is not None and tree.num_leaves > 1:
            # quantized leaf refit (GrowerParams.quant_refit): the grower
            # leaf ids ARE the Tree leaf indices (left child keeps the
            # parent's id, right child takes the next fresh id — the same
            # contract the record replay above follows), so the device-
            # refitted outputs overwrite the record values positionally
            tree.leaf_value[:tree.num_leaves] = np.asarray(
                leaf_output[:tree.num_leaves], np.float64)
        return tree


class StreamedTreeLearner(TPUTreeLearner):
    """Out-of-core serial learner: host-resident bins, blocked H2D.

    Same construction surface as TPUTreeLearner, but the transposed bin
    matrix never lands on device as a whole — `_place_serial_bins`
    partitions it into C-contiguous host row blocks and train() drives
    the streamed grower (ops/stream.py), which double-buffers each
    block's H2D copy under the previous block's histogram contraction.
    For int8/int16 precisions the resulting model files are
    BYTE-IDENTICAL to the resident layout's (int32 histogram sums are
    associative across blocks; same n_pad, same quantization grid, same
    stochastic-rounding hash on GLOBAL row indices).

    Restrictions are validated loudly at construction (StreamGrower /
    stream_supported): serial only, numerical only, no EFB / sparse /
    CEGB / forced splits / per-node sampling / packed bins.
    """
    stream_layout = True

    def __init__(self, config: Config, train_data: TrainingData):
        if resolve_tree_learner(config.tree_learner) != "serial":
            raise NotImplementedError(
                "tpu_stream_mode=streamed requires tree_learner=serial")
        super().__init__(config, train_data)
        from ..ops.stream import StreamGrower

        # the resident external-pool/donation machinery is bypassed: the
        # streamed round state owns its pool (stream.root_finish) and
        # per-program donation is wired inside ops/stream.py
        self._donate = False
        self._external_pool = False
        self._stream = StreamGrower(
            self.params, self.g_pad, self.n_pad, self._stream_R,
            double_buffer=bool(config.tpu_stream_double_buffer),
            goss_top=float(config.tpu_stream_goss_top),
            goss_other=float(config.tpu_stream_goss_other),
            live_columns=self.live_columns)
        Log.info(
            f"streamed layout: {len(self._host_blocks)} host blocks x "
            f"{self._stream_R} rows "
            f"({self._host_blocks[0].nbytes >> 20} MiB/block, "
            f"double_buffer={self._stream.double_buffer})")

    def reset_pool(self) -> None:
        # no external donated pool: the streamed grower's pool lives in
        # its device round state and is rebuilt per tree
        self._pool_spec = None
        self._pool = None

    def _place_serial_bins(self, bins_t, n: int) -> None:
        from ..ops.stream import make_host_blocks, resolve_stream_rows
        from ..utils import membudget

        if not isinstance(bins_t, np.ndarray):
            # defensive: the device-transpose fast path is gated off for
            # stream_layout, so this only fires on exotic ingest sources
            bins_t = np.asarray(bins_t)
        precision = self._resolve_precision(self.config)
        _, block = self._resolve_hist_impl(self.config, self.num_bins,
                                           precision)
        self._stream_R = resolve_stream_rows(
            int(self.config.tpu_stream_block_rows), self.n_pad,
            bytes_per_row=int(bins_t.shape[0]) * bins_t.dtype.itemsize,
            inner_block=min(block, self.n_pad),
            budget_bytes=membudget.budget_bytes(self.config))
        self._host_blocks = make_host_blocks(bins_t, self._stream_R)
        self.bins_t = None  # never device-resident on this layout
        self._ones_mask = jnp.ones(self.n_pad, jnp.float32).at[n:].set(0.0)

    def train(self, grad: jnp.ndarray, hess: jnp.ndarray,
              row_mask: Optional[jnp.ndarray] = None
              ) -> Tuple[Tree, jnp.ndarray, Dict]:
        """Grow one tree via the streamed grower.

        RNG consumption order (sample_features THEN the key draw) is the
        resident train()'s — seeded streamed and resident runs consume
        identical randomness, which the bitwise-equality tests pin."""
        fmask = self.sample_features()
        key = jax.random.PRNGKey(int(self._feature_rng.integers(2 ** 31)))
        mask = self._ones_mask if row_mask is None else \
            self.pad_vector(row_mask) * self._ones_mask
        out = self._stream.grow(self._host_blocks, self.pad_vector(grad),
                                self.pad_vector(hess), mask, fmask,
                                self.meta, key)
        tree = self.build_tree(out)
        return tree, out["leaf_ids"][:self.n], out

    @property
    def stream_stats(self) -> Dict[str, float]:
        """Last tree's streaming telemetry (overlap %, H2D wall, blocks
        streamed/skipped) — read by bench.py and perf_probe stream."""
        return dict(self._stream.last_stats)


def make_tree_learner(config: Config,
                      train_data: TrainingData) -> TPUTreeLearner:
    """Layout-dispatching learner constructor — gbdt.py's single entry
    point.  ``tpu_stream_mode`` picks resident (the classic
    device-resident matrix), streamed (host-resident blocks), or auto,
    where membudget.select_layout keeps the resident layout unless its
    pre-construction estimate says the binned matrix would blow the HBM
    budget AND the run is streamable."""
    from ..utils import membudget

    with obs.span("learner/init"):
        if membudget.select_layout(config, train_data) == "streamed":
            return StreamedTreeLearner(config, train_data)
        return TPUTreeLearner(config, train_data)
