"""GBDT boosting driver (reference src/boosting/gbdt.cpp:368-449).

Owns the tree models, per-dataset raw-score vectors, the objective/metrics,
and the TPU tree learner.  One `train_one_iter` =
boost-from-average -> GetGradients (device) -> bagging mask -> per-class
grow-tree (device) -> RenewTreeOutput -> Shrinkage -> score update
(device gather for train, binned traversal for valids) — the same contract
as the reference driver, with mask-based bagging instead of index-subset
copies (SURVEY.md §7 M4).
"""

from __future__ import annotations

import io
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from .. import obs
from ..config import Config
from ..io.bin_mapper import BinMapper, MissingType
from ..io.dataset import TrainingData
from ..utils import faultline, membudget
from ..ops.lookup import lookup
from ..ops.predict import (PackedForest, feature_meta_dev, device_tables,
                           forest_class_scores, forest_leaf_values,
                           pack_trees, row_bucket)
from ..utils import timer
from .learner import TPUTreeLearner, make_tree_learner
from .metrics import Metric, create_metrics
from .objectives import (Objective, create_objective,
                         create_objective_from_model_string)
from .tree import Tree

K_EPSILON = 1e-15


def quant_headroom_check(precision: str, total_rows: int, mode: str) -> int:
    """int32 histogram-accumulator headroom sentinel (quantized mode).

    `quant_limit` already narrows the gradient grid so a worst-case bin
    cannot overflow int32, which means overflow is impossible but the
    effective quantization mantissa silently shrinks with the global row
    count.  The sentinel makes that visible: warn when the grid has
    narrowed below the dtype's own range, raise (under
    tpu_guard_numerics=raise) once the grid has lost two bits of the
    dtype's range (floor capped at 128, i.e. 7 effective bits, for wide
    dtypes) — at that point quantized split decisions are mostly noise.
    The floor is precision-relative: a flat 128 would make int8 (dtype
    max 127) raise on ANY narrowing."""
    from ..ops.histogram import _INT_TYPE_MAX, quant_limit
    from ..utils.log import LightGBMError, Log

    q = quant_limit(precision, total_rows)
    full = _INT_TYPE_MAX[precision]
    if q < full:
        msg = (f"int32 histogram headroom: {total_rows} rows narrow the "
               f"{precision} gradient grid to +-{q} (dtype max +-{full})")
        if mode == "raise" and q < min(128, full // 4):
            raise LightGBMError(
                msg + "; use a wider precision or fewer global rows")
        Log.warning(msg)
    return q

# model-string trailer carrying the bin-mapper snapshot (written by
# save_model_to_string, parsed back by from_model_string)
_MAPPER_MARKER = "tpu_bin_mappers:"

# training-quality histogram ladders (obs registry): leaf counts and
# tree depths are small ints; powers-of-two-ish bounds keep the
# distributions readable at any num_leaves
_LEAF_BUCKETS = (1.0, 2.0, 3.0, 4.0, 6.0, 8.0, 12.0, 16.0, 24.0, 32.0,
                 48.0, 64.0, 96.0, 128.0, 192.0, 256.0, 384.0, 512.0,
                 768.0, 1024.0)
_DEPTH_BUCKETS = (1.0, 2.0, 3.0, 4.0, 5.0, 6.0, 8.0, 10.0, 12.0, 16.0,
                  20.0, 24.0, 32.0, 48.0, 64.0)


def _predict_binned(tree: Tree, bins: np.ndarray,
                    meta: Dict[str, np.ndarray]) -> np.ndarray:
    """Leaf values via bin-space traversal (NumericalDecisionInner,
    reference tree.h:252-270) — used for validation-score updates."""
    n = bins.shape[0]
    if tree.num_leaves == 1:
        return np.full(n, tree.leaf_value[0])
    node = np.zeros(n, dtype=np.int32)
    num_bin = meta["num_bin"]
    default_bin = meta["default_bin"]
    missing = meta["missing_type"]
    for _ in range(tree.max_depth()):
        active = node >= 0
        if not active.any():
            break
        nid = node[active]
        f = tree.split_feature_inner[nid]
        fbin = bins[active, f].astype(np.int64)
        mt = missing[f]
        is_missing = np.where(
            mt == int(MissingType.NAN), fbin == num_bin[f] - 1,
            np.where(mt == int(MissingType.ZERO), fbin == default_bin[f], False))
        dt = tree.decision_type[nid]
        default_left = (dt & 2) != 0
        go_left = np.where(is_missing, default_left,
                           fbin <= tree.threshold_in_bin[nid])
        is_cat = (dt & 1) != 0
        if is_cat.any():
            # bin-space bitset membership (CategoricalDecisionInner,
            # reference tree.h:307-318): bins in the set go left
            cat_words = np.asarray(tree.cat_threshold_inner, dtype=np.uint32)
            cat_bounds = np.asarray(tree.cat_boundaries_inner, dtype=np.int64)
            cat_idx = tree.threshold_in_bin[nid].astype(np.int64)
            cat_idx = np.clip(cat_idx, 0, len(cat_bounds) - 2)
            start = cat_bounds[cat_idx]
            width = cat_bounds[cat_idx + 1] - start
            word_idx = fbin // 32
            in_range = word_idx < width
            word = (cat_words[np.clip(start + word_idx, 0,
                                      len(cat_words) - 1)]
                    if len(cat_words) else np.zeros(len(nid), np.uint32))
            bit = (word >> (fbin % 32).astype(np.uint32)) & 1
            go_left = np.where(is_cat, in_range & (bit == 1), go_left)
        node[active] = np.where(go_left, tree.left_child[nid],
                                tree.right_child[nid]).astype(np.int32)
    return tree.leaf_value[~node]


def _split_mapper_snapshot(text: str):
    """Split a model string into (model_text, _PredictContext | None) —
    the `tpu_bin_mappers:` analog of Booster's pandas_categorical
    split."""
    import json

    marker = "\n" + _MAPPER_MARKER
    pos = text.rfind(marker)
    if pos < 0:
        return text, None
    line_end = text.find("\n", pos + 1)
    payload = text[pos + len(marker): len(text) if line_end < 0
                   else line_end].strip()
    rest = "" if line_end < 0 else text[line_end:]
    try:
        ctx = _PredictContext.from_payload(json.loads(payload))
    except (json.JSONDecodeError, KeyError, ValueError, TypeError) as exc:
        raise ValueError(
            f"corrupt tpu_bin_mappers line in model: {payload[:80]!r}"
        ) from exc
    return text[:pos] + rest, ctx


def _rebind_tree_to_mappers(tree: Tree, mappers: List[BinMapper],
                            used_pos: Dict[int, int]) -> None:
    """Map a tree's real-feature splits into the given mappers' bin
    space (split_feature_inner / threshold_in_bin / *_inner bitsets) —
    shared by init_model continuation and model-string reload."""
    cat_nodes: Dict[int, List[int]] = {}  # cat_idx -> bin words
    for j in range(tree.num_leaves - 1):
        real_f = int(tree.split_feature[j])
        if real_f not in used_pos:
            raise ValueError(
                f"model splits on feature {real_f} which is trivial/"
                "unused in the binning context")
        tree.split_feature_inner[j] = used_pos[real_f]
        mapper = mappers[real_f]
        if int(tree.decision_type[j]) & 1:
            # categorical: decode the raw-category value bitset, re-map
            # each category to its bin under these mappers, re-encode
            cat_idx = int(tree.threshold[j])
            start = tree.cat_boundaries[cat_idx]
            end = tree.cat_boundaries[cat_idx + 1]
            words = tree.cat_threshold[start:end]
            cats = [w * 32 + b for w, word in enumerate(words)
                    for b in range(32) if (int(word) >> b) & 1]
            bins = [mapper.categorical_2_bin[c] for c in cats
                    if c in mapper.categorical_2_bin]
            bw = [0] * (max(bins) // 32 + 1 if bins else 1)
            for b in bins:
                bw[b // 32] |= 1 << (b % 32)
            cat_nodes[cat_idx] = bw
        else:
            tree.threshold_in_bin[j] = mapper.value_to_bin(
                float(tree.threshold[j]))
    if cat_nodes:
        bounds, words = [0], []
        for ci in range(tree.num_cat):
            bw = cat_nodes.get(ci, [0])
            words.extend(bw)
            bounds.append(bounds[-1] + len(bw))
        tree.cat_boundaries_inner = bounds
        tree.cat_threshold_inner = words


class _ScoreState:
    """Per-dataset raw scores [k, n], device-resident for train."""

    def __init__(self, num_class: int, num_data: int,
                 init_score: Optional[np.ndarray] = None):
        scores = np.zeros((num_class, num_data), np.float32)
        self.has_init_score = init_score is not None
        if init_score is not None:
            s = np.asarray(init_score, np.float64)
            if s.size == num_data * num_class:
                scores += s.reshape(num_class, num_data) if s.ndim == 1 \
                    else s.T.astype(np.float32)
            else:
                scores += s.reshape(1, -1)
        # .copy() forces an XLA-owned buffer: on CPU, asarray of
        # aligned host memory is zero-copy, and this buffer is later
        # DONATED by the train step — donating a numpy-aliased buffer
        # corrupts the heap (XLA rewrites memory numpy owns)
        self.scores = jnp.asarray(scores).copy()

    def add_constant(self, val: float, class_id: int):
        self.scores = self.scores.at[class_id].add(np.float32(val))

    def add(self, class_id: int, delta):
        self.scores = self.scores.at[class_id].add(delta)

    def multiply(self, class_id: int, val: float):
        """Scale one class's scores (RF running average,
        reference score_updater.hpp MultiplyScore)."""
        self.scores = self.scores.at[class_id].multiply(np.float32(val))

    def numpy(self) -> np.ndarray:
        return np.asarray(jax.device_get(self.scores), np.float64)


class GBDT:
    """The gradient boosting driver."""

    def __init__(self):
        self.models: List[Tree] = []
        self.iter_ = 0
        self.num_init_iteration = 0
        self.config: Optional[Config] = None
        self.objective: Optional[Objective] = None
        self.train_data: Optional[TrainingData] = None
        self.learner: Optional[TPUTreeLearner] = None
        self.metrics: List[Metric] = []
        self.valid_sets: List[TrainingData] = []
        self.valid_names: List[str] = []
        self.valid_metrics: List[List[Metric]] = []
        self.valid_scores: List[_ScoreState] = []
        self.train_scores: Optional[_ScoreState] = None
        self.num_class = 1
        self.num_tree_per_iteration = 1
        self.shrinkage_rate = 0.1
        self.feature_names: List[str] = []
        self.max_feature_idx = 0
        self.loaded_params: Dict = {}
        self.label_index = 0
        self._bag_rng: Optional[np.random.Generator] = None
        self._pending: List[Tuple] = []
        self._stopped = False
        self._train_step = None
        self._bag_cfg = None
        self._goss_cfg = None          # set by GOSS subclass
        self.average_output = False    # set by RF subclass / model load
        # training reference profile (obs/modelhealth.py): parsed from
        # a loaded model's tpu_feature_profile: trailer, or snapshotted
        # by free_dataset; live training boosters rebuild it per save
        self._profile = None
        # sync-path trees awaiting telemetry until the numerics guard
        # accepts the iteration (train_one_iter)
        self._note_after_guard = None
        # OOM degradation ladder (ISSUE 15): position persists across
        # recoveries so repeated OOMs keep descending, never loop
        self._mem_ladder = membudget.DegradationLadder()
        # cross-iteration learner state (feature RNG, CEGB planes) held
        # across a ladder rebuild: set when the old learner's device
        # buffers are dropped, cleared when a rebuild succeeds — so a
        # FAILED rebuild followed by a further descent still restores
        # the stream state onto the eventual replacement (bitwise)
        self._ladder_carry = None

    # ------------------------------------------------------------------
    def init(self, config: Config, train_data: TrainingData) -> None:
        # telemetry policy (tpu_telemetry / tpu_trace_dir) is process-
        # global and no-clobber; armed FIRST, so that a Booster given
        # telemetry its Dataset was not still records its own set-up
        obs.configure_from_config(config)
        with obs.span("booster/init"):
            self.config = config
            self.train_data = train_data
            # cap (or restore: the native side maps n<=0 back to the
            # captured startup default) the walker's OpenMP pool
            # unconditionally, so a cap from a previous Booster never
            # leaks into this training (reference honors num_threads
            # process-wide via omp_set_num_threads)
            from ..native import set_num_threads

            set_num_threads(int(config.num_threads))
            self.num_class = int(config.num_class)
            self.shrinkage_rate = float(config.learning_rate)
            with obs.span("objective/init"):
                self.objective = create_objective(config)
                if self.objective is not None:
                    self.objective.init(train_data.metadata,
                                        train_data.num_data)
                    self.num_tree_per_iteration = \
                        self.objective.num_model_per_iteration()
                else:
                    self.num_tree_per_iteration = self.num_class
            self.learner = make_tree_learner(config, train_data)
            self.metrics = create_metrics(
                config, self.objective.name if self.objective else "")
            for m in self.metrics:
                m.init(train_data.metadata, train_data.num_data)
            self.train_scores = _ScoreState(self.num_tree_per_iteration,
                                            train_data.num_data,
                                            train_data.metadata.init_score)
            self.feature_names = list(train_data.feature_names)
            self.max_feature_idx = train_data.num_total_features - 1
            self._bag_rng = np.random.default_rng(int(config.bagging_seed))
            self._boosted_from_average = \
                [False] * self.num_tree_per_iteration
            # async fast path: fused device step + lazily materialized
            # trees
            self._pending: List[Tuple] = []
            self._stopped = False
            self._key = jax.random.PRNGKey(int(config.seed))
            self._bag_key = jax.random.PRNGKey(int(config.bagging_seed))
            self._train_step = None
            self._bag_cfg = self._bagging_config()
            # numeric guardrails (tpu_guard_numerics=off|warn|raise|skip):
            # validated here so a typo fails at init, not mid-run; the
            # quantized headroom sentinel is a one-time init check
            self._guard = str(config.tpu_guard_numerics).strip().lower()
            if self._guard not in ("off", "warn", "raise", "skip"):
                raise ValueError("tpu_guard_numerics must be off|warn|raise|"
                                 f"skip, got {self._guard!r}")
            self._guard_streak = 0
            self._guard_skips_total = 0
            # collective watchdog defaults (Network::Init analog): armed
            # process-wide so metric sync / checkpoint barriers / binning
            # allgathers all share one deadline policy; no-clobber rule
            # lives in configure_from_config
            from ..parallel.collective import configure_from_config

            configure_from_config(config)
            if self._guard != "off" \
                    and str(config.tpu_hist_precision) in ("int8", "int16"):
                quant_headroom_check(str(config.tpu_hist_precision),
                                     train_data.num_data, self._guard)
            if self.learner.params.has_cegb and self._goss_cfg is not None:
                raise NotImplementedError(
                    "CEGB penalties do not compose with GOSS yet")
            # pre-partitioned rows: every statistic that must be GLOBAL
            # either reduces (metrics, boost-from-average, the renew leaf
            # averaging in _renew_and_update) or is local by the
            # reference's own distributed semantics (GOSS sampling,
            # per-query ranking lambdas, per-machine percentile renew)
                # GOSS composes: its threshold/sample run over LOCAL rows,
                # which is the reference's distributed behavior too (each
                # machine subsets its own data, goss.hpp Bagging override)
            self._maybe_make_train_step()
            # HBM preflight (ISSUE 15): predict peak device bytes from
            # the live buffers + closed-form models and enforce the
            # budget BEFORE iteration 0 burns a compile on a doomed
            # configuration
            self._run_preflight()

    def _maybe_make_train_step(self) -> None:
        """(Re)build the fused async step when the configuration supports
        it — the ONE place that owns the eligibility rule, so every
        rebuild site (init / reset_training_data / reset_config) applies
        identical conditions."""
        self._train_step = None
        with obs.span("train_step/build"):
            if (self.objective is not None and not self.objective.needs_renew
                    and self.objective.steps_on_device(self.learner)
                    # CEGB threads cross-tree used/paid state through
                    # learner.train (the sync path); the async step hands
                    # the grower the learner's meta as it stood at layout
                    and not self.learner.params.has_cegb
                    # multi-host meshes need learner.train's global array
                    # placement (put_global); the fused step mixes local
                    # score state into the global-mesh program
                    and not self.learner._multiproc
                    # the streamed layout has no device-resident bins_t for
                    # the async step to pass: its train() drives the
                    # per-block host loop (ops/stream.py) — sync path only
                    and not self.learner.stream_layout
                    and all(self.objective.class_need_train(k)
                            for k in range(self.num_tree_per_iteration))):
                self._train_step = self.learner.make_train_step(
                    self.objective, self.shrinkage_rate,
                    self._bag_cfg, self._goss_cfg)
        if self.objective is not None:
            obs.REGISTRY.set_gauge(
                "lgbm_train_step_fused", self._train_step is not None,
                objective=self.objective.name,
                help="1 where the booster built the fused asynchronous "
                     "device step (gradients, grower and score update "
                     "dispatched back to back), 0 on the synchronous path")

    def _bagging_config(self) -> Optional[Dict]:
        cfg = self.config
        frac = float(cfg.bagging_fraction)
        freq = int(cfg.bagging_freq)
        pos_frac = float(cfg.pos_bagging_fraction)
        neg_frac = float(cfg.neg_bagging_fraction)
        balanced = (pos_frac < 1.0 or neg_frac < 1.0)
        if freq <= 0 or (frac >= 1.0 and not balanced):
            return None
        out = {"fraction": frac, "pos_fraction": pos_frac,
               "neg_fraction": neg_frac, "freq": freq}
        if balanced:
            label = np.asarray(self.train_data.metadata.label)
            is_pos = np.zeros(self.learner.n_pad, bool)
            is_pos[:len(label)] = label > 0
            out["is_pos"] = is_pos
        return out

    def reset_training_data(self, data: TrainingData) -> None:
        """Swap the training dataset, replaying the existing model onto the
        new rows (reference GBDT::ResetTrainingData via
        LGBM_BoosterResetTrainingData, c_api.h:436): bins must come from
        the same mappers (created with reference=old dataset)."""
        if self.train_data is None or self.config is None:
            # file-loaded boosters carry no training context, and their
            # trees are not bound to bin space — a clear error beats a
            # late AttributeError (continuation uses init_model instead)
            raise ValueError(
                "reset_training_data needs a booster constructed with a "
                "training dataset; load continuation goes through "
                "init_model")
        if data.mappers is not self.train_data.mappers:
            raise ValueError("new training data must be created with "
                             "reference=the original dataset")
        self._materialize()
        self.train_data = data
        self.learner = make_tree_learner(self.config, data)
        if self.objective is not None:
            self.objective.init(data.metadata, data.num_data)
        self.metrics = create_metrics(
            self.config, self.objective.name if self.objective else "")
        for m in self.metrics:
            m.init(data.metadata, data.num_data)
        self.train_scores = _ScoreState(self.num_tree_per_iteration,
                                        data.num_data,
                                        data.metadata.init_score)
        # replay the whole model onto the new rows: one device pass over
        # the packed forest (class = position % K), host walker fallback
        if not self._replay_scores_device(self.train_scores, data,
                                          self.models,
                                          scale=self._replay_scale(),
                                          cache_bins=False):
            K = max(self.num_tree_per_iteration, 1)
            for k in range(K):
                trees = [t for i, t in enumerate(self.models)
                         if i % K == k and t.num_leaves >= 1]
                if trees:
                    self.train_scores.add(k, jnp.asarray(
                        (self._replay_scale() * self._score_trees_binned(
                            data.bins, trees, [1.0] * len(trees)))
                        .astype(np.float32)))
        # stale per-dataset state: bagging mask and the fused step close
        # over the old row count (reference ResetTrainingData rebuilds its
        # bagging buffers too)
        self._cached_bag_mask = None
        self._pending = []
        self._stopped = False
        self._bag_cfg = self._bagging_config()
        self._maybe_make_train_step()

    def _replay_scale(self) -> float:
        """Scale applied when replaying stored trees onto new data
        (RF overrides: scores are a running AVERAGE of tree outputs)."""
        return 1.0

    def add_valid(self, data: TrainingData, name: str) -> None:
        if data.mappers is not self.train_data.mappers:
            raise ValueError("validation set must be created with "
                             "reference=train dataset")
        self.valid_sets.append(data)
        self.valid_names.append(name)
        ms = create_metrics(self.config,
                            self.objective.name if self.objective else "")
        for m in ms:
            m.init(data.metadata, data.num_data)
        self.valid_metrics.append(ms)
        self.valid_scores.append(_ScoreState(
            self.num_tree_per_iteration, data.num_data,
            data.metadata.init_score))
        # replay existing model onto the new valid set: one packed-forest
        # device pass when eligible, host walker otherwise
        if not self._replay_scores_device(self.valid_scores[-1], data,
                                          self.models):
            meta = self.learner.meta_np
            for i, tree in enumerate(self.models):
                k = i % self.num_tree_per_iteration
                self.valid_scores[-1].add(
                    k, jnp.asarray(_predict_binned(tree, data.bins, meta)
                                   .astype(np.float32)))

    # ------------------------------------------------------------------
    def _boost_from_average(self, class_id: int) -> float:
        if (self.models or self._boosted_from_average[class_id]
                or self.objective is None
                or self.train_scores.has_init_score):
            return 0.0
        self._boosted_from_average[class_id] = True
        if not self.config.boost_from_average:
            return 0.0
        init = self.objective.boost_from_score(class_id)
        if self.learner is not None and self.learner._multiproc:
            # every rank's init comes from its LOCAL rows; agree on the
            # cross-machine mean like the reference
            # (ObtainAutomaticInitialScore -> GlobalSyncUpByMean,
            # gbdt.cpp:333-342).  Identity in the replicated-data mode.
            from ..parallel.metric_sync import process_count, sync_sums

            init = float(sync_sums([init])[0] / process_count())
        if abs(init) > K_EPSILON:
            self.train_scores.add_constant(init, class_id)
            for vs in self.valid_scores:
                vs.add_constant(init, class_id)
            return init
        return 0.0

    def bagging_mask(self, it: int) -> Optional[jnp.ndarray]:
        """Row mask for this iteration (None = all rows). Mask-based analog
        of reference GBDT::Bagging (gbdt.cpp:210-276)."""
        cfg = self.config
        frac = float(cfg.bagging_fraction)
        freq = int(cfg.bagging_freq)
        pos_frac = float(cfg.pos_bagging_fraction)
        neg_frac = float(cfg.neg_bagging_fraction)
        balanced = (pos_frac < 1.0 or neg_frac < 1.0)
        if freq <= 0 or (frac >= 1.0 and not balanced):
            return None
        if it % freq != 0 and self._cached_bag_mask is not None:
            return self._cached_bag_mask
        n = self.train_data.num_data
        if balanced:
            label = np.asarray(self.train_data.metadata.label)
            is_pos = label > 0
            r = self._bag_rng.random(n)
            keep = np.where(is_pos, r < pos_frac, r < neg_frac)
        else:
            cnt = int(n * frac)
            idx = self._bag_rng.choice(n, size=cnt, replace=False)
            keep = np.zeros(n, bool)
            keep[idx] = True
        mask = jnp.asarray(keep.astype(np.float32))
        self._cached_bag_mask = mask
        return mask

    _cached_bag_mask = None
    # guardrail defaults for drivers that never ran init() (file-loaded
    # predict-only boosters)
    _guard = "off"
    _guard_streak = 0
    _guard_skips_total = 0
    _GUARD_MAX_STREAK = 5
    # set by a skip-mode rollback: the retry must draw a FRESH bagging
    # mask even off the bagging_freq boundary, or it would replay the
    # poisoned iteration bit-identically
    _force_bag_refresh = False

    # ------------------------------------------------------------------
    def train_one_iter(self, grad: Optional[jnp.ndarray] = None,
                       hess: Optional[jnp.ndarray] = None) -> bool:
        """One boosting iteration; True when training has stalled.

        The iteration applies ATOMICALLY: SIGTERM / KeyboardInterrupt /
        an XLA runtime error (or an armed `grow_step` fault) anywhere
        inside rolls the partial iteration back — scores, PRNG streams,
        pending trees and counters return to their pre-iteration state
        before the exception re-raises — so the booster stays usable
        (predict / continue-training / checkpoint-flush) after an
        interrupt.  tpu_guard_numerics adds a per-iteration isfinite
        check on the updated scores (warn | raise | skip; skip =
        rollback + re-bag).

        A classified device OOM (membudget.DeviceOutOfMemory from any
        guarded site inside the iteration) rides the same rollback,
        then descends one deterministic, bitwise-invisible degradation-
        ladder step and RETRIES the iteration — ladder exhaustion
        raises a structured MemoryLadderExhausted instead (ISSUE 15)."""
        while True:
            try:
                return self._train_one_iter_guarded(grad, hess)
            except membudget.DeviceOutOfMemory as exc:
                # the partial iteration was already rolled back by the
                # guarded body; recover (one ladder step) or re-raise
                # structured
                self._recover_from_oom(exc)

    def _train_one_iter_guarded(self, grad, hess) -> bool:
        if self._stopped:
            return True
        if self.learner is None and self._ladder_carry is not None:
            # a ladder rebuild OOMed and the run ended exhausted;
            # pressure may have subsided since — retry the rebuild
            # (classified on failure, riding the same recovery loop) so
            # continue-training stays possible after an exhaustion
            self._rebuild_learner()
        self._note_after_guard = None
        snap = self._iter_snapshot()
        try:
            with obs.span("train/iteration", iteration=self.iter_), \
                    membudget.oom_guard("train_step",
                                        iteration=self.iter_):
                action = faultline.fire("grow_step", iteration=self.iter_)
                ret = self._train_one_iter_impl(grad, hess, snap)
        except BaseException:
            self._iter_restore(snap)
            raise
        if action == "poison":
            # fault harness: NaN-poison this iteration's scores so the
            # guardrail modes below are exercised deterministically
            self.train_scores.scores = (self.train_scores.scores
                                        + jnp.float32(np.nan))
        if self._guard != "off" and not ret and not self._scores_finite():
            return self._poisoned_iteration(snap)
        self._guard_streak = 0
        self._force_bag_refresh = False  # the skip retry (if any) is done
        # sync-path trees survived the guard: record their telemetry now
        if self._note_after_guard:
            for t in self._note_after_guard:
                self._note_tree_telemetry(t)
            self._note_after_guard = None
        return ret

    def _train_one_iter_impl(self, grad, hess, snap) -> bool:
        if (grad is None or hess is None) and self._train_step is not None:
            return self._train_one_iter_fused(snap)
        return self._train_one_iter_sync(grad, hess)

    def _train_one_iter_fused(self, snap) -> bool:
        """Fast path: one fused async device dispatch per class and NO
        host<->device sync; host Tree objects materialize lazily at
        eval/predict/save time (`_materialize`)."""
        # the fused step is one async dispatch holding the histogram
        # pool + score buffers; its watermark is tagged hist_build (the
        # grow program owns the [L, G/P, B, 3] pool, the dominant HBM
        # consumer).  Async means the bracket reads allocation, not
        # execution — an under-estimate on accelerators, never an
        # over-estimate
        with timer.PHASE("train_dispatch"), \
                obs.resources.phase_peak("hist_build"):
            bag = self._bag_cfg
            extra = {}
            if self._goss_cfg is not None:
                extra["goss_on"] = self.iter_ >= self._goss_cfg["warmup"]
            inits = [self._boost_from_average(k)
                     for k in range(self.num_tree_per_iteration)]
            base_scores = self.train_scores.scores
            if getattr(self.learner, "_donate", False):
                # the step donates the scores buffer (arg 1); at class 0
                # base_scores IS that buffer, so snapshot a copy — a
                # donated-then-read alias would either spam copy warnings
                # or (multiclass) read a deleted buffer at class 1
                base_scores = jnp.copy(base_scores)
                if snap is not None \
                        and snap["scores"] is self.train_scores.scores:
                    # the pre-iteration buffer is about to be DONATED;
                    # the copy (bitwise equal — no boost-from-average
                    # constant was added this iteration, or the buffers
                    # would already differ) becomes the live rollback
                    # snapshot
                    snap["scores"] = base_scores
            pool = getattr(self.learner, "_pool", None)
            for k in range(self.num_tree_per_iteration):
                refresh = bag is not None and (
                    self.iter_ % bag["freq"] == 0
                    or self._force_bag_refresh)
                (records, scores, leaf_ids, leaf_out, self._key,
                 self._bag_key, pool, hist_rows) = self._train_step(
                    base_scores, self.train_scores.scores,
                    self._key, self._bag_key, pool, k, refresh, **extra)
                self.train_scores.scores = scores
                if pool is not None:
                    # write the donated pool back IMMEDIATELY: the step
                    # deleted the previous buffer, so deferring this past
                    # a raising later class would leave learner._pool
                    # pointing at a deleted array and break every
                    # subsequent update()
                    self.learner._pool = pool
                # quantized leaf refit: the host Tree must take its leaf
                # values from the refitted device vector, not the records
                self._pending.append((
                    records,
                    leaf_out if self.learner.refits_leaves else None,
                    k, inits[k], hist_rows))
            self.iter_ += 1
        return False

    # -- atomic-iteration rollback -------------------------------------
    def _iter_snapshot(self) -> Dict:
        """Cheap pre-iteration capture for atomic rollback: array
        REFERENCES (jax arrays are immutable; the one donation hazard is
        patched inside the fused path) plus host RNG/counter state."""
        snap = {
            "scores": (self.train_scores.scores
                       if self.train_scores is not None else None),
            "valid": [vs.scores for vs in self.valid_scores],
            "key": getattr(self, "_key", None),
            "bag_key": getattr(self, "_bag_key", None),
            "pending": len(self._pending),
            "models": len(self.models),
            "bfa": list(getattr(self, "_boosted_from_average", [])),
            "bag_mask": self._cached_bag_mask,
            "bag_rng": (self._bag_rng.bit_generator.state
                        if self._bag_rng is not None else None),
            "feature_rng": (self.learner._feature_rng.bit_generator.state
                            if self.learner is not None and
                            getattr(self.learner, "_feature_rng", None)
                            is not None else None),
            "iter": self.iter_,
            "stopped": self._stopped,
            "shrinkage": self.shrinkage_rate,
        }
        snap.update(self._snapshot_extra())
        return snap

    def _snapshot_extra(self) -> Dict:
        return {}

    def _restore_extra(self, snap: Dict) -> None:
        pass

    def _iter_restore(self, snap: Dict) -> None:
        """Roll a partially-applied iteration back to its snapshot."""
        if self.train_scores is not None and snap["scores"] is not None:
            self.train_scores.scores = snap["scores"]
        for vs, s in zip(self.valid_scores, snap["valid"]):
            vs.scores = s
        if snap["key"] is not None:
            self._key = snap["key"]
        if snap["bag_key"] is not None:
            self._bag_key = snap["bag_key"]
        del self._pending[snap["pending"]:]
        del self.models[snap["models"]:]
        if snap["bfa"]:
            self._boosted_from_average = snap["bfa"]
        self._cached_bag_mask = snap["bag_mask"]
        if snap["bag_rng"] is not None:
            self._bag_rng.bit_generator.state = snap["bag_rng"]
        if snap["feature_rng"] is not None:
            self.learner._feature_rng.bit_generator.state = \
                snap["feature_rng"]
        self.iter_ = snap["iter"]
        self._stopped = snap["stopped"]
        self.shrinkage_rate = snap["shrinkage"]
        # a failed DONATING dispatch may have consumed the threaded
        # histogram pool; it is per-iteration scratch, so zeros restore
        # it bit-equivalently
        pool = (getattr(self.learner, "_pool", None)
                if self.learner is not None else None)
        if pool is not None and pool.is_deleted():
            self.learner.reset_pool()
        self._invalidate_tables()
        self._restore_extra(snap)

    # -- memory-pressure recovery (membudget, ISSUE 15) ----------------
    def _oom_recoverable(self) -> bool:
        """May a classified OOM descend the degradation ladder here?
        Needs a live training context and tpu_oom_recovery=true; multi-
        process groups always propagate instead — a one-sided retry
        would desynchronize the collective streams."""
        if (self.config is None or self.train_data is None
                or not bool(self.config.tpu_oom_recovery)):
            return False
        if self.learner is None:
            # mid-rebuild (the ladder dropped the old learner and the
            # replacement OOMed): the parked carry marks a live context
            return self._ladder_carry is not None
        return not getattr(self.learner, "_multiproc", False)

    def _recover_from_oom(self, exc: "membudget.DeviceOutOfMemory",
                          in_recovery: bool = False) -> None:
        """One ladder descent after a rolled-back OOM iteration, or the
        structured exhaustion error (blackbox dumped WITH the memory
        snapshot; engine.train then flushes the final checkpoint).

        `in_recovery` marks re-entry from a failed ladder REBUILD:
        recoverability was already established for this episode, and the
        learner reference is legitimately None mid-rebuild."""
        from ..utils.log import Log

        if not (in_recovery or self._oom_recoverable()):
            # recovery disabled (or a multi-host group): the classified
            # OOM propagates AS ITSELF — labeling it ladder exhaustion
            # would send the postmortem reader chasing a ladder that
            # was never tried
            obs.flightrecorder.note(
                "oom", "oom_propagated", site=exc.site,
                recovery="off",
                **{k: v for k, v in membudget.memory_snapshot().items()
                   if v is not None})
            obs.flightrecorder.dump("oom_unrecovered", exc=exc)
            raise exc
        step = self._mem_ladder.next_step(self.config)
        if step is None:
            taken = self._mem_ladder.describe()
            obs.flightrecorder.note(
                "oom", "ladder_exhausted", site=exc.site,
                steps_taken=",".join(taken) or "none",
                **{k: v for k, v in membudget.memory_snapshot().items()
                   if v is not None})
            err = membudget.MemoryLadderExhausted(
                f"device out of memory at {exc.site!r} and the "
                "degradation ladder is exhausted "
                f"(steps taken: {taken or 'none'}); the failed "
                "iteration was rolled back — the booster is usable and "
                "a final checkpoint covers the last complete iteration",
                site=exc.site, info=dict(exc.info))
            obs.flightrecorder.dump("oom_ladder_exhausted", exc=err)
            raise err from exc
        name, overrides = step
        membudget.note_ladder_step(exc.site, name, overrides)
        Log.warning(
            f"device OOM at {exc.site!r} (iteration {self.iter_}): "
            f"rolled back; degradation ladder step {name!r} applies "
            f"{overrides} — retrying (bitwise-invisible: the settled "
            "model is byte-identical to an undisturbed run at this "
            "config)")
        try:
            self.apply_memory_degradation(overrides)
        except membudget.DeviceOutOfMemory as rebuild_exc:
            # the learner rebuild itself OOMed on the still-full device:
            # descend again (no new rollback needed — no iteration is in
            # flight), so persistent pressure still ends in the
            # structured exhaustion contract, not a mid-recovery abort
            self._recover_from_oom(rebuild_exc, in_recovery=True)

    def apply_memory_degradation(self, overrides: Dict) -> None:
        """Apply ladder-step param overrides to the LIVE training run.

        Chunk-size overrides take effect at the next launch; the
        aggregation / bucket-policy overrides rebuild the learner (and
        the fused step) in place — cross-iteration learner state
        (feature-fraction RNG, CEGB used/paid planes) carries over so
        the retry stays bitwise vs an undisturbed run at the settled
        configuration."""
        if not overrides:
            return
        self.config.update(overrides)
        if not ({"tpu_hist_agg", "tpu_bucket_policy", "tpu_stream_mode"}
                & set(overrides)):
            return  # chunk-only: nothing compiled closes over it
        if self.train_data is None or (self.learner is None
                                       and self._ladder_carry is None):
            return  # no live training context (and not mid-rebuild)
        if self.learner is not None:
            self._materialize()  # pending records belong to the OLD grower
            old = self.learner
            # carry the cross-iteration learner state out first: the
            # feature-fraction RNG stream and the CEGB used/paid planes
            # (cross-tree — a rebuild must not reset what earlier trees
            # already paid for); held on self until a rebuild SUCCEEDS,
            # so a failed rebuild + further descent still restores it
            rng_state = None
            if getattr(old, "_feature_rng", None) is not None:
                rng_state = old._feature_rng.bit_generator.state
            self._ladder_carry = (rng_state, [
                (attr, key, getattr(old, attr, None))
                for attr, key in (("_cegb_used", "cegb_used"),
                                  ("_cegb_paid", "cegb_paid"))])
            # ...then drop the old generation's device residency
            # (histogram pool + transposed bins + the step closure
            # holding both) BEFORE the replacement re-allocates them:
            # this runs on a device that just OOMed, and holding two
            # generations of the largest buffers would transiently
            # double residency and OOM the rebuild itself
            self._train_step = None
            self.learner = None
            old._pool = None
            old._pool_spec = None
            if hasattr(old, "bins_t"):
                old.bins_t = None
            del old
        self._rebuild_learner()

    def _rebuild_learner(self) -> None:
        """(Re)construct the learner for the CURRENT config, restoring
        the parked cross-iteration state (`_ladder_carry`).  Runs under
        `oom_guard`: a rebuild-time allocation failure is still an OOM
        at the train step — classified (counted + blackboxed), never a
        raw XlaRuntimeError escaping the recovery path unnamed."""
        with membudget.oom_guard("train_step", stage="ladder_rebuild"):
            self.learner = make_tree_learner(self.config, self.train_data)
        rng_state, cegb_vals = self._ladder_carry or (None, [])
        self._ladder_carry = None
        if rng_state is not None and \
                getattr(self.learner, "_feature_rng", None) is not None:
            self.learner._feature_rng.bit_generator.state = rng_state
        for attr, key, val in cegb_vals:
            if val is not None and hasattr(self.learner, attr):
                setattr(self.learner, attr, val)
                self.learner.meta[key] = val
        self._invalidate_tables()
        self._maybe_make_train_step()

    def _run_preflight(self) -> None:
        """tpu_hbm_preflight before iteration 0: itemized plan vs the
        budget — warn, refuse with the named plan, or auto-degrade
        down the same bitwise-invisible ladder mid-train OOMs use."""
        from ..utils.log import LightGBMError, Log

        mode = str(self.config.tpu_hbm_preflight).strip().lower()
        if mode not in ("off", "warn", "raise", "degrade"):
            raise ValueError("tpu_hbm_preflight must be off|warn|raise|"
                             f"degrade, got {mode!r}")
        if mode == "off":
            return
        plan = membudget.plan_training(self.config, self.learner,
                                       self.num_tree_per_iteration)
        membudget.publish_budget_gauge(plan.budget, "training")
        if plan.fits is not False:
            return  # fits, or no budget resolves (nothing to enforce)
        if mode == "degrade":
            pending: Dict = {}
            while plan.fits is False:
                step = self._mem_ladder.next_step(self.config)
                if step is None:
                    break
                name, overrides = step
                membudget.note_ladder_step("preflight", name, overrides,
                                           recovery=False)
                # stage config-only so one learner rebuild covers all
                self.config.update(overrides)
                pending.update(overrides)
                plan = membudget.plan_training(
                    self.config, self.learner,
                    self.num_tree_per_iteration)
            if plan.fits is not False:
                Log.warning(
                    "HBM preflight degraded the configuration to fit "
                    f"the budget: {pending} (bitwise-invisible); "
                    f"headroom now {plan.headroom:,d} bytes")
                if {"tpu_hist_agg", "tpu_bucket_policy",
                        "tpu_stream_mode"} & set(pending):
                    self.apply_memory_degradation(
                        {k: pending[k] for k in
                         ("tpu_hist_agg", "tpu_bucket_policy",
                          "tpu_stream_mode")
                         if k in pending})
                return
        if mode == "warn":
            Log.warning("HBM preflight: predicted peak exceeds the "
                        "budget (tpu_hbm_preflight=warn):\n"
                        + plan.format_table())
            return
        obs.flightrecorder.note("oom", "preflight_refused",
                                total=plan.total, budget=plan.budget)
        raise LightGBMError(plan.refuse_message(
            "training preflight (tpu_hbm_preflight="
            f"{mode}): this configuration"))

    # -- numeric guardrails (tpu_guard_numerics) -----------------------
    def _scores_finite(self) -> bool:
        """One all-isfinite reduction over the train scores, piggybacked
        after the iteration's own device pass.  Forces one device sync
        per iteration — the cost of guarding, paid only when armed."""
        if self.train_scores is None:
            return True
        return bool(jax.device_get(
            jnp.isfinite(self.train_scores.scores).all()))

    def _poisoned_iteration(self, snap: Dict) -> bool:
        from ..utils.log import LightGBMError, Log

        it = snap["iter"]
        # guard firings are rare and vital: count unconditionally, and
        # leave a narrative event in the trace stream when one is open
        obs.REGISTRY.inc("lgbm_guard_poisoned_total", mode=self._guard,
                         help="non-finite-score iterations caught by "
                              "tpu_guard_numerics")
        obs.event("guard_poisoned", iteration=it, mode=self._guard)
        obs.flightrecorder.note("guard", "guard_poisoned",
                                iteration=it, mode=self._guard)
        if self._guard == "warn":
            Log.warning(f"non-finite training scores after iteration {it} "
                        "(tpu_guard_numerics=warn): continuing")
            return False
        if self._guard == "raise":
            self._iter_restore(snap)  # leave the booster usable
            exc = LightGBMError(
                f"non-finite training scores after iteration {it} "
                "(tpu_guard_numerics=raise); the poisoned iteration was "
                "rolled back")
            # the blackbox is the postmortem for exactly this death
            obs.flightrecorder.dump("guard_raise", exc=exc)
            raise exc
        # skip: drop the iteration but KEEP the advanced PRNG streams so
        # the retry re-bags instead of replaying the same poison.  With
        # no stochastic lever at all the retry would be a bit-identical
        # replay — raise immediately instead of burning the streak.
        if not self._has_skip_lever():
            self._iter_restore(snap)
            raise LightGBMError(
                f"non-finite training scores after iteration {it} and no "
                "stochastic lever to re-bag (tpu_guard_numerics=skip "
                "needs bagging/GOSS/feature_fraction/quantized rounding "
                "to vary the retry)")
        keys = (getattr(self, "_key", None), getattr(self, "_bag_key", None))
        bag_rng = (self._bag_rng.bit_generator.state
                   if self._bag_rng is not None else None)
        feat_rng = (self.learner._feature_rng.bit_generator.state
                    if self.learner is not None and
                    getattr(self.learner, "_feature_rng", None) is not None
                    else None)
        self._iter_restore(snap)
        if keys[0] is not None:
            self._key = keys[0]
        if keys[1] is not None:
            self._bag_key = keys[1]
        if bag_rng is not None:
            self._bag_rng.bit_generator.state = bag_rng
        if feat_rng is not None:
            self.learner._feature_rng.bit_generator.state = feat_rng
        self._advance_streams_for_skip()
        self._guard_streak += 1
        self._guard_skips_total += 1
        if self._guard_streak > self._GUARD_MAX_STREAK:
            raise LightGBMError(
                f"{self._guard_streak} consecutive poisoned iterations "
                "under tpu_guard_numerics=skip; giving up")
        Log.warning(f"dropped poisoned iteration {it} "
                    "(tpu_guard_numerics=skip): rolled back, re-bagging")
        return False

    def _has_skip_lever(self) -> bool:
        """Does a skip-mode retry differ at all from the dropped
        iteration?  Without a stochastic lever the replay is
        bit-identical and skipping is pointless."""
        if self._bag_cfg is not None or self._goss_cfg is not None:
            return True
        if self.config is not None \
                and float(self.config.feature_fraction) < 1.0:
            return True
        return (self.learner is not None
                and getattr(self.learner, "params", None) is not None
                and self.learner.params.precision in ("int8", "int16"))

    def _advance_streams_for_skip(self) -> None:
        """Make the skip retry actually differ: force a fresh bagging
        mask even off the bagging_freq boundary (the fused step only
        consumes _bag_key on refresh; the sync path only redraws when
        the cached mask is gone)."""
        self._cached_bag_mask = None
        self._force_bag_refresh = True

    def _train_one_iter_sync(self, grad=None, hess=None) -> bool:
        """Synchronous path: custom fobj gradients or renew objectives."""
        init_scores = [0.0] * self.num_tree_per_iteration
        if grad is None or hess is None:
            for k in range(self.num_tree_per_iteration):
                init_scores[k] = self._boost_from_average(k)
            grad, hess = self.objective.get_gradients(self.train_scores.scores)
            if grad.ndim == 1:
                grad, hess = grad[None, :], hess[None, :]
        else:
            grad = jnp.asarray(grad, jnp.float32).reshape(
                self.num_tree_per_iteration, -1)
            hess = jnp.asarray(hess, jnp.float32).reshape(
                self.num_tree_per_iteration, -1)

        self._materialize()
        with obs.span("bagging"):
            mask = self.bagging_mask(self.iter_)
        should_continue = False
        for k in range(self.num_tree_per_iteration):
            need = (self.objective is None
                    or self.objective.class_need_train(k))
            tree = None
            if need:
                with obs.span("grow", class_id=k), \
                        obs.resources.phase_peak("hist_build"):
                    tree, leaf_ids, out = self.learner.train(
                        grad[k], hess[k], mask)
            if tree is not None and tree.num_leaves > 1:
                should_continue = True
                with obs.span("score_update", class_id=k), \
                        obs.resources.phase_peak("score_update"):
                    self._renew_and_update(tree, leaf_ids, k, mask)
                if abs(init_scores[k]) > K_EPSILON:
                    tree.add_bias(init_scores[k])
            else:
                tree = Tree(2)
                if len(self.models) < self.num_tree_per_iteration:
                    if not need and self.objective is not None:
                        output = self.objective.boost_from_score(k)
                    else:
                        output = init_scores[k]
                    tree.as_constant_tree(output)
                    self.train_scores.add_constant(output, k)
                    for vs in self.valid_scores:
                        vs.add_constant(output, k)
            self.models.append(tree)

        if not should_continue:
            if len(self.models) > self.num_tree_per_iteration:
                del self.models[-self.num_tree_per_iteration:]
            self._stopped = True
            return True
        # telemetry defers to train_one_iter AFTER the numerics guard:
        # a tpu_guard_numerics=skip rollback deletes these trees again,
        # and noting them would break the counter <-> feature_importance
        # bit-equality (the fused path gets this for free — rolled-back
        # pending records never materialize)
        self._note_after_guard = self.models[-self.num_tree_per_iteration:]
        self.iter_ += 1
        return False

    def _materialize(self) -> None:
        """Fetch pending device records and build host Tree models."""
        if not self._pending:
            return
        ctx = timer.PHASE("tree_materialize")
        ctx.__enter__()
        try:
            self._materialize_inner()
        finally:
            ctx.__exit__(None, None, None)

    def _materialize_inner(self) -> None:
        pending, self._pending = self._pending, []
        # one batched fetch for all pending trees (None leaf-out entries
        # are empty pytrees and fetch as None)
        fetched = jax.device_get([(p[0], p[1], p[4]) for p in pending])
        meta = self.learner.meta_np
        for (_, _, class_id, init, _), (rec, leaf_out, hist_rows) in zip(
                pending, fetched):
            if self._stopped:
                break  # drop queued post-stall iterations (reference pops them)
            self.learner.note_hist_rows(hist_rows)
            tree = self.learner.build_tree_from_records(
                np.asarray(rec),
                None if leaf_out is None else np.asarray(leaf_out))
            if tree.num_leaves > 1:
                tree.apply_shrinkage(self.shrinkage_rate)
                # valid scores stay device-resident: the new tree's packed
                # table traverses all rows on device (zero device_get per
                # tree — the async train pipeline never stalls on eval)
                pc: Dict = {}
                for vs, vd in zip(self.valid_scores, self.valid_sets):
                    delta = self._tree_delta_device(vd, tree, pack_cache=pc)
                    if delta is None:
                        delta = jnp.asarray(
                            self._score_trees_binned(vd.bins, [tree], [1.0])
                            .astype(np.float32))
                    vs.add(class_id, delta)
                if abs(init) > K_EPSILON:
                    tree.add_bias(init)
                self.models.append(tree)
                self._note_tree_telemetry(tree)
            else:
                # no split happened: device scores were not changed; stop
                # training like the reference ("no more leaves that meet the
                # split requirements", gbdt.cpp:434-442). A first-iteration
                # stall still records the constant boost-from-average tree.
                self._stopped = True
                if len(self.models) < self.num_tree_per_iteration:
                    tree.as_constant_tree(init)
                    self.models.append(tree)
        # iter_ counts NEW boosting rounds (the index bagging refresh,
        # GOSS warmup, and DART's drop bookkeeping key on) — init_model
        # trees live in models but not in iter_, or a mid-train
        # materialize (checkpoint, eval) would shift the bagging
        # schedule of a continuation run
        self.iter_ = (len(self.models) // max(self.num_tree_per_iteration, 1)
                      - self.num_init_iteration)

    def train_one_iter_custom(self, grad: np.ndarray, hess: np.ndarray) -> bool:
        return self.train_one_iter(jnp.asarray(grad), jnp.asarray(hess))

    def _renew_and_update(self, tree: Tree, leaf_ids, class_id: int, mask):
        # RenewTreeOutput (objective-specific percentile refits)
        if self.objective is not None and self.objective.needs_renew:
            leaf_np = np.asarray(jax.device_get(leaf_ids))
            score_np = np.asarray(
                jax.device_get(self.train_scores.scores[class_id]), np.float64)
            mask_np = (np.ones(len(leaf_np), bool) if mask is None
                       else np.asarray(jax.device_get(mask)) > 0)
            self.objective.renew_tree_output(tree, score_np, leaf_np, mask_np)
            if getattr(self.learner, "_partitioned", False):
                # distributed renew averages each leaf's PER-MACHINE
                # local-percentile output over the machines that had
                # rows on that leaf — the reference's exact scheme
                # (serial_tree_learner.cpp:865-891: GlobalSum of
                # outputs / GlobalSum of nonzero-worker counts)
                from ..parallel.metric_sync import sync_sums

                L = tree.num_leaves
                cnt = np.bincount(leaf_np[mask_np], minlength=L)[:L]
                has = (cnt > 0).astype(np.float64)
                outs = np.asarray(tree.leaf_value[:L], np.float64) * has
                g = sync_sums(np.concatenate([outs, has]))
                tree.leaf_value[:L] = g[:L] / np.maximum(g[L:], 1.0)
        tree.apply_shrinkage(self.shrinkage_rate)
        # train scores: leaf-partition lookup (ScoreUpdater::AddScore train
        # path); the whole leaf vector, so every tree has one table shape
        leaf_vals = jnp.asarray(tree.leaf_value.astype(np.float32))
        self.train_scores.add(class_id, lookup(leaf_vals, leaf_ids))
        # valid scores: binned traversal (device kernel, host fallback)
        pc: Dict = {}
        for vs, vd in zip(self.valid_scores, self.valid_sets):
            delta = self._tree_delta_device(vd, tree, pack_cache=pc)
            if delta is None:
                delta = jnp.asarray(
                    self._score_trees_binned(vd.bins, [tree], [1.0])
                    .astype(np.float32))
            vs.add(class_id, delta)

    def rollback_one_iter(self) -> None:
        self._materialize()
        self._invalidate_tables()
        if self.iter_ <= 0:
            return
        train_bins = None
        if (self.train_data.has_bins and self.learner is not None
                and self._device_replay_ok(self.train_data.num_data)):
            # one upload shared by every popped tree this call
            train_bins = self._device_bins_for(self.train_data, cache=False)
        for k in range(self.num_tree_per_iteration):
            tree = self.models.pop()
            k_id = self.num_tree_per_iteration - 1 - k
            pc: Dict = {}
            delta = self._tree_delta_device(self.train_data, tree,
                                            bins_dev=train_bins,
                                            pack_cache=pc)
            self.train_scores.add(k_id, -delta if delta is not None
                                  else jnp.asarray(self._score_trees_binned(
                                      self.train_data.bins, [tree], [-1.0])
                                      .astype(np.float32)))
            for vs, vd in zip(self.valid_scores, self.valid_sets):
                delta = self._tree_delta_device(vd, tree, pack_cache=pc)
                vs.add(k_id, -delta if delta is not None
                       else jnp.asarray(self._score_trees_binned(
                           vd.bins, [tree], [-1.0]).astype(np.float32)))
        self.iter_ -= 1

    # ------------------------------------------------------------------
    def current_iteration(self) -> int:
        self._materialize()
        return len(self.models) // max(self.num_tree_per_iteration, 1)

    def num_total_model(self) -> int:
        self._materialize()
        return len(self.models)

    def num_model_per_iteration(self) -> int:
        return self.num_tree_per_iteration

    def current_score_for_fobj(self) -> np.ndarray:
        return self.train_scores.numpy()

    # ------------------------------------------------------------------
    # checkpoint/resume (utils/checkpoint.py): the driver-level bundle
    # ------------------------------------------------------------------
    @staticmethod
    def _key_words(key) -> List[int]:
        """PRNG key -> raw uint32 words (JSON-able)."""
        arr = key
        if jnp.issubdtype(arr.dtype, jax.dtypes.prng_key):
            arr = jax.random.key_data(arr)
        return [int(w) for w in
                np.ravel(np.asarray(jax.device_get(arr))).astype(np.uint32)]

    @staticmethod
    def _words_to_key(words, like):
        """uint32 words -> a key matching `like`'s representation."""
        arr = jnp.asarray(np.asarray(words, np.uint32).reshape(-1))
        if jnp.issubdtype(like.dtype, jax.dtypes.prng_key):
            return jax.random.wrap_key_data(arr)
        return arr

    def topology_snapshot(self) -> Dict:
        """What the multihost group manifest records and elastic resume
        validates/re-shards against (ISSUE 8).  "rows" is THIS process's
        local row count — the global count under replicated/single-
        process ingest.  Pure host metadata: NO device transfer, so the
        flush path's global-commit retry can call it for free."""
        if self.train_data is None or self.learner is None:
            raise ValueError("topology snapshot needs a live training "
                             "context")
        return {
            "rows": int(self.train_data.num_data),
            "host_count": int(jax.process_count()),
            "host_index": int(jax.process_index()),
            "partitioned": bool(getattr(self.learner, "_partitioned",
                                        False)),
            "data_shards": int(getattr(self.learner, "d_shards", 1)),
            "feature_shards": int(getattr(self.learner, "f_shards", 1)),
            "hosts": int(getattr(self.learner, "hosts", 1)),
            "tree_learner": str(self.config.tree_learner),
        }

    def capture_train_state(self) -> Tuple[Dict, Dict]:
        """The restart bundle's driver half: a JSON-able state dict plus
        the f32 score arrays.  Pairs with `restore_train_state`; the
        model string (trees + mapper trailer) travels separately."""
        if self.train_data is None or self.learner is None \
                or self.train_scores is None:
            raise ValueError("checkpointing needs a live training context "
                             "(predict-only/file-loaded boosters have "
                             "nothing to resume)")
        self._materialize()
        state = {
            "iteration": int(self.current_iteration()),
            "num_init_iteration": int(self.num_init_iteration),
            "stopped": bool(self._stopped),
            "boosted_from_average": [
                bool(b) for b in getattr(self, "_boosted_from_average", [])],
            "key": self._key_words(self._key),
            "bag_key": self._key_words(self._bag_key),
            "bag_rng": self._bag_rng.bit_generator.state,
            "feature_rng": (self.learner._feature_rng.bit_generator.state
                            if getattr(self.learner, "_feature_rng", None)
                            is not None else None),
            "valid_names": list(self.valid_names),
            "guard_skips": int(self._guard_skips_total),
            "topology": self.topology_snapshot(),
        }
        arrays = {"train_scores": np.asarray(
            jax.device_get(self.train_scores.scores), np.float32)}
        for name, vs in zip(self.valid_names, self.valid_scores):
            arrays[f"valid_scores/{name}"] = np.asarray(
                jax.device_get(vs.scores), np.float32)
        if self._cached_bag_mask is not None:
            arrays["bag_mask"] = np.asarray(
                jax.device_get(self._cached_bag_mask), np.float32)
        extra = self._capture_extra_state()
        if extra:
            state["extra"] = extra
        return state, arrays

    def _capture_extra_state(self) -> Dict:
        return {}

    def _restore_extra_state(self, extra: Dict) -> None:
        pass

    def restore_train_state(self, model_text: str, state: Dict,
                            arrays: Dict) -> None:
        """Rebuild this (freshly-initialized) driver to the checkpointed
        iteration: trees rebind through the bitwise `from_model_string`
        path onto the LIVE training mappers, the f32 score buffers
        restore byte-for-byte (replaying trees through the forest kernel
        would re-round the f32 accumulation in a different order), and
        every PRNG stream resumes mid-sequence — so continued training
        is bit-identical to a never-interrupted run."""
        if self.train_data is None or self.learner is None:
            raise ValueError("restore needs a booster constructed with "
                             "the training dataset")
        self._materialize()
        other = GBDT.from_model_string(model_text)
        if other.num_tree_per_iteration != self.num_tree_per_iteration:
            raise ValueError(
                "checkpoint has different num_tree_per_iteration")
        for tree in other.models:
            if tree.num_leaves > 1:
                self._rebind_tree(tree)
        self.models = list(other.models)
        self._pending = []
        k = max(self.num_tree_per_iteration, 1)
        total = len(self.models) // k
        if int(state.get("iteration", total)) != total:
            raise ValueError(
                f"checkpoint iteration {state.get('iteration')} does not "
                f"match its model ({total} iterations)")
        self.num_init_iteration = int(state.get("num_init_iteration", 0))
        # iter_ counts NEW rounds only (see _materialize_inner)
        self.iter_ = total - self.num_init_iteration
        ts = np.asarray(arrays["train_scores"], np.float32)
        want = (max(self.num_tree_per_iteration, 1),
                int(self.train_data.num_data))
        if tuple(ts.shape) != want:
            raise ValueError(
                f"checkpoint train-score buffer has shape {ts.shape} but "
                f"the live training context needs {want}; the checkpoint "
                "was taken over different data (elastic topology changes "
                "are re-sharded upstream — this is a data mismatch)")
        # .copy() forces an XLA-owned buffer (the fused step DONATES the
        # scores; donating a numpy-aliased zero-copy upload corrupts the
        # heap — same rule as _ScoreState)
        self.train_scores.scores = jnp.asarray(ts).copy()
        meta = self.learner.meta_np
        for name, vs, vd in zip(self.valid_names, self.valid_scores,
                                self.valid_sets):
            a = arrays.get(f"valid_scores/{name}")
            if a is not None and tuple(np.asarray(a).shape) \
                    != tuple(np.asarray(vs.scores.shape)):
                # an elastic resume re-partitioned the valid rows: the
                # stored slice no longer matches — replay instead
                a = None
            if a is not None:
                vs.scores = jnp.asarray(np.asarray(a, np.float32)).copy()
                continue
            # a valid set the checkpointed run did not have: replay the
            # restored model onto it (bitwise matters for TRAIN state;
            # eval-only scores may take the batched path)
            if not self._replay_scores_device(vs, vd, self.models):
                for i, tree in enumerate(self.models):
                    vs.add(i % k, jnp.asarray(
                        _predict_binned(tree, vd.bins, meta)
                        .astype(np.float32)))
        self._key = self._words_to_key(state["key"], self._key)
        self._bag_key = self._words_to_key(state["bag_key"], self._bag_key)
        if state.get("bag_rng") is not None:
            rng = np.random.default_rng(0)
            rng.bit_generator.state = state["bag_rng"]
            self._bag_rng = rng
        if state.get("feature_rng") is not None and \
                getattr(self.learner, "_feature_rng", None) is not None:
            rng = np.random.default_rng(0)
            rng.bit_generator.state = state["feature_rng"]
            self.learner._feature_rng = rng
        bfa = state.get("boosted_from_average")
        if bfa:
            self._boosted_from_average = [bool(b) for b in bfa]
        self._stopped = bool(state.get("stopped", False))
        self._guard_skips_total = int(state.get("guard_skips", 0))
        mask = arrays.get("bag_mask")
        self._cached_bag_mask = (
            None if mask is None
            else jnp.asarray(np.asarray(mask, np.float32)))
        self._invalidate_tables()
        self._restore_extra_state(state.get("extra") or {})

    # ------------------------------------------------------------------
    def eval(self, name: str, valid_idx: int, feval=None, booster=None
             ) -> List[Tuple]:
        with obs.span("metric_eval", dataset=name):
            self._materialize()
            out = []
            if valid_idx < 0:
                scores = self.train_scores.numpy()
                metrics = self.metrics
            else:
                scores = self.valid_scores[valid_idx].numpy()
                metrics = self.valid_metrics[valid_idx]
            for m in metrics:
                for metric_name, val in m.eval_all(scores, self.objective):
                    out.append((name, metric_name, val, m.higher_is_better))
            if feval is not None:
                ds = (self.train_data if valid_idx < 0
                      else self.valid_sets[valid_idx])
                res = feval(scores.reshape(-1), _FevalData(ds))
                for item in (res if isinstance(res, list) else [res]):
                    out.append((name, item[0], item[1], item[2]))
            return out

    def eval_for_data(self, data: TrainingData, name: str, feval=None):
        """Metrics on an AD-HOC dataset without registering it as a valid
        set (reference c_api.cpp:207-230's AddValidData + Eval pair, but
        transient: nothing is appended to valid_sets, so repeated calls
        do not accumulate score state).  The dataset must share the
        training mappers (created with reference=the train set) — same
        alignment contract as add_valid; scores replay through the binned
        walker exactly like add_valid's model replay."""
        self._materialize()
        if self.config is None:
            raise ValueError("eval on data needs a booster constructed "
                             "with a training dataset (file-loaded "
                             "boosters carry no metric config)")
        # alignment contract: bin-space traversal silently produces
        # garbage on foreign mappers.  train() frees train_data by
        # default (free_dataset), so identity can only be checked while
        # the training context is still alive; afterwards the
        # adopted_reference flag (set by reference= construction) is the
        # remaining guard — the reference keeps its C++ train set alive
        # inside the handle and needs neither
        ref_td = (self.train_data if self.train_data is not None
                  else self.learner.td if self.learner is not None else None)
        if ref_td is not None:
            if data.mappers is not ref_td.mappers:
                raise ValueError("eval data must be created with "
                                 "reference=the training dataset")
        elif not getattr(data, "adopted_reference", False):
            raise ValueError("eval data must be created with "
                             "reference=the training dataset")
        ms = create_metrics(self.config,
                            self.objective.name if self.objective else "")
        for m in ms:
            m.init(data.metadata, data.num_data)
        state = _ScoreState(self.num_tree_per_iteration, data.num_data,
                            data.metadata.init_score)
        # per-feature bin metadata comes from the shared mappers, so the
        # eval dataset's own arrays equal the training ones
        meta = data.feature_arrays()
        if not self._replay_scores_device(state, data, self.models,
                                          meta=meta, cache_bins=False):
            for i, tree in enumerate(self.models):
                k = i % self.num_tree_per_iteration
                state.add(k, jnp.asarray(
                    _predict_binned(tree, data.bins, meta)
                    .astype(np.float32)))
        scores = state.numpy()
        out = []
        for m in ms:
            for metric_name, val in m.eval_all(scores, self.objective):
                out.append((name, metric_name, val, m.higher_is_better))
        if feval is not None:
            res = feval(scores.reshape(-1), _FevalData(data))
            for item in (res if isinstance(res, list) else [res]):
                out.append((name, item[0], item[1], item[2]))
        return out

    # ------------------------------------------------------------------
    def _invalidate_tables(self) -> None:
        """Drop the cached raw-value node tables.  The cache keys on model
        COUNT, so any in-place leaf mutation (DART shrinkage, refit,
        set_leaf_value) must invalidate explicitly.  (The binned walker
        packs its tables per call and has no cache to go stale.)"""
        self._ft_key = None
        self._pf = None  # device forest tables share the contract

    def _forest_tables(self):
        """Concatenated node tables for the native predictor, cached per
        model count (models only ever grow or get truncated wholesale)."""
        from ..native import ForestTables

        key = (len(self.models),
               id(self.models[-1]) if self.models else 0)
        if getattr(self, "_ft_key", None) != key:
            self._ft = ForestTables(self.models)
            self._ft_key = key
        return self._ft

    def _score_trees_binned(self, bins: np.ndarray, trees, scales
                            ) -> np.ndarray:
        """sum_i scales[i] * trees[i](binned row) per row.

        One native OMP pass over the listed Tree objects (valid-score
        updates, DART drop/restore, rollback); numpy per-tree level-walk
        fallback when the native lib is unavailable.  The node tables are
        packed PER CALL from just the listed subset — the sets are small,
        and per-call packing cannot go stale when leaf values mutate in
        place (DART shrinkage, refit, set_leaf_value)."""
        from ..native import BinnedForestTables, native_lib

        meta = self.learner.meta_np
        if native_lib() is not None and bins.dtype in (np.uint8, np.uint16):
            tables = BinnedForestTables(list(trees), meta)
            out = tables.predict_subset(
                bins, np.arange(len(trees), dtype=np.int32), scales)
            if out is not None:
                return out
        acc = np.zeros(bins.shape[0], np.float64)
        for tree, sc in zip(trees, scales):
            acc += sc * _predict_binned(tree, bins, meta)
        return acc

    # ------------------------------------------------------------------
    # device-resident prediction (ops/predict.py): jitted bin-space
    # traversal for valid-score updates, score replay, and device='tpu'
    # predict.  The host walker (_predict_binned/_score_trees_binned)
    # stays as the parity oracle and the tiny-data fallback.
    # ------------------------------------------------------------------
    def _device_replay_ok(self, n_rows: int) -> bool:
        """Should score replay for `n_rows` rows run on device?"""
        if self.config is None:
            return False
        from ..config import parse_tristate

        mode = parse_tristate(self.config.tpu_predict_device)
        if mode == "false":
            return False
        if mode == "true":
            return True
        # auto: jit dispatch + compile dominate tiny sets; the host
        # walker stays cheaper there
        return n_rows >= int(self.config.tpu_predict_min_rows)

    def _meta_dev(self):
        """Device (num_bin, default_bin, missing_type) triple, cached per
        learner rebuild (init/reset_training_data/reset_config swap
        meta_np wholesale, never mutate it)."""
        meta = self.learner.meta_np
        # identity held via a strong ref (never a bare id(): a freed dict
        # and its successor can share an address)
        if getattr(self, "_meta_dev_for", None) is not meta:
            self._meta_dev_cache = feature_meta_dev(meta)
            self._meta_dev_for = meta
        return self._meta_dev_cache

    def _device_bins_for(self, data: TrainingData, cache: bool):
        """Device int32 bins for a dataset.  cache=True keeps them on the
        dataset (valid sets: reused every iteration); cache=False ships a
        one-shot copy for replay over the TRAINING bins, which the
        learner already holds in its own layout — caching a second
        full-size copy there would pin 4x-uint8 HBM for one pass."""
        faultline.fire("h2d_copy", rows=data.num_data)
        if cache:
            return data.device_bins()
        if data._device_bins is not None:  # already resident: reuse
            return data._device_bins
        if data._ingest_bins is not None:  # device ingest: widen in place
            return data.ingest_matrix().astype(jnp.int32)
        return jnp.asarray(data.bins.astype(np.int32))

    def _tree_delta_device(self, data: TrainingData, tree: Tree,
                           bins_dev=None, pack_cache: Optional[Dict] = None):
        """Device [n] f32 leaf values of ONE tree over a binned dataset;
        None -> caller uses the host walker.  The per-iteration valid-
        score path: packs just the new tree (never the forest) and does
        zero device_get.  `pack_cache` (a per-tree dict) reuses the
        packed device tables across multiple valid sets."""
        if not data.has_bins or tree.num_leaves < 1 \
                or self.learner is None \
                or not self._device_replay_ok(data.num_data):
            return None
        if bins_dev is None:
            bins_dev = data.device_bins()
        if pack_cache is not None and "packed" in pack_cache:
            tables_dev, depth = pack_cache["packed"]
        else:
            # pinned leaf width + pow2-padded bitset pool: every tree of
            # a training run packs to ONE table shape, so the jitted
            # kernel compiles a handful of programs instead of one per
            # tree shape
            tables, depth = pack_trees(
                [tree], leaf_width=int(self.config.num_leaves),
                pad_cat_words=True)
            tables_dev = device_tables(tables)
            if pack_cache is not None:
                pack_cache["packed"] = (tables_dev, depth)
        with membudget.oom_guard("score_replay", rows=data.num_data):
            vals = forest_leaf_values(tables_dev, bins_dev,
                                      self._meta_dev(), depth,
                                      policy=self.bucket_policy())
        return vals[0]

    def _replay_scores_device(self, state: "_ScoreState", data: TrainingData,
                              trees, scale: float = 1.0, meta=None,
                              cache_bins: bool = True) -> bool:
        """Batch-replay `trees` (class = position % k) into a score state
        on device; False -> caller must use the host walker."""
        if not trees or not data.has_bins \
                or not self._device_replay_ok(data.num_data):
            return False
        if meta is not None:
            md = feature_meta_dev(meta)
        elif self.learner is not None:
            md = self._meta_dev()
        else:
            return False
        bins_dev = self._device_bins_for(data, cache_bins)
        k = max(self.num_tree_per_iteration, 1)
        # bound the kernel's [T, rows] node-state intermediates: trees in
        # blocks of ~128 (multiples of k so position % k stays the global
        # class id), rows in device-sliced chunks — a 2000-tree forest on
        # a multi-million-row set must not become one O(T*n) launch
        t_block = k * max(128 // k, 1)
        chunk = max(int(self.config.tpu_predict_chunk_rows), 1024)
        n = data.num_data
        for s in range(0, len(trees), t_block):
            tables, depth = pack_trees(list(trees[s:s + t_block]))
            tables_dev = device_tables(tables)
            with membudget.oom_guard("score_replay", rows=n,
                                     trees=len(trees)):
                if n > chunk:
                    parts = []
                    for lo in range(0, n, chunk):
                        hi = min(lo + chunk, n)
                        sub = bins_dev[lo:hi]
                        if hi - lo < chunk:
                            # pad the tail: every launch = ONE program
                            sub = jnp.concatenate(
                                [sub, jnp.zeros((chunk - (hi - lo),
                                                 sub.shape[1]),
                                                sub.dtype)])
                        parts.append(forest_class_scores(
                            tables_dev, sub, md, k, depth, scale,
                            policy=self.bucket_policy())[:, :hi - lo])
                    scores = jnp.concatenate(parts, axis=1)
                else:
                    scores = forest_class_scores(
                        tables_dev, bins_dev, md, k, depth, scale,
                        policy=self.bucket_policy())
            for kk in range(k):
                state.add(kk, scores[kk])
        return True

    def snapshot_predict_context(self) -> None:
        """Capture the bin mappers + per-feature metadata so device
        predict survives free_dataset (the training data itself is
        dropped; the mappers are small host objects)."""
        td = (self.train_data if self.train_data is not None
              else self.learner.td if self.learner is not None else None)
        if td is not None:
            self._pred_ctx = _PredictContext.from_training_data(td)
        # the health profile needs the training data too: capture it now
        # so a freed (predict-only) booster still writes the trailer
        self._profile = self.health_profile()

    def _pred_context(self) -> Optional["_PredictContext"]:
        td = (self.train_data if self.train_data is not None
              else self.learner.td if self.learner is not None else None)
        if td is not None:
            # cache per dataset object (strong ref, compared by identity:
            # mappers/meta only change when the dataset itself is swapped
            # by reset_training_data, which replaces the ref here too)
            if getattr(self, "_pred_ctx_for", None) is not td:
                self._pred_ctx_live = _PredictContext.from_training_data(td)
                self._pred_ctx_for = td
            return self._pred_ctx_live
        return getattr(self, "_pred_ctx", None)

    def _packed_forest(self) -> PackedForest:
        """Appendable device forest tables, cached across predict calls;
        append-only between invalidations (truncation or a reordering
        rebuilds, in-place leaf mutation goes through
        _invalidate_tables)."""
        pf = getattr(self, "_pf", None)
        if pf is not None and pf._count > 0 and (
                pf._count > len(self.models)
                or id(self.models[pf._count - 1]) != self._pf_last):
            pf = None  # truncated or reordered: rebuild from scratch
        if pf is None:
            pf = PackedForest()
            self._pf = pf
        pf.sync(self.models)
        self._pf_last = id(self.models[pf._count - 1]) if pf._count else 0
        return pf

    def _model_subset(self, num_iteration: int) -> Tuple[int, float]:
        """(tree count, RF-averaging divisor) for a num_iteration subset
        — the ONE place the slicing + average_output rules live, shared
        by every predict path."""
        k = max(self.num_tree_per_iteration, 1)
        total = len(self.models)
        if num_iteration is not None and num_iteration > 0:
            total = min(total, num_iteration * k)
        div = (float(max(total // k, 1))
               if self.average_output and total > 0 else 1.0)
        return total, div

    def predict_raw_device(self, X: np.ndarray, num_iteration: int = -1
                           ) -> Optional[np.ndarray]:
        """[k, n] raw scores via the jitted bin-space predictor: bin the
        raw rows with the training mappers, then traverse the packed
        forest on device in fixed row chunks.  None when the booster has
        no binning context (file-loaded model) or no trees."""
        self._materialize()
        ctx = self._pred_context()
        if ctx is None or not self.models:
            return None
        total, div = self._model_subset(num_iteration)
        if total == 0:
            return None
        pf = self._packed_forest()
        out = self._chunked_device_scores(
            pf.device(total),  # num_iteration subset = table slice
            ctx.meta_dev(), self.num_tree_per_iteration, pf.depth,
            X.shape[0], lambda lo, hi: ctx.bin_rows(X[lo:hi]))
        return out / div

    def predict_chunk_rows(self) -> int:
        """Rows per device-predict launch (file-loaded boosters carry no
        Config; they use the registry default) — the chunk every predict
        row bucket is computed against.  A chunked-predict OOM shrinks
        this (config param, or the local override for config-less
        boosters) down to the membudget floor."""
        if self.config is not None:
            return max(int(self.config.tpu_predict_chunk_rows), 1024)
        ov = getattr(self, "_predict_chunk_override", None)
        return max(int(ov) if ov is not None else 65536, 1024)

    def _shrink_predict_chunk(self) -> bool:
        """Halve the predict chunk after a classified predict-path OOM;
        False at the floor (the caller re-raises the structured error).
        Bitwise-invisible: traversal is row-independent, so chunking
        never changes an output byte (the PR-3/PR-6 chunk contracts)."""
        from ..utils.log import Log

        cur = self.predict_chunk_rows()
        if cur <= membudget.CHUNK_FLOOR:
            return False
        new = max(cur // 2, membudget.CHUNK_FLOOR)
        if self.config is not None:
            self.config.update({"tpu_predict_chunk_rows": new})
        else:
            self._predict_chunk_override = new
        membudget.note_ladder_step("predict_chunk", "shrink_chunk_rows",
                                   {"tpu_predict_chunk_rows": new})
        Log.warning(f"device OOM in chunked predict: shrinking "
                    f"tpu_predict_chunk_rows {cur} -> {new} and "
                    "re-running (outputs are chunk-invariant)")
        return True

    def bucket_policy(self) -> str:
        """Launch-shape bucket policy (tpu_bucket_policy) — the ONE
        quantization ladder shared by score replay, chunked predict, and
        the serving warmup enumeration (ops/predict.py
        BUCKET_POLICIES)."""
        return (str(self.config.tpu_bucket_policy)
                if self.config is not None else "wide")

    def _chunked_device_scores(self, tables, meta_dev, k: int, depth: int,
                               n: int, get_bins) -> np.ndarray:
        """[k, n] f64 host scores from the packed device forest, chunked
        over rows: one bounded [chunk, F] int32 upload per launch, tail
        chunks padded so every launch reuses ONE compiled program.
        `get_bins(lo, hi)` supplies host bins per chunk.

        A classified device OOM shrinks the predict chunk (floor 4096)
        and resumes AT THE FAILED CHUNK — completed chunks are kept
        (outputs are chunk-invariant, so the recovered result is
        byte-identical and no finished device work is re-paid); at the
        floor the structured DeviceOutOfMemory propagates to the
        caller (the serving layer then fails the batch over to the
        native walker)."""
        out = np.zeros((k, n), np.float64)
        lo = 0
        with obs.resources.phase_peak("predict"):
            while True:
                # the chunk re-reads per launch: a shrink mid-predict
                # applies from the failed chunk onward
                chunk = self.predict_chunk_rows()
                hi = min(lo + chunk, n)
                rows = hi - lo
                try:
                    faultline.fire("h2d_copy", rows=rows)
                    bins = get_bins(lo, hi)
                    # pad every launch to a bucketed row count
                    # (row_bucket: full chunks for multi-chunk
                    # predicts, the policy's geometric ladder below
                    # that) so repeated predicts of varying batch
                    # sizes reuse a handful of compiled programs
                    # instead of one per distinct n
                    policy = self.bucket_policy()
                    target = (chunk if n > chunk
                              else row_bucket(rows, chunk,
                                              policy=policy))
                    if rows < target:
                        bins = np.concatenate(
                            [bins,
                             np.zeros((target - rows, bins.shape[1]),
                                      np.int32)])
                    with membudget.oom_guard("predict_chunk",
                                             rows=rows):
                        scores = forest_class_scores(
                            tables, jnp.asarray(bins), meta_dev, k,
                            depth, policy=policy)
                        out[:, lo:hi] = np.asarray(
                            jax.device_get(scores),
                            np.float64)[:, :rows]
                except membudget.DeviceOutOfMemory:
                    if not self._shrink_predict_chunk():
                        raise
                    continue  # retry THIS chunk at the smaller size
                lo = hi
                if lo >= n:
                    break
        return out

    def predict_binned_device(self, data: TrainingData,
                              num_iteration: int = -1,
                              raw_score: bool = False) -> np.ndarray:
        """Device predict on an ALREADY-BINNED dataset sharing the
        training mappers (the pre-binned half of the device='tpu'
        predict path — no host binning pass at all)."""
        self._materialize()
        ctx = self._pred_context()
        if ctx is None:
            raise ValueError("device predict on binned data needs a booster "
                             "with a training context (file-loaded boosters "
                             "carry no bin mappers)")
        if not data.has_bins:
            raise ValueError("dataset has no binned representation")
        # strict identity: the mapper list survives free_dataset inside
        # the snapshot, so unlike eval_for_data there is no freed-booster
        # gap to bridge — a looser check would silently traverse foreign
        # bin space
        if data.mappers is not ctx.mappers:
            raise ValueError("predict data must be created with "
                             "reference=the training dataset")
        k = self.num_tree_per_iteration
        total, div = self._model_subset(num_iteration)
        n = data.num_data
        raw = np.zeros((k, n), np.float64)
        if total > 0:
            pf = self._packed_forest()
            # chunk straight off the HOST bins: one bounded [chunk, F]
            # upload per launch, nothing cached on the caller's dataset
            raw = self._chunked_device_scores(
                pf.device(total), ctx.meta_dev(), k, pf.depth, n,
                lambda lo, hi: np.ascontiguousarray(
                    data.bins[lo:hi].astype(np.int32))) / div
        return self._finish_predict(raw, raw_score)

    def _finish_predict(self, raw: np.ndarray, raw_score: bool) -> np.ndarray:
        if not raw_score and self.objective is not None:
            raw = self.objective.convert_output(raw)
        if raw.shape[0] == 1:
            return raw[0]
        return raw.T  # [n, k] multiclass

    def predict_raw(self, X: np.ndarray, num_iteration: int = -1,
                    early_stop_freq: int = 0,
                    early_stop_margin: float = 0.0) -> np.ndarray:
        """[k, n] raw scores from raw feature matrix.

        early_stop_freq > 0 enables prediction early stopping (reference
        src/boosting/prediction_early_stop.cpp:75-81): rows whose margin
        already exceeds early_stop_margin skip the remaining trees.
        """
        self._materialize()
        X = np.asarray(X, np.float64)
        if X.ndim == 1:
            X = X[None, :]
        k = self.num_tree_per_iteration
        total, rf_div = self._model_subset(num_iteration)
        # native OpenMP walker over all trees at once (the per-tree Python
        # loop dominated wall-clock at hundreds of trees); numpy fallback
        # when the native lib is unavailable
        out = self._forest_tables().predict(X, total, k, early_stop_freq,
                                            early_stop_margin)
        if out is None:
            out = np.zeros((k, X.shape[0]), np.float64)
            active = np.ones(X.shape[0], bool)
            for i in range(total):
                if early_stop_freq > 0 and not active.any():
                    break
                Xa = X[active] if early_stop_freq > 0 else X
                if early_stop_freq > 0:
                    out[i % k, active] += self.models[i].predict(Xa)
                else:
                    out[i % k] += self.models[i].predict(X)
                if (early_stop_freq > 0 and i % k == k - 1
                        and (i // k + 1) % early_stop_freq == 0):
                    if k == 1:
                        margin = np.abs(out[0])
                    else:
                        top2 = np.sort(out, axis=0)[-2:]
                        margin = top2[1] - top2[0]
                    active &= margin < early_stop_margin
        out /= rf_div  # RF averaging (gbdt_prediction.cpp:55)
        return out

    def predict(self, X: np.ndarray, num_iteration: int = -1,
                raw_score: bool = False, pred_leaf: bool = False,
                pred_contrib: bool = False, pred_early_stop: bool = False,
                pred_early_stop_freq: int = 10,
                pred_early_stop_margin: float = 10.0,
                device_predict: bool = False) -> np.ndarray:
        self._materialize()
        X = np.asarray(X, np.float64)
        if X.ndim == 1:
            X = X[None, :]
        if pred_leaf:
            k = self.num_tree_per_iteration
            total = len(self.models)
            if num_iteration is not None and num_iteration > 0:
                total = min(total, num_iteration * k)
            leaves = self._forest_tables().predict_leaf(X, total)
            if leaves is None:
                leaves = np.stack([self.models[i].predict_leaf(X)
                                   for i in range(total)], axis=1)
            return leaves
        if pred_contrib:
            from .shap import forest_contribs

            k = self.num_tree_per_iteration
            total = len(self.models)
            if num_iteration is not None and num_iteration > 0:
                total = min(total, num_iteration * k)
            out = forest_contribs(self.models, X, total, k)
            if k == 1:
                return out[:, 0, :]                      # [n, F+1]
            return out.reshape(X.shape[0], -1)           # [n, k*(F+1)]
        raw = None
        if device_predict and not pred_early_stop:
            # device bin-space traversal; prediction early stopping keeps
            # the native walker (its per-row margin bailout is inherently
            # row-sequential)
            raw = self.predict_raw_device(X, num_iteration)
        if raw is None:
            raw = self.predict_raw(
                X, num_iteration,
                early_stop_freq=(int(pred_early_stop_freq)
                                 if pred_early_stop else 0),
                early_stop_margin=float(pred_early_stop_margin))
        return self._finish_predict(raw, raw_score)

    # ------------------------------------------------------------------
    def refit(self, X: np.ndarray, label: np.ndarray,
              decay_rate: float = 0.9,
              config: Optional[Config] = None) -> None:
        """Re-fit leaf values on new data, keeping every tree's structure.

        The analog of GBDT::RefitTree (reference src/boosting/gbdt.cpp:298)
        + FitByExistingTree (serial_tree_learner.cpp:239-270): per
        iteration, gradients are taken at the running refit scores; each
        tree's rows are grouped by the OLD tree's leaf assignment on the
        new data, the regularized leaf output is recomputed from the new
        sums, and blended as decay*old + (1-decay)*new*shrinkage.
        """
        self._materialize()
        cfg = config or self.config or Config({})
        X = np.asarray(X, np.float64)
        if X.ndim == 1:
            X = X[None, :]
        n = X.shape[0]
        leaf_preds = self.predict(X, pred_leaf=True)       # [n, T]

        from ..io.dataset import Metadata

        md = Metadata(num_data=n, label=np.asarray(label, np.float32))
        # a fresh objective instance bound to the NEW labels (never re-init
        # the live training objective)
        try:
            obj = create_objective(cfg)
        except ValueError:
            # a loaded booster's params carry the MODEL-STRING objective
            # format ('binary sigmoid:1'), which the config-side factory
            # rejects — fall through to the model-string parser
            obj = None
        if obj is None:
            obj = create_objective_from_model_string(
                self.loaded_params.get("objective", "")
                or str(cfg.objective or ""))
        if obj is None:
            raise ValueError("cannot refit without an objective")
        obj.init(md, n)

        scores = self._refit_by_leaf_preds(leaf_preds, obj, decay_rate, cfg)
        self._recapture_profile_scores(scores, np.asarray(label, np.float64))

    def _refit_by_leaf_preds(self, leaf_preds: np.ndarray, obj,
                             decay_rate: float, cfg: Config) -> None:
        """Shared RefitTree core: per iteration take gradients at the
        running refit scores and re-fit each tree's leaf values from the
        given [n, T] leaf assignment (reference gbdt.cpp:298 +
        FitByExistingTree)."""
        n = leaf_preds.shape[0]
        k = self.num_tree_per_iteration
        l1 = float(cfg.lambda_l1)
        l2 = float(cfg.lambda_l2)
        mds = float(cfg.max_delta_step)
        decay = float(decay_rate)
        scores = np.zeros((k, n), np.float64)
        grad = hess = None
        for i, tree in enumerate(self.models):
            cid = i % k
            if cid == 0:
                g, h = obj.get_gradients(jnp.asarray(scores, jnp.float32))
                grad = np.asarray(g, np.float64).reshape(k, n)
                hess = np.asarray(h, np.float64).reshape(k, n)
            leaves = leaf_preds[:, i].astype(np.int64)
            nl = tree.num_leaves
            sum_g = np.bincount(leaves, weights=grad[cid], minlength=nl)
            sum_h = np.bincount(leaves, weights=hess[cid], minlength=nl) \
                + K_EPSILON
            # CalculateSplittedLeafOutput (feature_histogram.hpp:449-456)
            reg = np.maximum(np.abs(sum_g) - l1, 0.0) * np.sign(sum_g)
            new_out = -reg / (sum_h + l2)
            if mds > 0.0:
                new_out = np.clip(new_out, -mds, mds)
            old = tree.leaf_value[:nl]
            tree.leaf_value[:nl] = (decay * old
                                    + (1.0 - decay) * new_out * tree.shrinkage)
            scores[cid] += tree.leaf_value[leaves]
        self._invalidate_tables()  # leaf values changed in place
        return scores

    def _recapture_profile_scores(self, scores: np.ndarray,
                                  label: np.ndarray) -> None:
        """Carry the model-health profile through refit: tree structure
        and the per-feature bin occupancy stay the TRAINING reference,
        but the raw-score histogram (and label stats) must describe the
        REFIT scores — a drift monitor comparing the stale histogram
        against post-refit traffic would flag the refit itself as a
        score shift."""
        base = self.health_profile()
        if base is None:
            return
        from ..obs import modelhealth

        s = np.asarray(scores, np.float64)
        if s.ndim == 1:
            s = s[None, :]
        fin = s[np.isfinite(s)]
        lo = float(fin.min()) if fin.size else 0.0
        hi = float(fin.max()) if fin.size else 1.0
        if hi <= lo:
            hi = lo + 1.0
        nb = max(len(base.score_edges) - 1, 2)
        edges = [float(x) for x in np.linspace(lo, hi, nb + 1)]
        counts = [[int(x) for x in
                   modelhealth.score_hist_counts(edges, row)]
                  for row in s]
        y = np.asarray(label, np.float64)
        lab = {"n": int(y.size),
               "mean": float(y.mean()) if y.size else 0.0,
               "std": float(y.std()) if y.size else 0.0,
               "min": float(y.min()) if y.size else 0.0,
               "max": float(y.max()) if y.size else 0.0}
        self._profile = modelhealth.FeatureProfile(
            {c: dict(f) for c, f in base.features.items()},
            lab, edges, counts)

    def reset_config(self, config: Config) -> None:
        self._materialize()
        self.config = config
        self.shrinkage_rate = float(config.learning_rate)
        if self.learner is not None:
            self.learner = make_tree_learner(config, self.train_data)
            self._bag_cfg = self._bagging_config()
            self._maybe_make_train_step()

    def shuffle_models(self, start: int = 0, end: int = -1) -> None:
        self._materialize()
        # reordering invalidates both node-table caches: their staleness
        # keys sample only (count, last tree), which a shuffle can leave
        # untouched
        self._invalidate_tables()
        if end < 0:
            end = len(self.models)
        rng = np.random.default_rng(0)
        seg = self.models[start:end]
        rng.shuffle(seg)
        self.models[start:end] = seg

    def _note_tree_telemetry(self, tree: Tree) -> None:
        """Training-quality telemetry for one NEWLY-TRAINED tree (ISSUE
        14): per-feature split/gain counters plus leaf-count and depth
        distributions into the process-global registry.  Gated on
        `obs.metrics_on()` (one bool check per tree when off).  The
        per-split inc order matches `feature_importance`'s flat
        (tree, node) walk exactly, so the f64 counter totals are
        BIT-EQUAL to feature_importance('gain')/('split') over the same
        trees (tests/test_modelhealth.py cross-checks both, including
        after a model-string reload).  Counters are monotonic: a
        rolled-back iteration's trees are not subtracted."""
        if not obs.metrics_on():
            return
        names = self.feature_names
        for j in range(tree.num_leaves - 1):
            f = int(tree.split_feature[j])
            fname = names[f] if f < len(names) else f"Column_{f}"
            obs.REGISTRY.inc(
                "lgbm_train_splits_total", 1,
                help="splits per feature across trained trees",
                feature=fname)
            obs.REGISTRY.inc(
                "lgbm_train_split_gain_total",
                max(float(tree.split_gain[j]), 0.0),
                help="summed split gain per feature", feature=fname)
        obs.REGISTRY.observe(
            "lgbm_train_leaf_count", float(tree.num_leaves),
            buckets=_LEAF_BUCKETS,
            help="leaves per trained tree")
        obs.REGISTRY.observe(
            "lgbm_train_tree_depth", float(tree.max_depth()),
            buckets=_DEPTH_BUCKETS,
            help="depth per trained tree")

    def health_profile(self):
        """The model-health reference profile (obs/modelhealth.py
        FeatureProfile) this booster serializes as its
        ``tpu_feature_profile:`` trailer.  A LIVE training booster
        rebuilds it per call (scores move every iteration); a loaded or
        freed booster returns the parsed/snapshotted one unchanged —
        which is what makes the trailer byte-identical through
        save -> load -> save.  None when capture is disabled
        (tpu_profile_capture=false) and nothing was loaded."""
        td = self.train_data
        if td is not None and self.train_scores is not None:
            if self.config is not None and \
                    not bool(self.config.tpu_profile_capture):
                return self._profile
            from ..obs import modelhealth

            score_bins = (int(self.config.tpu_profile_score_bins)
                          if self.config is not None
                          else modelhealth.DEFAULT_SCORE_BINS)
            prof = modelhealth.FeatureProfile.from_training(
                td, self.feature_names, self.train_scores.numpy(),
                score_bins)
            # nothing capturable (e.g. count-less mappers from an old
            # snapshot): a profile loaded from the trailer must still
            # round-trip rather than silently vanish on re-save
            return prof if prof is not None else self._profile
        return self._profile

    def feature_importance(self, importance_type: str = "split") -> np.ndarray:
        self._materialize()
        imp = np.zeros(self.max_feature_idx + 1, np.float64)
        for tree in self.models:
            ni = tree.num_leaves - 1
            for j in range(ni):
                f = int(tree.split_feature[j])
                if importance_type == "split":
                    imp[f] += 1
                else:
                    imp[f] += max(float(tree.split_gain[j]), 0.0)
        if importance_type == "split":
            return imp.astype(np.int64).astype(np.float64)
        return imp

    # ------------------------------------------------------------------
    # model IO (reference src/boosting/gbdt_model_text.cpp)
    # ------------------------------------------------------------------
    def _feature_infos(self) -> List[str]:
        infos = []
        td = self.train_data
        if td is None:
            return list(self.loaded_params.get("feature_infos", []))
        used = set(td.used_feature_idx)
        for i, m in enumerate(td.mappers):
            if i not in used or m.is_trivial:
                infos.append("none")
            elif m.bin_type.name == "CATEGORICAL":
                cats = sorted(m.bin_2_categorical)
                infos.append(f"{':'.join(str(c) for c in cats)}")
            else:
                infos.append(f"[{m.min_val!r}:{m.max_val!r}]")
        return infos

    def save_model_to_string(self, num_iteration: int = -1,
                             start_iteration: int = 0) -> str:
        self._materialize()
        buf = io.StringIO()
        buf.write("tree\n")
        buf.write("version=v3\n")
        buf.write(f"num_class={self.num_class}\n")
        buf.write(f"num_tree_per_iteration={self.num_tree_per_iteration}\n")
        buf.write(f"label_index={self.label_index}\n")
        buf.write(f"max_feature_idx={self.max_feature_idx}\n")
        if self.objective is not None:
            buf.write(f"objective={self.objective.to_model_string()}\n")
        if self.average_output:
            buf.write("average_output\n")  # bare flag (gbdt_model_text.cpp:289)
        buf.write("feature_names=" + " ".join(self.feature_names) + "\n")
        buf.write("feature_infos=" + " ".join(self._feature_infos()) + "\n")

        total = len(self.models)
        k = self.num_tree_per_iteration
        start = start_iteration * k
        end = total
        if num_iteration is not None and num_iteration > 0:
            end = min(total, start + num_iteration * k)
        tree_strs = []
        for i in range(start, end):
            s = f"Tree={i - start}\n" + self.models[i].to_string()
            tree_strs.append(s)
        buf.write("tree_sizes=" + " ".join(str(len(s)) for s in tree_strs) + "\n")
        buf.write("\n")
        for s in tree_strs:
            buf.write(s)
        buf.write("\nend of trees\n")
        # feature importances (split counts, descending)
        imp = self.feature_importance("split")
        pairs = [(int(v), self.feature_names[i]) for i, v in enumerate(imp) if v > 0]
        pairs.sort(key=lambda t: -t[0])
        buf.write("\nfeature_importances:\n")
        for v, name in pairs:
            buf.write(f"{name}={v}\n")
        buf.write("\nparameters:\n")
        if self.config is not None:
            for key, val in self.config.params.items():
                if isinstance(val, list):
                    val = ",".join(str(x) for x in val)
                if isinstance(val, bool):
                    val = int(val)
                buf.write(f"[{key}: {val}]\n")
        buf.write("\nend of parameters\n")
        # Python-layer trailer (like `pandas_categorical:` below it): the
        # bin-mapper snapshot that lets a RELOADED model keep the device
        # predict path.  The reference parser ignores trailing lines, so
        # files stay interchange-compatible.
        ctx = self._pred_context()
        if ctx is not None:
            import json

            buf.write(_MAPPER_MARKER + json.dumps(ctx.to_payload()) + "\n")
        # model-health trailer (ISSUE 14): the training reference
        # profile, same round-trip contract as the mapper snapshot —
        # the reference parser ignores trailing lines either way
        prof = self.health_profile()
        if prof is not None:
            buf.write(prof.to_line())
        return buf.getvalue()

    @classmethod
    def from_model_string(cls, text: str) -> "GBDT":
        self = cls()
        # Python-layer files end with one `pandas_categorical:<json>` line
        # (both here and in the reference package); the model parser
        # ignores it — Booster extracts its value separately
        pos = text.rfind("\npandas_categorical:")
        if pos >= 0:
            text = text[:pos]
        from ..obs.modelhealth import split_profile_trailer

        text, profile = split_profile_trailer(text)
        self._profile = profile
        text, ctx = _split_mapper_snapshot(text)
        lines = text.split("\n")
        kv: Dict[str, str] = {}
        tree_blocks: List[str] = []
        i = 0
        while i < len(lines):
            line = lines[i]
            if line.startswith("Tree="):
                block = [line]
                i += 1
                while i < len(lines) and not lines[i].startswith("Tree=") \
                        and not lines[i].startswith("end of trees"):
                    block.append(lines[i])
                    i += 1
                tree_blocks.append("\n".join(block))
                continue
            if line.startswith("end of trees"):
                break
            if line.strip() == "average_output":
                kv["average_output"] = "1"
            elif "=" in line:
                key, v = line.split("=", 1)
                kv[key] = v
            i += 1
        self.num_class = int(kv.get("num_class", "1"))
        self.average_output = "average_output" in kv
        self.num_tree_per_iteration = int(kv.get("num_tree_per_iteration", "1"))
        self.label_index = int(kv.get("label_index", "0"))
        self.max_feature_idx = int(kv.get("max_feature_idx", "0"))
        self.feature_names = kv.get("feature_names", "").split()
        self.loaded_params = {"feature_infos": kv.get("feature_infos", "").split(),
                              "objective": kv.get("objective", "")}
        if "objective" in kv:
            self.objective = create_objective_from_model_string(kv["objective"])
        for block in tree_blocks:
            self.models.append(Tree.from_string(
                block.split("\n", 1)[1] if "\n" in block else ""))
        if ctx is not None:
            # re-enter bin space: loaded trees carry only raw-value
            # thresholds; with the snapshot mappers restored, rebinding
            # is EXACT (each saved threshold is a bin upper bound, and
            # value_to_bin maps it back to the same bin)
            try:
                used_pos = {col: j for j, col
                            in enumerate(ctx.used_feature_idx)}
                for tree in self.models:
                    if tree.num_leaves > 1:
                        _rebind_tree_to_mappers(tree, ctx.mappers, used_pos)
                self._pred_ctx = ctx
            except (KeyError, ValueError, IndexError):
                # a hand-edited model may split on columns the snapshot
                # never binned; the native walker stays available
                self._pred_ctx = None
        self.num_init_iteration = self.current_iteration()
        self.iter_ = 0
        return self

    def _rebind_tree(self, tree: Tree) -> None:
        """Map a loaded tree's real-feature splits back into bin space so the
        binned traversal (_predict_binned) is valid for score replay."""
        used_pos = {col: j for j, col in
                    enumerate(self.train_data.used_feature_idx)}
        _rebind_tree_to_mappers(tree, self.train_data.mappers, used_pos)

    def merge_from_model_string(self, text: str) -> None:
        """Continued training: prepend a loaded model (init_model)."""
        self._materialize()
        other = GBDT.from_model_string(text)
        if other.num_tree_per_iteration != self.num_tree_per_iteration:
            raise ValueError("init model has different num_tree_per_iteration")
        for tree in other.models:
            if tree.num_leaves > 1:
                self._rebind_tree(tree)
        self.models = other.models + self.models
        self.num_init_iteration = other.current_iteration()
        # replay loaded trees onto the score states (device batch pass
        # per dataset when eligible, host walker otherwise)
        datasets = [(self.train_scores, self.train_data, False)] + \
            [(vs, vd, True) for vs, vd in zip(self.valid_scores,
                                              self.valid_sets)]
        meta = self.learner.meta_np
        for state, data, cache in datasets:
            if self._replay_scores_device(state, data, other.models,
                                          cache_bins=cache):
                continue
            for i, tree in enumerate(other.models):
                kk = i % self.num_tree_per_iteration
                state.add(kk, jnp.asarray(
                    _predict_binned(tree, data.bins, meta)
                    .astype(np.float32)))

    def dump_model(self, num_iteration: int = -1, start_iteration: int = 0) -> Dict:
        self._materialize()
        k = self.num_tree_per_iteration
        start = start_iteration * k
        end = len(self.models)
        if num_iteration is not None and num_iteration > 0:
            end = min(end, start + num_iteration * k)
        out = {
            "name": "tree",
            "version": "v3",
            "average_output": bool(self.average_output),
            "num_class": self.num_class,
            "num_tree_per_iteration": self.num_tree_per_iteration,
            "label_index": self.label_index,
            "max_feature_idx": self.max_feature_idx,
            "objective": (self.objective.to_model_string()
                          if self.objective else "none"),
            "feature_names": list(self.feature_names),
            "tree_info": [self._tree_to_json(i, self.models[i])
                          for i in range(start, end)],
        }
        return out

    def _tree_to_json(self, idx: int, tree: Tree) -> Dict:
        def node(i: int) -> Dict:
            if i < 0:
                leaf = ~i
                return {
                    "leaf_index": int(leaf),
                    "leaf_value": float(tree.leaf_value[leaf]),
                    "leaf_weight": float(tree.leaf_weight[leaf]),
                    "leaf_count": int(tree.leaf_count[leaf]),
                }
            dt = int(tree.decision_type[i])
            if dt & 1:
                # categorical: the reference dump emits the bitset's raw
                # categories joined by "||" (reference src/io/tree.cpp
                # ToJSON categorical branch), not the internal set index
                ci = int(tree.threshold[i])
                lo, hi = tree.cat_boundaries[ci], tree.cat_boundaries[ci + 1]
                cats = [32 * (w - lo) + b
                        for w in range(lo, hi) for b in range(32)
                        if (tree.cat_threshold[w] >> b) & 1]
                thr = "||".join(str(c) for c in cats)
            else:
                thr = float(tree.threshold[i])
            d = {
                "split_index": int(i),
                "split_feature": int(tree.split_feature[i]),
                "split_gain": float(tree.split_gain[i]),
                "threshold": thr,
                "decision_type": "==" if dt & 1 else "<=",
                "default_left": bool(dt & 2),
                "missing_type": ["None", "Zero", "NaN"][(dt >> 2) & 3],
                "internal_value": float(tree.internal_value[i]),
                "internal_weight": float(tree.internal_weight[i]),
                "internal_count": int(tree.internal_count[i]),
                "left_child": node(int(tree.left_child[i])),
                "right_child": node(int(tree.right_child[i])),
            }
            return d
        return {
            "tree_index": idx,
            "num_leaves": int(tree.num_leaves),
            "num_cat": int(tree.num_cat),
            "shrinkage": float(tree.shrinkage),
            "tree_structure": node(0) if tree.num_leaves > 1 else {
                "leaf_value": float(tree.leaf_value[0])},
        }


class _PredictContext:
    """The slice of a TrainingData needed to bin + device-predict raw
    rows: mappers, used-column map, per-feature bin metadata.  Snapshot
    by free_dataset so trained boosters keep the device path, and
    round-tripped through the model string (`tpu_bin_mappers:` trailer)
    so SAVED models keep it too — the serving registry depends on
    reloaded models staying on the packed-forest path."""

    def __init__(self, mappers: List[BinMapper], used_feature_idx):
        self.mappers = mappers
        self.used_feature_idx = list(used_feature_idx)
        idx = self.used_feature_idx
        self.meta = {
            "num_bin": np.array([mappers[i].num_bin for i in idx], np.int32),
            "default_bin": np.array([mappers[i].default_bin for i in idx],
                                    np.int32),
            "missing_type": np.array([int(mappers[i].missing_type)
                                      for i in idx], np.int32),
        }
        self._meta_dev = None

    @classmethod
    def from_training_data(cls, td: TrainingData) -> "_PredictContext":
        # keeps the SAME mapper list object: predict_binned_device's
        # strict `data.mappers is ctx.mappers` identity check relies on it
        return cls(td.mappers, td.used_feature_idx)

    # -- model-string round trip ---------------------------------------
    def to_payload(self) -> Dict:
        """JSON-able snapshot: only used columns carry a real mapper
        (trivial columns rebuild as defaults — bin_rows never reads
        them)."""
        return {
            "num_total_features": len(self.mappers),
            "used_feature_idx": self.used_feature_idx,
            "mappers": {str(c): self.mappers[c].to_dict()
                        for c in self.used_feature_idx},
        }

    @classmethod
    def from_payload(cls, payload: Dict) -> "_PredictContext":
        total = int(payload["num_total_features"])
        used = [int(i) for i in payload["used_feature_idx"]]
        mappers = [BinMapper() for _ in range(total)]
        for key, d in payload["mappers"].items():
            mappers[int(key)] = BinMapper.from_dict(d)
        return cls(mappers, used)

    def meta_dev(self):
        """Device (num_bin, default_bin, missing_type) triple, uploaded
        once per context."""
        if self._meta_dev is None:
            from ..ops.predict import feature_meta_dev

            self._meta_dev = feature_meta_dev(self.meta)
        return self._meta_dev

    def bin_rows(self, X: np.ndarray) -> np.ndarray:
        """[n, F_used] int32 bins from raw rows, training-mapper space."""
        bins = np.zeros((X.shape[0], len(self.used_feature_idx)), np.int32)
        for j, col in enumerate(self.used_feature_idx):
            bins[:, j] = self.mappers[col].values_to_bins(X[:, col])
        return bins


class _FevalData:
    """Minimal Dataset-like shim passed to custom feval callbacks."""

    def __init__(self, td: TrainingData):
        self._td = td

    def get_label(self):
        return np.asarray(self._td.metadata.label)

    def get_weight(self):
        w = self._td.metadata.weight
        return None if w is None else np.asarray(w)

    def get_group(self):
        b = self._td.metadata.query_boundaries
        return None if b is None else np.diff(b)
