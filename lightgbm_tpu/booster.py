"""Booster: the training/prediction handle (reference basic.py Booster class).

Wraps the boosting driver in `lightgbm_tpu.models` the way the reference
Booster wraps the C API handle (reference python-package/lightgbm/basic.py,
src/c_api.cpp:98-320).
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from .config import Config


def _split_pandas_categorical(text: str):
    """Split a model string into (model_text, pandas_categorical).

    The Python layer appends one `pandas_categorical:<json>` line to saved
    models (the reference package does the same at the end of its files,
    python-package/lightgbm/basic.py _dump_pandas_categorical), so both
    packages' files interchange."""
    import json

    marker = "\npandas_categorical:"
    pos = text.rfind(marker)
    if pos < 0:
        return text, None
    payload = text[pos + len(marker):].split("\n", 1)[0].strip()
    try:
        pc = json.loads(payload) if payload else None
    except json.JSONDecodeError as exc:
        raise ValueError(
            f"corrupt pandas_categorical line in model: {payload[:80]!r}"
        ) from exc
    return text[:pos] + "\n", pc


class Booster:
    def __init__(self, params: Optional[Dict[str, Any]] = None,
                 train_set: Optional["Dataset"] = None,
                 model_file: Optional[str] = None,
                 model_str: Optional[str] = None, silent: bool = False):
        from .basic import Dataset
        from .models import create_boosting
        from .models.gbdt import GBDT

        self.params = dict(params) if params else {}
        self.best_iteration = -1
        self.best_score: Dict[str, Dict[str, float]] = {}
        self._valid_names: List[str] = []
        self._train_set: Optional[Dataset] = None
        self._driver = None
        self.pandas_categorical = None
        self._attr: Dict[str, str] = {}

        if train_set is not None:
            if not isinstance(train_set, Dataset):
                raise TypeError("train_set must be a Dataset")
            if train_set._inner is None:
                # merge training params into dataset params before lazy
                # construction (reference basic.py _update_params): dataset-
                # affecting keys like max_bin / monotone_constraints may be
                # given at train() level
                train_set.params = {**train_set.params, **self.params}
            train_set.construct()
            self._train_set = train_set
            cfg = Config(self.params)
            self._driver = create_boosting(cfg)
            self._driver.init(cfg, train_set._inner)
            self.pandas_categorical = train_set.pandas_categorical
        elif model_file is not None:
            with open(model_file) as f:
                text = f.read()
            text, self.pandas_categorical = _split_pandas_categorical(text)
            self._driver = GBDT.from_model_string(text)
            self.params = dict(self._driver.loaded_params)
        elif model_str is not None:
            model_str, self.pandas_categorical = \
                _split_pandas_categorical(model_str)
            self._driver = GBDT.from_model_string(model_str)
            self.params = dict(self._driver.loaded_params)
        else:
            raise ValueError("need train_set, model_file or model_str")
        if train_set is None and params:
            # reference basic.py merges user-supplied params over the
            # loaded model's stored ones, so introspection reflects them
            self.params.update(params)
            # loaded-model boosters skip GBDT.init (which applies the cap
            # on the train path), so honor the USER-supplied num_threads
            # (and aliases, via Config) here
            n_threads = int(Config(dict(params)).num_threads)
            if n_threads > 0:
                from .native import set_num_threads

                set_num_threads(n_threads)

    # -- copy / pickling (reference basic.py Booster round-trips its
    # C handle through the model string; the driver plays that role) ----
    def __copy__(self) -> "Booster":
        return self.__deepcopy__(None)

    def __deepcopy__(self, _memo) -> "Booster":
        out = Booster(model_str=self.model_to_string(num_iteration=-1))
        out.params = dict(self.params)
        out.best_iteration = self.best_iteration
        out._attr = dict(self._attr)
        return out

    def __getstate__(self):
        state = self.__dict__.copy()
        state.pop("_train_set", None)
        state.pop("_driver", None)
        state["_model_str"] = self.model_to_string(num_iteration=-1)
        return state

    def __setstate__(self, state):
        from .models.gbdt import GBDT

        model_str = state.pop("_model_str", None)
        self.__dict__.update(state)
        self._train_set = None
        self._driver = None
        if model_str is not None:
            model_str, pc = _split_pandas_categorical(model_str)
            self._driver = GBDT.from_model_string(model_str)
            if self.pandas_categorical is None:
                self.pandas_categorical = pc

    # -- attributes (reference basic.py Booster.attr/set_attr) ---------
    def attr(self, key: str) -> Optional[str]:
        return self._attr.get(key)

    def set_attr(self, **kwargs) -> "Booster":
        for key, value in kwargs.items():
            if value is None:
                self._attr.pop(key, None)
            elif isinstance(value, str):
                self._attr[key] = value
            else:
                raise ValueError("Only string values are accepted")
        return self

    # ------------------------------------------------------------------
    def add_valid(self, data, name: str) -> "Booster":
        data.construct()
        self._driver.add_valid(data._inner, name)
        self._valid_names.append(name)
        return self

    def update(self, train_set=None, fobj=None) -> bool:
        """One boosting iteration; returns True if no further splits occurred."""
        if fobj is None:
            return self._driver.train_one_iter()
        grad, hess = fobj(self._driver.current_score_for_fobj(), self._train_set)
        return self._driver.train_one_iter_custom(np.asarray(grad, np.float32),
                                                  np.asarray(hess, np.float32))

    def rollback_one_iter(self) -> "Booster":
        self._driver.rollback_one_iter()
        return self

    def current_iteration(self) -> int:
        # a METHOD, not a property — reference basic.py Booster API
        return self._driver.current_iteration()

    def num_trees(self) -> int:
        return self._driver.num_total_model()

    def num_model_per_iteration(self) -> int:
        return self._driver.num_model_per_iteration()

    def eval_train(self, feval=None) -> List[Tuple]:
        return self._driver.eval("training", -1, feval=feval,
                                 booster=self)

    def eval_valid(self, feval=None) -> List[Tuple]:
        out: List[Tuple] = []
        for i, name in enumerate(self._valid_names):
            out.extend(self._driver.eval(name, i, feval=feval, booster=self))
        return out

    def eval(self, data, name: str, feval=None) -> List[Tuple]:
        data.construct()
        return self._driver.eval_for_data(data._inner, name, feval=feval)

    def _device_predict_requested(self, kwargs,
                                  for_dataset: bool = False) -> bool:
        """Route this predict through the jitted bin-space forest
        predictor?  `device='tpu'` (kwarg, or the stored device_type)
        selects it, modulated by tpu_predict_device: `true` forces it,
        `false` pins the native walker, `auto` (default) uses it only
        when the default jax backend is an actual TPU — on CPU hosts the
        native OMP walker stays faster for one-shot predicts.
        Pre-binned Dataset input (`for_dataset`) has NO native
        alternative, so auto mode accepts it on every backend."""
        # raw param reads (alias-aware), not a full Config build: this
        # runs on EVERY predict call and only needs two values
        from .config import parse_tristate

        raw_dev = self.params.get("device_type",
                                  self.params.get("device", "tpu"))
        dev = str(kwargs.get("device", raw_dev)).strip().lower()
        if dev != "tpu":
            return False
        # kwargs override the stored mode (serving pins the device path
        # per call without mutating the booster's own params)
        mode = parse_tristate(kwargs.get(
            "tpu_predict_device",
            self.params.get("tpu_predict_device", "auto")))
        if mode == "true":
            return True
        if mode == "false":
            return False
        if for_dataset:
            return True
        import jax

        return jax.default_backend() == "tpu"

    def predict(self, data, num_iteration: Optional[int] = None,
                raw_score: bool = False, pred_leaf: bool = False,
                pred_contrib: bool = False, **kwargs) -> np.ndarray:
        from .basic import Dataset, _to_2d_array
        if isinstance(data, Dataset):
            # pre-binned device predict: a constructed Dataset sharing the
            # training mappers skips the host binning pass entirely
            if pred_leaf or pred_contrib or kwargs.get("pred_early_stop"):
                raise ValueError("pred_leaf/pred_contrib/pred_early_stop "
                                 "need raw data, not a Dataset (they run "
                                 "on the native walker)")
            if not self._device_predict_requested(kwargs, for_dataset=True):
                raise TypeError(
                    "Cannot use Dataset instance for prediction on the "
                    "native path; pass raw data, or enable the device "
                    "predictor (device='tpu' with tpu_predict_device "
                    "not 'false')")
            data.construct()
            if num_iteration is None:
                num_iteration = (self.best_iteration
                                 if self.best_iteration >= 0 else -1)
            return self._driver.predict_binned_device(
                data._inner, num_iteration=num_iteration,
                raw_score=raw_score)
        if isinstance(data, str):
            from .io.parser import load_text_file
            cfg = Config(self.params)
            X = load_text_file(data, label_column=cfg.label_column,
                               header=True if cfg.header else None)[0]
            # file without a label column: reload keeping all columns
            if X.shape[1] == self.num_feature() - 1:
                X = load_text_file(data, label_column="", header=None)[0]
        else:
            from .io.dataset import _is_scipy_sparse

            if _is_scipy_sparse(data):
                # densify in bounded row chunks for the native walker —
                # never the whole [n, F] f64 (reference PredictForCSR
                # walks rows sparse; chunking keeps peak memory O(chunk))
                return self._predict_sparse_chunked(
                    data, num_iteration, raw_score, pred_leaf, pred_contrib,
                    kwargs)
            X = _to_2d_array(data, self.pandas_categorical)
        n_feat = self.num_feature()
        if X.shape[1] != n_feat:
            self._check_predict_shape(X.shape[1], kwargs)
            if X.shape[1] < n_feat:
                # absent trailing features predict as missing, like the
                # reference C predictor reading past ncol
                pad = np.full((X.shape[0], n_feat - X.shape[1]), np.nan)
                X = np.concatenate([np.asarray(X, np.float64), pad], axis=1)
            else:
                X = np.asarray(X, np.float64)[:, :n_feat]
        if num_iteration is None:
            num_iteration = self.best_iteration if self.best_iteration >= 0 else -1
        return self._driver.predict(
            X, num_iteration=num_iteration, raw_score=raw_score,
            pred_leaf=pred_leaf, pred_contrib=pred_contrib,
            pred_early_stop=bool(kwargs.get("pred_early_stop", False)),
            pred_early_stop_freq=int(kwargs.get("pred_early_stop_freq", 10)),
            pred_early_stop_margin=float(
                kwargs.get("pred_early_stop_margin", 10.0)),
            device_predict=self._device_predict_requested(kwargs))

    def _check_predict_shape(self, ncols: int, kwargs) -> None:
        """Raise on a predict feature-count mismatch unless
        predict_disable_shape_check (kwargs over stored params) is set —
        reference Parameters.rst semantics, string values accepted."""
        from .config import _parse_bool

        if _parse_bool(kwargs.get(
                "predict_disable_shape_check",
                Config(self.params).predict_disable_shape_check)):
            return
        from .utils.log import LightGBMError

        raise LightGBMError(
            f"The number of features in data ({ncols}) is not the same as "
            f"it was in training data ({self.num_feature()}).\n"
            "You can set ``predict_disable_shape_check=true`` to discard "
            "this error, but please be aware what you are doing.")

    def _predict_sparse_chunked(self, data, num_iteration, raw_score,
                                pred_leaf, pred_contrib, kwargs,
                                chunk_rows: int = 65536) -> np.ndarray:
        """Predict a scipy sparse matrix in dense row chunks.

        Every driver output is n-first ([n], [n, k], [n, T], [n, k*(F+1)])
        so chunks concatenate on axis 0; peak host memory is one
        [chunk_rows, F] f64 block instead of the full densified matrix."""
        n_feat = self.num_feature()
        if data.shape[1] != n_feat:
            self._check_predict_shape(data.shape[1], kwargs)
        if num_iteration is None:
            num_iteration = self.best_iteration if self.best_iteration >= 0 else -1
        device_predict = self._device_predict_requested(kwargs)
        Xr = data.tocsr()
        if Xr.shape[1] > n_feat:
            # drop extra columns while still sparse (O(nnz)) — densifying
            # at full width would defeat the bounded-memory chunking
            Xr = Xr[:, :n_feat]
        outs = []
        for lo in range(0, max(Xr.shape[0], 1), chunk_rows):
            chunk = np.asarray(
                Xr[lo:lo + chunk_rows].todense(), dtype=np.float64)
            if chunk.shape[1] < n_feat:
                pad = np.full((chunk.shape[0], n_feat - chunk.shape[1]),
                              np.nan)
                chunk = np.concatenate([chunk, pad], axis=1)
            outs.append(self._driver.predict(
                chunk, num_iteration=num_iteration, raw_score=raw_score,
                pred_leaf=pred_leaf, pred_contrib=pred_contrib,
                pred_early_stop=bool(kwargs.get("pred_early_stop", False)),
                pred_early_stop_freq=int(kwargs.get("pred_early_stop_freq",
                                                    10)),
                pred_early_stop_margin=float(
                    kwargs.get("pred_early_stop_margin", 10.0)),
                device_predict=device_predict))
        return outs[0] if len(outs) == 1 else np.concatenate(outs, axis=0)

    def model_from_string(self, model_str: str, verbose: bool = True
                          ) -> "Booster":
        """Replace this Booster's model in place from a model string
        (reference basic.py Booster.model_from_string)."""
        from .models.gbdt import GBDT

        model_str, self.pandas_categorical = \
            _split_pandas_categorical(model_str)
        self._driver = GBDT.from_model_string(model_str)
        self.params = dict(self._driver.loaded_params)
        self._train_set = None
        return self

    def get_leaf_output(self, tree_id: int, leaf_id: int) -> float:
        """Value of one leaf (reference Booster.get_leaf_output ->
        LGBM_BoosterGetLeafValue)."""
        self._driver._materialize()
        return float(self._driver.models[tree_id].leaf_value[leaf_id])

    def get_split_value_histogram(self, feature, bins=None,
                                  xgboost_style: bool = False):
        """Histogram of this feature's used split thresholds across all
        trees (reference basic.py Booster.get_split_value_histogram)."""
        model = self.dump_model()
        feature_names = model["feature_names"]

        def want(split_feature) -> bool:
            if isinstance(feature, str):
                return (feature_names is not None
                        and feature_names[split_feature] == feature)
            return split_feature == feature

        values: List[float] = []

        def walk(node):
            if "split_index" in node:
                if want(node["split_feature"]):
                    if node["decision_type"] == "==":
                        raise ValueError(
                            "cannot compute a split value histogram for a "
                            "categorical feature")
                    values.append(float(node["threshold"]))
                walk(node["left_child"])
                walk(node["right_child"])

        for t in model["tree_info"]:
            walk(t["tree_structure"])
        if bins is None or (isinstance(bins, int)
                            and bins > len(set(values))
                            and xgboost_style):
            bins = max(len(set(values)), 1)
        hist, edges = np.histogram(values, bins=bins)
        if not xgboost_style:
            return hist, edges
        mask = hist != 0
        out = np.column_stack([edges[1:][mask], hist[mask]])
        try:
            import pandas as pd

            return pd.DataFrame(out, columns=["SplitValue", "Count"])
        except ImportError:
            return out

    def trees_to_dataframe(self):
        """All trees' nodes as one pandas DataFrame (reference basic.py
        Booster.trees_to_dataframe; same column contract)."""
        import pandas as pd

        if self.num_trees() == 0:
            raise ValueError("no trees to parse")
        model = self.dump_model()
        feature_names = model["feature_names"]
        rows: List[Dict[str, Any]] = []

        def node_index(node, ti):
            if "split_index" in node:
                return f"{ti}-S{node['split_index']}"
            return f"{ti}-L{node.get('leaf_index', 0)}"

        def walk(node, ti, depth, parent):
            is_split = "split_index" in node
            row = {
                "tree_index": ti,
                "node_depth": depth,
                "node_index": node_index(node, ti),
                "left_child": None,
                "right_child": None,
                "parent_index": parent,
                "split_feature": None,
                "split_gain": None,
                "threshold": None,
                "decision_type": None,
                "missing_direction": None,
                "missing_type": None,
                "value": None,
                "weight": None,
                "count": None,
            }
            if is_split:
                f = node["split_feature"]
                row.update(
                    left_child=node_index(node["left_child"], ti),
                    right_child=node_index(node["right_child"], ti),
                    split_feature=(feature_names[f] if feature_names
                                   else f),
                    split_gain=node["split_gain"],
                    threshold=node["threshold"],
                    decision_type=node["decision_type"],
                    missing_direction=("left" if node["default_left"]
                                       else "right"),
                    missing_type=node["missing_type"],
                    value=node["internal_value"],
                    weight=node["internal_weight"],
                    count=node["internal_count"])
            else:
                row.update(value=node["leaf_value"],
                           weight=node.get("leaf_weight"),
                           count=node.get("leaf_count"))
            rows.append(row)
            if is_split:
                me = row["node_index"]
                walk(node["left_child"], ti, depth + 1, me)
                walk(node["right_child"], ti, depth + 1, me)

        for t in model["tree_info"]:
            walk(t["tree_structure"], t["tree_index"], 1, None)
        return pd.DataFrame(rows)

    def refit(self, data, label, decay_rate: float = 0.9) -> "Booster":
        """New Booster with every tree's leaf values re-fit on `data`
        (reference basic.py Booster.refit -> GBDT::RefitTree)."""
        from .basic import _to_2d_array
        from .config import Config

        X = _to_2d_array(data, self.pandas_categorical)
        out = Booster(model_str=self._driver.save_model_to_string())
        out.params = dict(self.params)
        out.pandas_categorical = self.pandas_categorical
        out._driver.refit(X, np.asarray(label), decay_rate,
                          config=Config(self.params) if self.params else None)
        return out

    # -- fault tolerance (utils/checkpoint.py) -------------------------
    def save_checkpoint(self, directory: str, keep: int = 3) -> str:
        """Write one atomic training checkpoint (model + PRNG streams +
        score buffers) into `directory`; returns the checkpoint path.
        In a jax.distributed group every host writes its local bundle
        and rank 0 commits the global topology manifest after the
        all-hosts-durable barrier.  `lgb.train` does this automatically
        when `tpu_checkpoint_dir` is configured."""
        from .utils.checkpoint import make_manager, save_checkpoint

        return save_checkpoint(self, make_manager(directory, keep=keep))

    def resume_from_checkpoint(self, directory: str) -> Optional[int]:
        """Restore this (freshly-constructed, same training data)
        booster from the newest valid checkpoint in `directory`;
        returns the restored iteration, or None when no valid
        checkpoint exists.  The shard/host topology may DIFFER from the
        checkpointed run's (elastic resume): global score buffers are
        re-sharded onto the live mesh, and continued int8/int16
        training stays bit-identical to a never-interrupted run.  A
        material params mismatch names the differing keys (warning, or
        error under `tpu_resume_strict`)."""
        from .utils.checkpoint import make_manager, restore_checkpoint

        state = restore_checkpoint(self, make_manager(directory))
        return None if state is None else int(state["iteration"])

    # -- model IO ------------------------------------------------------
    def save_model(self, filename: str, num_iteration: Optional[int] = None,
                   start_iteration: int = 0) -> "Booster":
        if num_iteration is None:
            num_iteration = self.best_iteration if self.best_iteration >= 0 else -1
        with open(filename, "w") as f:
            f.write(self._driver.save_model_to_string(
                num_iteration=num_iteration, start_iteration=start_iteration))
            f.write(self._pandas_categorical_line())
        return self

    def model_to_string(self, num_iteration: Optional[int] = None,
                        start_iteration: int = 0) -> str:
        if num_iteration is None:
            num_iteration = self.best_iteration if self.best_iteration >= 0 else -1
        return (self._driver.save_model_to_string(
            num_iteration=num_iteration, start_iteration=start_iteration)
            + self._pandas_categorical_line())

    def _pandas_categorical_line(self) -> str:
        import json

        def np_default(o):
            if isinstance(o, np.integer):
                return int(o)
            if isinstance(o, np.floating):
                return float(o)
            if isinstance(o, np.bool_):
                return bool(o)
            # a str() fallback would save a table whose values no longer
            # match the frame's at predict time (everything -> missing);
            # fail at save time instead
            raise TypeError(
                f"cannot persist pandas category value {o!r} "
                f"({type(o).__name__}); use str/int/float categories")

        return ("\npandas_categorical:"
                + json.dumps(self.pandas_categorical, default=np_default)
                + "\n")

    def dump_model(self, num_iteration: Optional[int] = None,
                   start_iteration: int = 0) -> Dict:
        if num_iteration is None:
            num_iteration = self.best_iteration if self.best_iteration >= 0 else -1
        return self._driver.dump_model(num_iteration=num_iteration,
                                       start_iteration=start_iteration)

    # -- introspection -------------------------------------------------
    def feature_importance(self, importance_type: str = "split",
                           iteration: Optional[int] = None) -> np.ndarray:
        return self._driver.feature_importance(importance_type)

    def feature_name(self) -> List[str]:
        return list(self._driver.feature_names)

    def num_feature(self) -> int:
        return self._driver.max_feature_idx + 1

    def reset_parameter(self, params: Dict[str, Any]) -> "Booster":
        self.params.update(params)
        self._driver.reset_config(Config(self.params))
        return self

    def set_network(self, machines: str, local_listen_port: int = 12400,
                    listen_time_out: int = 120, num_machines: int = 1
                    ) -> "Booster":
        """Join the multi-host training mesh (reference basic.py
        Booster.set_network -> LGBM_NetworkInit; here the machine list maps
        onto jax.distributed, parallel/mesh.py init_multihost).

        listen_time_out is accepted for signature parity; rendezvous
        timeouts are governed by jax.distributed itself."""
        from .parallel.mesh import init_multihost

        init_multihost(machines, int(local_listen_port), int(num_machines))
        self.params.update({"machines": machines,
                            "local_listen_port": int(local_listen_port),
                            "num_machines": int(num_machines)})
        self._network_set = True
        return self

    def free_network(self) -> "Booster":
        """Reference Booster.free_network analog: forget the network params
        (the jax.distributed runtime itself stays up for the process)."""
        for k in ("machines", "local_listen_port", "num_machines"):
            self.params.pop(k, None)
        self._network_set = False
        return self

    def set_train_data_name(self, name: str) -> "Booster":
        self._train_data_name = name
        return self

    def free_dataset(self) -> "Booster":
        """Drop the training/validation data (reference basic.py:1808):
        the trained model stays usable for predict/save/dump, but further
        update()/eval calls need data and will fail — same contract as the
        reference's freed booster."""
        drv = self._driver
        drv._materialize()
        # snapshot the model-header fields that are derived from the
        # training data at save time (the oracle rejects a model file
        # without feature_infos)
        drv.loaded_params["feature_infos"] = drv._feature_infos()
        # keep the bin mappers + per-feature metadata: device='tpu'
        # predict stays available on the freed (predict-only) booster
        drv.snapshot_predict_context()
        self._train_set = None
        drv.train_data = None
        drv.learner = None
        drv.train_scores = None
        drv.valid_sets = []
        drv.valid_scores = []
        drv._train_step = None
        return self

    def shuffle_models(self, start_iteration: int = 0, end_iteration: int = -1):
        self._driver.shuffle_models(start_iteration, end_iteration)
        return self
