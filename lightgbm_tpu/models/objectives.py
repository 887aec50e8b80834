"""Objective functions: gradients/hessians as jitted elementwise device ops.

Interface contract mirrors the reference ObjectiveFunction (reference
include/LightGBM/objective_function.h:29-70): `get_gradients`,
`boost_from_score`, `convert_output`, `num_model_per_iteration`,
`is_constant_hessian`, `renew_tree_output`.

Formulas cite the reference implementation per class.  Gradients are
computed on device ([k, n] f32) since they feed the histogram kernel
directly; RenewTreeOutput percentile refits run on host (they are per-leaf
sorts, cheap relative to histogram work).
"""

from __future__ import annotations

import copy
import functools
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..config import Config
from ..io.dataset import Metadata


class Objective:
    name = "none"
    num_class = 1

    def __init__(self, config: Config):
        self.config = config

    def init(self, metadata: Metadata, num_data: int) -> None:
        self.metadata = metadata
        self.num_data = num_data
        self.label = jnp.asarray(metadata.label)
        self.weights = (None if metadata.weight is None
                        else jnp.asarray(metadata.weight))

    # -- contract ------------------------------------------------------
    def num_model_per_iteration(self) -> int:
        return 1

    def is_constant_hessian(self) -> bool:
        return False

    def boost_from_score(self, class_id: int) -> float:
        return 0.0

    def convert_output(self, raw: np.ndarray) -> np.ndarray:
        return raw

    def get_gradients(self, score: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """score: [k, n] raw scores -> (grad, hess) [k, n]."""
        raise NotImplementedError

    #: the attributes `get_gradients` reads that hold one entry per row
    #: (device arrays, the row axis last).  The training step takes them
    #: as ARGUMENTS, padded and sharded like every other row vector, so
    #: no program of the step holds a data set's labels as constants
    row_attrs: Tuple[str, ...] = ("label", "weights")

    def row_arrays(self) -> Dict[str, jnp.ndarray]:
        """name -> per-row device array, for the `row_attrs` that are set."""
        return {a: getattr(self, a) for a in self.row_attrs
                if getattr(self, a, None) is not None}

    def gradients(self, score: jnp.ndarray, rows: Dict[str, jnp.ndarray]
                  ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """`get_gradients` as a function of its per-row arrays: `rows`
        (the keys of `row_arrays`, any row count that matches `score`)
        stand in for the attributes of the same names."""
        bound = copy.copy(self)
        vars(bound).update(rows)
        return bound.get_gradients(score)

    #: whether renew_tree_output does anything (lets the driver skip
    #: device->host transfers of scores/leaf ids on the hot path)
    needs_renew = False

    def layout_arrays(self) -> Dict:
        """What `gradients` reads besides the per-row arrays and differs by
        data set without being row-shaped (a ranking objective's query
        layout): a pytree of host arrays, which the training step takes as
        arguments whole, under `rows["layout"]`."""
        return {}

    def steps_on_device(self, learner) -> bool:
        """Whether `gradients` can be traced into `learner`'s fused device
        step; where not (host RNG, per-query work over rows the learner
        shards) the driver takes the synchronous path."""
        return True

    def renew_tree_output(self, tree, score: np.ndarray,
                          leaf_ids: np.ndarray, row_mask: np.ndarray) -> None:
        """Post-hoc leaf re-fit (L1/quantile/MAPE family). Default: no-op."""

    def class_need_train(self, class_id: int) -> bool:
        return True

    def to_model_string(self) -> str:
        return self.name


def _apply_weight(grad, hess, weights):
    if weights is None:
        return grad, hess
    return grad * weights, hess * weights


class BinaryLogloss(Objective):
    """reference src/objective/binary_objective.hpp:20-213.

    `is_pos_fn` customizes label binarization — the hook MulticlassOVA uses
    to build its per-class losses (reference multiclass_objective.hpp:186).
    """
    name = "binary"
    row_attrs = ("_sign", "_lw", "weights")

    def __init__(self, config: Config, is_pos_fn=None):
        super().__init__(config)
        self.sigmoid = float(config.sigmoid)
        if self.sigmoid <= 0:
            raise ValueError("sigmoid must be > 0")
        self.is_unbalance = bool(config.is_unbalance)
        self.scale_pos_weight = float(config.scale_pos_weight)
        if self.is_unbalance and abs(self.scale_pos_weight - 1.0) > 1e-6:
            raise ValueError("cannot set is_unbalance and scale_pos_weight together")
        self._is_pos_fn = is_pos_fn

    def init(self, metadata: Metadata, num_data: int) -> None:
        super().init(metadata, num_data)
        label = np.asarray(metadata.label)
        is_pos = (label > 0 if self._is_pos_fn is None
                  else self._is_pos_fn(label))
        cnt_pos = int(is_pos.sum())
        cnt_neg = num_data - cnt_pos
        self.need_train = cnt_pos > 0 and cnt_neg > 0
        w_pos, w_neg = 1.0, 1.0
        if self.is_unbalance and cnt_pos > 0 and cnt_neg > 0:
            if cnt_pos > cnt_neg:
                w_neg = cnt_pos / cnt_neg
            else:
                w_pos = cnt_neg / cnt_pos
        w_pos *= self.scale_pos_weight
        self._sign = jnp.where(jnp.asarray(is_pos), 1.0, -1.0).astype(jnp.float32)
        self._lw = jnp.where(jnp.asarray(is_pos), w_pos, w_neg).astype(jnp.float32)

    def class_need_train(self, class_id: int) -> bool:
        return self.need_train

    def get_gradients(self, score):
        sig = self.sigmoid

        def f(s):
            response = -self._sign * sig / (1.0 + jnp.exp(self._sign * sig * s))
            ar = jnp.abs(response)
            g = response * self._lw
            h = ar * (sig - ar) * self._lw
            return _apply_weight(g, h, self.weights)
        return f(score[0])

    def boost_from_score(self, class_id: int) -> float:
        label = np.asarray(self.metadata.label)
        is_pos = ((label > 0) if self._is_pos_fn is None
                  else self._is_pos_fn(label)).astype(np.float64)
        w = self.metadata.weight
        if w is not None:
            suml = float((is_pos * w).sum())
            sumw = float(np.asarray(w, np.float64).sum())
        else:
            suml = float(is_pos.sum())
            sumw = float(self.num_data)
        pavg = min(max(suml / sumw, 1e-15), 1.0 - 1e-15)
        init = float(np.log(pavg / (1.0 - pavg)) / self.sigmoid)
        return init

    def convert_output(self, raw):
        return 1.0 / (1.0 + np.exp(-self.sigmoid * raw))

    def to_model_string(self) -> str:
        return f"binary sigmoid:{self.sigmoid:g}"


class RegressionL2(Objective):
    """reference src/objective/regression_objective.hpp:78-158."""
    name = "regression"
    row_attrs = ("trans_label", "weights")

    def __init__(self, config: Config):
        super().__init__(config)
        self.sqrt = bool(config.reg_sqrt)

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        if self.sqrt:
            lbl = np.asarray(metadata.label, np.float64)
            self.trans_label = jnp.asarray(
                np.sign(lbl) * np.sqrt(np.abs(lbl)), dtype=jnp.float32)
        else:
            self.trans_label = self.label

    def is_constant_hessian(self) -> bool:
        return self.metadata.weight is None

    def get_gradients(self, score):
        g = score[0] - self.trans_label
        h = jnp.ones_like(g)
        return _apply_weight(g, h, self.weights)

    def boost_from_score(self, class_id: int) -> float:
        lbl = np.asarray(self.trans_label, np.float64)
        w = self.metadata.weight
        if w is not None:
            return float((lbl * w).sum() / np.asarray(w, np.float64).sum())
        return float(lbl.mean())

    def convert_output(self, raw):
        if self.sqrt:
            return np.sign(raw) * raw * raw
        return raw

    def to_model_string(self) -> str:
        return "regression"


_REGISTRY: Dict[str, type] = {}


def register(cls):
    _REGISTRY[cls.name] = cls
    return cls


for _cls in (BinaryLogloss, RegressionL2):
    register(_cls)


def create_objective(config: Config) -> Optional[Objective]:
    """Objective factory (reference src/objective/objective_function.cpp:16-53)."""
    name = config.objective
    if name in ("none", ""):
        return None
    # late imports so the extended zoo registers itself
    from . import objectives_ext  # noqa: F401
    if name not in _REGISTRY:
        raise ValueError(f"unknown objective {name!r}")
    return _REGISTRY[name](config)


def create_objective_from_model_string(spec: str) -> Optional[Objective]:
    """Rebuild an objective from the model-file 'objective=...' line."""
    toks = spec.split()
    if not toks:
        return None
    name = toks[0]
    params = {}
    for t in toks[1:]:
        if ":" in t:
            k, v = t.split(":", 1)
            params[k] = v
    cfg = Config({"objective": name, **params})
    from . import objectives_ext  # noqa: F401
    if cfg.objective not in _REGISTRY:
        return None
    obj = _REGISTRY[cfg.objective](cfg)
    return obj
