"""lightgbm_tpu: a TPU-native gradient-boosted decision tree framework.

A from-scratch reimplementation of the capabilities of LightGBM v2.3.2
(reference: jpkoponen/LightGBM) designed for TPUs: the compute core
(histogram construction, split search, partitioning) runs as JAX/XLA
programs over fixed-shape tensors, and distribution uses `jax.sharding`
meshes with XLA collectives instead of socket/MPI allreduce.

Public API mirrors the reference Python package
(reference python-package/lightgbm/__init__.py):
  Dataset, Booster, train, cv, and sklearn-style wrappers.
"""

from .utils.backend import enable_compilation_cache as _enable_cache

# persistent XLA compilation cache: the grower is one big program whose
# cold compile costs minutes; cached compiles load in seconds.  The
# directory is JAX_COMPILATION_CACHE_DIR where set, else
# <checkout>/.jax_cache (utils/backend.py holds the rule).
_enable_cache()

from .version import __version__
from .config import Config
from .basic import Dataset, Booster
from .utils.log import LightGBMError
from .engine import train, cv, CVBooster
from .callback import (
    checkpoint,
    early_stopping,
    log_evaluation,
    print_evaluation,
    record_evaluation,
    reset_parameter,
    EarlyStopException,
)
from .plotting import (
    create_tree_digraph,
    plot_importance,
    plot_metric,
    plot_split_value_histogram,
    plot_tree,
)
# serving runtime (registry + micro-batched inference) stays a lazy
# submodule: `from lightgbm_tpu.serving import ServingSession`

__all__ = [
    "__version__",
    "Config",
    "Dataset",
    "Booster",
    "LightGBMError",
    "train",
    "cv",
    "CVBooster",
    "checkpoint",
    "early_stopping",
    "log_evaluation",
    "print_evaluation",
    "record_evaluation",
    "reset_parameter",
    "EarlyStopException",
    "plot_importance",
    "plot_metric",
    "plot_split_value_histogram",
    "plot_tree",
    "create_tree_digraph",
]

try:  # sklearn wrappers are optional (scikit-learn may be absent)
    from .sklearn import LGBMModel, LGBMClassifier, LGBMRegressor, LGBMRanker
    __all__ += ["LGBMModel", "LGBMClassifier", "LGBMRegressor", "LGBMRanker"]
except ImportError:  # pragma: no cover
    pass
