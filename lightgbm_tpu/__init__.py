"""lightgbm_tpu: a TPU-native gradient-boosted decision tree framework.

A from-scratch reimplementation of the capabilities of LightGBM
(nick-zocdoc/LightGBM) designed for TPUs: histogram construction, split
search and partitioning run as jitted JAX/XLA (Pallas kernels for the hot
ops), distributed training maps the reference's socket/MPI collectives onto
XLA collectives over a ``jax.sharding.Mesh``.

Public surface mirrors the reference python-package (lightgbm/__init__.py):
``Dataset``, ``Booster``, ``train``, ``cv``, callbacks, sklearn wrappers.
"""

import sys as _sys
import time as _time

_IMPORT_T0_NS = _time.perf_counter_ns()  # before anything heavy: setup/import, below
_JAX_PRELOADED = "jax" in _sys.modules

from .basic import (  # noqa: F401
    LGBMDeprecationWarning,
    LightGBMError,
)

# common user-code alias for the reference error class
LGBMError = LightGBMError
from .boosting.gbdt import Booster
from .callback import (
    EarlyStopException,
    TelemetryCallback,
    checkpoint_callback,
    early_stopping,
    log_evaluation,
    print_evaluation,
    record_evaluation,
    reset_parameter,
)
from .config import Config
from .dataset import Dataset
from .engine import CVBooster, cv, train, train_fleet
from .dask import DaskLGBMClassifier, DaskLGBMRanker, DaskLGBMRegressor
from .dataset import Sequence
from .plotting import (
    create_tree_digraph,
    plot_importance,
    plot_metric,
    plot_split_value_histogram,
    plot_tree,
)
from .obs import (
    compile_count,
    compile_counts_by_label,
    get_session,
)
from .resilience import (
    NumericsError,
    latest_checkpoint,
    restore_checkpoint,
    save_checkpoint,
)
from .parser import register_parser
from .serving import ModelRegistry, RefreshLoop, ServingServer, serve
from .utils.log import register_logger, unregister_logger
from .utils.timer import global_timer

try:
    from .sklearn import LGBMClassifier, LGBMModel, LGBMRanker, LGBMRegressor
except Exception:  # pragma: no cover - sklearn not installed
    LGBMClassifier = LGBMModel = LGBMRanker = LGBMRegressor = None

__version__ = "0.1.0"

# the import as a span of the set-up's timeline (obs/trace.py, ``setup``)
from .obs import get_tracer as _get_tracer

_get_tracer().add_span(
    "setup/import", "setup", _IMPORT_T0_NS // 1000,
    (_time.perf_counter_ns() - _IMPORT_T0_NS) // 1000,
    args={"jax_preloaded": _JAX_PRELOADED},
)

__all__ = [
    "LGBMError",
    "LightGBMError",
    "Dataset",
    "Booster",
    "CVBooster",
    "train",
    "train_fleet",
    "cv",
    "early_stopping",
    "log_evaluation",
    "print_evaluation",
    "record_evaluation",
    "reset_parameter",
    "EarlyStopException",
    "register_logger",
    "unregister_logger",
    "register_parser",
    "global_timer",
    "TelemetryCallback",
    "get_session",
    "compile_count",
    "compile_counts_by_label",
    "NumericsError",
    "serve",
    "ServingServer",
    "ModelRegistry",
    "RefreshLoop",
    "checkpoint_callback",
    "save_checkpoint",
    "restore_checkpoint",
    "latest_checkpoint",
    "plot_importance",
    "plot_metric",
    "plot_split_value_histogram",
    "plot_tree",
    "create_tree_digraph",
    "Sequence",
    "DaskLGBMClassifier",
    "DaskLGBMRegressor",
    "DaskLGBMRanker",
    "Config",
    "LGBMModel",
    "LGBMClassifier",
    "LGBMRegressor",
    "LGBMRanker",
]
