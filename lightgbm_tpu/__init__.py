"""lightgbm_tpu: a TPU-native gradient-boosting framework.

A from-scratch JAX/XLA re-design of the LightGBM GBDT framework
(reference: /root/reference) for TPU hardware: the tree learner is a fully
device-resident jitted program (histograms on the MXU, vectorized split
scans, row->leaf partition vector), distributed training uses XLA
collectives over a `jax.sharding.Mesh`, and the Python API mirrors the
reference's (`Dataset`, `Booster`, `train`, `cv`, sklearn wrappers).
"""

__version__ = "0.1.0"

from .basic import LightGBMError
from .binning import BinMapper, BinType, MissingType
from .booster import Booster
from .callback import (EarlyStopException, early_stopping, log_evaluation,
                       log_telemetry, record_evaluation, reset_parameter)
from .config import Config
from .dataset import Dataset, Sequence
from .engine import CVBooster, cv, train
from .fleet import FleetResult, fleet_train
from .ingest import IngestRunner, ingest_dataset
from .pipeline import ContinualTrainer, GateFailure
from .plotting import (create_tree_digraph, plot_importance, plot_metric,
                       plot_split_value_histogram, plot_tree)
from .sklearn import LGBMClassifier, LGBMModel, LGBMRanker, LGBMRegressor
from .utils.log import register_logger

__all__ = [
    "BinMapper", "BinType", "MissingType", "Booster", "Config",
    "ContinualTrainer", "CVBooster",
    "Dataset", "EarlyStopException", "GateFailure", "IngestRunner",
    "FleetResult", "LightGBMError", "Sequence", "cv", "fleet_train",
    "ingest_dataset",
    "early_stopping", "log_evaluation", "log_telemetry",
    "record_evaluation", "reset_parameter", "train",
    "LGBMModel", "LGBMRegressor", "LGBMClassifier", "LGBMRanker",
    "DaskLGBMRegressor", "DaskLGBMClassifier", "DaskLGBMRanker",
    "register_logger",
    "plot_importance", "plot_split_value_histogram", "plot_metric",
    "plot_tree", "create_tree_digraph",
]

_DASK_TO_DIST = {
    "DaskLGBMRegressor": "DistributedLGBMRegressor",
    "DaskLGBMClassifier": "DistributedLGBMClassifier",
    "DaskLGBMRanker": "DistributedLGBMRanker",
}


def __getattr__(name: str):
    # the reference exports Dask estimators from the top level; the
    # Distributed* estimators are their analog here (distributed.py) and
    # answer to BOTH spellings — resolved lazily so importing the
    # package doesn't pay for the orchestration module
    if name in _DASK_TO_DIST or name.startswith("DistributedLGBM"):
        from . import distributed
        return getattr(distributed, _DASK_TO_DIST.get(name, name))
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
