"""analytics_zoo_tpu — a TPU-native analytics + AI framework.

A ground-up JAX/XLA/Pallas re-design with the capabilities of Analytics Zoo
(reference: pgargesa/analytics-zoo): a unified platform where one driver
program does data wrangling, Keras-style model definition, and distributed
training/inference — except the execution engine is XLA on TPU meshes
(GSPMD data/tensor/sequence parallelism over ICI) instead of BigDL's
MKL-on-Spark engine.

Top-level surface (mirrors the capability map in SURVEY.md §1):

- ``analytics_zoo_tpu.common``    — context & engine init (L1)
- ``analytics_zoo_tpu.feature``   — FeatureSet / ImageSet / TextSet (L2)
- ``analytics_zoo_tpu.pipeline``  — autograd, keras API, estimator, nnframes,
                                    inference (L3/L4/L7/L8/L9)
- ``analytics_zoo_tpu.models``    — built-in model zoo (L6)
- ``analytics_zoo_tpu.parallel``  — mesh / sharding / collectives / ring
                                    attention (replaces §2.10's Spark
                                    parameter-manager all-reduce)
- ``analytics_zoo_tpu.ops``       — losses, metrics, optimizers, pallas kernels
"""

from analytics_zoo_tpu.version import __version__
from analytics_zoo_tpu.common.nncontext import (
    init_nncontext,
    get_nncontext,
    NNContext,
    ZooTpuConf,
)

__all__ = [
    "__version__",
    "init_nncontext",
    "get_nncontext",
    "NNContext",
    "ZooTpuConf",
    "Net",
]


def __getattr__(name):
    if name == "Net":  # lazy: pulls in jax/layer machinery
        from analytics_zoo_tpu.pipeline.api.net_load import Net
        return Net
    raise AttributeError(name)
