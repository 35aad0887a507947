"""Context & engine init (L1).

The reference's `init_nncontext()` creates/gets a SparkContext with the zoo
conf overlay and runs BigDL `Engine.init` to discover nodes/cores
(reference `Z/common/NNContext.scala:132-207`, `P/common/nncontext.py:21-40`).

The TPU-native equivalent discovers the accelerator topology instead: it
builds a `jax.sharding.Mesh` over the local (or multi-host) TPU slice and
registers it process-wide. Everything downstream — the Estimator's pjit'd
train step, FeatureSet's sharded host ingest, model predict — asks this
context for the mesh and shardings rather than an RDD partition count.

There is deliberately no Spark dependency in-core: data ingest accepts any
sharded-iterable (see `feature.feature_set`), which is the role RDDs played.
"""

from __future__ import annotations

import logging
import threading
from typing import Any, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from analytics_zoo_tpu.common.config import (
    MeshConf,
    ZooBuildInfo,
    ZooTpuConf,
    parse_axes,
)
from analytics_zoo_tpu.common import tracing
from analytics_zoo_tpu.common.device import setup_compile_cache
from analytics_zoo_tpu.version import __version__

logger = logging.getLogger("analytics_zoo_tpu")

# one clock: every span the program opens is also a ``zoo:<name>``
# annotation in any profiler session (tracing stays stdlib-only, so
# the module that imports jax hands it the factory)
tracing.set_annotation_hook(jax.profiler.TraceAnnotation)

_lock = threading.RLock()
_current: "NNContext | None" = None
_distributed_done = False


class NNContext:
    """Process-wide engine context: mesh + config + rng root.

    Analog of SparkContext+Engine in the reference (NNContext.scala:132-146),
    with the device mesh playing the role of the cluster.
    """

    def __init__(self, conf: ZooTpuConf, mesh: Mesh):
        self.conf = conf
        self.mesh = mesh
        self._rng = jax.random.key(conf.seed)
        self._rng_lock = threading.Lock()
        self.build_info = ZooBuildInfo(
            version=__version__, jax_version=jax.__version__)

    # ---- topology ----------------------------------------------------------
    @property
    def num_devices(self) -> int:
        return self.mesh.size

    @property
    def data_axes(self) -> "tuple[str, ...]":
        """Mesh axes over which the batch dimension is sharded."""
        return tuple(a for a in self.mesh.axis_names if a in ("data", "fsdp"))

    @property
    def data_parallel_size(self) -> int:
        n = 1
        for a in self.data_axes:
            n *= self.mesh.shape[a]
        return n

    def batch_sharding(self, ndim: int = 2) -> NamedSharding:
        """Sharding for a host batch: dim0 split over the data axes."""
        spec = [None] * ndim
        spec[0] = self.data_axes or None
        return NamedSharding(self.mesh, P(*spec))

    def replicated_sharding(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())

    def check_batch_size(self, batch_size: int) -> int:
        """Enforce batch divisibility over the data-parallel size.

        Mirrors the reference's `batch_size % total_cores == 0` rule
        (`P/pipeline/api/net.py:741-749`), with devices standing in for
        cores.
        """
        dp = self.data_parallel_size
        if self.conf.check_batch_divisibility and batch_size % dp != 0:
            raise ValueError(
                f"batch_size ({batch_size}) must be divisible by the "
                f"data-parallel size ({dp}). Per-device batch = "
                f"batch_size // {dp}.")
        return batch_size

    # ---- rng ---------------------------------------------------------------
    def next_rng_key(self, n: Optional[int] = None):
        """Split fresh PRNG key(s) off the context root key (thread-safe)."""
        with self._rng_lock:
            if n is None:
                self._rng, out = jax.random.split(self._rng)
            else:
                keys = jax.random.split(self._rng, n + 1)
                self._rng, out = keys[0], keys[1:]
            return out

    def __repr__(self) -> str:
        return (f"NNContext(devices={self.num_devices}, "
                f"mesh={dict(self.mesh.shape)}, "
                f"platform={jax.devices()[0].platform})")


def _build_mesh(mesh_conf: MeshConf) -> Mesh:
    devices = mesh_conf.devices
    if devices is None:
        devices = jax.devices()
    devices = list(devices)
    axes = mesh_conf.resolved_axes(len(devices))
    shape = tuple(axes.values())
    names = tuple(axes.keys())
    total = int(np.prod(shape)) if shape else 1
    dev_array = np.array(devices[:total]).reshape(shape)
    return Mesh(dev_array, names)


def _maybe_init_distributed(multi_host) -> None:
    """Join the multi-host JAX cluster (the reference's
    executor-registration role, played by `jax.distributed`).

    ``multi_host=True`` forces it; ``multi_host=None`` auto-joins when
    the standard coordinator env (``JAX_COORDINATOR_ADDRESS`` /
    ``COORDINATOR_ADDRESS``) or a Cloud-TPU pod environment announces
    one. After init, ``jax.devices()`` is the GLOBAL device set and
    ``jax.process_index()`` feeds the per-host data sharding
    (`feature/rdd.py:process_shard_spec`)."""
    import os

    global _distributed_done
    if multi_host is False or _distributed_done:
        return
    announced = os.environ.get("JAX_COORDINATOR_ADDRESS") or \
        os.environ.get("COORDINATOR_ADDRESS")
    if not multi_host and not announced:
        return
    # NOTE: no jax.* probes before initialize() — touching the backend
    # (even jax.process_count()) initializes XLA and makes
    # jax.distributed.initialize() unconditionally raise
    kwargs = {}
    if announced and not os.environ.get("JAX_COORDINATOR_ADDRESS"):
        # forward the generic spelling jax doesn't read itself
        kwargs["coordinator_address"] = announced
        npz = os.environ.get("JAX_NUM_PROCESSES") or \
            os.environ.get("NUM_PROCESSES")
        pid = os.environ.get("JAX_PROCESS_ID") or \
            os.environ.get("PROCESS_ID")
        if npz is not None:
            kwargs["num_processes"] = int(npz)
        if pid is not None:
            kwargs["process_id"] = int(pid)
    try:
        jax.distributed.initialize(**kwargs)
        _distributed_done = True
        logger.info("jax.distributed initialized: process %d/%d",
                    jax.process_index(), jax.process_count())
    except RuntimeError as e:
        if "already" in str(e).lower():  # initialized elsewhere — fine
            _distributed_done = True
            return
        if multi_host:
            raise
        logger.warning("jax.distributed.initialize failed (%s); "
                       "continuing single-host", e)
    except Exception as e:  # single-host fallback stays usable
        if multi_host:
            raise
        logger.warning("jax.distributed.initialize failed (%s); "
                       "continuing single-host", e)


def init_nncontext(
    conf: "ZooTpuConf | None" = None,
    *,
    app_name: Optional[str] = None,
    tpu_mesh: "str | Mapping[str, int] | Sequence | Mesh | None" = None,
    devices: Optional[Sequence[Any]] = None,
    seed: Optional[int] = None,
    log_level: Optional[str] = None,
    multi_host: Optional[bool] = None,
) -> NNContext:
    """Create (or replace) the process-wide :class:`NNContext`.

    Analog of `init_nncontext()` (reference `P/common/nncontext.py:21-40`)
    with the north-star `tpu_mesh=` argument: instead of attaching a Spark
    cluster, attach a TPU mesh.

    Args:
      conf: full typed config; env vars ``ZOO_TPU_*`` overlay on top.
      app_name: convenience override of ``conf.app_name``.
      tpu_mesh: mesh axes spec (``"data=8"``, ``{"data": 4, "model": 2}``)
        or a prebuilt `jax.sharding.Mesh`. Default: all devices on ``data``.
      devices: explicit device list (default ``jax.devices()`` — the
        GLOBAL device set after multi-host init).
      seed: root RNG seed.
      log_level: python logging level for the zoo logger.
      multi_host: True → require `jax.distributed.initialize()` (all
        hosts of the pod run the same program); None (default) →
        auto-join when a coordinator address env is present; False →
        never.
    """
    global _current
    _maybe_init_distributed(multi_host)
    setup_compile_cache()
    conf = ZooTpuConf.from_env(conf)
    if app_name is not None:
        conf.app_name = app_name
    if seed is not None:
        conf.seed = seed
    if log_level is not None:
        conf.log_level = log_level

    # configure only our own logger — never touch the root logger
    logger.setLevel(conf.log_level)
    if not logger.handlers:
        handler = logging.StreamHandler()
        handler.setFormatter(logging.Formatter(
            "%(asctime)s %(name)s %(levelname)s: %(message)s"))
        logger.addHandler(handler)
        logger.propagate = False

    if isinstance(tpu_mesh, Mesh):
        mesh = tpu_mesh
    else:
        if tpu_mesh is not None:
            conf.mesh = MeshConf(axes=parse_axes(tpu_mesh), devices=devices)
        elif devices is not None:
            conf.mesh.devices = devices
        mesh = _build_mesh(conf.mesh)

    ctx = NNContext(conf, mesh)
    with _lock:
        _current = ctx
    logger.info("Initialized %s", ctx)
    return ctx


def get_nncontext(create_if_missing: bool = True) -> NNContext:
    """Return the current context, creating a default one if needed
    (mirrors SparkContext.getOrCreate semantics, NNContext.scala:143)."""
    global _current
    with _lock:
        if _current is not None:
            return _current
        if not create_if_missing:
            raise RuntimeError("NNContext not initialized; "
                               "call init_nncontext() first")
        return init_nncontext()


def reset_nncontext() -> None:
    global _current
    with _lock:
        _current = None
