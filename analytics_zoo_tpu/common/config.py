"""Typed configuration system.

The reference layers its config across a Spark conf resource file, env vars,
system properties, and per-example scopt CLIs (SURVEY.md §5 "Config / flag
system"; reference `Z/common/NNContext.scala:185-197`). Here the whole thing
collapses into one typed dataclass tree + env-var overlay, which is the
TPU-idiomatic equivalent: a single source of truth handed to `init_nncontext`.
"""

from __future__ import annotations

import dataclasses
import os
import platform
import sys
from dataclasses import dataclass, field
from typing import Any, Mapping, Sequence


_ENV_PREFIX = "ZOO_TPU_"


@dataclass(frozen=True)
class ZooBuildInfo:
    """Build/version info (analog of `ZooBuildInfo`,
    NNContext.scala:78-118)."""

    version: str
    python_version: str = field(
        default_factory=lambda: sys.version.split()[0])
    platform: str = field(default_factory=platform.platform)
    jax_version: str = ""

    def report(self) -> str:
        lines = [f"analytics_zoo_tpu version: {self.version}"]
        lines.append(f"python: {self.python_version}")
        lines.append(f"jax: {self.jax_version}")
        lines.append(f"platform: {self.platform}")
        return "\n".join(lines)


@dataclass
class MeshConf:
    """Device-mesh specification.

    ``axes`` maps axis name -> size; a size of -1 means "all remaining
    devices". Axis names follow the scaling-book convention:

    - ``data``  : pure data parallelism (batch sharded, params replicated)
    - ``fsdp``  : data parallel + ZeRO-sharded params/optimizer state
    - ``model`` : tensor parallelism (weight matrices sharded)
    - ``seq``   : sequence/context parallelism (ring attention)
    """

    axes: "dict[str, int]" = field(default_factory=lambda: {"data": -1})
    devices: Any = None  # explicit device list; None = jax.devices()
    allow_partial: bool = False  # allow leaving devices unused

    def resolved_axes(self, n_devices: int) -> "dict[str, int]":
        axes = dict(self.axes)
        fixed = 1
        wildcard = None
        for name, size in axes.items():
            if size == -1:
                if wildcard is not None:
                    raise ValueError(
                        "at most one mesh axis may have size -1, got "
                        f"{self.axes}")
                wildcard = name
            else:
                fixed *= size
        if wildcard is not None:
            if n_devices % fixed != 0:
                raise ValueError(
                    f"cannot fit wildcard axis: {n_devices} devices not "
                    f"divisible by fixed axes product {fixed}")
            axes[wildcard] = n_devices // fixed
        else:
            total = fixed
            if total > n_devices:
                raise ValueError(
                    f"mesh axes {axes} need {total} devices but only "
                    f"{n_devices} are available")
            if total < n_devices and not self.allow_partial:
                raise ValueError(
                    f"mesh axes {axes} use {total} devices but "
                    f"{n_devices} are available; set allow_partial=True to "
                    "leave devices unused")
        return axes


@dataclass
class ZooTpuConf:
    """Top-level configuration for :func:`init_nncontext`.
    ``ingest_threads`` is a ceiling, not a demand: `Estimator.train`
    copies the rows of one batch on up to that many threads (and no
    more than the host's cores), one for every 8 MiB the batch's
    largest column holds, so a batch of ids or tokens stays on the
    prefetch thread and a batch of images splits
    (`feature.feature_set.ingest_width`; the ``threads`` field of the
    ``train/input_gather`` span says what a batch got).

    Analog of the SparkConf + `spark-analytics-zoo.conf` overlay
    (reference `Z/common/NNContext.scala:132-207`): perf-relevant defaults
    live here rather than scattered through user code.
    """

    app_name: str = "analytics-zoo-tpu"
    mesh: MeshConf = field(default_factory=MeshConf)
    seed: int = 0
    # matmul/conv compute dtype. bf16 keeps the MXU fed; params stay f32.
    compute_dtype: str = "bfloat16"
    param_dtype: str = "float32"
    # batch_size must divide evenly over the data axes (the reference enforces
    # batch_size % total_cores == 0, `P/pipeline/api/net.py:741-749`).
    check_batch_divisibility: bool = True
    log_level: str = "INFO"
    version_check: bool = False
    # host data-ingest workers: the most threads that copy one train
    # batch's rows into its host buffer (see the docstring)
    ingest_threads: int = 4
    # default checkpoint root
    checkpoint_dir: str = ""
    extra: "dict[str, Any]" = field(default_factory=dict)

    @staticmethod
    def from_env(base: "ZooTpuConf | None" = None) -> "ZooTpuConf":
        """Overlay ``ZOO_TPU_*`` env vars onto ``base`` (env wins).

        e.g. ``ZOO_TPU_SEED=7``, ``ZOO_TPU_COMPUTE_DTYPE=float32``.
        """
        if base is not None:
            # deep-ish copy: replace mutable sub-configs so later in-place
            # edits never write through to the caller's objects
            conf = dataclasses.replace(
                base,
                mesh=dataclasses.replace(base.mesh),
                extra=dict(base.extra))
        else:
            conf = ZooTpuConf()
        for f in dataclasses.fields(conf):
            key = _ENV_PREFIX + f.name.upper()
            if key not in os.environ:
                continue
            raw = os.environ[key]
            if f.type in ("int", int):
                setattr(conf, f.name, int(raw))
            elif f.type in ("bool", bool):
                setattr(conf, f.name, raw.lower() in ("1", "true", "yes"))
            elif f.type in ("str", str):
                setattr(conf, f.name, raw)
        return conf


def parse_axes(spec: "str | Mapping[str, int] | Sequence | None",
               ) -> "dict[str, int]":
    """Parse a mesh-axes spec: ``"data=8"``, ``"data=4,model=2"``,
    ``{"data": 8}``, or ``[("data", 8)]``."""
    if spec is None:
        return {"data": -1}
    if isinstance(spec, str):
        out: dict[str, int] = {}
        for part in spec.split(","):
            part = part.strip()
            if not part:
                continue
            name, _, size = part.partition("=")
            out[name.strip()] = int(size) if size else -1
        return out or {"data": -1}
    if isinstance(spec, Mapping):
        return dict(spec)
    return dict(spec)
