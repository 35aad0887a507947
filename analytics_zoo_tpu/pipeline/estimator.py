"""Training runtime (L7): `Estimator` — the TPU-native replacement for the
reference's `InternalDistriOptimizer` → BigDL `DistriOptimizer` stack
(reference `Topology.scala:902-1145`, `pipeline/estimator/Estimator.scala`).

Where the reference runs two Spark jobs per iteration (replica
forward/backward, then shuffle-based gradient aggregation + block-manager
weight broadcast — `docs/docs/wp-bigdl.md:146-160`), here one jit'd
train-step runs SPMD over the device mesh: the batch is sharded on the
data axes, parameters are replicated (or FSDP-sharded), and XLA inserts
the gradient all-reduce over ICI. There is no parameter server and no
host round-trip in the hot loop; the host only feeds the next sharded
batch and reads back scalar metrics.

Checkpointing, TensorBoard scalars (Throughput/Loss/LearningRate — the
same scalars BigDL's TrainSummary records), trigger-based validation, and
gradient clipping mirror the reference's training features (SURVEY.md §5).
"""

from __future__ import annotations

import logging
import os
import pickle
import time
from dataclasses import dataclass
from typing import Any, Callable, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax

from analytics_zoo_tpu.common import diagnostics
from analytics_zoo_tpu.common import faults
from analytics_zoo_tpu.common import observability as obs
from analytics_zoo_tpu.common import slo as slo_lib
from analytics_zoo_tpu.common import tracing
from analytics_zoo_tpu.common.device import on_tpu
from analytics_zoo_tpu.feature import feature_set
from analytics_zoo_tpu.perf import goodput as goodput_lib
from analytics_zoo_tpu.common.nncontext import NNContext, get_nncontext, \
    logger
from analytics_zoo_tpu.ops import losses as losses_lib
from analytics_zoo_tpu.ops import metrics as metrics_lib
from analytics_zoo_tpu.ops import optimizers as optim_lib
from analytics_zoo_tpu.parallel.mesh import shard_batch, shard_params

logger = logging.getLogger("analytics_zoo_tpu")

# fires after the pickle lands in the tmp file but before any
# durability/rename work — a kill here must leave only an unpromoted
# tmp, never a torn ckpt_*.pkl (tests/test_faults.py proves resume
# skips it)
_CKPT_FAULT = faults.point("estimator/checkpoint_write")


def _fsync_dir(path: str) -> None:
    """fsync a directory so a rename inside it is durable; tolerated
    to fail on filesystems (or platforms) that refuse O_RDONLY dir
    fds — atomicity does not depend on it, only crash durability."""
    try:
        fd = os.open(path, os.O_RDONLY)
    except OSError:
        return
    try:
        os.fsync(fd)
    except OSError:
        pass
    finally:
        os.close(fd)


# ---------------------------------------------------------------------------
# Triggers (BigDL Trigger analog: EveryEpoch / SeveralIteration / MaxEpoch /
# MaxIteration — used for validation, checkpoint and stop conditions)
# ---------------------------------------------------------------------------

class Trigger:
    """Training-control predicate (reference: BigDL `Trigger` algebra —
    everyEpoch/severalIteration/maxEpoch/maxIteration/minLoss/maxScore
    plus and/or composition). ``**state`` carries the current epoch
    loss and validation metrics at epoch-end evaluations."""

    def __call__(self, epoch: int, iteration: int,
                 epoch_end: bool, **state) -> bool:
        raise NotImplementedError

    @staticmethod
    def every_epoch() -> "Trigger":
        return EveryEpoch()

    @staticmethod
    def several_iteration(n: int) -> "Trigger":
        return SeveralIteration(n)

    @staticmethod
    def max_epoch(n: int) -> "Trigger":
        return MaxEpoch(n)

    @staticmethod
    def max_iteration(n: int) -> "Trigger":
        return MaxIteration(n)

    @staticmethod
    def min_loss(v: float) -> "Trigger":
        return MinLoss(v)

    @staticmethod
    def max_score(v: float, metric: "Optional[str]" = None) -> "Trigger":
        return MaxScore(v, metric)

    @staticmethod
    def and_(*triggers: "Trigger") -> "Trigger":
        return TriggerAnd(*triggers)

    @staticmethod
    def or_(*triggers: "Trigger") -> "Trigger":
        return TriggerOr(*triggers)


class EveryEpoch(Trigger):
    def __call__(self, epoch, iteration, epoch_end, **state):
        return epoch_end


class SeveralIteration(Trigger):
    def __init__(self, n: int):
        self.n = int(n)

    def __call__(self, epoch, iteration, epoch_end, **state):
        return iteration > 0 and iteration % self.n == 0


class MaxEpoch(Trigger):
    def __init__(self, n: int):
        self.n = int(n)

    def __call__(self, epoch, iteration, epoch_end, **state):
        return epoch >= self.n


class MaxIteration(Trigger):
    def __init__(self, n: int):
        self.n = int(n)

    def __call__(self, epoch, iteration, epoch_end, **state):
        return iteration >= self.n


class MinLoss(Trigger):
    """Stop once the epoch training loss drops to ``v`` (BigDL
    `Trigger.minLoss`); evaluated at epoch end."""

    def __init__(self, v: float):
        self.v = float(v)

    def __call__(self, epoch, iteration, epoch_end, **state):
        loss = state.get("loss")
        return epoch_end and loss is not None and loss <= self.v


class MaxScore(Trigger):
    """Stop once a validation metric reaches ``v`` (BigDL
    `Trigger.maxScore`); uses ``metric`` or the first validation
    metric reported."""

    def __init__(self, v: float, metric: "Optional[str]" = None):
        self.v = float(v)
        self.metric = metric

    def __call__(self, epoch, iteration, epoch_end, **state):
        metrics = state.get("val_metrics") or {}
        if not (epoch_end and metrics):
            return False
        if self.metric is not None:
            score = metrics.get(self.metric)
        else:
            score = next(iter(metrics.values()), None)
        return score is not None and score >= self.v


class TriggerAnd(Trigger):
    def __init__(self, *triggers: Trigger):
        self.triggers = triggers

    def __call__(self, *a, **state):
        return all(t(*a, **state) for t in self.triggers)


class TriggerOr(Trigger):
    def __init__(self, *triggers: Trigger):
        self.triggers = triggers

    def __call__(self, *a, **state):
        return any(t(*a, **state) for t in self.triggers)


# ---------------------------------------------------------------------------
# In-memory dataset (the FeatureSet protocol's simplest implementation;
# feature.FeatureSet provides the cached/sharded/tiered version)
# ---------------------------------------------------------------------------

class ArrayDataset:
    """Numpy (x, y) pairs with per-epoch shuffling and fixed-size batches.

    Implements the data protocol the Estimator consumes:
    ``num_samples`` and ``iter_batches(batch_size, shuffle, seed)``.
    Incomplete trailing batches are dropped during training (static shapes
    keep XLA from recompiling; the reference similarly requires
    batch % cores == 0, `P/pipeline/api/net.py:741-749`).
    """

    def __init__(self, x, y=None):
        self.x = x if isinstance(x, (list, tuple)) else [x]
        self.x = [np.asarray(a) for a in self.x]
        # normalize_labels is the one decision point for single-array
        # vs multi-output label lists (scalar lists stay one array)
        self._y_cols, self._multi_y = feature_set.normalize_labels(y)
        self.y = (self._y_cols if self._multi_y
                  else self._y_cols[0] if self._y_cols else None)
        n = self.x[0].shape[0]
        for a in self.x:
            if a.shape[0] != n:
                raise ValueError("inconsistent sample counts in x")
        for a in self._y_cols:
            if a.shape[0] != n:
                raise ValueError("x and y sample counts differ")
        self._n = n

    @property
    def num_samples(self) -> int:
        return self._n

    def batch_selections(self, batch_size: int, shuffle: bool = True,
                         seed: int = 0, drop_last: bool = True):
        """Row indices of each batch of one epoch, in its order."""
        return feature_set.batch_selections(
            self._n, batch_size, shuffle, seed, drop_last)

    def gather(self, sel, out=None, threads: int = 1):
        """Rows ``sel`` as one ``(xb, yb)`` batch
        (`feature_set.gather_batch`)."""
        return feature_set.gather_batch(
            self.x, self._y_cols, self._multi_y, sel, out, threads)

    def iter_batches(self, batch_size: int, shuffle: bool = True,
                     seed: int = 0, drop_last: bool = True):
        """Fresh arrays each batch: the caller may keep them."""
        for sel in self.batch_selections(batch_size, shuffle, seed,
                                         drop_last):
            yield self.gather(sel)


def to_dataset(data, y=None):
    if hasattr(data, "iter_batches"):
        return data
    if hasattr(data, "to_arrays"):
        # TextSet / ImageSet passed straight to fit/evaluate/predict
        # (reference `model.fit(train_set, ...)` over TextSet,
        # `qa_ranker.py`; ImageSet via `ImageSet.toDataSet`)
        xs, ys = data.to_arrays()
        return ArrayDataset(xs, ys if y is None else y)
    from analytics_zoo_tpu.feature.rdd import is_rdd_like, \
        is_spark_dataframe
    if is_rdd_like(data) or is_spark_dataframe(data):
        # RDD[Sample] / Spark-DataFrame ingest (reference
        # `KerasNet.fit(RDD[Sample])`, Topology.scala:411): this host
        # collects its partition share into a cached FeatureSet
        from analytics_zoo_tpu.feature.feature_set import FeatureSet
        return FeatureSet.from_rdd(data)
    return ArrayDataset(data, y)


def _prefetch_iter(it, place, depth: int):
    """Pipeline host batch prep + device placement `depth` batches
    ahead of compute on a background thread (flax
    ``prefetch_to_device`` pattern; role of the reference's
    executor-side Sample→MiniBatch pipelining, SURVEY.md §3.2).

    ``place`` runs IN the worker thread (numpy prep + ``device_put``
    are thread-safe and async); exceptions re-raise at the consumer's
    next pull. ``depth<=0`` = synchronous (debugging / profiling the
    unpipelined path)."""
    if depth <= 0:
        for item in it:
            yield place(item)
        return
    import queue
    import threading

    q: "queue.Queue" = queue.Queue(maxsize=depth)
    stop = threading.Event()
    sentinel = object()

    def _put(obj) -> bool:
        while not stop.is_set():
            try:
                q.put(obj, timeout=0.1)
                return True
            except queue.Full:
                continue
        return False

    def worker():
        try:
            for item in it:
                if stop.is_set() or not _put(place(item)):
                    return
            _put(sentinel)
        except BaseException as e:  # noqa: BLE001 — re-raised below
            _put(e)

    t = threading.Thread(target=worker, daemon=True,
                         name="zoo-tpu-prefetch")
    t.start()
    try:
        while True:
            item = q.get()
            if item is sentinel:
                return
            if isinstance(item, BaseException):
                raise item
            yield item
    finally:
        stop.set()


def _timed_iter(it):
    """Wrap an iterator, yielding ``(wait_s, item)`` — how long the
    consumer blocked waiting for each item. With the prefetch worker
    ahead of compute this is ~0; a sustained positive wait means the
    input pipeline, not the device, is the bottleneck."""
    it = iter(it)
    while True:
        t0 = time.perf_counter()
        try:
            # annotation only: train/step's data_wait_s carries it
            with tracing.annotate("train/data_wait"):
                item = next(it)
        except StopIteration:
            return
        yield time.perf_counter() - t0, item


class _HostRing:
    """One ``train()`` call's host side of the input path: each batch
    is gathered INTO the next of ``length`` recycled buffers (page-warm
    after their first use; a fresh 77 MB destination costs as much in
    page faults as the copy itself), its rows split over the ingest
    threads where the batch is large enough (`feature_set.ingest_width`).

    **A buffer is free only when the runtime has read it**: `placed`
    takes the device arrays made from batch k's buffer and, before
    gather k+1 writes the ring's oldest buffer, waits for the arrays
    that were placed from THAT one (``device_put`` returns at once and
    the runtime lays the batch out and copies it on its own threads).
    Where the placed arrays ARE the host buffer (the CPU backend
    aliases an aligned numpy array, zero-copy, for the array's whole
    life) nothing is recycled: every batch gets a fresh destination.

    A dataset without ``gather`` (a foreign `iter_batches`) keeps its
    own batches: the ring cannot know who else holds them."""

    def __init__(self, ds, batch_size: int, length: int, threads: int):
        self._ds, self._batch_size = ds, batch_size
        self._threads = threads
        self._buffers: "list[Optional[list]]" = [None] * length
        self._placed: "list[Any]" = [None] * length
        self._k = 0  # batches placed so far
        self._recycle = self._gathers = hasattr(ds, "gather")

    def epoch(self, seed: int):
        """``(batch, span fields)`` of one shuffled epoch, drop-last."""
        ds, size = self._ds, self._batch_size
        if not self._gathers:
            for batch in ds.iter_batches(size, shuffle=True, seed=seed):
                yield batch, {"threads": 1, "recycled": False}
            return
        for sel in ds.batch_selections(size, shuffle=True, seed=seed):
            slot = self._k % len(self._buffers)
            recycled = self._recycle
            batch = ds.gather(sel, out=self._buffers[slot],
                              threads=self._threads)
            leaves = jax.tree_util.tree_leaves(batch)
            if recycled:
                self._buffers[slot] = leaves
            yield batch, {
                "threads": max(feature_set.ingest_width(
                    a.nbytes, len(sel), self._threads) for a in leaves),
                "recycled": recycled}

    def placed(self, arrays) -> float:
        """Batch k is placed as ``arrays``; returns the seconds waited
        for the buffer gather k+1 writes to come free (> 0 in steady
        state: the ring is too short for the runtime's copy)."""
        if self._recycle and self._k == 0 and _on_host(arrays):
            self._recycle = False
            self._buffers = [None] * len(self._buffers)
        if not self._recycle:
            return 0.0
        n = len(self._placed)
        self._placed[self._k % n] = arrays
        self._k += 1
        oldest, self._placed[self._k % n] = self._placed[self._k % n], None
        if oldest is None:
            return 0.0
        t0 = time.perf_counter()
        jax.block_until_ready(oldest)
        return time.perf_counter() - t0

    def close(self):
        """Let go of the buffers and of the device arrays (a retained
        traceback must not pin them)."""
        self._buffers = [None] * len(self._buffers)
        self._placed = [None] * len(self._placed)


def _on_host(arrays) -> bool:
    """Whether any placed array lives on a CPU device, where it may
    alias the numpy buffer it was placed from."""
    return any(d.platform == "cpu"
               for a in jax.tree_util.tree_leaves(arrays)
               if isinstance(a, jax.Array) for d in a.devices())


def _traced_gather(it):
    """The train input path's first half, under its own name: each
    ``next()`` on `_HostRing.epoch` (the gather of one batch's rows)
    is one ``train/input_gather`` span. Runs wherever the iterator is
    pulled — the prefetch worker. Yields ``(trace_id, batch)``: the
    batch's trace id is minted here and travels with it through
    `_traced_place` and the queue to the consumer's ``train/step``,
    so one batch reads gather -> place -> step."""
    it = iter(it)
    done = object()
    while True:
        t0_wall, t0 = time.time(), time.perf_counter()
        with tracing.annotate("train/input_gather"):
            item = next(it, done)
        dur_s = time.perf_counter() - t0
        if item is done:
            return
        batch, fields = item
        tid = tracing.new_trace_id()
        tracing.record_span((tid, None), "train/input_gather",
                            t0_wall, dur_s,
                            rows=_batch_dim(batch[0]),
                            bytes=_nbytes(batch), **fields)
        yield tid, batch


def _traced_place(place, ring: _HostRing):
    """``place`` (``device_put``; the runtime's relayout and copy follow
    on its own threads) and the ring's wait for its next buffer as the
    batch's ``train/input_place`` span; takes and returns
    ``(trace_id, …)``."""
    def traced(item):
        tid, batch = item
        with tracing.trace("train/input_place", trace_id=tid,
                           bytes=_nbytes(batch)) as tr:
            placed = place(batch)
            tr.annotate(reuse_wait_s=round(ring.placed(placed), 6))
            return tid, placed
    return traced


class _EpochTurn:
    """``train/epoch_turn``: from an epoch's last dispatch to the next
    epoch's first batch being in hand (or the run's end) — the
    prefetch worker's shutdown, the losses' ``device_get`` (``fetch_s``),
    gauges and summaries, the new worker's first gather and place."""

    def __init__(self, epoch: int):
        self.fields = {"epoch": epoch}
        self._ann = tracing.annotation_start("train/epoch_turn")
        self._t0_wall, self._t0 = time.time(), time.perf_counter()

    def close(self):
        tracing.annotation_end(self._ann)
        tracing.record_span((tracing.new_trace_id(), None),
                            "train/epoch_turn", self._t0_wall,
                            time.perf_counter() - self._t0,
                            **self.fields)


def _prefetch_depth() -> int:
    raw = os.environ.get("ZOO_TPU_PREFETCH", "2")
    try:
        return int(raw)
    except ValueError:
        logger.warning("ZOO_TPU_PREFETCH=%r is not an integer; "
                       "using default depth 2", raw)
        return 2


def _apply_loss(loss_fn, y, out):
    """Keras multi-output semantics: a list/tuple of model outputs
    against a list/tuple of label columns sums per-output losses
    (``loss`` may itself be a list, one fn per output — the
    reference's nested-TensorMeta TFPark contract)."""
    if isinstance(out, (list, tuple)) and isinstance(y, (list, tuple)):
        fns = (list(loss_fn) if isinstance(loss_fn, (list, tuple))
               else [loss_fn] * len(out))
        if not (len(fns) == len(out) == len(y)):
            raise ValueError(
                f"multi-output mismatch: {len(out)} outputs, "
                f"{len(y)} label columns, {len(fns)} losses")
        total = fns[0](y[0], out[0])
        for f, t, o in zip(fns[1:], y[1:], out[1:]):
            total = total + f(t, o)
        return total
    if isinstance(loss_fn, (list, tuple)):
        raise ValueError(
            f"a list of {len(loss_fn)} losses needs a multi-output "
            f"model AND a list of label columns (outputs are "
            f"{type(out).__name__}, labels {type(y).__name__})")
    # mixed structures (list outputs + one packed label array, or the
    # reverse) pass through to the single loss fn: custom joint losses
    # legitimately unpack them (e.g. tfpark IntentEntity)
    return loss_fn(y, out)


def _cast_floats(x, dtype):
    """Cast floating leaves of an input (array or list of arrays);
    ints (ids/labels) pass through."""
    def c(a):
        a = jnp.asarray(a)
        return a.astype(dtype) if jnp.issubdtype(a.dtype, jnp.floating) \
            else a
    if isinstance(x, (list, tuple)):
        return [c(a) for a in x]
    return c(x)


# ---------------------------------------------------------------------------
# Estimator
# ---------------------------------------------------------------------------

@dataclass
class TrainResult:
    history: "list[dict]"
    params: Any
    opt_state: Any
    step: int


class Estimator:
    """`Estimator.train/evaluate` (reference
    `pipeline/estimator/Estimator.scala:31-56`) over a pjit'd step."""

    def __init__(self, model, optimizer="adam", loss="mse",
                 metrics: Optional[List] = None,
                 ctx: Optional[NNContext] = None,
                 parallel_mode: str = "dp",
                 dtype_policy: Optional[str] = None,
                 augment: Optional[Callable] = None):
        if parallel_mode not in ("dp", "fsdp", "tp", "ep"):
            raise ValueError("parallel_mode must be dp|fsdp|tp|ep")
        # default: bf16 activations on TPU (the MXU-native dtype,
        # PERF.md), exact f32 elsewhere (golden tests, CPU parity);
        # explicit arg > env > backend default
        announce_bf16_default = False
        if dtype_policy is None and not os.environ.get(
                "ZOO_TPU_DTYPE_POLICY"):
            dtype_policy = ("mixed_bfloat16" if on_tpu()
                            else "float32")
            announce_bf16_default = dtype_policy == "mixed_bfloat16"
        else:
            dtype_policy = dtype_policy or os.environ.get(
                "ZOO_TPU_DTYPE_POLICY")
        if dtype_policy not in ("float32", "mixed_bfloat16"):
            raise ValueError(
                "dtype_policy must be float32|mixed_bfloat16")
        # mixed_bfloat16: activations/compute in bf16 (the MXU-native
        # dtype), params + loss in f32 — the framework-wide policy the
        # round-1 bench applied ad hoc (VERDICT "What's weak" #8)
        self.dtype_policy = dtype_policy
        self.augment = augment  # train-only on-device augmentation
        self.model = model
        self.ctx = ctx or get_nncontext()
        if announce_bf16_default and not getattr(
                Estimator, "_warned_bf16_default", False):
            # one-time signal: callers who never chose a policy get
            # changed numerics on TPU — make that traceable. Emitted
            # AFTER ctx resolution: get_nncontext() configures the
            # package logger, so an INFO fired earlier in a fresh
            # process would be dropped at the root WARNING level.
            Estimator._warned_bf16_default = True
            logger.info(
                "Estimator defaulting to mixed_bfloat16 on "
                "%s backend (pass dtype_policy='float32' or "
                "set ZOO_TPU_DTYPE_POLICY to override)",
                jax.default_backend())
        self.parallel_mode = parallel_mode
        # a list of losses = one per model output (multi-output
        # training; _apply_loss sums them)
        if isinstance(loss, (list, tuple)):
            self.loss_fn = [losses_lib.get(l) for l in loss]
            for f in self.loss_fn:
                base = getattr(f, "func", f)
                if base is losses_lib.rank_hinge or getattr(
                        base, "__name__", "") == "rank_hinge":
                    # pairwise losses need the whole-batch eval path,
                    # which the per-output vmap decomposition bypasses
                    raise ValueError(
                        "rank_hinge is pairwise and not supported "
                        "inside a multi-output loss list")
        else:
            self.loss_fn = losses_lib.get(loss)
        self.metrics = [metrics_lib.get(m) for m in (metrics or [])]
        self._base_tx = optim_lib.get(optimizer)
        self._clip: Optional[optax.GradientTransformation] = None
        self._lr_fn = self._extract_lr_fn(optimizer)

        self.params = None
        self.opt_state = None
        self.step = 0
        self._train_step = None
        self._eval_step = None
        self._predict_fn = None

        # training features
        self.checkpoint_path: Optional[str] = None
        self.checkpoint_trigger: Trigger = EveryEpoch()
        self.tensorboard_dir: Optional[str] = None
        self.tensorboard_app: str = "zoo_tpu"
        self._tb_writer = None
        # True only for writers _tb() opened itself — train() must not
        # close a caller-injected writer (duck-typed fakes/adapters)
        self._tb_owns_writer = False
        self._summary_triggers: "Dict[str, Trigger]" = {}
        # jax.profiler trace capture (SURVEY §5: the TPU analog of the
        # reference's TrainSummary observability)
        self._profile_dir: Optional[str] = None
        self._profile_start = 0
        self._profile_end = 0
        self._profiling = False

    # -- knobs (reference `Topology.scala:197-284`) -------------------------
    @staticmethod
    def _extract_lr_fn(optimizer):
        if isinstance(optimizer, optim_lib.ZooOptimizer):
            lr = optimizer.lr
            return lr if callable(lr) else (lambda step: lr)
        return lambda step: float("nan")

    def set_gradient_clipping_by_l2_norm(self, clip_norm: float):
        self._clip = optax.clip_by_global_norm(clip_norm)
        self._train_step = None
        return self

    def set_constant_gradient_clipping(self, min_value: float,
                                       max_value: float):
        # optax.clip is symmetric; emulate [min, max] clamping
        lo, hi = float(min_value), float(max_value)

        def clamp(updates):
            return jax.tree_util.tree_map(
                lambda g: jnp.clip(g, lo, hi), updates)
        self._clip = optax.stateless(lambda u, p=None: clamp(u))
        self._train_step = None
        return self

    def set_checkpoint(self, path: str,
                       trigger: Optional[Trigger] = None):
        self.checkpoint_path = path
        if trigger is not None:
            self.checkpoint_trigger = trigger
        return self

    def set_tensorboard(self, log_dir: str, app_name: str = "zoo_tpu"):
        self.tensorboard_dir = log_dir
        self.tensorboard_app = app_name
        return self

    def set_summary_trigger(self, name: str, trigger: Trigger):
        """Enable extra summaries on a trigger (BigDL
        `TrainSummary.setSummaryTrigger`). Supported: "Parameters" —
        per-layer weight histograms (device fetch per firing; keep the
        trigger sparse on remote transports) — and "LearningRate" —
        the current schedule value, written to TensorBoard at firing
        time and mirrored to the ``zoo_tpu_learning_rate`` gauge."""
        if name not in ("Parameters", "LearningRate"):
            raise ValueError(
                f"unsupported summary {name!r}; supported: "
                f"Parameters, LearningRate")
        self._summary_triggers[name] = trigger
        return self

    def _record_lr(self, tb, step: int) -> float:
        """Schedule value at ``step`` → the ``zoo_tpu_learning_rate``
        gauge, plus the TensorBoard ``LearningRate`` scalar when a
        writer is passed (the "LearningRate" summary-trigger path)."""
        lr = float(self._lr_fn(step))
        if lr == lr:  # not NaN (a ZooOptimizer schedule is attached)
            obs.gauge("zoo_tpu_learning_rate",
                      help="current learning-rate schedule value"
                      ).set(lr)
            if tb is not None:
                tb.add_scalar("LearningRate", lr, step)
        return lr

    def _write_param_histograms(self, tb, step: int):
        # ONE whole-tree fetch (per-leaf device_get would be a
        # round-trip storm on remote transports)
        flat, _ = jax.tree_util.tree_flatten_with_path(
            jax.device_get(self.params))
        for path, leaf in flat:
            tag = jax.tree_util.keystr(path).strip("'[]").replace(
                "']['", "/")
            tb.add_histogram(f"Parameters/{tag}", np.asarray(leaf),
                             step)

    def set_dtype_policy(self, policy: str):
        """"float32" or "mixed_bfloat16" (bf16 activations, f32
        params/loss — the TPU mixed-precision recipe)."""
        if policy not in ("float32", "mixed_bfloat16"):
            raise ValueError(
                "dtype_policy must be float32|mixed_bfloat16")
        self.dtype_policy = policy
        self._train_step = None
        self._eval_step = None
        self._predict_fn = None
        return self

    def set_profile(self, log_dir: str, start_step: int = 3,
                    n_steps: int = 3):
        """Capture a ``jax.profiler`` trace of training steps
        [start_step, start_step + n_steps) into ``log_dir`` —
        TensorBoard-viewable (reference observability analog,
        Topology.scala:197-229 / SURVEY §5). Default skips the compile
        step so the trace shows steady-state device time."""
        self._profile_dir = log_dir
        self._profile_start = int(start_step)
        self._profile_end = int(start_step) + int(n_steps)
        return self

    def _tb(self):
        if self.tensorboard_dir is None:
            return None
        if self._tb_writer is None:
            from torch.utils.tensorboard import SummaryWriter
            self._tb_writer = SummaryWriter(
                os.path.join(self.tensorboard_dir, self.tensorboard_app))
            self._tb_owns_writer = True
        return self._tb_writer

    def _place_params(self, params):
        """DP: replicate (the reference's broadcast-weights semantics);
        FSDP: ZeRO-shard over the 'fsdp' mesh axis; TP: Megatron-style
        output-dim kernel sharding over 'model' (GSPMD propagates the
        activation shardings and inserts the collectives); EP: shard
        layer-declared expert-stacked params over 'expert', replicate
        the rest."""
        if self.parallel_mode == "fsdp":
            from analytics_zoo_tpu.parallel.mesh import shard_params_fsdp
            return shard_params_fsdp(params, self.ctx.mesh)
        if self.parallel_mode == "tp":
            from analytics_zoo_tpu.parallel.mesh import shard_params_tp
            return shard_params_tp(params, self.ctx.mesh)
        if self.parallel_mode == "ep":
            from analytics_zoo_tpu.parallel.mesh import (
                collect_ep_paths, shard_params_ep)
            return shard_params_ep(
                params, self.ctx.mesh,
                ep_paths=collect_ep_paths(self.model))
        return shard_params(params, self.ctx.mesh)

    # -- compiled steps -----------------------------------------------------
    def _tx(self) -> optax.GradientTransformation:
        mask = self.model.trainable_mask(self.params)
        labels = jax.tree_util.tree_map(
            lambda t: "train" if t else "freeze", mask)
        parts = []
        if self._clip is not None:
            parts.append(self._clip)
        parts.append(self._base_tx)
        return optax.multi_transform(
            {"train": optax.chain(*parts), "freeze": optax.set_to_zero()},
            labels)

    @staticmethod
    def _merge_updates(params, updates):
        """Recursively fold BatchNorm-style state updates into params.
        Lists merge element-wise with ``None`` meaning "unchanged"
        (the tfpark bridge's sparse weight-list updates)."""
        if updates is None:
            return params
        if isinstance(updates, (list, tuple)) and \
                isinstance(params, (list, tuple)):
            return type(params)(
                Estimator._merge_updates(p, u)
                for p, u in zip(params, updates))
        if not isinstance(updates, dict) or not isinstance(params, dict):
            return updates
        out = dict(params)
        for k, v in updates.items():
            out[k] = Estimator._merge_updates(params.get(k), v)
        return out

    def _build_train_step(self, tx):
        model = self.model
        loss_fn = self.loss_fn
        mixed = self.dtype_policy == "mixed_bfloat16"
        augment = self.augment

        def train_step(params, opt_state, rng, x, y):
            if augment is not None:
                # train-only, traced into the step (on-device; see
                # feature/image/device_transforms) — eval/predict
                # never augment, like the reference's train-phase
                # transformer chains
                r_aug, rng = jax.random.split(rng)
                x = augment(r_aug, x)
            if mixed:
                x = _cast_floats(x, jnp.bfloat16)

            def compute_loss(p):
                out, state_upd = model.apply(p, x, training=True, rng=rng)
                with jax.named_scope("zoo:train/loss"):
                    if mixed:  # loss in f32 for numeric stability
                        out = _cast_floats(out, jnp.float32)
                    loss = _apply_loss(loss_fn, y, out)
                    loss = loss + model.regularization_loss(p)
                return loss, state_upd

            (loss, state_upd), grads = jax.value_and_grad(
                compute_loss, has_aux=True)(params)
            with jax.named_scope("zoo:train/optimizer"):
                updates, opt_state = tx.update(grads, opt_state,
                                               params)
                params = optax.apply_updates(params, updates)
            if state_upd:
                params = Estimator._merge_updates(params, state_upd)
            return params, opt_state, loss

        return jax.jit(train_step, donate_argnums=(0, 1))

    def _build_eval_step(self):
        model = self.model
        metrics = self.metrics
        loss_fn = self.loss_fn

        # pairwise losses can't be decomposed per-sample (vmapping one
        # would see an empty negative set → NaN); detect rank_hinge
        # through functools.partial wrapping too
        base_loss = getattr(loss_fn, "func", loss_fn)
        pairwise = base_loss is losses_lib.rank_hinge or \
            getattr(base_loss, "__name__", "") == "rank_hinge"
        margin = float(getattr(loss_fn, "keywords", {})
                       .get("margin", 1.0)) if pairwise else 1.0

        mixed = self.dtype_policy == "mixed_bfloat16"

        def eval_step(params, x, y, w):
            if mixed:
                x = _cast_floats(x, jnp.bfloat16)
            out = model.forward(params, x, training=False)
            if mixed:
                out = _cast_floats(out, jnp.float32)
            if pairwise:
                # pairwise over adjacent (pos, neg) rows — mask pairs,
                # not samples
                scores = out.reshape(-1)
                wp = w[0::2] * w[1::2]
                per_pair = jnp.maximum(
                    margin - scores[0::2] + scores[1::2], 0.0)
                loss_sum, count = jnp.sum(per_pair * wp), jnp.sum(wp)
            else:
                # per-sample losses so padding samples (w=0) drop out;
                # each sample is evaluated as a batch of 1 so loss fns
                # keep their batch-mean semantics (tree_map: y/out may
                # be multi-output lists)
                _b1 = lambda tree: jax.tree_util.tree_map(
                    lambda a: a[None], tree)
                per = jax.vmap(
                    lambda t, p: _apply_loss(
                        loss_fn, _b1(t), _b1(p)))(y, out)
                loss_sum, count = jnp.sum(per * w), jnp.sum(w)
            stats = {"loss": {"loss_sum": loss_sum, "count": count}}
            if metrics and isinstance(out, (list, tuple)):
                # built-in metrics assume single arrays; fail at trace
                # time with the real reason, not a TypeError deep in
                # the arithmetic
                raise ValueError(
                    "metrics are not supported with multi-output "
                    "models yet — evaluate with metrics=[] (the "
                    "summed multi-output loss is still reported)")
            for m in metrics:
                if _accepts_mask(m):
                    stats[m.name] = m.batch_stats(y, out, mask=w)
                else:  # user Metric subclass on the pre-mask signature
                    stats[m.name] = m.batch_stats(y, out)
            return stats

        for m in metrics:
            if not _accepts_mask(m):
                logger.warning(
                    "metric %s has a batch_stats(y_true, y_pred) without "
                    "a mask parameter: padded tail samples may bias it; "
                    "add mask=None support for exact results", m.name)
        return jax.jit(eval_step)

    def _build_predict_fn(self):
        model = self.model
        mixed = self.dtype_policy == "mixed_bfloat16"

        def predict_fn(params, x):
            if mixed:
                x = _cast_floats(x, jnp.bfloat16)
            out = model.forward(params, x, training=False)
            return _cast_floats(out, jnp.float32) if mixed else out

        return jax.jit(predict_fn)

    def _ensure_initialized(self, sample_batch=None):
        if self.params is None:
            # host init, then ONE sharded placement — device-0 never
            # holds a transient full replica under FSDP/TP
            self.params = self._place_params(self.model.init_params(
                self.ctx.next_rng_key(), device="host"))
        if self.opt_state is None:
            tx = self._tx()
            # one compiled program, one dispatch — eager tx.init is a
            # per-leaf op storm over a remote-device transport, and jit
            # inherits the params' shardings for the momentum/adam
            # buffers (the state lands pre-sharded under FSDP/TP/EP)
            self.opt_state = jax.jit(tx.init)(self.params)
            self._train_step = self._build_train_step(tx)
        elif self._train_step is None:
            self._train_step = self._build_train_step(self._tx())

    def _measure_step_flops(self, rng, xb, yb):
        """Executed-semantics FLOPs of one compiled train step
        (:mod:`analytics_zoo_tpu.perf.flops` — dilation zeros counted
        the way the MXU executes them), via a one-off AOT retrace.
        None when the graph cannot be lowered or parsed; the goodput
        ledger then reports MFU as 0 but keeps the wall-time
        decomposition live."""
        try:
            from analytics_zoo_tpu.perf import flops as flops_lib
            with obs.span("train/flops_lowering"):
                lowered = self._train_step.lower(
                    self.params, self.opt_state, rng, xb, yb)
                return flops_lib.executed_flops(
                    flops_lib.hlo_text(lowered))
        except Exception:
            return None

    # -- API ---------------------------------------------------------------
    def train(self, data, y=None, batch_size: int = 32,
              nb_epoch: int = 1,
              validation_data=None,
              validation_trigger: Optional[Trigger] = None,
              end_trigger: Optional[Trigger] = None) -> TrainResult:
        ds = to_dataset(data, y)
        self.ctx.check_batch_size(batch_size)
        self._ensure_initialized()
        tb = self._tb()
        validation_trigger = validation_trigger or EveryEpoch()
        base_rng = self.ctx.next_rng_key()
        history: "list[dict]" = []
        stop = False
        # profile window is relative to THIS run (self.step may already
        # be far along from a previous train() call)
        p_start = self.step + self._profile_start
        p_end = self.step + self._profile_end
        # telemetry (docs/observability.md): per-step host wall time is
        # dispatch-to-dispatch — under queue backpressure it converges
        # to device step time without forcing a per-step sync
        step_hist = obs.histogram(
            "zoo_tpu_train_step_seconds",
            help="host wall time per training step "
                 "(dispatch-to-dispatch)")
        steps_total = obs.counter("zoo_tpu_train_steps_total",
                                  help="training steps dispatched")
        examples_total = obs.counter(
            "zoo_tpu_train_examples_total",
            help="training examples consumed")
        first_step = True
        # diagnostics (docs/observability.md anomaly catalog):
        # straggler steps + recompile storms fire structured events
        watcher = diagnostics.StepTimeWatcher()
        diagnostics.install_recompile_monitor()
        # judgement layer: shipped training objectives (docs/slo.md)
        # + the live goodput/MFU ledger (docs/observability.md) —
        # each env-gated (ZOO_TPU_SLO / ZOO_TPU_GOODPUT)
        slo_lib.ensure_default_slos("training")
        ledger = goodput_lib.ledger_for_backend()
        turn: "Optional[_EpochTurn]" = None
        depth = _prefetch_depth()
        # one buffer being written, `depth` placed and in the queue
        ring = _HostRing(ds, batch_size, max(depth, 0) + 1,
                         self.ctx.conf.ingest_threads)

        try:
            for epoch in range(1, nb_epoch + 1):
                n_records = 0
                # keep losses on-device during the epoch: fetching per
                # step would stall the dispatch pipeline (expensive
                # over remote device transports)
                pending: "list[tuple[int, Any]]" = []
                mesh = self.ctx.mesh

                def _place(batch, mesh=mesh):
                    xb, yb = batch
                    return (shard_batch(xb, mesh),
                            shard_batch(yb, mesh))

                # closing(): break/exception must stop the worker
                # thread NOW, not at GC — a retained traceback would
                # otherwise pin depth+1 device-resident batches
                # (notebook OOM-retry trap)
                batches = _prefetch_iter(
                    _traced_gather(ring.epoch(seed=epoch)),
                    _traced_place(_place, ring), depth)
                ep_span = obs.span("train/epoch", epoch=epoch,
                                   step=self.step)
                with ep_span:
                    try:
                        t_prev = time.perf_counter()
                        t_led_prev = t_prev
                        for wait_s, (tid, (xb, yb)) in \
                                _timed_iter(batches):
                            if turn is not None:
                                turn.close()
                                turn = None
                            with tracing.trace(
                                      "train/step", trace_id=tid,
                                      step=self.step + 1,
                                      epoch=epoch) as tr:
                                rng = jax.random.fold_in(base_rng,
                                                         self.step)
                                if self._profile_dir and \
                                        not self._profiling and \
                                        self.step + 1 >= p_start:
                                    jax.profiler.start_trace(
                                        self._profile_dir)
                                    self._profiling = True
                                # step markers line up with our spans in
                                # on-demand XLA profiles (/debug/profile)
                                t_disp = time.perf_counter()
                                with jax.profiler.StepTraceAnnotation(
                                        "train", step_num=self.step):
                                    self.params, self.opt_state, loss = \
                                        self._train_step(
                                            self.params, self.opt_state,
                                            rng, xb, yb)
                                dispatch_s = (time.perf_counter()
                                              - t_disp)
                                self.step += 1
                                if first_step:
                                    # includes XLA compile when this call
                                    # traced a fresh step fn; the one-time
                                    # sync is noise next to the compile
                                    jax.block_until_ready(loss)
                                    obs.gauge(
                                        "zoo_tpu_train_first_step_seconds",
                                        help="first-step wall time of the "
                                             "latest run (incl. compile)"
                                    ).set(time.perf_counter() - t_prev)
                                    first_step = False
                                    if ledger is not None and \
                                            goodput_lib.flops_enabled():
                                        ledger.set_flops_per_step(
                                            self._measure_step_flops(
                                                rng, xb, yb))
                                if self._profiling and self.step >= p_end:
                                    jax.block_until_ready(loss)
                                    jax.profiler.stop_trace()
                                    self._profiling = False
                                    self._profile_dir = None
                                now = time.perf_counter()
                                step_hist.observe(now - t_prev)
                                watcher.observe(now - t_prev,
                                                step=self.step)
                                t_prev = now
                                steps_total.inc()
                                examples_total.inc(batch_size)
                                n_records += batch_size
                                pending.append((self.step, loss))
                                if self._summary_triggers:
                                    trig = self._summary_triggers.get(
                                        "Parameters")
                                    if tb is not None and trig is not None \
                                            and trig(epoch, self.step,
                                                     False):
                                        self._write_param_histograms(
                                            tb, self.step)
                                    trig = self._summary_triggers.get(
                                        "LearningRate")
                                    if trig is not None and trig(
                                            epoch, self.step, False):
                                        self._record_lr(tb, self.step)
                                ckpt_s = None
                                if self.checkpoint_path and \
                                        self.checkpoint_trigger(
                                            epoch, self.step, False):
                                    t_ck = time.perf_counter()
                                    self.save_checkpoint()
                                    ckpt_s = (time.perf_counter()
                                              - t_ck)
                                tr.annotate(
                                    data_wait_s=round(wait_s, 6),
                                    dispatch_s=round(dispatch_s, 6),
                                    checkpoint_s=ckpt_s)
                                if ledger is not None:
                                    # ledger wall is iteration-to-
                                    # iteration (incl. checkpoint) so
                                    # the decomposition sums to 1
                                    t_led = time.perf_counter()
                                    ledger.note_step(
                                        t_led - t_led_prev,
                                        data_wait_s=wait_s,
                                        dispatch_s=dispatch_s,
                                        checkpoint_s=ckpt_s or 0.0)
                                    t_led_prev = t_led
                                if end_trigger is not None and end_trigger(
                                        epoch - 1, self.step, False):
                                    stop = True
                                    break
                        turn = _EpochTurn(epoch)
                    finally:
                        # break/exception must stop the worker thread
                        # NOW, not at GC — a retained traceback would
                        # otherwise pin depth+1 device-resident
                        # batches (notebook OOM-retry trap)
                        batches.close()

                    t_fetch = time.perf_counter()
                    losses_np = ([float(v) for v in
                                  jax.device_get(
                                      [v for _, v in pending])]
                                 if pending else [])
                    turn.fields["fetch_s"] = round(
                        time.perf_counter() - t_fetch, 6)
                dt = max(ep_span.elapsed, 1e-9)
                if tb is not None:
                    for (s, _), lf in zip(pending, losses_np):
                        tb.add_scalar("Loss", lf, s)
                        lr = self._lr_fn(s)
                        if lr == lr:  # not NaN
                            tb.add_scalar("LearningRate", lr, s)
                epoch_batches = len(pending)
                epoch_loss = float(np.sum(losses_np))
                throughput = n_records / dt
                obs.gauge(
                    "zoo_tpu_train_throughput_examples_per_sec",
                    help="epoch training throughput").set(throughput)
                self._record_lr(None, self.step)  # gauge refresh
                diagnostics.update_device_memory_gauges()
                entry = {"epoch": epoch,
                         "loss": epoch_loss / max(epoch_batches, 1),
                         "throughput": throughput, "step": self.step}
                if ledger is not None:
                    gp = ledger.epoch_summary(epoch=epoch)
                    if gp is not None:
                        entry["goodput"] = gp
                if tb is not None:
                    tb.add_scalar("Throughput", throughput, self.step)
                if validation_data is not None and validation_trigger(
                        epoch, self.step, True):
                    # keras-style (x_val, y_val) tuples are
                    # (data, labels), not a two-input feature list
                    if isinstance(validation_data, tuple) and \
                            len(validation_data) == 2 and not hasattr(
                                validation_data, "iter_batches"):
                        val = self.evaluate(validation_data[0],
                                            validation_data[1],
                                            batch_size=batch_size)
                    else:
                        val = self.evaluate(validation_data,
                                            batch_size=batch_size)
                    entry.update(
                        {f"val_{k}": v for k, v in val.items()})
                    if tb is not None:
                        for k, v in val.items():
                            tb.add_scalar(f"Validation/{k}", v,
                                          self.step)
                if self.checkpoint_path and self.checkpoint_trigger(
                        epoch, self.step, True):
                    self.save_checkpoint()
                if self._summary_triggers:
                    trig = self._summary_triggers.get("Parameters")
                    if tb is not None and trig is not None and trig(
                            epoch, self.step, True):
                        # epoch-end firing (EveryEpoch-style triggers)
                        self._write_param_histograms(tb, self.step)
                    trig = self._summary_triggers.get("LearningRate")
                    if trig is not None and trig(
                            epoch, self.step, True):
                        self._record_lr(tb, self.step)
                history.append(entry)
                logger.info("epoch %d: %s", epoch, entry)
                if stop or (end_trigger is not None and end_trigger(
                        epoch, self.step, True,
                        loss=entry.get("loss"),
                        val_metrics={k[4:]: v for k, v in entry.items()
                                     if k.startswith("val_")})):
                    break
        finally:
            ring.close()
            if turn is not None:  # the run's last epoch, or an error
                turn.close()
            if self._profiling:  # run ended inside the trace window
                jax.profiler.stop_trace()
                self._profiling = False
                self._profile_dir = None
            if self._tb_writer is not None:
                self._tb_writer.flush()
                if self._tb_owns_writer:
                    # per-fit lifecycle for writers _tb() opened:
                    # close on every exit path (incl. exceptions) — a
                    # writer leaked across runs keeps its event file
                    # growing and holds the fd until GC. Injected
                    # writers stay attached: the caller owns them.
                    self._tb_writer.close()
                    self._tb_writer = None
                    self._tb_owns_writer = False
        # durable on return: join any in-flight async checkpoint write
        self.wait_for_checkpoint()
        return TrainResult(history, self.params, self.opt_state, self.step)

    def evaluate(self, data, y=None, batch_size: int = 32
                 ) -> "dict[str, float]":
        ds = to_dataset(data, y)
        self._ensure_initialized()
        if self._eval_step is None:
            self._eval_step = self._build_eval_step()
        totals: "dict[str, dict[str, np.ndarray]]" = {}
        # every batch (incl. the tail) is padded to ONE static shape
        # divisible by the data-parallel size and evaluated with a
        # per-sample {0,1} weight vector: no tail samples are dropped
        # (dropping them biases metrics) and the eval step compiles
        # exactly once
        dp = self.ctx.data_parallel_size
        padded = -(-batch_size // dp) * dp
        mesh = self.ctx.mesh

        def _place(batch, mesh=mesh):
            xb, yb = batch
            bsize = _batch_dim(xb)
            w = np.zeros((padded,), np.float32)
            w[:bsize] = 1.0
            if bsize < padded:
                xb = _pad_batch(xb, padded)
                yb = _pad_batch(yb, padded) if yb is not None else None
            return (shard_batch(xb, mesh), shard_batch(yb, mesh),
                    shard_batch(w, mesh))

        batches = _prefetch_iter(
            ds.iter_batches(batch_size, shuffle=False,
                            drop_last=False),
            _place, _prefetch_depth())
        try:
            # each evaluate() call is one trace: the eval span (and
            # any nested spans) lands in /debug/traces & the exporter
            with tracing.trace("train/eval_run", step=self.step), \
                    obs.span("train/eval", step=self.step,
                             n=ds.num_samples):
                for xb, yb, wb in batches:
                    with jax.profiler.StepTraceAnnotation(
                            "eval", step_num=self.step):
                        stats = jax.device_get(
                            self._eval_step(self.params, xb, yb, wb))
                    for mname, mstats in stats.items():
                        acc = totals.setdefault(mname, {})
                        for k, v in mstats.items():
                            acc[k] = acc.get(k, 0) + np.asarray(v)
        finally:
            batches.close()  # deterministic worker shutdown
        out = {}
        if "loss" in totals:
            out["loss"] = float(totals["loss"]["loss_sum"] /
                                np.maximum(totals["loss"]["count"], 1.0))
        for m in self.metrics:
            if m.name in totals:
                out[m.name] = m.aggregate(totals[m.name])
        return out

    def predict(self, data, batch_size: int = 32) -> np.ndarray:
        ds = to_dataset(data)
        self._ensure_initialized()
        if self._predict_fn is None:
            self._predict_fn = self._build_predict_fn()
        outs = []
        n = ds.num_samples
        # compiled batch must divide over the data-parallel size; pad
        # every chunk (incl. full ones when batch_size itself doesn't
        # divide) and trim after
        dp = self.ctx.data_parallel_size
        padded = -(-batch_size // dp) * dp
        mesh = self.ctx.mesh

        def _place(batch, mesh=mesh):
            xb, _ = batch
            bsize = _batch_dim(xb)
            if bsize < padded:  # pad to keep the compiled shape
                xb = _pad_batch(xb, padded)
            return shard_batch(xb, mesh), bsize

        batches = _prefetch_iter(
            ds.iter_batches(batch_size, shuffle=False,
                            drop_last=False),
            _place, _prefetch_depth())
        try:
            for xb, bsize in batches:
                y = jax.device_get(self._predict_fn(self.params, xb))
                outs.append(_trim_batch(y, bsize))
        finally:
            batches.close()  # deterministic worker shutdown
        if not outs:
            return np.empty((0,))
        return _concat_pytree(outs)[:n] if not isinstance(outs[0], (list,
            tuple)) else _concat_pytree(outs)

    # -- checkpoint / resume (reference `Topology.scala:238-248,996-1004`,
    #    resume via Module.load, SURVEY.md §5 "Checkpoint / resume") -------
    def save_checkpoint(self, path: Optional[str] = None,
                        block: Optional[bool] = None):
        """Snapshot params/opt_state/step to ``path``.

        The device→host fetch is always synchronous (donated step
        buffers make a background fetch unsafe); with ``block=False``
        (or ``ZOO_TPU_ASYNC_CKPT=1``) the pickle + atomic write happen
        on a background thread so the train loop resumes immediately.
        Writes are serialized; a failed background write re-raises at
        the next save (or at :meth:`wait_for_checkpoint`)."""
        path = path or self.checkpoint_path
        if path is None:
            raise ValueError("no checkpoint path set")
        if block is None:
            block = os.environ.get("ZOO_TPU_ASYNC_CKPT", "0") != "1"
        self.wait_for_checkpoint()  # serialize + surface prior errors
        os.makedirs(path, exist_ok=True)
        state = {
            "params": jax.device_get(self.params),
            "opt_state": jax.device_get(self.opt_state),
            "step": self.step,
        }
        step = self.step

        def write():
            with obs.span("train/checkpoint", step=step):
                tmp = os.path.join(path, f".tmp_ckpt_{step}")
                with open(tmp, "wb") as f:
                    pickle.dump(state, f)
                    # fault point sits between "bytes written" and
                    # "made durable/visible": a kill/error here leaves
                    # only the .tmp_* file, which load_checkpoint
                    # never considers
                    _CKPT_FAULT.fire(step=step)
                    f.flush()
                    os.fsync(f.fileno())
                final = os.path.join(path, f"ckpt_{step}.pkl")
                os.replace(tmp, final)
                # LATEST is promoted atomically too: a reader (or a
                # crash) can never observe a half-written pointer
                latest = os.path.join(path, "LATEST")
                ltmp = latest + ".tmp"
                with open(ltmp, "w") as f:
                    f.write(os.path.basename(final))
                    f.flush()
                    os.fsync(f.fileno())
                os.replace(ltmp, latest)
                _fsync_dir(path)
            return final

        if block:
            return write()

        def worker():
            try:
                write()
            except BaseException as e:  # noqa: BLE001 — re-raised
                self._ckpt_error = e

        import threading
        # non-daemon: if training dies mid-write, interpreter shutdown
        # still joins the writer, so the newest checkpoint survives —
        # the exact crash-recovery scenario async writes exist for
        t = threading.Thread(target=worker, daemon=False,
                             name="zoo-tpu-ckpt-write")
        t.start()
        self._ckpt_thread = t
        return os.path.join(path, f"ckpt_{step}.pkl")

    def save_checkpoint_sharded(self, path: Optional[str] = None):
        """Orbax-backed checkpoint: each host writes only its own
        param/opt-state shards (no full-tree gather through one host —
        the scalable path for FSDP/TP models too big for a single
        host's RAM; the pickle path stays the default for small
        models and whole-file portability). Layout:
        ``<path>/sharded/<step>`` + the same ``LATEST`` pointer file
        with a ``sharded:`` prefix, so :meth:`load_checkpoint`
        dispatches transparently."""
        import orbax.checkpoint as ocp

        path = path or self.checkpoint_path
        if path is None:
            raise ValueError("no checkpoint path set")
        self.wait_for_checkpoint()
        root = os.path.join(os.path.abspath(path), "sharded")
        os.makedirs(root, exist_ok=True)
        step_dir = os.path.join(root, str(self.step))
        with ocp.StandardCheckpointer() as ckptr:
            # force=True: orbax writes to a tmp dir and renames, so an
            # existing same-step checkpoint stays intact until the new
            # one is complete (the pickle path's tmp+os.replace
            # atomicity)
            ckptr.save(step_dir,
                       {"params": self.params,
                        "opt_state": self.opt_state},
                       force=True)
        with open(os.path.join(path, "LATEST"), "w") as f:
            f.write(f"sharded:{self.step}")
        return step_dir

    def _load_checkpoint_sharded(self, path: str, step: int):
        import orbax.checkpoint as ocp

        self._ensure_initialized()  # abstract tree + shardings
        step_dir = os.path.join(os.path.abspath(path), "sharded",
                                str(step))
        tx = self._tx()
        # ONE opt-state materialization serves both the restore target
        # and the placement template (a second one would transiently
        # double the Adam-state footprint on large FSDP models)
        template = jax.jit(tx.init)(self.params)

        def absify(tree):  # aval + SHARDING per leaf (scalars too)
            return jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(
                    a.shape, a.dtype, sharding=a.sharding), tree)

        target = {
            "params": absify(self.params),
            "opt_state": absify(template),
        }
        with ocp.StandardCheckpointer() as ckptr:
            state = ckptr.restore(step_dir, target)
        # explicit re-placement: orbax (and jit's own output layout
        # for fresh scalars like optimizer step counts) can leave 0-d
        # leaves on a single device; mesh-replicate anything without a
        # mesh sharding so the train step sees one device set
        from jax.sharding import NamedSharding, PartitionSpec
        mesh = self.ctx.mesh

        def place(tmpl, restored):
            def put(t, r):
                sh = t.sharding
                if not isinstance(sh, NamedSharding):
                    sh = NamedSharding(mesh, PartitionSpec())
                return jax.device_put(jnp.asarray(r), sh)
            return jax.tree_util.tree_map(put, tmpl, restored)

        self.params = place(self.params, state["params"])
        self.opt_state = place(template, state["opt_state"])
        self.step = step
        self._train_step = self._build_train_step(tx)
        return self

    def _join_ckpt_write(self):
        """Join any in-flight async checkpoint write without raising
        (safe inside ``finally`` — must not mask an active
        exception)."""
        t = getattr(self, "_ckpt_thread", None)
        if t is not None:
            t.join()
            self._ckpt_thread = None

    def wait_for_checkpoint(self):
        """Join any in-flight async checkpoint write; re-raise its
        error if it failed."""
        self._join_ckpt_write()
        err = getattr(self, "_ckpt_error", None)
        if err is not None:
            self._ckpt_error = None
            raise err

    def load_checkpoint(self, path: Optional[str] = None,
                        step: Optional[int] = None):
        # join only (no raise): LATEST may be mid-rewrite, and a
        # failed-save error must not abort the load — but the caller
        # must know LATEST may be older than they think, and the error
        # stays pending so the next save/wait still raises it
        self._join_ckpt_write()
        err = getattr(self, "_ckpt_error", None)
        if err is not None:
            logger.warning(
                "an async checkpoint write failed (%s); LATEST may "
                "point at an older step. The error will re-raise at "
                "the next save_checkpoint/wait_for_checkpoint.", err)
        path = path or self.checkpoint_path
        if step is not None:
            if os.path.isdir(os.path.join(path, "sharded", str(step))):
                return self._load_checkpoint_sharded(path, step)
            fname = os.path.join(path, f"ckpt_{step}.pkl")
        else:
            with open(os.path.join(path, "LATEST")) as f:
                latest = f.read().strip()
            if latest.startswith("sharded:"):
                return self._load_checkpoint_sharded(
                    path, int(latest.split(":", 1)[1]))
            fname = os.path.join(path, latest)
        from analytics_zoo_tpu.common.safe_pickle import checked_load
        state = checked_load(fname)  # class-whitelist deserialization
        params = state["params"]
        _check_params_compatible(self.model, params)
        self.params = self._place_params(params)
        # opt_state leaves are keyed by the saving process's layer names;
        # rebuild the state tree for THIS model and pour the leaves in
        tx = self._tx()
        # structure only — eval_shape runs zero device ops
        template = jax.eval_shape(tx.init, self.params)
        saved_leaves = jax.tree_util.tree_leaves(state["opt_state"])
        template_def = jax.tree_util.tree_structure(template)
        if len(saved_leaves) != template_def.num_leaves:
            raise ValueError(
                "optimizer state in checkpoint does not match this "
                f"model/optimizer ({len(saved_leaves)} vs "
                f"{template_def.num_leaves} leaves)")
        self.opt_state = jax.device_put(
            jax.tree_util.tree_unflatten(template_def, saved_leaves))
        self.step = state["step"]
        self._train_step = self._build_train_step(tx)
        return self


def _check_params_compatible(model, saved: dict) -> None:
    """Layer names are deterministic per architecture
    (`KerasNet._canonicalize_names`), so a checkpoint's keys must match
    this model's layer names exactly; mismatch means a different
    architecture (or user-renamed layers)."""
    expected = {lyr.name for lyr in model.layers}
    got = set(saved)
    if expected != got:
        raise ValueError(
            "checkpoint does not match model architecture; missing "
            f"layers {sorted(expected - got)}, unexpected "
            f"{sorted(got - expected)}")


def _accepts_mask(metric) -> bool:
    import inspect
    try:
        return "mask" in inspect.signature(metric.batch_stats).parameters
    except (TypeError, ValueError):
        return False


def _nbytes(tree) -> int:
    return sum(int(getattr(a, "nbytes", 0))
               for a in jax.tree_util.tree_leaves(tree))


def _batch_dim(x) -> int:
    leaf = x[0] if isinstance(x, (list, tuple)) else x
    return int(leaf.shape[0])


def _pad_batch(x, target: int):
    def pad(a):
        missing = target - a.shape[0]
        return np.concatenate(
            [a, np.repeat(a[-1:], missing, axis=0)], axis=0)
    if isinstance(x, (list, tuple)):
        return [pad(np.asarray(a)) for a in x]
    return pad(np.asarray(x))


def _trim_batch(y, n: int):
    if isinstance(y, (list, tuple)):
        return [np.asarray(a)[:n] for a in y]
    return np.asarray(y)[:n]


def _concat_pytree(chunks):
    if isinstance(chunks[0], (list, tuple)):
        n_out = len(chunks[0])
        return [np.concatenate([c[i] for c in chunks], axis=0)
                for i in range(n_out)]
    return np.concatenate(chunks, axis=0)
