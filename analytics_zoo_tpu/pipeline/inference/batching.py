"""Dynamic request batching for the serving layer (L9).

The reference platform's serving story is per-request: one POST, one
forward (`AbstractInferenceModel.java:25-103`, the web-service
samples). On TPU that shape is pathological twice over — the MXU is
utilization-starved at batch 1, and every distinct request batch size
is a distinct XLA program, so a production mix of request sizes
recompiles forever. This module supplies the two levers Clipper
(NSDI'17) and ORCA (OSDI'22) establish for the problem:

- **shape-bucketed coalescing** — requests land in a bounded queue; a
  dispatcher thread drains up to ``max_batch_size`` rows or until
  ``max_wait_ms`` expires, pads the coalesced batch up to the next
  size in a bucket ladder (powers of two by default), runs ONE
  compiled call per bucket shape, and scatters the un-padded result
  rows back to per-request futures;
- **admission discipline** — a full queue rejects immediately
  (:class:`QueueFullError` → HTTP 503 + ``Retry-After``), bounding
  queue latency instead of letting it grow without limit, and
  per-request deadlines evict expired entries before dispatch
  (:class:`DeadlineExpiredError` → HTTP 504).

Every bucket is AOT-lowered-and-compiled up front (server start when
the model declared ``example_inputs``; first sight of a signature
otherwise), so steady-state serving performs **zero** compilations
regardless of the request-size mix.

Configuration: constructor kwargs override the environment —
``ZOO_TPU_SERVING_BATCH`` (``0`` disables, reverting to the
per-request path), ``ZOO_TPU_SERVING_MAX_BATCH``,
``ZOO_TPU_SERVING_MAX_WAIT_MS``, ``ZOO_TPU_SERVING_QUEUE_DEPTH``,
``ZOO_TPU_SERVING_DEADLINE_MS``, ``ZOO_TPU_SERVING_BUCKETS``
(comma-separated ladder override). See docs/serving.md for the
request lifecycle and the tuning guide, docs/perf_flags.md for the
flag catalog.

Correctness contract: the served forward must be row-wise in eval
mode (row *i* of the output depends only on row *i* of the inputs) —
true of every model the zoo serves (inference runs with
``training=False``, so BatchNorm uses moving statistics). Padding
rows are zeros and are sliced off before scatter.
"""

from __future__ import annotations

import os
import threading
import time
from collections import deque
from concurrent.futures import Future
from typing import Optional, Sequence, Tuple

import numpy as np

from analytics_zoo_tpu.common import diagnostics
from analytics_zoo_tpu.common import faults
from analytics_zoo_tpu.common import observability as obs
from analytics_zoo_tpu.common import tracing
from analytics_zoo_tpu.common.nncontext import logger

__all__ = [
    "DynamicBatcher",
    "ContinuousBatcher",
    "QueueFullError",
    "DeadlineExpiredError",
    "bucket_ladder",
]

# fill-ratio histogram buckets: rows / bucket capacity in (0, 1]
_FILL_BUCKETS = (0.125, 0.25, 0.375, 0.5, 0.625, 0.75, 0.875, 1.0)

# chaos hook: armed via ZOO_TPU_FAULTS or tests (docs/robustness.md);
# fires at the head of every batch dispatch, inside the dispatcher
# thread — the spot a pad/scatter bug would surface
_DISPATCH_FAULT = faults.point("batcher/dispatch")


def _fail_entry(entry, exc):
    """Fail one entry's future without ever raising back into the
    dispatcher: a future a client already cancelled (or that a prior
    pass resolved) refuses ``set_exception``, and that must not take
    the serving thread down with it."""
    try:
        if not entry.future.done():
            entry.future.set_exception(exc)
    except Exception:  # cancelled/resolved between check and set
        pass


class QueueFullError(Exception):
    """Admission rejected: the batcher queue is at capacity. Carries
    ``retry_after_s``, an estimate of when capacity frees up (served
    to clients as HTTP 503 + ``Retry-After``)."""

    def __init__(self, depth: int, retry_after_s: float):
        super().__init__(
            f"serving queue full ({depth} requests waiting); "
            f"retry in ~{retry_after_s:.2f}s")
        self.retry_after_s = retry_after_s


class DeadlineExpiredError(Exception):
    """The request's deadline elapsed while it waited in the queue
    (served to clients as HTTP 504)."""


def bucket_ladder(max_batch: int,
                  override: Optional[Sequence[int]] = None
                  ) -> "Tuple[int, ...]":
    """The batch sizes the batcher compiles and pads to: powers of
    two up to ``max_batch`` (with ``max_batch`` itself appended when
    it is not a power of two), or a validated copy of ``override``."""
    if override is not None:
        ladder = sorted({int(b) for b in override})
        if not ladder or ladder[0] < 1:
            raise ValueError(f"invalid bucket ladder: {override!r}")
        return tuple(ladder)
    ladder = []
    b = 1
    while b < max_batch:
        ladder.append(b)
        b *= 2
    ladder.append(max_batch)
    return tuple(ladder)


class _Entry:
    """One queued request: input arrays, row count, completion
    future, the two clocks (enqueue time, absolute deadline), and —
    when the submitting thread had an open trace — its captured
    trace context, so the dispatcher can credit queue-wait / execute
    / scatter back to the request's trace."""

    __slots__ = ("xs", "n", "sig", "future", "t_enq", "deadline",
                 "trace", "t_enq_wall")

    def __init__(self, xs, n, sig, deadline):
        self.xs = xs
        self.n = n
        self.sig = sig
        self.future: "Future" = Future()
        self.t_enq = time.monotonic()
        self.deadline = deadline  # absolute monotonic, or None
        self.trace = tracing.current()  # None when untraced
        self.t_enq_wall = time.time() if self.trace else 0.0


def _signature(xs) -> tuple:
    """Coalescing key: per-input (row shape, dtype). Requests only
    merge when every input position agrees on both."""
    return tuple((tuple(x.shape[1:]), str(x.dtype)) for x in xs)


class DynamicBatcher:
    """Cross-request micro-batching between the HTTP front-ends and
    :class:`InferenceModel` (module docstring has the design).

    Thread model: any number of handler threads call :meth:`submit`;
    ONE dispatcher thread drains, pads, executes, and scatters — so
    device execution is serialized by construction and the model's
    slot pool is not consumed by the batched path.
    """

    def __init__(self, model, *,
                 max_batch_size: Optional[int] = None,
                 max_wait_ms: Optional[float] = None,
                 queue_depth: Optional[int] = None,
                 deadline_ms: Optional[float] = None,
                 buckets: Optional[Sequence[int]] = None,
                 labels: Optional[dict] = None):
        env = os.environ
        if max_batch_size is None:
            max_batch_size = int(env.get(
                "ZOO_TPU_SERVING_MAX_BATCH", 32))
        if max_wait_ms is None:
            max_wait_ms = float(env.get(
                "ZOO_TPU_SERVING_MAX_WAIT_MS", 5))
        if queue_depth is None:
            queue_depth = int(env.get(
                "ZOO_TPU_SERVING_QUEUE_DEPTH", 256))
        if deadline_ms is None:
            deadline_ms = float(env.get(
                "ZOO_TPU_SERVING_DEADLINE_MS", 0))
        if buckets is None and env.get("ZOO_TPU_SERVING_BUCKETS"):
            buckets = [int(b) for b in
                       env["ZOO_TPU_SERVING_BUCKETS"].split(",")]
        self.model = model
        self.buckets = bucket_ladder(int(max_batch_size), buckets)
        self.max_batch = self.buckets[-1]
        self.max_wait_s = float(max_wait_ms) / 1e3
        self.queue_depth = int(queue_depth)
        self.deadline_s = (float(deadline_ms) / 1e3
                           if deadline_ms else None)

        self._q: "deque[_Entry]" = deque()
        self._cond = threading.Condition()
        self._stop = False
        self._thread: Optional[threading.Thread] = None
        # (signature, bucket) -> compiled executable; invalidated
        # when the model swaps generations (reload)
        self._compiled: dict = {}
        self._unlowerable: set = set()  # sigs that failed to warm
        self._compile_lock = threading.Lock()
        self._model_gen = getattr(model, "generation", 0)
        # optional metric labels (the serving fleet tags each
        # replica's batcher with {"replica": name} so the shared
        # gauge families stay per-queue; label-free children keep
        # the exact pre-fleet exposition)
        self._labels = dict(labels) if labels else None
        self._ema_batch_s = 0.01  # retry-after estimator seed
        # touch the gauges so /metrics carries them from the start
        self._depth_gauge().set(0)
        self._warmed_gauge().set(0)

    # -- factory ------------------------------------------------------------
    @classmethod
    def from_env(cls, model) -> "Optional[DynamicBatcher]":
        """The servers' default construction path: a batcher with
        env-derived settings, or ``None`` when
        ``ZOO_TPU_SERVING_BATCH=0`` reverts to per-request serving."""
        if os.environ.get("ZOO_TPU_SERVING_BATCH", "1") == "0":
            return None
        return cls(model)

    # -- metrics handles ----------------------------------------------------
    def _depth_gauge(self):
        return obs.gauge("zoo_tpu_serving_queue_depth",
                         help="requests waiting in the batcher queue",
                         labels=self._labels)

    def _warmed_gauge(self):
        return obs.gauge("zoo_tpu_serving_warmed_buckets",
                         help="bucket executables compiled and ready",
                         labels=self._labels)

    # -- lifecycle ----------------------------------------------------------
    def start(self) -> "DynamicBatcher":
        """Warm every bucket (when the model declared example inputs)
        and start the dispatcher thread. Idempotent."""
        if self._thread is not None and self._thread.is_alive():
            return self
        diagnostics.install_recompile_monitor()
        # re-touch the gauges at start: the serving_queue_depth SLO
        # (docs/slo.md) must see the family before the first request,
        # even if the registry was reset since construction
        with self._cond:
            self._depth_gauge().set(len(self._q))
        self._warmed_gauge().set(self.warmed_buckets)
        self.warm()
        self._stop = False
        self._thread = threading.Thread(
            target=self._run, name="zoo-tpu-batcher", daemon=True)
        self._thread.start()
        return self

    def stop(self, timeout: float = 30.0):
        """Drain the queue (pending entries execute or expire), then
        stop the dispatcher."""
        with self._cond:
            self._stop = True
            self._cond.notify_all()
        if self._thread is not None:
            self._thread.join(timeout=timeout)
            self._thread = None

    def warm(self) -> int:
        """AOT-lower-and-compile the whole bucket ladder for the
        model's declared example-input signature (the `_install`
        example-inputs path). Returns the number of warmed buckets;
        0 when the signature is unknown (warming then happens on
        first sight of each request signature) or the model cannot
        re-lower (a `load_compiled` serialized executable)."""
        specs = getattr(self.model, "example_input_specs", None)
        if not specs or not getattr(self.model, "can_relower", False):
            return 0
        sig = tuple((tuple(shape[1:]), str(np.dtype(dt)))
                    for shape, dt in specs)
        try:
            return self._warm_signature(sig)
        except Exception as e:
            with self._compile_lock:
                self._unlowerable.add(sig)
            logger.warning(
                "bucket warm failed at start for declared signature "
                "%s (%s: %s); serving it unpadded", sig,
                type(e).__name__, e)
            return 0

    # -- admission ----------------------------------------------------------
    def batchable(self, xs: Sequence[np.ndarray]) -> bool:
        """Whether these inputs can ride the coalescing path: every
        input has a leading (row) dimension and all agree on it."""
        if not xs:
            return False
        if any(x.ndim < 1 for x in xs):
            return False
        n = xs[0].shape[0]
        return n >= 1 and all(x.shape[0] == n for x in xs)

    def submit(self, xs: Sequence[np.ndarray]) -> "Future":
        """Enqueue one request (a list of row-aligned input arrays).
        Returns a future resolving to exactly what
        ``model.predict`` would return for these inputs (one array,
        or a list for multi-output models). Raises
        :class:`QueueFullError` when the queue is at capacity."""
        xs = [np.asarray(x) for x in xs]
        if not self.batchable(xs):
            raise ValueError(
                "inputs are not row-aligned (every input needs the "
                "same leading dimension >= 1)")
        n = xs[0].shape[0]
        deadline = (time.monotonic() + self.deadline_s
                    if self.deadline_s else None)
        entry = _Entry(xs, n, _signature(xs), deadline)
        with self._cond:
            if len(self._q) >= self.queue_depth:
                # ~time for the backlog to drain at current exec rate
                retry = max(
                    0.05, len(self._q) * self._ema_batch_s
                    * max(1.0, n / self.max_batch))
                obs.counter("zoo_tpu_serving_errors_total",
                            help="serving errors by kind",
                            labels={"kind": "queue_full"}).inc()
                raise QueueFullError(len(self._q), retry)
            self._q.append(entry)
            self._depth_gauge().set(len(self._q))
            self._cond.notify_all()
        return entry.future

    # -- dispatcher ---------------------------------------------------------
    def _evict_expired_locked(self):
        if self.deadline_s is None or not self._q:
            return
        now = time.monotonic()
        kept = deque()
        for e in self._q:
            if e.deadline is not None and e.deadline < now:
                obs.counter("zoo_tpu_serving_errors_total",
                            help="serving errors by kind",
                            labels={"kind": "deadline_expired"}).inc()
                _fail_entry(e, DeadlineExpiredError(
                    f"request waited past its "
                    f"{self.deadline_s * 1e3:.0f}ms deadline"))
            else:
                kept.append(e)
        if len(kept) != len(self._q):
            self._q = kept
            self._depth_gauge().set(len(self._q))

    def _ready_rows_locked(self) -> int:
        """Row count of the maximal coalescible prefix (same
        signature as the head, cumulative rows <= max_batch)."""
        rows = 0
        sig = self._q[0].sig
        for e in self._q:
            if e.sig != sig or (rows and rows + e.n > self.max_batch):
                break
            rows += e.n
        return rows

    def _take_batch_locked(self) -> "list[_Entry]":
        batch: "list[_Entry]" = []
        rows = 0
        while self._q:
            e = self._q[0]
            if batch and (e.sig != batch[0].sig
                          or rows + e.n > self.max_batch):
                break
            batch.append(self._q.popleft())
            rows += e.n
            if rows >= self.max_batch:
                break
        self._depth_gauge().set(len(self._q))
        return batch

    def _run(self):
        # Hardening contract (docs/robustness.md): NOTHING that goes
        # wrong while handling one batch — pad, scatter, an injected
        # fault, even a bug in the queue bookkeeping itself — may
        # escape this loop. An escape would kill the one dispatcher
        # thread and wedge the queue forever: every later submit
        # would enqueue, never dispatch, and time out. Each iteration
        # therefore fails at most its own batch and keeps serving.
        while True:
            batch: "list[_Entry]" = []
            try:
                with self._cond:
                    while not self._q and not self._stop:
                        self._cond.wait(timeout=0.1)
                    if not self._q:
                        if self._stop:
                            return
                        continue
                    self._evict_expired_locked()
                    if not self._q:
                        continue
                    # coalescing window anchored at the head's
                    # arrival: the oldest request never waits past
                    # max_wait_ms
                    wait_until = self._q[0].t_enq + self.max_wait_s
                    while (not self._stop
                           and self._ready_rows_locked()
                           < self.max_batch):
                        remaining = wait_until - time.monotonic()
                        if remaining <= 0:
                            break
                        self._cond.wait(timeout=min(remaining, 0.05))
                        self._evict_expired_locked()
                        if not self._q:
                            break
                    if not self._q:
                        continue
                    batch = self._take_batch_locked()
                if batch:
                    self._execute(batch)
            except Exception as e:
                for entry in batch:
                    _fail_entry(entry, e)
                obs.counter("zoo_tpu_serving_errors_total",
                            help="serving errors by kind",
                            labels={"kind": "dispatch_error"}).inc()
                logger.warning("batcher dispatch error (%s: %s); "
                               "dispatcher continues",
                               type(e).__name__, e)

    # -- execution ----------------------------------------------------------
    def _execute(self, batch: "list[_Entry]"):
        _DISPATCH_FAULT.fire(rows=sum(e.n for e in batch))
        now = time.monotonic()
        wait_h = obs.histogram(
            "zoo_tpu_serving_queue_wait_seconds",
            help="time requests spent queued before dispatch")
        rows = sum(e.n for e in batch)
        for e in batch:
            wait_h.observe(now - e.t_enq)
            # credit the queue wait back to each request's trace
            tracing.record_span(
                e.trace, "serving/queue_wait", e.t_enq_wall,
                now - e.t_enq, rows=e.n, batch_rows=rows,
                n_requests=len(batch))
        sig = batch[0].sig
        n_inputs = len(batch[0].xs)
        if len(batch) == 1:
            xs = batch[0].xs
        else:
            xs = [np.concatenate([e.xs[i] for e in batch])
                  for i in range(n_inputs)]
        t0 = time.monotonic()
        t0_wall = time.time()
        try:
            # the first entry's trace becomes ambient, so the pad /
            # predict spans inside _pad_and_run join it as children
            with tracing.activate(batch[0].trace):
                outs, multi = self._run_rows(sig, xs, rows)
        except Exception as e:
            for entry in batch:
                _fail_entry(entry, e)
            return
        exec_s = time.monotonic() - t0
        # coalesced requests beyond the first get an explicit execute
        # span (their trace was not the ambient one during the call)
        for e in batch[1:]:
            tracing.record_span(
                e.trace, "serving/execute", t0_wall, exec_s,
                rows=e.n, batch_rows=rows, n_requests=len(batch))
        self._ema_batch_s = (0.8 * self._ema_batch_s + 0.2 * exec_s)
        off = 0
        t_sc = time.monotonic()
        t_sc_wall = time.time()
        for entry in batch:
            rows_out = [o[off:off + entry.n] for o in outs]
            try:
                if not entry.future.done():
                    entry.future.set_result(
                        rows_out if multi else rows_out[0])
            except Exception:  # cancelled under us: drop the rows,
                pass           # the batchmates still get theirs
            off += entry.n
        scatter_s = time.monotonic() - t_sc
        for e in batch:
            tracing.record_span(
                e.trace, "serving/scatter", t_sc_wall, scatter_s,
                rows=e.n, n_requests=len(batch))

    def _run_rows(self, sig, xs, rows):
        """Execute ``rows`` coalesced rows, chunking when a single
        oversized request exceeds ``max_batch``. Returns ``(outs,
        multi)``: row-aligned output arrays (one per model output)
        and whether the model returned a list (so scatter can
        preserve the per-request output structure)."""
        if rows <= self.max_batch:
            return self._pad_and_run(sig, xs, rows)
        chunks = []
        multi = False
        for lo in range(0, rows, self.max_batch):
            hi = min(lo + self.max_batch, rows)
            part, multi = self._pad_and_run(
                sig, [x[lo:hi] for x in xs], hi - lo)
            chunks.append(part)
        return [np.concatenate([c[i] for c in chunks])
                for i in range(len(chunks[0]))], multi

    def _pad_and_run(self, sig, xs, n):
        bucket = next(b for b in self.buckets if b >= n)
        fn = self._get_compiled(sig, bucket)
        obs.histogram("zoo_tpu_serving_batch_size",
                      help="predict batch size (leading dim)",
                      buckets=obs.SIZE_BUCKETS).observe(n)
        obs.histogram("zoo_tpu_serving_batch_fill_ratio",
                      help="coalesced rows / bucket capacity",
                      buckets=_FILL_BUCKETS).observe(n / bucket)
        if fn is None:
            # model cannot re-lower (serialized executable without a
            # batch-polymorphic blob): coalesce without padding via
            # the per-request path — still one call per drained batch
            with obs.span("serving/predict", rows=n, bucket=0):
                out = self.model.predict(
                    list(xs) if len(xs) > 1 else xs[0])
            multi = isinstance(out, list)
            outs = out if multi else [out]
            return [np.asarray(o) for o in outs], multi
        pad = bucket - n
        if pad:
            with obs.span("serving/pad", rows=n, bucket=bucket,
                          pad=pad):
                xs = [np.concatenate(
                    [x, np.zeros((pad,) + x.shape[1:], x.dtype)])
                    for x in xs]
            obs.counter("zoo_tpu_serving_padding_rows_total",
                        help="padding rows executed (bucket waste)"
                        ).inc(pad)
        obs.counter("zoo_tpu_serving_batch_executions_total",
                    help="bucket executions",
                    labels={"bucket": str(bucket)}).inc()
        with obs.span("serving/predict", rows=n, bucket=bucket,
                      fill=round(n / bucket, 4)):
            out = fn(*xs)
        multi = isinstance(out, (list, tuple))
        outs = list(out) if multi else [out]
        outs = [np.asarray(o) for o in outs]
        for o in outs:
            if o.ndim < 1 or o.shape[0] != bucket:
                raise ValueError(
                    "model output is not row-aligned with its input "
                    f"(expected leading dim {bucket}, got "
                    f"{o.shape}); dynamic batching requires a "
                    "row-wise forward")
        return [o[:n] for o in outs], multi

    # -- bucket executables -------------------------------------------------
    def _get_compiled(self, sig, bucket: int):
        gen = getattr(self.model, "generation", 0)
        with self._compile_lock:
            if gen != self._model_gen:  # model reloaded underneath us
                self._compiled.clear()
                self._unlowerable.clear()
                self._model_gen = gen
                self._warmed_gauge().set(0)
            fn = self._compiled.get((sig, bucket))
            blocked = sig in self._unlowerable
        if fn is not None:
            return fn
        if blocked or not getattr(self.model, "can_relower", False):
            return None
        # first sight of this signature: warm the WHOLE ladder so the
        # request mix that follows never compiles again
        try:
            self._warm_signature(sig)
        except Exception as e:
            # e.g. a program that only lowers at its declared shapes
            # — serve this signature through the un-padded fallback
            with self._compile_lock:
                self._unlowerable.add(sig)
            logger.warning(
                "bucket warm failed for signature %s (%s: %s); "
                "serving it unpadded through model.predict",
                sig, type(e).__name__, e)
        with self._compile_lock:
            return self._compiled.get((sig, bucket))

    def _warm_signature(self, sig) -> int:
        import jax
        warmed = 0
        for b in self.buckets:
            with self._compile_lock:
                if (sig, b) in self._compiled:
                    continue
            args = [jax.ShapeDtypeStruct((b,) + tuple(shape),
                                         np.dtype(dt))
                    for shape, dt in sig]
            with obs.span("serving/bucket_warm", bucket=b):
                fn = self.model.lower_for(args)
            obs.counter("zoo_tpu_serving_bucket_compiles_total",
                        help="bucket executables compiled "
                        "(warm-up only in steady state)").inc()
            with self._compile_lock:
                self._compiled[(sig, b)] = fn
                self._warmed_gauge().set(len(self._compiled))
            warmed += 1
        return warmed

    # -- introspection ------------------------------------------------------
    @property
    def warmed_buckets(self) -> int:
        with self._compile_lock:
            return len(self._compiled)

    def retry_hint_s(self) -> float:
        """The Retry-After estimate a ``QueueFullError`` raised right
        now would carry (EMA batch execution time x queued entries).
        The fleet router aggregates this across replicas to hint
        clients when the whole fleet is saturated."""
        with self._cond:
            depth = len(self._q)
        return max(0.05, depth * self._ema_batch_s)

    def stats(self) -> dict:
        """JSON-able summary for ``GET /health``."""
        with self._cond:
            depth = len(self._q)
        return {
            "enabled": True,
            "queue_depth": depth,
            "queue_capacity": self.queue_depth,
            "buckets": list(self.buckets),
            "warmed_buckets": self.warmed_buckets,
            "max_wait_ms": self.max_wait_s * 1e3,
            "deadline_ms": (self.deadline_s * 1e3
                            if self.deadline_s else None),
        }

    def __repr__(self):
        return (f"DynamicBatcher(buckets={list(self.buckets)}, "
                f"max_wait_ms={self.max_wait_s * 1e3:g}, "
                f"queue_depth={self.queue_depth}, "
                f"warmed={self.warmed_buckets})")


class _GenEntry:
    """One queued generation request: prompt tokens, decode budget,
    sampling knobs, completion future, clocks, and — once admitted —
    its slot, the tokens emitted so far and the times between them."""

    __slots__ = ("ids", "max_new", "temperature", "eos_id", "future",
                 "t_enq", "t_enq_wall", "trace", "slot", "tokens",
                 "queue_s", "first_s", "chunks", "t_last", "gap_sum",
                 "gap_max", "gaps_behind", "prefilling", "handoff",
                 "blob", "prompt_len", "ahead")

    def __init__(self, ids, max_new, temperature, eos_id):
        self.ids = ids
        self.max_new = max_new
        self.temperature = temperature
        self.eos_id = eos_id
        self.future: "Future" = Future()
        self.t_enq = time.monotonic()
        self.t_enq_wall = time.time()
        self.trace = tracing.current()
        self.slot = -1
        self.tokens: "list[int]" = []
        self.queue_s = 0.0  # submit to popped by the loop
        self.first_s = 0.0  # submit to the first token handed out
        self.chunks = 0     # chunk programs that wrote the prompt
        # the gaps between its tokens: the time of the last hand-out,
        # their sum and longest (there are ``len(tokens) - 1``), and
        # how many closed behind another request's prompt program
        self.t_last = 0.0
        self.gap_sum = 0.0
        self.gap_max = 0.0
        self.gaps_behind = 0
        self.prefilling = False  # admitted, prompt not fully cached
        # disaggregation: None = ordinary request; "out" = prefill
        # side (future resolves to a handoff blob at first token);
        # "in" = decode side (admitted from ``blob``, no prefill)
        self.handoff = None
        self.blob = None
        # page-accounting length: the prompt length, or — for a
        # handoff-in entry that never sees the prompt — the blob's
        # cached position
        self.prompt_len = len(ids)
        # tokens dispatched for it that the host has not fetched yet
        # (its first, or a decode step's): counted, never waited for,
        # when the next step's mask is built
        self.ahead = 0


class _Pass:
    """What one pass of the loop dispatched and waited for, summed
    from the handles' own times (`decode/iteration`'s ``dispatch_s``,
    ``wait_s``, ``programs``)."""

    __slots__ = ("dispatch_s", "wait_s", "programs")

    def __init__(self):
        self.dispatch_s = self.wait_s = 0.0
        self.programs = 0

    def sent(self, dispatch_s: float, programs: int = 1):
        self.dispatch_s += dispatch_s
        self.programs += programs


class ContinuousBatcher:
    """Iteration-level scheduling for autoregressive decode — the
    generation-side sibling of :class:`DynamicBatcher` (ORCA,
    OSDI'22). Where DynamicBatcher coalesces whole fixed-shape
    forwards, generation requests run for a variable number of steps,
    so batching whole *requests* would hold every sequence hostage to
    the longest one. Instead ONE compiled decode step runs
    continuously over a fixed slot array
    (`pipeline/inference/generation.py::GenerationEngine`), and this
    batcher reschedules **between steps**: finished sequences retire
    (pages reclaimed, future resolved) and queued ones are admitted
    into the freed slots, each by a prefill of its own prompt alone —
    the running neighbours never stop, and (a prefill touching only
    the slot it is addressed to) never observe the churn.

    Thread model: handler threads call :meth:`submit`; ONE loop
    thread drives admit → step → retire. Admission is gated on a free
    slot AND a full worst-case page reservation
    (`GenerationEngine.can_admit`), so an admitted sequence always
    runs to completion.

    The loop runs ONE decode step ahead of its fetches: the slots'
    last tokens stay on the device, so a pass dispatches step k —
    its mask built from counts alone, a slot being in it while
    tokens emitted + tokens in flight < ``max_new`` — and only then
    fetches step k - 1 and the pass's first tokens, hands them out,
    retires and pops the queue, all while the device runs step k.
    The device executes in dispatch order, so a slot retired with a
    row still in flight (a request that met its ``eos_id``: the row
    is discarded and counted) may be re-admitted at once: the next
    prefill into its pages is ordered behind that row. Under
    speculation (``engine.spec_k > 0``: a round's emission count is
    data the next mask needs) and whenever nothing is in flight the
    same loop is the synchronous order.

    Telemetry (docs/observability.md): one `decode/iteration` trace a
    pass of the loop (split into ``dispatch_s``, the compiled calls
    returning, ``wait_s``, the blocking fetches, and the host's own
    rest), whose children `decode/prefill` (the wait for an
    admission's first tokens) and `decode/step` (the dispatch of
    step k and the fetch of step k - 1: the loop's period) time the
    engine calls; per request, the already-timed `decode/queue_wait`,
    `decode/admit` (submit to the admission: the first token on the
    one-row path, the slot claimed on the chunked one),
    `decode/first_token` (submit to the first token handed out, on
    every path) and `decode/retire` (submit to last, with the mean
    and the longest gap between its tokens) records under the
    request's own trace; slot-occupancy + free-page gauges, a tokens
    counter, a time-to-first-token histogram, a histogram of the gaps
    between a request's tokens and a counter of the gap seconds
    spent behind other requests' prompt programs.
    ``ZOO_TPU_GEN_QUEUE_DEPTH`` bounds the wait queue (default 64;
    full → :class:`QueueFullError` → 503),
    ``ZOO_TPU_GEN_MAX_NEW`` caps any request's decode budget
    (default 256).
    """

    def __init__(self, engine, *,
                 queue_depth: Optional[int] = None,
                 max_new_cap: Optional[int] = None):
        env = os.environ
        if queue_depth is None:
            queue_depth = int(env.get("ZOO_TPU_GEN_QUEUE_DEPTH", 64))
        if max_new_cap is None:
            max_new_cap = int(env.get("ZOO_TPU_GEN_MAX_NEW", 256))
        self.engine = engine
        self.queue_depth = int(queue_depth)
        self.max_new_cap = int(max_new_cap)
        self._q: "deque[_GenEntry]" = deque()
        self._active: "list[_GenEntry]" = []
        # the decode step the last pass left running, its tokens
        # unfetched: (handle, the entries in its mask, whether its
        # pass dispatched a prompt program before it), or None
        self._flight = None
        self._cond = threading.Condition()
        self._stop = False
        self._draining = False
        self._thread: Optional[threading.Thread] = None
        self._ema_req_s = 0.05  # retry-after estimator seed
        self._slots_gauge().set(0)
        self._pages_gauge().set(engine.free_pages)

    # -- metrics handles ----------------------------------------------------
    def _slots_gauge(self):
        return obs.gauge("zoo_tpu_serving_gen_slots_active",
                         help="decode slots currently generating")

    def _pages_gauge(self):
        return obs.gauge("zoo_tpu_serving_gen_free_pages",
                         help="free KV-cache pages in the pool")

    def _depth_gauge(self):
        return obs.gauge("zoo_tpu_serving_gen_queue_depth",
                         help="generation requests waiting for a slot")

    def _gap_hist(self):
        return obs.histogram(
            "zoo_tpu_serving_gen_token_gap_seconds",
            help="time between two tokens handed to one request",
            buckets=obs.DEFAULT_BUCKETS[:13])   # 1 ms to 10 s

    # -- lifecycle ----------------------------------------------------------
    def start(self) -> "ContinuousBatcher":
        """AOT-warm the decode/prefill programs and start the loop
        thread. Idempotent."""
        if self._thread is not None and self._thread.is_alive():
            return self
        diagnostics.install_recompile_monitor()
        with obs.span("decode/warm"):
            self.engine.warm()
        self._stop = False
        self._draining = False
        self._thread = threading.Thread(
            target=self._run, name="zoo-tpu-gen-batcher", daemon=True)
        self._thread.start()
        return self

    def stop(self, timeout: float = 30.0):
        """Drain first (resident sequences run to completion within
        ``timeout``), then stop the loop thread. Whatever is STILL
        resident or queued when the budget runs out fails with
        RuntimeError and has its slot pages reclaimed — generation
        cannot be handed off mid-sequence the way a queued predict
        can, but an orderly stop should never have to cut anyone off
        (`drain` waited for them)."""
        self.drain(timeout=timeout)
        with self._cond:
            self._stop = True
            self._cond.notify_all()
        if self._thread is not None:
            self._thread.join(timeout=timeout)
            self._thread = None
        with self._cond:
            pending = list(self._q) + list(self._active)
            self._q.clear()
            self._active = []
            self._flight = None     # its requests fail below
        for e in pending:
            if e.slot >= 0:
                self.engine.release(e.slot)
            _fail_entry(e, RuntimeError("generation batcher stopped"))
        self._slots_gauge().set(self.engine.slots_active)
        self._pages_gauge().set(self.engine.free_pages)

    def drain(self, timeout: float = 30.0) -> bool:
        """Stop admitting new sequences but run the RESIDENT ones to
        completion: their futures resolve with real tokens and their
        pages return to the pool (iteration-level scheduling makes
        this cheap — the loop simply steps the shrinking active set
        until it empties). Queued-but-unadmitted entries fail
        immediately with a retryable RuntimeError — the fleet router
        redispatches them to a sibling, exactly like a queued predict
        during a predict-replica drain. New submits are rejected
        while draining. Returns True when every resident sequence
        retired within ``timeout`` (False = some still running; a
        following `stop` cuts them off). Idempotent."""
        deadline = time.monotonic() + timeout
        with self._cond:
            self._draining = True
            queued = list(self._q)
            self._q.clear()
            self._depth_gauge().set(0)
            self._cond.notify_all()
        for e in queued:
            _fail_entry(e, RuntimeError(
                "replica draining; resubmit to another replica"))
        alive = (self._thread is not None
                 and self._thread.is_alive())
        while time.monotonic() < deadline:
            with self._cond:
                if not self._active or not alive:
                    break
            time.sleep(0.005)
        with self._cond:
            drained = not self._active
            owned = {e.slot for e in self._active}
        # page-leak audit (disaggregated serving): a sequence whose
        # handoff was in flight when we started draining may hold a
        # claimed slot no entry owns — e.g. the decode-side splice
        # failed after its entry was failed back to the router.
        # Reclaim such orphans and count the pages; in a correct
        # handoff flow this counter stays at exactly 0 (the smoke
        # asserts it), because export reclaims prefill-side pages
        # the moment the blob exists and a rejected blob is refunded
        # before any allocation.
        before = self.engine.free_pages
        orphans = [s for s in range(self.engine.max_slots)
                   if s not in self.engine.free_slots
                   and s not in owned]
        for s in orphans:
            self.engine.release(s)
        obs.counter(
            "zoo_tpu_serving_gen_handoff_pages_leaked",
            help="pages the drain audit reclaimed from slots no "
                 "request owned (0 = exact pool refill)"
        ).inc(self.engine.free_pages - before)
        self._pages_gauge().set(self.engine.free_pages)
        return drained

    # -- admission ----------------------------------------------------------
    def submit(self, prompt_ids, max_new_tokens: int = 32,
               temperature: float = 0.0, eos_id=None) -> "Future":
        """Enqueue one generation request. The future resolves to a
        1-D int array of the NEWLY generated token ids (eos, when
        hit, included). Raises ValueError for prompts the cache can
        never hold and :class:`QueueFullError` at capacity."""
        ids = [int(t) for t in prompt_ids]
        max_new = min(int(max_new_tokens), self.max_new_cap)
        if max_new < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if not 1 <= len(ids) <= self.engine.max_context - 1:
            raise ValueError(
                f"prompt length {len(ids)} outside [1, "
                f"{self.engine.max_context - 1}] for this cache")
        entry = _GenEntry(ids, max_new, float(temperature), eos_id)
        self._enqueue(entry)
        return entry.future

    def _enqueue(self, entry: "_GenEntry"):
        with self._cond:
            if self._draining or self._stop:
                raise RuntimeError(
                    "generation batcher is draining/stopped")
            if len(self._q) >= self.queue_depth:
                retry = max(0.05, len(self._q) * self._ema_req_s)
                obs.counter("zoo_tpu_serving_errors_total",
                            help="serving errors by kind",
                            labels={"kind": "gen_queue_full"}).inc()
                raise QueueFullError(len(self._q), retry)
            self._q.append(entry)
            self._depth_gauge().set(len(self._q))
            self._cond.notify_all()

    def submit_prefill(self, prompt_ids, max_new_tokens: int = 32,
                       temperature: float = 0.0) -> "Future":
        """Prefill-pool admission (disaggregated serving): the prompt
        runs through the normal whole-prompt or chunked prefill path,
        but at the first sampled token the slot's cache state is
        exported and its pages reclaimed — the future resolves to a
        handoff blob (`ops/kv_cache.export`), not tokens. ``max_new``
        rides along in the reservation so admission applies the same
        worst-case page gate a monolithic engine would."""
        ids = [int(t) for t in prompt_ids]
        max_new = min(int(max_new_tokens), self.max_new_cap)
        if max_new < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if not 1 <= len(ids) <= self.engine.max_context - 1:
            raise ValueError(
                f"prompt length {len(ids)} outside [1, "
                f"{self.engine.max_context - 1}] for this cache")
        entry = _GenEntry(ids, max_new, float(temperature), None)
        entry.handoff = "out"
        self._enqueue(entry)
        return entry.future

    def submit_handoff(self, blob: dict, max_new_tokens: int = 32,
                       eos_id=None) -> "Future":
        """Decode-pool admission (disaggregated serving): claim a
        slot + pages for a prefilled sequence and splice its shipped
        KV pages in — no forward pass. The future resolves to the
        FULL new-token stream (the blob's first token included), so
        the router's caller sees exactly the monolithic result.
        Raises ValueError for a blob this engine can never hold
        (geometry/dtype mismatch — a client error, not a retry)."""
        max_new = min(int(max_new_tokens), self.max_new_cap)
        if max_new < 2:
            raise ValueError(
                "handoff admission needs max_new_tokens >= 2 "
                "(the first token was already sampled at prefill)")
        self.engine._check_handoff_blob(blob)
        entry = _GenEntry([], max_new,
                          float(blob.get("temperature", 0.0)),
                          eos_id)
        entry.handoff = "in"
        entry.blob = blob
        entry.prompt_len = int(blob["seq_len"])
        # the prefill side already emitted token 1 — seed it so the
        # done/budget arithmetic and the resolved stream match the
        # monolithic engine byte-for-byte
        entry.tokens = [int(blob["last_token"])]
        self._enqueue(entry)
        return entry.future

    # -- the decode loop ----------------------------------------------------
    def _finish(self, e: "_GenEntry"):
        self.engine.release(e.slot)
        # submit to the last token's hand-out: with the time to first
        # token, the gaps sum to it exactly
        dur = e.t_last - e.t_enq
        self._ema_req_s = 0.8 * self._ema_req_s + 0.2 * dur
        gaps = len(e.tokens) - 1
        tracing.record_span(
            e.trace, "decode/retire", e.t_enq_wall, dur, slot=e.slot,
            tokens=len(e.tokens), first_token_s=round(e.first_s, 6),
            gap_mean_s=round(e.gap_sum / gaps, 6) if gaps else 0.0,
            gap_max_s=round(e.gap_max, 6),
            gaps_behind_prompt=e.gaps_behind)
        e.future.set_result(np.asarray(e.tokens, np.int32))

    def _finish_handoff_out(self, e: "_GenEntry", now: float):
        """Prefill-side retirement: export the slot's cache state
        (which reclaims its pages immediately) and resolve the future
        with the blob. The entry never joins the decode set."""
        with obs.span("decode/page_export", slot=e.slot):
            blob = self.engine.export_handoff(e.slot)
        obs.counter(
            "zoo_tpu_serving_gen_handoffs_total",
            help="KV-page handoffs between prefill and decode pools",
            labels={"direction": "out"}).inc()
        dur = now - e.t_enq
        self._ema_req_s = 0.8 * self._ema_req_s + 0.2 * dur
        tracing.record_span(e.trace, "decode/handoff_export",
                            e.t_enq_wall, dur, slot=e.slot,
                            seq_len=blob["seq_len"])
        e.future.set_result(blob)

    def _admit_handoffs(self, entries, done):
        """Decode-side admission: splice each blob into the engine —
        no forward pass — and join the active set. A failed splice
        fails only its own entry (the router refunds the blob to a
        sibling); the engine validates before allocating, so a
        rejected blob leaves the pool intact."""
        engine = self.engine
        for e in entries:
            try:
                with obs.span("decode/page_import"):
                    slot = engine.admit_from_handoff(e.blob,
                                                     e.max_new)
            except Exception as exc:
                _fail_entry(e, exc)
                continue
            now = time.monotonic()
            e.slot = slot
            e.t_last = now  # its first gap here runs from the splice
            e.blob = None  # drop the host copy once spliced
            obs.histogram(
                "zoo_tpu_serving_gen_handoff_seconds",
                help="decode-pool handoff admission latency "
                     "(blob enqueue to pages spliced)"
            ).observe(now - e.t_enq)
            obs.counter(
                "zoo_tpu_serving_gen_handoffs_total",
                help="KV-page handoffs between prefill and decode "
                     "pools", labels={"direction": "in"}).inc()
            tracing.record_span(e.trace, "decode/handoff_admit",
                                e.t_enq_wall, now - e.t_enq,
                                slot=slot, seq_len=e.prompt_len)
            # the seeded first token may already satisfy the budget
            # (or be eos — the router normally short-circuits that
            # case before the hop, but stay defensive)
            if (e.eos_id is not None
                    and e.tokens[-1] == e.eos_id) \
                    or len(e.tokens) >= e.max_new:
                done.append(e)
            else:
                self._active.append(e)

    def _token_out(self, e: "_GenEntry", tok: int, now: float,
                   gaps) -> bool:
        """Hand one token out: a request's first leaves its
        ``decode/first_token`` record, whichever path admitted it;
        every later one closes a gap, kept on the entry and observed
        into ``gaps`` (:meth:`_gap_hist`, looked up by the caller).
        True when the request is done."""
        if not e.tokens:
            e.first_s = now - e.t_enq
            obs.histogram(
                "zoo_tpu_serving_gen_ttft_seconds",
                help="time from submit to first generated token"
            ).observe(e.first_s)
            tracing.record_span(
                e.trace, "decode/first_token", e.t_enq_wall,
                e.first_s, slot=e.slot, prompt_len=len(e.ids),
                path="handoff_out" if e.handoff == "out"
                else "chunked" if e.chunks else "prefill",
                chunks=e.chunks, queue_s=round(e.queue_s, 6))
        else:
            gap = now - e.t_last
            e.gap_sum += gap
            if gap > e.gap_max:
                e.gap_max = gap
            gaps.observe(gap)
        e.t_last = now
        e.tokens.append(tok)
        if e.eos_id is not None and tok == e.eos_id:
            return True
        return len(e.tokens) >= e.max_new

    def _admit_locked_pop(self) -> "list[_GenEntry]":
        """Pop the longest queue prefix that fits (FIFO — no request
        starves behind a smaller one that jumped it). Slots and pages
        consumed by entries popped earlier in the SAME batch are
        debited provisionally — `engine.can_admit` alone only knows
        the committed state."""
        take = []
        slots = len(self.engine.free_slots)
        pages = self.engine.free_pages
        while self._q and slots > 0:
            e = self._q[0]
            need = self.engine.pages_for(e.prompt_len, e.max_new)
            if need > pages:
                break
            take.append(self._q.popleft())
            slots -= 1
            pages -= need
        if take:
            self._depth_gauge().set(len(self._q))
        return take

    def _spec_eligible(self, e: "_GenEntry") -> bool:
        """Whether a resident slot may take a speculative round. A
        round consumes a full k-token verify window even when the
        request only needs one more token, so the window must fit
        inside the slot's page reservation AND the cache context:
        consumed rows after the round are ``plen + emitted - 1 + k``
        and the reservation covers ``min(plen + max_new,
        max_context)`` rows. Ineligible slots fall back to regular
        one-token steps in the same iteration."""
        k = self.engine.spec_k
        consumed_after = e.prompt_len + len(e.tokens) - 1 + k
        budget = min(e.prompt_len + e.max_new,
                     self.engine.max_context)
        return consumed_after <= budget

    def _run(self):
        engine = self.engine
        while True:
            with self._cond:
                while not self._q and not self._active \
                        and self._flight is None and not self._stop:
                    self._cond.wait(timeout=0.1)
                if self._stop:
                    return
                fresh = ([] if self._draining
                         else self._admit_locked_pop())
            # one pass of the loop body (never the idle wait above)
            # is one trace: the engine calls below are its children
            with tracing.trace("decode/iteration") as it:
                try:
                    self._iterate(fresh, it)
                except Exception as exc:
                    # a device/step failure must fail its requests,
                    # not the loop thread; slots are reclaimed so the
                    # batch keeps serving whoever comes next
                    failing = {id(e): e
                               for e in fresh + self._active}
                    for e in failing.values():
                        if e.slot >= 0:
                            engine.release(e.slot)
                        _fail_entry(e, exc)
                    self._active = []
                    self._flight = None
                    logger.warning("generation batcher error: %s",
                                   exc)
            self._slots_gauge().set(engine.slots_active)
            self._pages_gauge().set(engine.free_pages)

    def _prefill_span(self, entries, bucket: int, calls: int = 0,
                      rows: int = 0):
        """The ``decode/prefill`` span of one admission: round the
        wait for its first tokens, or round ``admit_partial`` for a
        chunked one; ``bucket``: the longest padded length its
        programs run at. ``calls`` programs held ``rows`` prompt rows
        between them (none yet for a chunked admission): ``rows / n``
        is 1 when only admitted prompts are computed."""
        return obs.span(
            "decode/prefill", n=len(entries), bucket=bucket,
            prompt_tokens=sum(len(e.ids) for e in entries),
            calls=calls, rows=rows)

    @staticmethod
    def _gap_behind(e: "_GenEntry", now: float) -> float:
        """The gap ``e``'s next token closes, counted on the entry as
        spent behind another request's prompt program; 0.0 for its
        first gap, which starts where its own prompt program ended."""
        if len(e.tokens) < 2:
            return 0.0
        e.gaps_behind += 1
        return now - e.t_last

    def _count_behind(self, seconds: float):
        obs.counter(
            "zoo_tpu_serving_gen_token_gap_behind_prompt_seconds_total",
            help="time between tokens that resident requests spent "
                 "behind other requests' prompt programs"
        ).inc(seconds)

    def _tokens_in(self, pairs, now: float, done,
                   behind: bool = False) -> int:
        """Hand fetched tokens, ``(entry, token)`` pairs, to their
        requests; one that finishes leaves the active set (to
        ``done``, or straight out as a handoff blob). A row that ran
        for a request which had already met its ``eos_id`` is
        discarded and counted. ``behind``: the step they come from
        was dispatched behind a prompt program, and the gaps they
        close are counted as such (one ``inc`` a step). Returns the
        tokens emitted."""
        emitted, behind_s = 0, 0.0
        gaps = self._gap_hist()
        for e, tok in pairs:
            e.ahead -= 1
            if e.future.done():
                obs.counter(
                    "zoo_tpu_decode_rows_discarded_total",
                    help="rows a decode step ran for a request that "
                         "had already met its eos_id").inc()
                continue
            emitted += 1
            if behind:
                behind_s += self._gap_behind(e, now)
            if e.handoff == "out":
                self._token_out(e, tok, now, gaps)
                self._active.remove(e)
                self._finish_handoff_out(e, now)
            elif self._token_out(e, tok, now, gaps):
                self._active.remove(e)
                done.append(e)
        if behind:
            self._count_behind(behind_s)
        return emitted

    def _collect(self, h, ps: "_Pass"):
        """``engine.collect`` with its wait added to the pass's."""
        toks = self.engine.collect(h)
        ps.wait_s += h.fetch_s
        return toks

    def _land(self, flight, done, ps: "_Pass"):
        """Fetch what this pass's admission and chunk left on the
        device — ``(span, handle, entries)`` each, the wait under
        its span (the programs' time on the device once what ran
        before them has ended) — and hand the first tokens to their
        requests; a request whose first token this is, and which was
        not admitted chunk by chunk, gets its ``decode/admit``."""
        for span, h, entries in flight:
            with span:
                toks = self._collect(h, ps)
            now = time.monotonic()
            if span.name == "decode/prefill":
                for e in entries:
                    tracing.record_span(
                        e.trace, "decode/admit", e.t_enq_wall,
                        now - e.t_enq, slot=e.slot,
                        prompt_len=len(e.ids))
            self._tokens_in(zip(entries, toks.tolist()), now, done)

    def _retire(self, done) -> int:
        """Finish the requests in ``done`` and empty it; how many."""
        n = len(done)
        while done:
            self._finish(done.pop(0))
        return n

    def _iterate(self, fresh: "list[_GenEntry]", it):
        """One pass of the loop (:meth:`_advance`); what finished in
        it is retired even when the pass ends in an error."""
        done: "list[_GenEntry]" = []
        ps = _Pass()
        emitted = retired = 0
        try:
            emitted, retired = self._advance(fresh, done, ps)
        finally:
            retired += self._retire(done)
        # the pass split: the span's length less `wait_s` is the
        # host's own time, overlapped by the device or not
        it.annotate(admitted=len(fresh), active=len(self._active),
                    emitted=emitted, retired=retired,
                    dispatch_s=round(ps.dispatch_s, 6),
                    wait_s=round(ps.wait_s, 6), programs=ps.programs)

    def _advance(self, fresh: "list[_GenEntry]", done, ps: "_Pass"
                 ) -> "tuple[int, int]":
        """Admit ``fresh`` and advance a chunked prefill
        (programs dispatched, their first tokens left on the
        device), dispatch the resident slots' step from counts
        alone, THEN fetch what was in flight before it — the step of
        the pass before, whose finished requests are retired at once,
        and this pass's first tokens — all while the device runs the
        step. With nothing in flight (the first pass, after a drain,
        under speculation) that is the synchronous order. ``ps``
        sums what the pass dispatched and waited for. Returns the
        tokens the steps emitted and the requests retired before the
        first tokens were waited for (the rest are left in
        ``done``)."""
        engine = self.engine
        chunked = engine.prefill_chunk > 0
        spec_k = engine.spec_k
        now = time.monotonic()
        for e in fresh:
            e.queue_s = now - e.t_enq
            tracing.record_span(e.trace, "decode/queue_wait",
                                e.t_enq_wall, e.queue_s)
        # the step the pass before left running, if any
        older, self._flight = self._flight, None
        # what this pass's admission and chunk leave on the device
        flight = []
        hand_in = [e for e in fresh if e.handoff == "in"]
        if hand_in:
            fresh = [e for e in fresh if e.handoff != "in"]
            self._admit_handoffs(hand_in, done)
        if fresh:
            # chunked admission only pays off past one
            # chunk: a prompt that fits in a single chunk
            # would run the full-width chunk program padded,
            # where the whole-prompt prefill runs one row at
            # the prompt's own bucket — so short prompts keep
            # the direct path even when chunking is on
            long_p = [e for e in fresh if chunked
                      and len(e.ids) > engine.prefill_chunk]
            short_p = [e for e in fresh if e not in long_p]
            if long_p:
                # claim slots + pages only; the prompt is
                # written chunk-by-chunk below, interleaved
                # with decode steps of resident slots
                reqs = [(e.ids, e.max_new, e.temperature)
                        for e in long_p]
                with self._prefill_span(long_p,
                                        engine.prefill_chunk):
                    slots = engine.admit_partial(reqs)
                now = time.monotonic()
                for e, slot in zip(long_p, slots):
                    e.slot = slot
                    e.prefilling = True
                    tracing.record_span(
                        e.trace, "decode/admit",
                        e.t_enq_wall, now - e.t_enq,
                        slot=slot, prompt_len=len(e.ids))
                    self._active.append(e)
            if short_p:
                reqs = [(e.ids, e.max_new, e.temperature)
                        for e in short_p]
                h = engine.admit_dispatch(reqs)
                calls, rows = engine.prefill_counts
                ps.sent(h.dispatch_s, calls)
                for e, slot in zip(short_p, h.slots):
                    e.slot = slot
                    e.ahead = 1     # its first token
                    self._active.append(e)
                flight.append((self._prefill_span(
                    short_p, engine.prompt_bucket(
                        max(len(e.ids) for e in short_p)),
                    calls=calls, rows=rows), h, short_p))
        if chunked and engine.prefilling_slots:
            # advance the mid-prefill slot whose turn it is by one
            # chunk; its prompt's last leaves the first token on the
            # device and the slot decodes from this pass's step on
            n_mid = len(engine.prefilling_slots)
            h = engine.prefill_dispatch()
            ps.sent(h.dispatch_s)
            obs.counter(
                "zoo_tpu_serving_gen_prefill_chunks_total",
                help="prompt chunks written by chunked "
                     "prefill").inc()
            # whose chunk, the tokens it wrote, and the cached
            # context it wrote them behind
            slot, context, tokens = engine.chunk_work
            mid = [e for e in self._active if e.slot == slot]
            for e in mid:
                e.chunks += 1
            last_of = mid if h.slots else []
            for e in last_of:
                e.prefilling = False
                e.ahead = 1
            flight.append((obs.span(
                "decode/prefill_chunk", n=n_mid, tokens=tokens,
                context=context), h, last_of))
        emitted = 0
        # the step below runs behind this pass's prompt programs, and
        # so do the tokens it brings
        behind = bool(flight)
        if spec_k > 0:
            # a round's emission count is data the host reads before
            # it can build the next mask: nothing runs ahead
            self._land(flight, done, ps)
            flight = []
        spec: "list[_GenEntry]" = []
        regular: "list[_GenEntry]" = []
        for e in self._active:
            if e.prefilling or e.handoff == "out":
                continue
            if spec_k > 0 and self._spec_eligible(e):
                spec.append(e)
            elif len(e.tokens) + e.ahead < e.max_new:
                # from counts alone: a slot whose last token is in
                # flight is not in the step
                regular.append(e)
        if spec:
            active = np.zeros((engine.max_slots,),
                              np.bool_)
            for e in spec:
                active[e.slot] = True
            prev_acc = engine.spec_accepted
            with obs.span("decode/spec_step",
                          n=len(spec)):
                out, n_emit = engine.spec_step(active)
            ps.sent(engine.spec_times[0], 2)    # draft and verify
            ps.wait_s += engine.spec_times[1]
            now = time.monotonic()
            obs.counter(
                "zoo_tpu_serving_gen_spec_proposed_total",
                help="draft tokens proposed for "
                     "verification").inc(
                spec_k * len(spec))
            obs.counter(
                "zoo_tpu_serving_gen_spec_accepted_total",
                help="draft tokens accepted by the "
                     "target model").inc(
                engine.spec_accepted - prev_acc)
            # a round's tokens are handed out at one instant: the
            # first closes the gap since the round before, the rest
            # gaps of 0
            gaps, behind_s = self._gap_hist(), 0.0
            for e in spec:
                fin = False
                if behind:
                    behind_s += self._gap_behind(e, now)
                for j in range(int(n_emit[e.slot])):
                    emitted += 1
                    if self._token_out(
                            e, int(out[e.slot, j]), now, gaps):
                        fin = True
                        break
                if fin:
                    done.append(e)
                    self._active.remove(e)
            if behind:
                self._count_behind(behind_s)
        # the step whose tokens this pass waits for
        waited = toks = None
        if regular:
            active = np.zeros((engine.max_slots,),
                              np.bool_)
            for e in regular:
                active[e.slot] = True
            # what of the page table the step's slots hold, from the
            # lengths kept here (a slot has cached its prompt and all
            # but the last of its tokens, those in flight among
            # them): the share a step that reads live pages only has
            # to read
            page = engine.page_size
            pages_live = sum(
                -(-(e.prompt_len + len(e.tokens) + e.ahead - 1)
                  // page) for e in regular)
            pages_table = engine.max_slots * engine.pages_per_slot
            with obs.span("decode/step", n=len(regular),
                          pages_live=pages_live,
                          pages_table=pages_table,
                          ahead=int(older is not None)) as sp:
                h = engine.dispatch(active)
                ps.sent(h.dispatch_s)
                for e in regular:
                    e.ahead += 1
                # wait for the step before while this one runs behind
                # it; under speculation, for this step itself
                mine = (h, regular, behind)
                waited, self._flight = (mine, None) \
                    if spec_k > 0 else (older, mine)
                if waited is not None:
                    toks = self._collect(waited[0], ps)
                # the span's two waits: the compiled call returning,
                # then the tokens
                sp.annotate(
                    dispatch_s=round(h.dispatch_s, 6),
                    fetch_s=round(waited[0].fetch_s, 6)
                    if waited is not None else 0.0)
            if older is not None:
                obs.counter(
                    "zoo_tpu_decode_steps_ahead_total",
                    help="decode steps dispatched while an earlier "
                         "step's tokens were unfetched").inc()
            obs.counter(
                "zoo_tpu_decode_pages_live_total",
                help="pages the active slots of decode steps held "
                     "(up to each slot's cached length)"
            ).inc(pages_live)
            obs.counter(
                "zoo_tpu_decode_pages_table_total",
                help="pages the page table of decode steps spans "
                     "(slots x pages a slot)").inc(pages_table)
        elif older is not None:
            # nothing to run behind it: the last step's tokens
            waited, toks = older, self._collect(older[0], ps)
        if waited is not None:
            emitted += self._tokens_in(
                [(e, int(toks[e.slot])) for e in waited[1]],
                time.monotonic(), done, behind=waited[2])
        # answers leave before the wait for this pass's prompt
        # programs (a chunk runs for 0.1-0.8 s): the client's next
        # request is in the queue when the pass ends
        retired = self._retire(done)
        self._land(flight, done, ps)
        if spec or regular:
            obs.counter(
                "zoo_tpu_serving_gen_steps_total",
                help="decode iterations executed").inc()
        obs.counter(
            "zoo_tpu_serving_gen_tokens_total",
            help="tokens generated").inc(emitted)
        return emitted, retired

    # -- introspection ------------------------------------------------------
    def stats(self) -> dict:
        """JSON-able summary for ``GET /health``."""
        with self._cond:
            depth = len(self._q)
            active = len(self._active)
        s = {"enabled": True, "queue_depth": depth,
             "queue_capacity": self.queue_depth,
             "requests_active": active,
             "max_new_cap": self.max_new_cap}
        s.update(self.engine.stats())
        return s

    def __repr__(self):
        return (f"ContinuousBatcher(slots={self.engine.max_slots}, "
                f"context={self.engine.max_context}, "
                f"queue_depth={self.queue_depth})")
