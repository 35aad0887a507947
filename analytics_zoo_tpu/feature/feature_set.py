"""FeatureSet (L2): the cached training-set abstraction.

Reference: `Z/feature/FeatureSet.scala` — `CachedDistributedFeatureSet`
caches samples per partition in an `ArrayLike` store with per-epoch
random-offset iteration and index-permutation reshuffle (`:216-296`), with
memory tiers DRAM / PMEM / DIRECT selectable per dataset
(`FeatureSet.scala:310-329`, `feature/pmem/FeatureSet.scala:171`).

TPU-native redesign: the "cluster" is the set of ingest hosts; each host
caches its shard of the dataset and hands fixed-shape batches to the
pjit'd step (the role Spark RDD partitions played). Memory tiers:

- DRAM   — materialized numpy arrays (the default, fastest)
- DIRECT — no cache; records re-read/re-transformed every epoch
- PMEM   — disk-backed `np.memmap` arena: the TPU-VM analog of the
  reference's Optane JNI allocator (persistent-memory tier for datasets
  larger than RAM), see §2.11.3

The native C arena allocator behind the PMEM tier lives in
`native/host_arena` (ctypes-loaded); numpy memmap is the fallback.
"""

from __future__ import annotations

import enum
import os
import tempfile
import threading
from typing import Any, Iterable, Iterator, Optional, Tuple

import numpy as np

from analytics_zoo_tpu.feature.common import Preprocessing, Sample


class MemoryType(enum.Enum):
    DRAM = "dram"
    PMEM = "pmem"
    DIRECT = "direct"

    @staticmethod
    def of(v: "str | MemoryType") -> "MemoryType":
        if isinstance(v, MemoryType):
            return v
        return MemoryType(v.lower())


def _stack_column(column: "list[np.ndarray]") -> np.ndarray:
    return np.stack([np.asarray(a) for a in column], axis=0)


class _MemmapStore:
    """PMEM-tier store: columns spilled to a disk-backed memmap arena."""

    def __init__(self, columns: "list[np.ndarray]", path: Optional[str]):
        self.dir = path or tempfile.mkdtemp(prefix="zoo_pmem_")
        os.makedirs(self.dir, exist_ok=True)
        self.columns = []
        for i, col in enumerate(columns):
            fname = os.path.join(self.dir, f"col{i}.mm")
            mm = np.memmap(fname, dtype=col.dtype, mode="w+",
                           shape=col.shape)
            mm[:] = col
            mm.flush()
            self.columns.append(mm)


def normalize_labels(y):
    """The ONE place deciding how user-supplied labels are read:
    returns ``(y_cols, multi)`` where ``y_cols`` is a list of numpy
    label columns (empty = unlabeled) and ``multi`` says whether they
    are separate output columns.

    Multi-output means a list/tuple of ARRAY-LIKES (objects with
    ``ndim >= 1`` — numpy/jax arrays): ``[ya, yb]`` stays two
    columns. A plain Python list of per-sample scalars or rows
    (``[0, 1, 0, 1]`` or ``[[0], [1]]``) is ONE label array, as it
    always was."""
    if y is None:
        return [], False
    if isinstance(y, (list, tuple)):
        if len(y) == 0:
            raise ValueError(
                "empty label list — pass None for unlabeled data")
        if all(getattr(c, "ndim", 0) >= 1 for c in y):
            return [np.asarray(c) for c in y], True
    return [np.asarray(y)], False


# a slice of a batch under this many bytes does not repay the hand-off
# to a thread of its own: token and id batches (KBs to a few MB) stay
# on the calling thread, image batches (tens of MB and up) split
_MIN_SLICE_BYTES = 8 << 20


def ingest_width(nbytes: int, rows: int, ceiling: int) -> int:
    """How many threads copy one column of one batch: as many whole
    `_MIN_SLICE_BYTES` slices as it holds, at most ``ceiling``
    (`ZooTpuConf.ingest_threads`), the host's cores and its rows."""
    return max(1, min(int(ceiling), os.cpu_count() or 1, rows,
                      nbytes // _MIN_SLICE_BYTES))


def batch_selections(n: int, batch_size: int, shuffle: bool, seed: int,
                     drop_last: bool, sort: bool = False
                     ) -> "Iterator[np.ndarray]":
    """Row indices of each batch of one epoch: the per-epoch index
    permutation (the reference's reshuffle via a shuffled index array,
    `FeatureSet.scala:216-296`). ``sort`` orders each batch's rows
    (the PMEM tier reads its memmap front to back)."""
    idx = np.arange(n)
    if shuffle:
        np.random.RandomState(seed).shuffle(idx)
    end = (n - n % batch_size) if drop_last else n
    for start in range(0, end, batch_size):
        sel = idx[start:start + batch_size]
        yield np.sort(sel) if sort else sel


def _take(a, sel, out):
    # mode="clip": the default "raise" copies through a temporary;
    # ``sel`` comes from `batch_selections` and is always in range
    np.take(a, sel, axis=0, out=out, mode="clip")


def _take_split(a, sel, out, width: int):
    """``out[i] = a[sel[i]]`` with the rows split over ``width``
    threads (this one among them), each writing its own contiguous
    slice of ``out`` (numpy releases the GIL in the copy). The threads
    live for this call only; what one raised re-raises here."""
    n = len(sel)
    cuts = [n * i // width for i in range(width + 1)]
    errors: "list[BaseException]" = []

    def work(lo, hi):
        try:
            _take(a, sel[lo:hi], out[lo:hi])
        except BaseException as e:  # noqa: BLE001 — re-raised below
            errors.append(e)

    workers = [threading.Thread(target=work, args=(lo, hi), daemon=True,
                                name=f"zoo-tpu-ingest-{i}")
               for i, (lo, hi) in enumerate(zip(cuts[1:-1], cuts[2:]), 1)]
    for t in workers:
        t.start()
    work(cuts[0], cuts[1])
    for t in workers:
        t.join()
    if errors:
        raise errors[0]


def gather_rows(columns, sel, out=None, threads: int = 1
                ) -> "list[np.ndarray]":
    """Rows ``sel`` of every column, bit for bit ``a[sel]``.

    ``out=None``: fresh arrays the caller may keep. ``out``: one
    buffer per column, of the batch's shape and dtype, that the rows
    are copied INTO and that is returned — the caller owns its
    lifetime (`Estimator.train` recycles page-warm buffers this way).
    ``threads`` is a ceiling: each column is split over
    `ingest_width` threads, one for a small batch."""
    got = []
    for a, buf in zip(columns, out or [None] * len(columns)):
        width = ingest_width(a.nbytes // max(len(a), 1) * len(sel),
                             len(sel), threads)
        if buf is None and width == 1:
            got.append(np.asarray(a[sel]))
            continue
        if buf is None:
            buf = np.empty((len(sel),) + a.shape[1:], a.dtype)
        _take_split(a, sel, buf, width)
        got.append(buf)
    return got


def gather_batch(x_cols, y_cols, multi_y: bool, sel, out=None,
                 threads: int = 1):
    """One ``(xb, yb)`` batch as `iter_batches` yields it: a single
    input or label column bare, several as a list, no labels None.
    ``out`` is the flat list of buffers, inputs then labels."""
    cols = gather_rows(list(x_cols) + list(y_cols), sel, out, threads)
    xb, yb = cols[:len(x_cols)], cols[len(x_cols):]
    return (xb[0] if len(xb) == 1 else xb,
            None if not yb else yb if multi_y else yb[0])


class FeatureSet:
    """Cached, shardable dataset implementing the Estimator data protocol
    (`num_samples`, `iter_batches`).

    Build with :meth:`array`, :meth:`sample_rdd` (any iterable of
    `Sample`s — the RDD role), or :meth:`from_iterable` + a
    `Preprocessing` chain via :meth:`transform`.
    """

    def __init__(self, x_columns: "list[np.ndarray]",
                 y_column=None,
                 memory_type: "str | MemoryType" = MemoryType.DRAM,
                 shard_index: int = 0, num_shards: int = 1,
                 pmem_path: Optional[str] = None):
        self.memory_type = MemoryType.of(memory_type)
        n = x_columns[0].shape[0]
        for c in x_columns:
            if c.shape[0] != n:
                raise ValueError("inconsistent column lengths")
        # ``y_column``: one label array, or a list/tuple of them
        # (multi-output training — the reference's nested TensorMeta
        # label contract); normalize_labels is the single decision
        # point for which is which
        y_cols, self._multi_y = normalize_labels(y_column)
        for c in y_cols:
            if c.ndim == 0 or c.shape[0] != n:
                raise ValueError(
                    f"label column shape {c.shape} does not match "
                    f"{n} samples")
        # multi-host sharding: this host keeps rows [lo, hi)
        if not (0 <= shard_index < num_shards):
            raise ValueError("bad shard spec")
        lo = shard_index * n // num_shards
        hi = (shard_index + 1) * n // num_shards
        x_columns = [c[lo:hi] for c in x_columns]
        y_cols = [c[lo:hi] for c in y_cols]

        if self.memory_type == MemoryType.PMEM:
            store = _MemmapStore(x_columns + y_cols, pmem_path)
            stored = store.columns
            self._x = stored[:len(x_columns)]
            y_cols = stored[len(x_columns):]
            self._store = store
        else:
            self._x = x_columns
        self._y_cols = y_cols
        self._n = self._x[0].shape[0]
        from analytics_zoo_tpu.feature.common import _count_ingest
        _count_ingest("feature_set", self._n,
                      sum(int(c.nbytes)
                          for c in list(self._x) + list(y_cols)))

    @property
    def _y(self):
        """Back-compat single-label view (None / array / list)."""
        if not self._y_cols:
            return None
        return list(self._y_cols) if self._multi_y else self._y_cols[0]

    # -- constructors (reference FeatureSet.rdd/array factories) -----------
    @staticmethod
    def array(x, y=None, memory_type="dram", **kw) -> "FeatureSet":
        xs = x if isinstance(x, (list, tuple)) else [x]
        xs = [np.asarray(a) for a in xs]
        return FeatureSet(xs, y, memory_type=memory_type, **kw)

    @staticmethod
    def sample_rdd(samples: Iterable[Sample], memory_type="dram",
                   **kw) -> "FeatureSet":
        """Materialize an iterable of `Sample`s (the reference's
        RDD[Sample] ingest path, cached like
        `CachedDistributedFeatureSet`)."""
        feats: "list[list[np.ndarray]]" = []
        labels: "list[list[np.ndarray]]" = []
        has_label = None
        multi_label = False
        for s in samples:
            arrays = s.feature_arrays()
            if not feats:
                feats = [[] for _ in arrays]
            for col, a in zip(feats, arrays):
                col.append(a)
            if has_label is None:
                has_label = s.label is not None
                multi_label = isinstance(s.label, (list, tuple))
                if has_label:
                    labels = [[] for _ in
                              (s.label if multi_label else [s.label])]
            if has_label:
                lab = s.label if multi_label else [s.label]
                for col, a in zip(labels, lab):
                    col.append(np.asarray(a))
        if not feats:
            raise ValueError("empty sample stream")
        x_cols = [_stack_column(c) for c in feats]
        if not has_label:
            y_col = None
        elif multi_label:
            # keep multi-output label columns separate (a bare
            # np.asarray over the pairs would silently stack
            # same-shaped outputs into one bogus column)
            y_col = [_stack_column(c) for c in labels]
        else:
            y_col = _stack_column(labels[0])
        return FeatureSet(x_cols, y_col, memory_type=memory_type, **kw)

    @staticmethod
    def from_rdd(rdd: Any,
                 preprocessing: Optional[Preprocessing] = None,
                 memory_type="dram",
                 shard_index: Optional[int] = None,
                 num_shards: Optional[int] = None, **kw) -> "FeatureSet":
        """Ingest from anything implementing the RDD protocol — a real
        ``pyspark.RDD`` or :class:`~analytics_zoo_tpu.feature.rdd.LocalRdd`
        (reference: ``FeatureSet.rdd``, `Z/feature/FeatureSet.scala:308`).

        Each JAX process collects only its round-robin share of the
        partitions (defaults wired to ``jax.process_index()`` /
        ``jax.process_count()``), so multi-host ingest needs no flags.
        Records may be `Sample`s or raw values run through
        ``preprocessing``.
        """
        from analytics_zoo_tpu.feature.rdd import collect_shard, \
            is_spark_dataframe
        if is_spark_dataframe(rdd):
            rdd = rdd.rdd
        records = collect_shard(rdd, shard_index, num_shards)
        if records and not isinstance(records[0], Sample) \
                and preprocessing is None:
            # raw (feature, label) tuples or bare feature arrays
            records = [Sample(feature=r[0], label=r[1])
                       if isinstance(r, tuple) and len(r) == 2
                       else Sample(feature=r) for r in records]
        # the shard filter already ran; the row-range splitter must not
        # re-shard what is now purely local data
        return FeatureSet.from_iterable(
            records, preprocessing, memory_type=memory_type,
            shard_index=0, num_shards=1, **kw)

    @staticmethod
    def from_iterable(records: Iterable[Any],
                      preprocessing: Optional[Preprocessing] = None,
                      memory_type="dram", **kw) -> "FeatureSet":
        stream: Iterable[Any] = records
        if preprocessing is not None:
            stream = preprocessing.transform(stream)
        return FeatureSet.sample_rdd(stream, memory_type=memory_type, **kw)

    # -- transforms ---------------------------------------------------------
    def transform(self, preprocessing: Preprocessing) -> "FeatureSet":
        """Apply a Preprocessing chain, re-caching the result (reference
        `FeatureSet.transform` returning a transformed cached set)."""
        return FeatureSet.from_iterable(
            self._iter_samples(), preprocessing,
            memory_type=self.memory_type.value)

    def _iter_samples(self) -> Iterator[Sample]:
        for i in range(self._n):
            feats = [c[i] for c in self._x]
            if not self._y_cols:
                label = None
            elif self._multi_y:
                label = [c[i] for c in self._y_cols]
            else:
                label = self._y_cols[0][i]
            yield Sample(feature=feats if len(feats) > 1 else feats[0],
                         label=label)

    # -- Estimator data protocol -------------------------------------------
    @property
    def num_samples(self) -> int:
        return self._n

    def batch_selections(self, batch_size: int, shuffle: bool = True,
                         seed: int = 0, drop_last: bool = True
                         ) -> "Iterator[np.ndarray]":
        """Row indices of each batch of one epoch, in its order."""
        return batch_selections(
            self._n, batch_size, shuffle, seed, drop_last,
            sort=self.memory_type == MemoryType.PMEM)

    def gather(self, sel, out=None, threads: int = 1):
        """Rows ``sel`` as one ``(xb, yb)`` batch (`gather_batch`)."""
        return gather_batch(self._x, self._y_cols, self._multi_y, sel,
                            out, threads)

    def iter_batches(self, batch_size: int, shuffle: bool = True,
                     seed: int = 0, drop_last: bool = True
                     ) -> Iterator[Tuple[Any, Any]]:
        """Fresh arrays each batch: the caller may keep them."""
        for sel in self.batch_selections(batch_size, shuffle, seed,
                                         drop_last):
            yield self.gather(sel)

    def __len__(self):
        return self._n

    def __repr__(self):
        return (f"FeatureSet(n={self._n}, tier={self.memory_type.value}, "
                f"x_cols={len(self._x)}, "
                f"labeled={self._y is not None})")
