"""The train input path (`pipeline/estimator.py::_HostRing`,
`feature/feature_set.py::gather_rows`): batches gathered into recycled
host buffers on the ingest threads are bit for bit ``x[sel]`` in the
parent's order, nothing a caller keeps is ever overwritten, and no
buffer is rewritten before the runtime has read it."""

import os
import threading
import time

import numpy as np
import pytest

from analytics_zoo_tpu.common import tracing
from analytics_zoo_tpu.feature import feature_set
from analytics_zoo_tpu.feature.feature_set import FeatureSet
from analytics_zoo_tpu.pipeline import estimator as E
from analytics_zoo_tpu.pipeline.estimator import ArrayDataset

N = 37  # rows: no batch size below divides it


def _columns(kind):
    rs = np.random.RandomState(3)
    if kind == "float32":
        return rs.rand(N, 5, 3).astype(np.float32), rs.rand(N, 2)
    if kind == "int32-unlabelled":
        return rs.randint(0, 1 << 30, size=(N, 6)).astype(np.int32), None
    return ([rs.rand(N, 4).astype(np.float32),
             rs.randint(0, 99, size=(N,)).astype(np.int32)],
            [rs.rand(N, 1).astype(np.float32),
             rs.randint(0, 9, size=(N, 2))])


def _flat(tree):
    if tree is None:
        return []
    return list(tree) if isinstance(tree, (list, tuple)) else [tree]


def _parent_batches(x, y, batch_size, seed):
    """`iter_batches` as the parent wrote it: ``a[sel]`` of a
    ``RandomState(seed)`` shuffle, drop-last."""
    n = _flat(x)[0].shape[0]
    idx = np.arange(n)
    np.random.RandomState(seed).shuffle(idx)
    for start in range(0, n - n % batch_size, batch_size):
        sel = idx[start:start + batch_size]
        yield [a[sel] for a in _flat(x)], [a[sel] for a in _flat(y)]


class _Placed:
    """What a fake ``place`` returns: a copy of the host batch taken
    ``delay`` seconds AFTER ``place`` returned (the runtime reading the
    buffer on its own thread), ready when the copy is done."""

    def __init__(self, batch, delay):
        self.copy = None
        self._t = threading.Thread(target=self._read,
                                   args=(batch, delay), daemon=True)
        self._t.start()

    def _read(self, batch, delay):
        time.sleep(delay)
        self.copy = [[a.copy() for a in _flat(part)] for part in batch]

    def block_until_ready(self):
        self._t.join(timeout=10)
        assert not self._t.is_alive()
        return self


def _feed(ring, seed, place, depth):
    return E._prefetch_iter(E._traced_gather(ring.epoch(seed=seed)),
                            E._traced_place(place, ring), depth)


@pytest.fixture
def tiny_slices(monkeypatch):
    """Let toy batches split: a slice of 16 bytes repays a thread."""
    monkeypatch.setattr(feature_set, "_MIN_SLICE_BYTES", 16)
    monkeypatch.setattr(os, "cpu_count", lambda: 8)


@pytest.mark.parametrize("make", [ArrayDataset, FeatureSet.array],
                         ids=["ArrayDataset", "FeatureSet"])
@pytest.mark.parametrize("batch_size", [8, 7])
@pytest.mark.parametrize("threads", [1, 4])
@pytest.mark.parametrize("kind",
                         ["float32", "int32-unlabelled", "multi-column"])
def test_recycled_batches_equal_fancy_index(tiny_slices, kind, threads,
                                            batch_size, make):
    x, y = _columns(kind)
    ring = E._HostRing(make(x, y), batch_size, 3, threads)
    buffers = set()
    for epoch in (1, 2):
        want = list(_parent_batches(x, y, batch_size, epoch))
        got = 0
        for (xb, yb), fields in ring.epoch(seed=epoch):
            wx, wy = want[got]
            for a, w in zip(_flat(xb) + _flat(yb), wx + wy):
                assert a.dtype == w.dtype and a.shape == w.shape
                assert a.tobytes() == w.tobytes()
            assert isinstance(xb, list) == isinstance(x, list)
            assert (yb is None) == (y is None)
            assert fields["recycled"] is True
            assert fields["threads"] == threads
            buffers.add(id(_flat(xb)[0]))
            ring.placed(_Placed((xb, yb), 0.0))
            got += 1
        assert got == len(want) == N // batch_size
    assert len(buffers) == 3  # the ring, and nothing but the ring


@pytest.mark.parametrize("make", [ArrayDataset, FeatureSet.array],
                         ids=["ArrayDataset", "FeatureSet"])
def test_iter_batches_yields_arrays_the_caller_may_keep(make):
    x, y = _columns("multi-column")
    kept = list(make(x, y).iter_batches(8, shuffle=True, seed=5))
    want = list(_parent_batches(x, y, 8, 5))
    arrays = [a for xb, yb in kept for a in xb + yb]
    for i, a in enumerate(arrays):
        assert not any(np.shares_memory(a, b) for b in arrays[i + 1:])
        assert not any(np.shares_memory(a, c) for c in x + y)
    for (xb, yb), (wx, wy) in zip(kept, want):
        for a, w in zip(xb + yb, wx + wy):
            assert a.tobytes() == w.tobytes()


def test_threaded_gather_into_fresh_arrays(tiny_slices):
    """Where nothing is recycled (the CPU backend) a large batch still
    splits, into a destination of its own."""
    x, _ = _columns("float32")
    sel = np.arange(N)[::-1]
    a, b = (feature_set.gather_rows([x], sel, threads=4)[0]
            for _ in range(2))
    assert a.tobytes() == x[sel].tobytes() == b.tobytes()
    assert not np.shares_memory(a, b)


@pytest.mark.parametrize("depth", [2, 0])
def test_no_buffer_rewritten_before_it_was_read(tiny_slices, depth):
    x, y = _columns("float32")
    ring = E._HostRing(ArrayDataset(x, y), 4, depth + 1, 4)
    tracing.reset_tracing()
    read, want = [], []
    for epoch in (1, 2, 3):  # 27 batches through 3 (or 1) buffers
        want += list(_parent_batches(x, y, 4, epoch))
        batches = _feed(ring, epoch, lambda b: _Placed(b, 0.02), depth)
        read += [placed for _, placed in batches]
    assert len(read) == len(want) == 27
    for placed, (wx, wy) in zip(read, want):
        (gx,), (gy,) = placed.block_until_ready().copy
        assert gx.tobytes() == wx[0].tobytes()
        assert gy.tobytes() == wy[0].tobytes()
    waits = [r.fields["reuse_wait_s"]
             for r in tracing.get_store().records()
             if r.name == "train/input_place"]
    assert len(waits) == 27 and max(waits) > 0  # the ring did wait


def test_a_ring_that_does_not_wait_is_caught(tiny_slices, monkeypatch):
    """The test above has teeth: without the wait, the late reader
    sees rows of a later batch."""
    monkeypatch.setattr(E.jax, "block_until_ready", lambda x: x)
    x, y = _columns("float32")
    ring = E._HostRing(ArrayDataset(x, y), 4, 3, 4)
    read = [p for _, p in _feed(ring, 1, lambda b: _Placed(b, 0.05), 2)]
    want = list(_parent_batches(x, y, 4, 1))
    torn = sum(p.block_until_ready().copy[0][0].tobytes()
               != w[0][0].tobytes() for p, w in zip(read, want))
    assert torn > 0


def test_nothing_recycled_where_the_device_array_is_the_buffer():
    """The CPU backend aliases an aligned numpy array for the device
    array's whole life: every batch keeps a destination of its own."""
    import analytics_zoo_tpu as zoo
    from analytics_zoo_tpu.parallel.mesh import shard_batch
    ctx = zoo.init_nncontext(seed=0, log_level="WARNING")
    x, y = _columns("float32")
    ring = E._HostRing(ArrayDataset(x, y), 8, 3, 4)
    host, placed = [], []
    for (xb, yb), fields in ring.epoch(seed=1):
        host.append((xb.copy(), xb))
        placed.append(shard_batch(xb, ctx.mesh))
        assert ring.placed(placed[-1]) == 0.0
        assert fields["recycled"] is (len(host) == 1)
    for (want, buf), dev in zip(host, placed):
        assert np.asarray(dev).tobytes() == want.tobytes()
    assert len({id(buf) for _, buf in host}) == len(host)


def test_small_batch_stays_on_one_thread_and_images_split():
    cores = os.cpu_count() or 1
    width = feature_set.ingest_width
    assert width(4096 * 2 * 8, 4096, 4) == 1       # NCF ids
    assert width(32 * 512 * 4, 32, 4) == 1         # BERT token ids
    assert width(4 << 20, 1 << 20, 4) == 1         # a few MB
    assert width(128 * 602112, 128, 4) == min(4, cores)
    assert width(512 * 602112, 512, 4) == min(4, cores)
    assert width(512 * 602112, 512, 1) == 1        # a ceiling ...
    assert width(512 * 602112, 512, 64) == min(36, cores)  # ... only


def test_image_batch_splits_in_the_span():
    """128 rows of 224x224x3 float32 (77 MB) through the ring: the
    span says how many threads copied it, and that it was recycled."""
    x = np.zeros((128, 224, 224, 3), np.float32)
    x[:, 0, 0, 0] = np.arange(128)
    ring = E._HostRing(ArrayDataset(x), 128, 3, 4)
    tracing.reset_tracing()
    for epoch in (1, 2):
        for _, placed in _feed(ring, epoch,
                               lambda b: _Placed(b, 0.0), 2):
            order = np.arange(128)
            np.random.RandomState(epoch).shuffle(order)
            got = placed.block_until_ready().copy[0][0]
            assert np.array_equal(got[:, 0, 0, 0], order)
    gathers = [r.fields for r in tracing.get_store().records()
               if r.name == "train/input_gather"]
    assert [f["threads"] for f in gathers] == \
        [min(4, os.cpu_count() or 1)] * 2
    assert all(f["recycled"] and f["rows"] == 128 for f in gathers)
    assert not any(t.name.startswith("zoo-tpu-ingest")
                   for t in threading.enumerate())


def _live(prefix):
    return [t for t in threading.enumerate()
            if t.name.startswith(prefix) and t.is_alive()]


def test_gather_thread_exception_reaches_the_consumer(tiny_slices,
                                                      monkeypatch):
    take, calls = feature_set._take, []

    def failing(a, sel, out):
        calls.append(threading.current_thread().name)
        if len(calls) > 8 and \
                threading.current_thread().name == "zoo-tpu-ingest-2":
            raise OSError("page of the memmap gone")
        take(a, sel, out)

    monkeypatch.setattr(feature_set, "_take", failing)
    x, y = _columns("float32")
    ring = E._HostRing(ArrayDataset(x, y), 8, 3, 4)
    batches = _feed(ring, 1, lambda b: _Placed(b, 0.0), 2)
    try:
        assert next(batches) is not None
        with pytest.raises(OSError, match="memmap gone"):
            list(batches)
    finally:
        batches.close()
    assert {"zoo-tpu-prefetch", "zoo-tpu-ingest-2"} <= set(calls)
    deadline = time.time() + 5
    while time.time() < deadline and (
            _live("zoo-tpu-ingest") or _live("zoo-tpu-prefetch")):
        time.sleep(0.02)
    assert not _live("zoo-tpu-ingest") and not _live("zoo-tpu-prefetch")


class _ParentFeed:
    """A foreign dataset, its `iter_batches` the parent's line for
    line: the ring leaves its batches alone."""

    def __init__(self, x, y):
        self.x, self.y, self.num_samples = x, y, x.shape[0]

    def iter_batches(self, batch_size, shuffle=True, seed=0,
                     drop_last=True):
        for (xb,), (yb,) in _parent_batches(self.x, self.y, batch_size,
                                            seed):
            yield xb, yb


def _train(data, y=None):
    import jax
    from analytics_zoo_tpu.common import nncontext
    from analytics_zoo_tpu.pipeline.api.keras import layers as L
    from analytics_zoo_tpu.pipeline.api.keras.models import Sequential
    nncontext.reset_nncontext()  # the same init RNG for every fit
    m = Sequential()
    m.add(L.Dense(16, input_shape=(6,), activation="relu"))
    m.add(L.Dense(3, activation="softmax"))
    est = E.Estimator(m, optimizer="sgd",
                      loss="sparse_categorical_crossentropy")
    tracing.reset_tracing()
    losses = []
    for _ in range(3):  # one call an epoch: its loss is one step's
        res = est.train(data, y, batch_size=48, nb_epoch=1)
        losses.append(res.history[0]["loss"])
    res = est.train(data, y, batch_size=16, nb_epoch=2)
    losses += [h["loss"] for h in res.history]
    return losses, jax.device_get(est.params)


def test_train_losses_are_the_parents_step_for_step(tiny_slices):
    import jax
    rs = np.random.RandomState(11)
    x = rs.rand(48, 6).astype(np.float32)
    y = rs.randint(0, 3, size=(48, 1))
    want, want_params = _train(_ParentFeed(x, y))
    assert {r.fields["threads"] for r in tracing.get_store().records()
            if r.name == "train/input_gather"} == {1}
    got, got_params = _train(x, y)
    assert {r.fields["threads"] for r in tracing.get_store().records()
            if r.name == "train/input_gather"} == {4}
    assert got == want
    for a, b in zip(jax.tree_util.tree_leaves(got_params),
                    jax.tree_util.tree_leaves(want_params)):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()
