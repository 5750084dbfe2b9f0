"""Data iterators.

API parity with the reference ``python/mxnet/io.py:42-932`` (DataDesc /
DataBatch / DataIter protocol, ResizeIter, PrefetchingIter, NDArrayIter)
plus the native-iterator equivalents CSVIter (src/io/iter_csv.cc:150) and
MNISTIter (src/io/iter_mnist.cc:259). Independent design: prefetching is
organised around per-source ``_Slot`` producer threads, and NDArrayIter's
cursor arithmetic lives in two small helpers.

TPU note: iterators build host batches; arrays land on device at ``forward``
time, one upload per batch.
"""
from __future__ import annotations

import os
import struct
import threading

import numpy as np

from .base import MXNetError
from . import ndarray as nd
from . import random as _random
from . import telemetry as _tel
from .ndarray import NDArray


_io_suppress = threading.local()


def _timed_batch(produce):
    """Time one batch fetch through *produce*.

    Feeds the data-starvation telemetry: ``io_batch_wait_us`` is the time
    the CONSUMER just spent waiting for this batch — when it rivals the
    step time, the input pipeline (not the device) is the bottleneck.
    Exactly ONE timing per logical batch: nested fetches (ResizeIter /
    wrapper iterators delegating to an inner iterator on the same
    thread) are suppressed by a reentrancy flag, and prefetch PRODUCER
    threads are suppressed permanently — counting either would
    double-book batches or overwrite the gauge with the producer's full
    fetch time, inverting the starvation signal for a healthy prefetched
    pipeline.  Off path is two cached-bool checks.
    """
    if getattr(_io_suppress, "active", False) or not _tel.trace_active():
        return produce()
    t0 = _tel.now_us()
    _io_suppress.active = True
    try:
        with _tel.span("data_batch", cat="io"):
            batch = produce()
    finally:
        _io_suppress.active = False
    if _tel.enabled():
        _tel.bump("io_batches")
        _tel.set_gauge("io_batch_wait_us", _tel.now_us() - t0)
    return batch

__all__ = ["DataDesc", "DataBatch", "DataIter", "ResizeIter",
           "PrefetchingIter", "NDArrayIter", "CSVIter", "MNISTIter",
           "LibSVMIter"]


class DataDesc:
    """name/shape/dtype/layout tuple-alike describing one input
    (ref io.py:42)."""

    def __init__(self, name, shape, dtype=np.float32, layout="NCHW"):
        self.name, self.shape = name, tuple(shape)
        self.dtype, self.layout = dtype, layout

    def __repr__(self):
        return "DataDesc[%s,%s,%s,%s]" % (self.name, self.shape,
                                          self.dtype, self.layout)

    # tuple compatibility: behaves as (name, shape) for legacy callers
    def __iter__(self):
        return iter((self.name, self.shape))

    def __getitem__(self, i):
        return (self.name, self.shape)[i]

    def __len__(self):
        return 2

    def __eq__(self, other):
        if isinstance(other, (tuple, list)):
            return (self.name, self.shape) == tuple(other)
        return (isinstance(other, DataDesc) and self.name == other.name
                and self.shape == other.shape)

    @staticmethod
    def get_batch_axis(layout):
        return 0 if layout is None else layout.find("N")

    @staticmethod
    def get_list(shapes, types=None):
        dtype_of = dict(types) if types is not None else {}
        return [DataDesc(name, shape, dtype_of.get(name, np.float32))
                for name, shape in shapes]


class DataBatch:
    """One minibatch of data+label arrays (ref io.py:115)."""

    def __init__(self, data, label=None, pad=None, index=None,
                 bucket_key=None, provide_data=None, provide_label=None):
        def listify(x):
            return x if x is None or isinstance(x, (list, tuple)) else [x]
        self.data, self.label = listify(data), listify(label)
        self.pad, self.index = pad, index
        self.bucket_key = bucket_key
        self.provide_data, self.provide_label = provide_data, provide_label


class DataIter:
    """Iterator protocol base (ref io.py:176): subclasses implement
    iter_next/getdata/getlabel/getpad; next() assembles the DataBatch."""

    def __init__(self, batch_size=0):
        self.batch_size = batch_size

    def __iter__(self):
        return self

    def __next__(self):
        return self.next()

    def reset(self):
        pass

    # -- checkpoint-state protocol (mxnet_tpu.checkpoint) ------------------
    # A resumable iterator returns a picklable cursor dict; the manager
    # stores it in the checkpoint and feeds it back on restore so the
    # post-resume batch sequence is bitwise-identical.  The base class
    # opts out (None = "not resumable": save records nothing, restore
    # skips) so wrapper/native iterators degrade gracefully.

    def get_checkpoint_state(self):
        return None

    def set_checkpoint_state(self, state):
        pass

    def skip_batches(self, n):
        """Advance the stream *n* batches (wrapping epochs like a
        training loop would) WITHOUT returning them — the guardian's
        quarantine primitive: after a rollback rewinds the cursor, the
        batch window that poisoned the run is skipped instead of
        replayed.  Returns the number of batches actually skipped (an
        exhausted, non-resetting stream stops early)."""
        skipped = 0
        for _ in range(int(n)):
            try:
                self.next()
            except StopIteration:
                self.reset()
                try:
                    self.next()
                except StopIteration:
                    break
            skipped += 1
        return skipped

    def next(self):
        return _timed_batch(self._produce_next)

    def _produce_next(self):
        if not self.iter_next():
            raise StopIteration
        return DataBatch(data=self.getdata(), label=self.getlabel(),
                         pad=self.getpad(), index=self.getindex())

    def iter_next(self):
        raise NotImplementedError()

    def getdata(self):
        raise NotImplementedError()

    def getlabel(self):
        raise NotImplementedError()

    def getindex(self):
        return None

    def getpad(self):
        raise NotImplementedError()


class _BatchView(DataIter):
    """Mixin for iterators that expose a held ``current_batch``."""

    current_batch = None

    def _held(self, field):
        return getattr(self.current_batch, field)

    def getdata(self):
        return self._held("data")

    def getlabel(self):
        return self._held("label")

    def getindex(self):
        return self._held("index")

    def getpad(self):
        return self._held("pad")


class ResizeIter(_BatchView):
    """Present an underlying iterator as exactly ``size`` batches,
    rewinding it on exhaustion (ref io.py:264)."""

    def __init__(self, data_iter, size, reset_internal=True):
        super().__init__(data_iter.batch_size)
        self.data_iter = data_iter
        self.size = size
        self.reset_internal = reset_internal
        self.cur = 0
        self.provide_data = data_iter.provide_data
        self.provide_label = data_iter.provide_label
        if hasattr(data_iter, "default_bucket_key"):
            self.default_bucket_key = data_iter.default_bucket_key

    def reset(self):
        self.cur = 0
        if self.reset_internal:
            self.data_iter.reset()

    def iter_next(self):
        if self.cur >= self.size:
            return False
        try:
            self.current_batch = self.data_iter.next()
        except StopIteration:
            self.data_iter.reset()
            self.current_batch = self.data_iter.next()
        self.cur += 1
        return True

    def get_checkpoint_state(self):
        inner = self.data_iter.get_checkpoint_state()
        if inner is None:
            return None
        return {"kind": "ResizeIter", "cur": int(self.cur), "inner": inner}

    def set_checkpoint_state(self, state):
        self.cur = int(state["cur"])
        self.data_iter.set_checkpoint_state(state["inner"])


class _Slot:
    """One producer thread double-buffering one source iterator.

    The thread fills ``batch`` whenever ``vacant`` is set, then flips
    ``ready``. StopIteration is represented by batch=None.
    """

    def __init__(self, source):
        self.source = source
        self.ready = threading.Event()
        self.vacant = threading.Event()
        self.vacant.set()
        self.batch = None
        self.live = True
        self.thread = threading.Thread(target=self._produce, daemon=True)
        self.thread.start()

    def _produce(self):
        _io_suppress.active = True       # producer fetches are never the
        while True:                      # consumer's wait
            self.vacant.wait()
            if not self.live:
                return
            try:
                self.batch = self.source.next()
            except StopIteration:
                self.batch = None
            self.vacant.clear()
            self.ready.set()

    def release(self):
        """Consume the held batch; producer refills in the background."""
        self.ready.clear()
        self.vacant.set()

    def reset(self):
        self.ready.wait()          # let any in-flight fill land
        self.source.reset()
        self.release()

    def shutdown(self):
        self.live = False
        self.vacant.set()


class PrefetchingIter(_BatchView):
    """Background-thread prefetcher over one or more iterators
    (ref io.py:343 / src/io/iter_prefetcher.h), merging their outputs
    into a single DataBatch per step."""

    def __init__(self, iters, rename_data=None, rename_label=None):
        super().__init__()
        sources = iters if isinstance(iters, list) else [iters]
        if not sources:
            raise ValueError("need at least one source iterator")
        self.iters = sources
        self.rename_data = rename_data
        self.rename_label = rename_label
        self.batch_size = self.provide_data[0][1][0]
        self._slots = [_Slot(src) for src in sources]

    def __del__(self):
        for slot in self._slots:
            slot.shutdown()

    def _described(self, per_iter_descs, renames):
        if renames is None:
            return sum(per_iter_descs, [])
        renamed = []
        for mapping, descs in zip(renames, per_iter_descs):
            for d in descs:
                if isinstance(d, DataDesc):
                    renamed.append(DataDesc(mapping[d.name], d.shape, d.dtype))
                else:
                    renamed.append(DataDesc(mapping[d[0]], d[1]))
        return renamed

    @property
    def provide_data(self):
        return self._described([it.provide_data for it in self.iters],
                               self.rename_data)

    @property
    def provide_label(self):
        return self._described([it.provide_label for it in self.iters],
                               self.rename_label)

    def reset(self):
        for slot in self._slots:
            slot.reset()

    def iter_next(self):
        for slot in self._slots:
            slot.ready.wait()
        parts = [slot.batch for slot in self._slots]
        if parts[0] is None:
            return False
        self.current_batch = DataBatch(
            sum((b.data for b in parts), []),
            sum((b.label for b in parts), []),
            parts[0].pad, parts[0].index,
            provide_data=self.provide_data,
            provide_label=self.provide_label)
        for slot in self._slots:
            slot.release()
        return True

    def next(self):
        return _timed_batch(self._produce_next)

    def _produce_next(self):
        if not self.iter_next():
            raise StopIteration
        return self.current_batch


def _init_data(data, allow_empty, default_name):
    """Normalise array / list / dict input into [(name, NDArray), ...]."""
    if data is None:
        data = []
    if isinstance(data, (np.ndarray, NDArray)):
        data = [data]
    if isinstance(data, list):
        if not data and not allow_empty:
            raise ValueError("empty data")
        if len(data) == 1:
            data = {default_name: data[0]}
        else:
            data = {"_%d_%s" % (i, default_name): d
                    for i, d in enumerate(data)}
    if not isinstance(data, dict):
        raise TypeError("Input must be NDArray, numpy.ndarray, a list of "
                        "them or dict with them as values")
    pairs = []
    for name, arr in data.items():
        if not isinstance(arr, NDArray):
            raw = np.asarray(arr)
            if raw.dtype == np.float64:
                raw = raw.astype(np.float32)
            arr = nd.array(raw, dtype=raw.dtype)
        pairs.append((name, arr))
    return pairs


class NDArrayIter(DataIter):
    """Batched iteration over in-memory arrays (ref io.py:516).

    ``last_batch_handle``: 'pad' wraps the tail batch around and reports
    pad; 'discard' drops it; 'roll_over' carries it into the next epoch.
    """

    def __init__(self, data, label=None, batch_size=1, shuffle=False,
                 last_batch_handle="pad", data_name="data",
                 label_name="softmax_label"):
        super().__init__(batch_size)
        self.data = _init_data(data, allow_empty=False,
                               default_name=data_name)
        self.label = _init_data(label, allow_empty=True,
                                default_name=label_name)
        self.shuffle = shuffle
        self.last_batch_handle = last_batch_handle

        total = self.data[0][1].shape[0]
        self.idx = np.arange(total)
        if shuffle:
            _random.host_rng().shuffle(self.idx)
        if last_batch_handle == "discard":
            self.idx = self.idx[:total - total % batch_size]
        self.num_data = self.idx.shape[0]
        if self.num_data < batch_size:
            raise ValueError("batch_size needs to be smaller than data size.")
        self.data_list = [arr for _, arr in self.data + self.label]
        self.num_source = len(self.data_list)
        self.cursor = -batch_size
        # host-side staging copies so slicing doesn't round-trip the device
        self._np_cache = {name: arr.asnumpy()
                         for name, arr in self.data + self.label}

    @property
    def provide_data(self):
        return [DataDesc(name, (self.batch_size,) + arr.shape[1:], arr.dtype)
                for name, arr in self.data]

    @property
    def provide_label(self):
        return [DataDesc(name, (self.batch_size,) + arr.shape[1:], arr.dtype)
                for name, arr in self.label]

    def hard_reset(self):
        # data iterators are single-consumer by contract: the prefetch
        # tier hands the whole iterator to ONE worker thread, it is
        # never advanced and reset concurrently
        self.cursor = -self.batch_size    # graftlint: disable=JG011

    def reset(self):
        if self.shuffle:
            _random.host_rng().shuffle(self.idx)
        if self.last_batch_handle == "roll_over" \
                and self.cursor > self.num_data:
            overhang = (self.cursor % self.num_data) % self.batch_size
            self.cursor = overhang - self.batch_size
        else:
            self.cursor = -self.batch_size

    def iter_next(self):
        self.cursor += self.batch_size
        return self.cursor < self.num_data

    def next(self):
        return _timed_batch(self._produce_next)

    def _produce_next(self):
        if not self.iter_next():
            raise StopIteration
        return DataBatch(data=self.getdata(), label=self.getlabel(),
                         pad=self.getpad(), index=None)

    def _window(self):
        """Index array for the current batch, wrapping the tail if short."""
        lo = self.cursor
        hi = lo + self.batch_size
        if hi <= self.num_data:
            return self.idx[lo:hi]
        wrap = hi - self.num_data
        return np.concatenate([self.idx[lo:], self.idx[:wrap]])

    def _slice(self, source):
        if self.cursor >= self.num_data:
            raise RuntimeError("DataIter needs reset.")
        sel = self._window()
        picked = []
        for name, _ in source:
            host = self._np_cache[name]
            picked.append(nd.array(host[sel], dtype=host.dtype))
        return picked

    def getdata(self):
        return self._slice(self.data)

    def getlabel(self):
        return self._slice(self.label)

    def getpad(self):
        overrun = self.cursor + self.batch_size - self.num_data
        if self.last_batch_handle == "pad" and overrun > 0:
            return overrun
        return 0

    def get_checkpoint_state(self):
        """Cursor + the epoch's shuffle permutation: restoring both (with
        the global host RNG snapshotted separately by the checkpoint
        manager) makes the remaining batch sequence of this epoch — and
        every reshuffle after it — bitwise-identical."""
        return {"kind": "NDArrayIter", "cursor": int(self.cursor),
                "idx": np.asarray(self.idx).copy()}

    def set_checkpoint_state(self, state):
        idx = np.asarray(state["idx"]).copy()
        if idx.shape[0] != self.idx.shape[0]:
            # dataset changed size between save and resume: raising here
            # routes into the checkpoint manager's non-fatal skip (the
            # stream restarts) instead of silently slicing garbage
            # batches from a stale permutation
            raise ValueError(
                "checkpoint cursor covers %d samples, iterator has %d"
                % (idx.shape[0], self.idx.shape[0]))
        self.idx = idx
        self.num_data = idx.shape[0]
        self.cursor = int(state["cursor"])


class _WrappedArrayIter(DataIter):
    """Shared shell for CSVIter/MNISTIter: parse files once, then delegate
    to an inner NDArrayIter."""

    def __init__(self, data, label, batch_size, **iter_kwargs):
        super().__init__(batch_size)
        self._inner = NDArrayIter(data, label, batch_size, **iter_kwargs)
        self.provide_data = self._inner.provide_data
        self.provide_label = self._inner.provide_label

    def reset(self):
        self._inner.reset()

    def next(self):
        return self._inner.next()

    def get_checkpoint_state(self):
        return self._inner.get_checkpoint_state()

    def set_checkpoint_state(self, state):
        self._inner.set_checkpoint_state(state)


class CSVIter(_WrappedArrayIter):
    """Comma-separated-file iterator (ref src/io/iter_csv.cc:150)."""

    def __init__(self, data_csv, data_shape, label_csv=None, label_shape=(1,),
                 batch_size=1, round_batch=True, **kwargs):
        table = np.loadtxt(data_csv, delimiter=",", dtype=np.float32)
        table = table.reshape((-1,) + tuple(data_shape))
        if label_csv is not None:
            label = np.loadtxt(label_csv, delimiter=",", dtype=np.float32)
            label = label.reshape((-1,) + tuple(label_shape))
            if label_shape == (1,):
                label = label.reshape(-1)
        else:
            label = np.zeros((table.shape[0],), dtype=np.float32)
        super().__init__(table, label, batch_size,
                         last_batch_handle="roll_over" if round_batch
                         else "pad")


def _read_idx_file(path):
    """Parse an MNIST idx file: magic 2051 = images, 2049 = labels."""
    with open(path, "rb") as fh:
        magic, count = struct.unpack(">ii", fh.read(8))
        if magic == 2051:
            rows, cols = struct.unpack(">ii", fh.read(8))
            return np.frombuffer(fh.read(), dtype=np.uint8) \
                .reshape(count, rows, cols)
        if magic == 2049:
            return np.frombuffer(fh.read(), dtype=np.uint8).reshape(count)
        raise MXNetError("bad idx magic %d in %s" % (magic, path))


class MNISTIter(_WrappedArrayIter):
    """MNIST idx-format iterator (ref src/io/iter_mnist.cc:259).

    Requires the standard idx files on disk; tests fall back to
    test_utils.get_mnist_iterator's synthetic digits when absent.
    """

    def __init__(self, image="train-images-idx3-ubyte",
                 label="train-labels-idx1-ubyte", batch_size=128, shuffle=True,
                 flat=False, silent=False, seed=0, input_shape=None, **kwargs):
        if not os.path.exists(image):
            raise MXNetError("MNIST file %s not found" % image)
        pixels = _read_idx_file(image).astype(np.float32) / 255.0
        digits = _read_idx_file(label).astype(np.float32)
        if flat:
            pixels = pixels.reshape(pixels.shape[0], -1)
        else:
            pixels = pixels.reshape(pixels.shape[0], 1, 28, 28)
        super().__init__(pixels, digits, batch_size, shuffle=shuffle)


def _parse_libsvm(path, expect_dim=None):
    """Parse a libsvm file → (dense feature matrix, labels).

    Format per line: ``label idx:val idx:val ...`` (ref
    src/io/iter_libsvm.cc:200). Indices are 0-based like the reference's
    LibSVMIter contract.
    """
    labels, rows = [], []
    max_idx = -1
    with open(path) as fh:
        for line in fh:
            cells = line.split()
            if not cells:
                continue
            labels.append(float(cells[0]))
            row = {}
            for tok in cells[1:]:
                idx, _, val = tok.partition(":")
                idx = int(idx)
                row[idx] = float(val)
                max_idx = max(max_idx, idx)
            rows.append(row)
    dim = expect_dim if expect_dim is not None else max_idx + 1
    data = np.zeros((len(rows), dim), np.float32)
    for i, row in enumerate(rows):
        for idx, val in row.items():
            if idx < dim:
                data[i, idx] = val
    return data, np.asarray(labels, np.float32)


class LibSVMIter(_WrappedArrayIter):
    """Sparse-format text iterator (ref src/io/iter_libsvm.cc:200).

    Batches come out as CSRNDArray data (the framework's sparse handle);
    an optional separate label file supplies multi-dim labels.
    """

    def __init__(self, data_libsvm, data_shape, label_libsvm=None,
                 label_shape=None, batch_size=1, round_batch=True, **kwargs):
        dim = int(np.prod(data_shape))
        data, labels = _parse_libsvm(data_libsvm, expect_dim=dim)
        if label_libsvm is not None:
            lab_dim = int(np.prod(label_shape)) if label_shape else None
            lab_data, _ = _parse_libsvm(label_libsvm, expect_dim=lab_dim)
            labels = lab_data.reshape(
                (-1,) + tuple(label_shape)) if label_shape else lab_data
        super().__init__(data, labels, batch_size,
                         last_batch_handle="roll_over" if round_batch
                         else "pad")

    def next(self):
        batch = self._inner.next()
        from .ndarray import sparse as _sp
        batch.data = [_sp.csr_matrix(d) for d in batch.data]
        return batch
