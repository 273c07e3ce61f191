"""Data iterators (reference: python/mxnet/io.py, 747 LoC + src/io/).

The python-side iterator contract is preserved exactly: ``DataIter`` yields
``DataBatch(data=[NDArray], label=[NDArray], pad, index)``; ``provide_data``/
``provide_label`` are lists of ``DataDesc``. The C++ decode/augment pipeline
(reference: src/io/iter_image_recordio_2.cc) is replaced by (a) in-memory
iterators here, (b) a RecordIO-backed ImageRecordIter in image.py, and (c)
``PrefetchingIter`` which gives the background-thread double-buffering the
reference's PrefetcherIter provides (iter_prefetcher.h:129).
"""
from __future__ import annotations

import functools
import struct
import gzip
import os
import threading
import queue as _queue
from collections import namedtuple

import numpy as np

from .base import MXNetError
from .ndarray import NDArray, array
from . import faults as _faults
from . import telemetry as _telemetry

__all__ = ["DataDesc", "DataBatch", "StackedDataBatch", "DataIter",
           "NDArrayIter", "ResizeIter", "PrefetchingIter", "MNISTIter",
           "CSVIter"]


def _instrumented_next(next_fn):
    """Wrap a ``next`` implementation with telemetry: an ``io.next`` span
    (labeled with the concrete iterator class), a batches-served counter
    and a fetch-latency histogram — batches/sec falls out of the two.
    With the span buffer off the span is the profiler's annotation
    alone."""
    import functools

    @functools.wraps(next_fn)
    def next_with_telemetry(self):
        cls = type(self).__name__
        with _telemetry.span("io.next", _hist="io.next.seconds", iter=cls):
            batch = next_fn(self)
        if _telemetry.enabled():
            _telemetry.counter("io.batches", iter=cls).inc()
        return batch
    return next_with_telemetry


class DataDesc(namedtuple("DataDesc", ["name", "shape"])):
    """reference: io.py:19 — (name, shape) + dtype/layout attributes."""

    def __new__(cls, name, shape, dtype=np.float32, layout="NCHW"):
        ret = super().__new__(cls, name, tuple(shape))
        ret.dtype = dtype
        ret.layout = layout
        return ret

    @staticmethod
    def get_batch_axis(layout):
        if layout is None:
            return 0
        return layout.find("N")

    @staticmethod
    def get_list(shapes, types=None):
        if types is not None:
            type_dict = dict(types)
            return [DataDesc(x[0], x[1], type_dict[x[0]]) for x in shapes]
        return [DataDesc(x[0], x[1]) for x in shapes]


class DataBatch:
    """reference: io.py:82."""

    def __init__(self, data, label=None, pad=None, index=None,
                 bucket_key=None, provide_data=None, provide_label=None):
        self.data = data
        self.label = label
        self.pad = pad
        self.index = index
        self.bucket_key = bucket_key
        self.provide_data = provide_data
        self.provide_label = provide_label


class StackedDataBatch(DataBatch):
    """K consecutive batches stacked on a new leading axis — one
    scan-dispatch window for ``Module.fit(steps_per_dispatch=K)``.

    ``data``/``label`` hold arrays of shape ``(steps, batch, ...)``;
    ``pads`` keeps the per-step pad values. ``split()`` recovers
    per-step ``DataBatch`` views (the single-step fallback path for
    partial tail windows).
    """

    def __init__(self, data, label=None, pads=None, index=None):
        steps = int(data[0].shape[0])
        pads = list(pads) if pads is not None else [0] * steps
        super().__init__(data, label, pad=pads[-1] if pads else 0,
                         index=index)
        self.steps = steps
        self.pads = pads

    def split(self):
        out = []
        for k in range(self.steps):
            out.append(DataBatch(
                [NDArray(d.asjax()[k]) for d in self.data],
                [NDArray(l.asjax()[k]) for l in (self.label or [])],
                pad=self.pads[k] if k < len(self.pads) else 0))
        return out


class DataIter:
    """Base iterator. reference: io.py:130."""

    def __init__(self, batch_size=0):
        self.batch_size = batch_size

    def __iter__(self):
        return self

    def reset(self):
        pass

    @_instrumented_next
    def next(self):
        if self.iter_next():
            return DataBatch(data=self.getdata(), label=self.getlabel(),
                             pad=self.getpad(), index=self.getindex())
        raise StopIteration

    def __next__(self):
        return self.next()

    def iter_next(self):
        pass

    def getdata(self):
        pass

    def getlabel(self):
        pass

    def getindex(self):
        return None

    def getpad(self):
        pass


class ResizeIter(DataIter):
    """Resize epoch length. reference: io.py:220."""

    def __init__(self, data_iter, size, reset_internal=True):
        super().__init__()
        self.data_iter = data_iter
        self.size = size
        self.reset_internal = reset_internal
        self.cur = 0
        self.current_batch = None
        self.provide_data = data_iter.provide_data
        self.provide_label = data_iter.provide_label
        self.batch_size = data_iter.batch_size
        if hasattr(data_iter, "default_bucket_key"):
            self.default_bucket_key = data_iter.default_bucket_key

    def reset(self):
        self.cur = 0
        if self.reset_internal:
            self.data_iter.reset()

    def iter_next(self):
        if self.cur == self.size:
            return False
        try:
            self.current_batch = self.data_iter.next()
        except StopIteration:
            self.data_iter.reset()
            self.current_batch = self.data_iter.next()
        self.cur += 1
        return True

    def next(self):
        if self.iter_next():
            return self.current_batch
        raise StopIteration

    def getdata(self):
        return self.current_batch.data

    def getlabel(self):
        return self.current_batch.label

    def getindex(self):
        return self.current_batch.index

    def getpad(self):
        return self.current_batch.pad


def _host_view(arr):
    """``arr`` as NumPy without a copy where it lives in host memory
    (NumPy itself, or one CPU-backend ``jax.Array``); an array on an
    accelerator or spread over several devices stays a ``jax.Array``."""
    if isinstance(arr, NDArray):
        arr = arr.asjax()
    if hasattr(arr, "devices"):
        devs = arr.devices()
        if len(devs) > 1 or next(iter(devs)).platform != "cpu":
            return arr
    return np.asarray(arr)


def _destination(placement, shape, stacked=False):
    """The ``jax.Device`` or ``Sharding`` a batch array of ``shape``
    goes to (``stacked``: a K-window, batch on axis 1). ``placement`` is
    what the consumer handed over: a ``Context``, a device, the bound
    executor group's data sharding, or its SPMD plan's
    ``data_sharding_for`` (the sharding depends on the shape there)."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    if hasattr(placement, "jax_device"):
        return placement.jax_device()
    if callable(placement):
        return placement(shape, stacked=stacked)
    if stacked and isinstance(placement, NamedSharding):
        return NamedSharding(placement.mesh, P(None, *placement.spec))
    return placement


@functools.lru_cache(maxsize=None)
def _stack_program(dst):
    """The jitted program that stacks a K-window's staged parts on a
    new leading axis in device memory, its output under ``dst`` (a
    host-side stack would fault in K batches of fresh pages a window)."""
    import jax
    import jax.numpy as jnp

    def io_stack(*parts):
        return jnp.stack(parts)

    sharded = isinstance(dst, jax.sharding.Sharding)
    return jax.jit(io_stack, out_shardings=dst if sharded else None)


def _stage(parts, placement, stacked=False):
    """Stage one batch array - or, ``stacked``, the K arrays of a scan
    window, stacked on a new leading axis - into device memory under
    ``placement``; returns when it is resident. The result is bit for
    bit what ``parts`` held, in their shape and dtype. A host array
    crosses the link once, as it lies, each device's rows straight to
    that device."""
    import jax
    hosts = [_host_view(p) for p in parts]
    shape = tuple(hosts[0].shape)
    dst = _destination(placement, shape)
    staged = [jax.device_put(h, dst) for h in hosts]
    if stacked:
        out = _stack_program(_destination(
            placement, (len(parts),) + shape, stacked=True))(*staged)
    else:
        out = staged[0]
    out.block_until_ready()
    _telemetry.counter("io.prefetch.staged_bytes").inc(out.nbytes)
    _telemetry.counter("io.prefetch.staged_batches").inc()
    return NDArray(out)


class PrefetchingIter(DataIter):
    """Background-thread prefetch over one or more iters.

    reference: io.py:285 (python) mirroring the C++ PrefetcherIter
    (src/io/iter_prefetcher.h): a producer thread stays one batch ahead so
    host decode overlaps device compute.

    Decode-failure policy (docs/faults.md): ``on_decode_error``
    (default ``MXNET_IO_ON_DECODE_ERROR``, else ``"raise"``) decides
    what a failing batch fetch does. ``"raise"`` propagates to the
    consumer (the pre-existing behavior); ``"skip"`` records the
    failure (``io.decode.skipped`` counter, ``io.decode.skip`` ring
    record, ``skipped_batches`` attribute) and moves on to the next
    batch — at pod scale one corrupt record must not kill an epoch.
    A run of more than ``MXNET_IO_DECODE_MAX_SKIP`` (default 100)
    *consecutive* failures is a broken pipeline, not bad records, and
    raises regardless. The ``io.decode`` injection point sits after
    each fetch so tier-1 drives both paths deterministically.

    Staging (docs/telemetry.md, "How a batch is staged"): with a
    ``device`` - a ``Context``, or whatever placement ``Module.fit``
    hands over through ``stack_windows`` - the producer thread lands
    each batch in device memory before it queues it, under spans
    ``io.prefetch.to_device`` / ``.stack`` (attribute ``placement``)
    that close when the batch is resident. A host array goes to its
    placement in one put, each device's rows straight to that device,
    and unchanged: same shape, dtype and bits. Counters
    ``io.prefetch.staged_bytes`` and ``io.prefetch.staged_batches``
    say how much went that way.
    """

    def __init__(self, iters, rename_data=None, rename_label=None,
                 device=None, on_decode_error=None, max_decode_skip=None):
        super().__init__()
        if not isinstance(iters, list):
            iters = [iters]
        self._on_decode_error = (
            on_decode_error if on_decode_error is not None
            else os.environ.get("MXNET_IO_ON_DECODE_ERROR", "raise"))
        if self._on_decode_error not in ("raise", "skip"):
            raise MXNetError(
                f"on_decode_error={self._on_decode_error!r} "
                "(want 'raise' or 'skip')")
        try:
            self._max_decode_skip = int(
                max_decode_skip if max_decode_skip is not None
                else os.environ.get("MXNET_IO_DECODE_MAX_SKIP", "") or 100)
        except ValueError:
            self._max_decode_skip = 100
        self.skipped_batches = 0        # cumulative skip bookkeeping
        self._consecutive_skips = 0
        self.n_iter = len(iters)
        assert self.n_iter > 0
        self.iters = iters
        self.rename_data = rename_data
        self.rename_label = rename_label
        # prefetch-to-device double buffering (the C++ pipeline's pinned
        # staging + async H2D copy, iter_prefetcher.h): the producer
        # thread lands each batch in HBM while the consumer computes on
        # the previous one, so the train step never waits on the copy
        self._device = device
        self._stack_k = 1      # >1: producer stacks K-batch scan windows
        self.batch_size = self.provide_data[0].shape[0]
        self._queue = _queue.Queue(maxsize=2)
        self._stop = threading.Event()
        self._thread = None
        self._start()

    @property
    def provide_data(self):
        if self.rename_data is None:
            return sum([i.provide_data for i in self.iters], [])
        return sum([[DataDesc(r[x.name], x.shape, x.dtype)
                     if isinstance(r[x.name], str) else r[x.name]
                     for x in i.provide_data]
                    for r, i in zip(self.rename_data, self.iters)], [])

    @property
    def provide_label(self):
        if self.rename_label is None:
            return sum([i.provide_label for i in self.iters], [])
        return sum([[DataDesc(r[x.name], x.shape, x.dtype)
                     if isinstance(r[x.name], str) else r[x.name]
                     for x in i.provide_label]
                    for r, i in zip(self.rename_label, self.iters)], [])

    def stack_windows(self, k, device=None):
        """Producer-side K-batch stacking for scan-fused training, and
        the consumer's word on where batches go.

        With ``k > 1`` the background thread groups every ``k``
        consecutive batches into one :class:`StackedDataBatch` (leading
        axis = step), staged in device memory off-thread, so
        ``Module.fit``'s K-step scan dispatch consumes HBM-resident
        windows without a per-batch host round trip. A short tail
        yields a partial window (``steps < k``). ``k=1`` is per-batch
        mode. ``device`` is the placement to stage under: a ``Context``,
        a ``jax.Device``, a ``Sharding`` over the consumer's mesh (rows
        on its first axis), or a callable ``(shape, stacked=False) ->
        Sharding``. ``Module.fit`` passes its bound executor group's
        own, so that every chip gets its rows straight from the host
        and the step finds the batch where it wants it - and that wins
        over what the iterator was built with. A change of either
        restarts the producer from the start of the epoch, so every
        batch the consumer sees lies under the one placement (a
        consumer's programs compile per input sharding). Returns self.
        """
        k = max(1, int(k))
        if device is None:
            device = self._device
        if k != self._stack_k or device != self._device:
            self._stack_k, self._device = k, device
            self.reset()       # restart the producer in the new mode
        return self

    def _merge(self, batches):
        """Merge one batch from each inner iter (multi-iter fan-in)."""
        return DataBatch(
            data=sum([b.data for b in batches], []),
            label=sum([(b.label or []) for b in batches], []),
            pad=batches[0].pad, index=batches[0].index)

    @staticmethod
    def _stack(window, placement):
        """Stack K merged batches into one StackedDataBatch, staged
        under ``placement`` (host-side where there is none)."""
        if placement is None:
            import jax.numpy as jnp

            def put(slot_arrays):
                return NDArray(jnp.stack(
                    [a.asjax() if isinstance(a, NDArray)
                     else jnp.asarray(np.asarray(a)) for a in slot_arrays]))
        else:
            put = functools.partial(_stage, placement=placement,
                                    stacked=True)
        data = [put([b.data[i] for b in window])
                for i in range(len(window[0].data))]
        label = [put([b.label[i] for b in window])
                 for i in range(len(window[0].label or []))]
        return StackedDataBatch(data, label,
                                pads=[b.pad or 0 for b in window],
                                index=window[0].index)

    def _next_batches(self):
        """One batch per inner iter, through the decode-failure policy:
        the ``io.decode`` injection point fires after the fetch (the
        batch is consumed either way, so a skip is a true skip, not a
        silent retry of the same cursor), and a failure under the
        ``skip`` policy records and moves on. StopIteration always
        propagates — end-of-epoch is not a failure."""
        # benign race with reset()'s re-zero: reset() joins the producer
        # first (so overlap needs a >1s wedged join), and the value is a
        # GIL-atomic int only this counter's own error path reads — a
        # lost reset costs one extra counted skip, never control flow
        while True:
            try:
                batches = [i.next() for i in self.iters]
                _faults.point("io.decode")
                self._consecutive_skips = 0  # mxlint: guarded-by(gil)
                return batches
            except StopIteration:
                raise
            except Exception as exc:
                if self._on_decode_error != "skip":
                    raise
                self._consecutive_skips += 1
                self.skipped_batches += 1
                _telemetry.counter("io.decode.skipped").inc()
                _telemetry.flightrec.note(
                    "io.decode.skip", skipped=self.skipped_batches,
                    error=f"{type(exc).__name__}: {exc}")
                if self._consecutive_skips > self._max_decode_skip:
                    raise MXNetError(
                        f"{self._consecutive_skips} consecutive decode "
                        "failures exceed MXNET_IO_DECODE_MAX_SKIP="
                        f"{self._max_decode_skip}: the pipeline is "
                        "broken, not the records") from exc

    def _producer(self):
        # _stack_k/_device are GIL-atomic snapshots of caller-side
        # config (stack_windows() restarts the producer via reset()
        # after writing); a stale read can only affect batches the
        # restart discards with the old queue
        # in the profiler's trace one queue entry is ``io.prefetch.batch``
        # enclosing ``.fetch`` (the inner iterators), ``.to_device`` or
        # ``.stack`` (staging onto the device) and ``.put`` (blocked on
        # the full queue: zero means this thread sets the pace)
        span = _telemetry.span
        while not self._stop.is_set():
            try:
                k = self._stack_k  # mxlint: guarded-by(gil)
                dev = self._device  # mxlint: guarded-by(gil)
                if k <= 1:
                    with span("io.prefetch.batch"):
                        with span("io.prefetch.fetch"):
                            batches = self._next_batches()
                        if dev is not None:
                            with span("io.prefetch.to_device",
                                      placement=str(dev)):
                                batches = [self._to_device(b, dev)
                                           for b in batches]
                        with span("io.prefetch.put"):
                            self._queue.put(batches)
                    continue
                window, exhausted = [], False
                with span("io.prefetch.batch", steps=k):
                    with span("io.prefetch.fetch"):
                        for _ in range(k):
                            try:
                                window.append(
                                    self._merge(self._next_batches()))
                            except StopIteration:
                                exhausted = True
                                break
                    if window:
                        with span("io.prefetch.stack",
                                  placement=str(dev)):
                            stacked = self._stack(window, dev)
                        with span("io.prefetch.put"):
                            self._queue.put(stacked)
                if exhausted:
                    self._queue.put(None)
                    return
            except StopIteration:
                self._queue.put(None)
                return
            except BaseException as exc:  # surface in the consumer, don't
                self._queue.put(("__error__", exc))  # die into a hang
                return

    @staticmethod
    def _to_device(batch, placement):
        return DataBatch([_stage([d], placement) for d in batch.data],
                         [_stage([l], placement)
                          for l in (batch.label or [])],
                         pad=batch.pad, index=batch.index)

    def _start(self):
        self._stop.clear()
        self._thread = threading.Thread(target=self._producer, daemon=True)
        self._thread.start()

    def __del__(self):
        stop = getattr(self, "_stop", None)     # ctor may have raised
        if stop is not None:                    # before creating it
            stop.set()

    def reset(self):
        self._stop.set()
        try:
            while True:
                self._queue.get_nowait()
        except _queue.Empty:
            pass
        if self._thread is not None:
            self._thread.join(timeout=1.0)
        for i in self.iters:
            i.reset()
        self._consecutive_skips = 0
        self._queue = _queue.Queue(maxsize=2)
        self._start()

    @_instrumented_next
    def next(self):
        batches = self._queue.get()
        if batches is None:
            raise StopIteration
        if isinstance(batches, tuple) and batches and \
                batches[0] == "__error__":
            raise batches[1]
        if isinstance(batches, StackedDataBatch):   # stack_windows mode
            return batches
        return self._merge(batches)


def _init_data(data, allow_empty, default_name):
    """Normalize input to list of (name, numpy). reference: io.py:395."""
    assert data is not None or allow_empty
    if data is None:
        data = []
    if isinstance(data, (np.ndarray, NDArray)):
        data = [data]
    if isinstance(data, list):
        if not allow_empty:
            assert len(data) > 0
        if len(data) == 1:
            data = {default_name: data[0]}
        else:
            data = {f"_{i}_{default_name}": d for i, d in enumerate(data)}
    if not isinstance(data, dict):
        raise TypeError("Input must be NDArray, numpy.ndarray, a list of "
                        "them or dict with them as values")
    ret = {}
    for k, v in data.items():
        if isinstance(v, NDArray):
            ret[k] = v.asnumpy()
        else:
            ret[k] = np.asarray(v)
    return list(ret.items())


_HOST_ALIGN = 64    # bytes: what the CPU backend wraps without a copy


def _host_aligned(arr):
    """``arr`` itself where its memory starts on a ``_HOST_ALIGN``
    boundary, else one aligned copy. A batch sliced out of aligned
    storage is wrapped by ``nd.array`` as it lies; out of any other it
    is copied into fresh pages, every batch (154 MB of page faults
    took 0.24 s a ResNet batch: PERF.md, PR 26)."""
    arr = np.ascontiguousarray(arr)
    if arr.ctypes.data % _HOST_ALIGN == 0:
        return arr
    raw = np.empty(arr.nbytes + _HOST_ALIGN, np.uint8)
    start = -raw.ctypes.data % _HOST_ALIGN
    out = raw[start:start + arr.nbytes].view(arr.dtype).reshape(arr.shape)
    out[...] = arr
    return out


class NDArrayIter(DataIter):
    """In-memory iterator. reference: io.py:457."""

    def __init__(self, data, label=None, batch_size=1, shuffle=False,
                 last_batch_handle="pad", data_name="data",
                 label_name="softmax_label"):
        super().__init__(batch_size)
        self.data = _init_data(data, allow_empty=False,
                               default_name=data_name)
        self.label = _init_data(label, allow_empty=True,
                                default_name=label_name)
        self.idx = np.arange(self.data[0][1].shape[0])
        if shuffle:
            np.random.shuffle(self.idx)
            self.data = [(k, v[self.idx]) for k, v in self.data]
            self.label = [(k, v[self.idx]) for k, v in self.label]
        if last_batch_handle == "discard":
            new_n = self.data[0][1].shape[0] - \
                self.data[0][1].shape[0] % batch_size
            self.idx = self.idx[:new_n]
        self.data = [(k, _host_aligned(v)) for k, v in self.data]
        self.label = [(k, _host_aligned(v)) for k, v in self.label]
        self.data_list = [x[1] for x in self.data] + \
            [x[1] for x in self.label]
        self.num_source = len(self.data_list)
        self.num_data = self.idx.shape[0]
        assert self.num_data >= batch_size, \
            "batch_size needs to be smaller than data size."
        self.cursor = -batch_size
        self.batch_size = batch_size
        self.last_batch_handle = last_batch_handle

    @property
    def provide_data(self):
        return [DataDesc(k, tuple([self.batch_size] + list(v.shape[1:])),
                         v.dtype) for k, v in self.data]

    @property
    def provide_label(self):
        return [DataDesc(k, tuple([self.batch_size] + list(v.shape[1:])),
                         v.dtype) for k, v in self.label]

    def hard_reset(self):
        self.cursor = -self.batch_size

    def reset(self):
        if self.last_batch_handle == "roll_over" and \
                self.cursor > self.num_data:
            self.cursor = -self.batch_size + \
                (self.cursor % self.num_data) % self.batch_size
        else:
            self.cursor = -self.batch_size

    def iter_next(self):
        self.cursor += self.batch_size
        return self.cursor < self.num_data

    @_instrumented_next
    def next(self):
        if self.iter_next():
            return DataBatch(data=self.getdata(), label=self.getlabel(),
                             pad=self.getpad(), index=None)
        raise StopIteration

    def _getdata(self, data_source):
        assert self.cursor < self.num_data, "DataIter needs reset."
        if self.cursor + self.batch_size <= self.num_data:
            return [array(x[1][self.cursor:self.cursor + self.batch_size])
                    for x in data_source]
        pad = self.batch_size - self.num_data + self.cursor
        return [array(np.concatenate(
            (x[1][self.cursor:], x[1][:pad]), axis=0)) for x in data_source]

    def getdata(self):
        return self._getdata(self.data)

    def getlabel(self):
        return self._getdata(self.label)

    def getpad(self):
        if self.last_batch_handle == "pad" and \
                self.cursor + self.batch_size > self.num_data:
            return self.cursor + self.batch_size - self.num_data
        return 0


class MNISTIter(DataIter):
    """idx-format MNIST reader (reference: src/io/iter_mnist.cc:61-241)."""

    def __init__(self, image, label, batch_size=128, shuffle=True, flat=False,
                 silent=False, seed=0, part_index=0, num_parts=1, **kwargs):
        super().__init__(batch_size)
        imgs = self._read_idx_images(image)
        labels = self._read_idx_labels(label)
        if num_parts > 1:
            n = imgs.shape[0] // num_parts
            imgs = imgs[part_index * n:(part_index + 1) * n]
            labels = labels[part_index * n:(part_index + 1) * n]
        if flat:
            imgs = imgs.reshape(imgs.shape[0], -1)
        else:
            imgs = imgs.reshape(imgs.shape[0], 1, imgs.shape[1],
                                imgs.shape[2])
        imgs = imgs.astype(np.float32) / 255.0
        self._inner = NDArrayIter(imgs, labels.astype(np.float32),
                                  batch_size, shuffle)

    @staticmethod
    def _open(path):
        if path.endswith(".gz"):
            return gzip.open(path, "rb")
        return open(path, "rb")

    @classmethod
    def _read_idx_images(cls, path):
        with cls._open(path) as f:
            magic, num, rows, cols = struct.unpack(">IIII", f.read(16))
            assert magic == 2051, f"bad MNIST image magic {magic}"
            return np.frombuffer(f.read(num * rows * cols),
                                 dtype=np.uint8).reshape(num, rows, cols)

    @classmethod
    def _read_idx_labels(cls, path):
        with cls._open(path) as f:
            magic, num = struct.unpack(">II", f.read(8))
            assert magic == 2049, f"bad MNIST label magic {magic}"
            return np.frombuffer(f.read(num), dtype=np.uint8)

    @property
    def provide_data(self):
        return self._inner.provide_data

    @property
    def provide_label(self):
        return self._inner.provide_label

    def reset(self):
        self._inner.reset()

    def next(self):
        return self._inner.next()


class CSVIter(DataIter):
    """CSV reader (reference: src/io/iter_csv.cc:41-132)."""

    def __init__(self, data_csv, data_shape, label_csv=None, label_shape=(1,),
                 batch_size=128, round_batch=True, **kwargs):
        super().__init__(batch_size)
        data = np.loadtxt(data_csv, delimiter=",", dtype=np.float32,
                          ndmin=2)
        data = data.reshape((-1,) + tuple(data_shape))
        label = None
        if label_csv is not None:
            label = np.loadtxt(label_csv, delimiter=",", dtype=np.float32,
                               ndmin=2)
            label = label.reshape((-1,) + tuple(label_shape))
            if label_shape == (1,):
                label = label.reshape(-1)
        else:
            label = np.zeros(data.shape[0], dtype=np.float32)
        self._inner = NDArrayIter(
            data, label, batch_size,
            last_batch_handle="roll_over" if round_batch else "pad")

    @property
    def provide_data(self):
        return self._inner.provide_data

    @property
    def provide_label(self):
        return self._inner.provide_label

    def reset(self):
        self._inner.reset()

    def next(self):
        return self._inner.next()
