"""Data iterators.

Reference: ``python/mxnet/io.py`` (DataIter ABC, NDArrayIter, ResizeIter,
PrefetchingIter, MXDataIter) over the C++ iterator chain in ``src/io/``
(SURVEY.md §3.5).  The TPU build keeps the iterator-chain design —
source → batcher → background prefetcher — with the prefetcher as a Python
thread double-buffering host→device transfers (the role of
``PrefetcherIter``/``dmlc::ThreadedIter``).
"""
from __future__ import annotations

import os
import queue
import threading
import time
from collections import namedtuple

import numpy as np

from .base import MXNetError, get_env
from .ndarray import NDArray, array

__all__ = ["DataDesc", "DataBatch", "DataIter", "NDArrayIter", "ResizeIter",
           "PrefetchingIter", "DevicePrefetchIter", "prefetch_to_device",
           "CSVIter", "MNISTIter", "ImageRecordIter",
           "LibSVMIter", "ImageDetRecordIter",
           "DataServiceIter", "fold_in", "epoch_permutation"]


def _queue_get_or_die(q, thread, what, poll_s=0.2):
    """``queue.get`` that survives worker death.

    A plain blocking ``get`` deadlocks the consumer forever when the
    worker thread died without enqueueing its end-of-data sentinel (hard
    crash, injected kill, interpreter teardown race).  Poll instead:
    whenever the queue stays empty, check the worker is still alive and
    raise a diagnosable :class:`MXNetError` the moment it is not (after
    one final non-blocking drain to close the put-then-exit race)."""
    while True:
        try:
            return q.get(timeout=poll_s)
        except queue.Empty:
            if thread is None or not thread.is_alive():
                try:
                    return q.get_nowait()
                except queue.Empty:
                    raise MXNetError(
                        "%s worker thread died without delivering a "
                        "batch, an error, or end-of-data; the input "
                        "pipeline is broken (worker crashed or was "
                        "killed)" % what) from None


def _fault_hook(site, out_queue, stop_event):
    """Run the fault-injection hook for a worker loop.  Returns True when
    the worker must die *silently* (injected ``kill`` — no sentinel, no
    error: the consumer-side dead-worker detection is what's under
    test); a ``raise`` fault is forwarded through the queue like any
    organic worker error."""
    from .testing import faults

    try:
        faults.inject(site)
    except faults.WorkerKilled:
        return True
    except Exception as exc:
        if not stop_event.is_set():
            out_queue.put(exc)
        return True
    return False


class DataDesc(namedtuple("DataDesc", ["name", "shape", "dtype", "layout"])):
    """Data description (reference ``DataDesc``: name, shape, dtype, layout)."""

    def __new__(cls, name, shape, dtype="float32", layout="NCHW"):
        return super().__new__(cls, name, tuple(shape), np.dtype(dtype), layout)

    @staticmethod
    def get_batch_axis(layout):
        return 0 if layout is None else layout.find("N")


class DataBatch:
    """One batch (reference ``DataBatch``: data/label lists + pad/index)."""

    def __init__(self, data, label=None, pad=None, index=None,
                 bucket_key=None, provide_data=None, provide_label=None):
        self.data = data
        self.label = label
        self.pad = pad
        self.index = index
        self.bucket_key = bucket_key
        self.provide_data = provide_data
        self.provide_label = provide_label


class DataIter:
    """Iterator ABC (reference ``io.py:175``)."""

    def __init__(self, batch_size=0):
        self.batch_size = batch_size

    def __iter__(self):
        return self

    def reset(self):
        pass

    def next(self):
        if self.iter_next():
            return DataBatch(data=self.getdata(), label=self.getlabel(),
                             pad=self.getpad(), index=self.getindex())
        raise StopIteration

    def __next__(self):
        return self.next()

    def iter_next(self):
        raise NotImplementedError

    def getdata(self):
        raise NotImplementedError

    def getlabel(self):
        raise NotImplementedError

    def getindex(self):
        return None

    def getpad(self):
        raise NotImplementedError

    # -- seekable protocol (O(1) resume) --------------------------------
    def seekable(self):
        """True when :meth:`seek` can jump this iterator to an absolute
        ``(epoch, nbatch)`` position without replaying batches — the O(1)
        resume path ``fit(resume_from=...)`` prefers over O(steps)
        replay.  Seekability requires the stream to be a pure function of
        position (deterministic or seeded shuffle)."""
        return False

    def seek(self, epoch, nbatch):
        """Position the stream so the next batch drawn is batch ``nbatch``
        of epoch ``epoch`` (both 0-based), exactly as if ``epoch`` resets
        and ``nbatch`` draws had been replayed."""
        raise MXNetError(
            "%s is not seekable (unseeded shuffle makes the stream a "
            "function of RNG history, not position); resume falls back "
            "to O(steps) replay" % type(self).__name__)


def _init_data(data, allow_empty, default_name):
    """Normalize data/label inputs to a list of (name, array) (reference
    ``io.py`` ``_init_data``)."""
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
            data = {"_%d_%s" % (i, default_name): d
                    for i, d in enumerate(data)}
    if not isinstance(data, dict):
        raise TypeError("Input must be NDArray, numpy.ndarray, list or dict")
    return [(k, v.asnumpy() if isinstance(v, NDArray) else np.asarray(v))
            for k, v in data.items()]


class NDArrayIter(DataIter):
    """Iterate over in-memory arrays with shuffle/pad semantics
    (reference ``NDArrayIter``, ``io.py:514``)."""

    def __init__(self, data, label=None, batch_size=1, shuffle=False,
                 last_batch_handle="pad", data_name="data",
                 label_name="softmax_label", seed=None):
        super().__init__(batch_size)
        self.data = _init_data(data, allow_empty=False, default_name=data_name)
        self.label = _init_data(label, allow_empty=True,
                                default_name=label_name)
        self.num_data = self.data[0][1].shape[0]
        assert self.num_data >= batch_size, \
            "batch_size needs to be smaller than data size."
        self.idx = np.arange(self.num_data)
        self.shuffle = shuffle
        # a private RNG makes the shuffle sequence a pure function of
        # (seed, reset count) — required for exact replay by
        # ``fit(resume_from=...)``, which fast-forwards by replaying
        # resets (the global np.random stream also feeds initializers,
        # so its draw position differs between cold start and resume)
        self._rng = np.random.RandomState(seed) if seed is not None \
            else np.random
        self._seed = seed
        self.last_batch_handle = last_batch_handle
        if last_batch_handle == "discard":
            self.num_data = (self.num_data // batch_size) * batch_size
        self.cursor = -batch_size
        self.reset()

    @property
    def provide_data(self):
        return [DataDesc(k, (self.batch_size,) + v.shape[1:], v.dtype)
                for k, v in self.data]

    @property
    def provide_label(self):
        return [DataDesc(k, (self.batch_size,) + v.shape[1:], v.dtype)
                for k, v in self.label]

    def hard_reset(self):
        self.cursor = -self.batch_size

    def reset(self):
        if self.shuffle:
            self._rng.shuffle(self.idx)
        if self.last_batch_handle == "roll_over" and \
                self.cursor > self.num_data:
            self.cursor = -self.batch_size + (self.cursor % self.num_data)
        else:
            self.cursor = -self.batch_size

    def iter_next(self):
        self.cursor += self.batch_size
        return self.cursor < self.num_data

    def seekable(self):
        return (not self.shuffle) or self._seed is not None

    def seek(self, epoch, nbatch):
        """O(1)-in-steps jump: rebuild the private shuffle RNG at its
        epoch-``epoch`` state (one in-place shuffle per epoch boundary,
        exactly the draws replayed resets would make — the constructor's
        reset is shuffle #1 for epoch 0) and place the cursor directly;
        no batches are drawn."""
        if not self.seekable():
            raise MXNetError(
                "NDArrayIter with shuffle=True but no seed= is not "
                "seekable: the shuffle order is a function of global RNG "
                "history, not of (epoch, nbatch)")
        epoch, nbatch = int(epoch), int(nbatch)
        if self.shuffle:
            self.idx = np.arange(self.idx.shape[0])
            rng = np.random.RandomState(self._seed)
            for _ in range(epoch + 1):
                rng.shuffle(self.idx)
            self._rng = rng
        self.cursor = nbatch * self.batch_size - self.batch_size

    def _getdata(self, data_source):
        assert self.cursor < self.num_data
        sel = self.idx[self.cursor:self.cursor + self.batch_size]
        if len(sel) < self.batch_size:  # pad: wrap around
            pad = self.batch_size - len(sel)
            sel = np.concatenate([sel, self.idx[:pad]])
        return [array(x[1][sel]) for x in data_source]

    def getdata(self):
        return self._getdata(self.data)

    def getlabel(self):
        return self._getdata(self.label)

    def getpad(self):
        if self.last_batch_handle == "pad" and \
                self.cursor + self.batch_size > self.num_data:
            return self.cursor + self.batch_size - self.num_data
        return 0


class ResizeIter(DataIter):
    """Resize an iterator to ``size`` batches per epoch (reference
    ``ResizeIter``)."""

    def __init__(self, data_iter, size, reset_internal=True):
        super().__init__(data_iter.batch_size)
        self.data_iter = data_iter
        self.size = size
        self.reset_internal = reset_internal
        self.cur = 0
        self.current_batch = None
        self.provide_data = data_iter.provide_data
        self.provide_label = data_iter.provide_label

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

    def getdata(self):
        return self.current_batch.data

    def getlabel(self):
        return self.current_batch.label

    def getindex(self):
        return self.current_batch.index

    def getpad(self):
        return self.current_batch.pad


class _ThreadedPrefetchTeardown(object):
    """Shared drain/stop/join teardown for the queue+thread prefetchers
    (:class:`PrefetchingIter`, :class:`DevicePrefetchIter`) — a dead- or
    wedged-worker fix lands once here, not per class."""

    def _drain(self, capture_error=False):
        """Empty the queue; with ``capture_error`` return the first
        pending worker exception found (an error the consumer never got
        to see), else None."""
        pending = None
        try:
            while True:
                item = self._queue.get_nowait()
                if capture_error and pending is None and \
                        isinstance(item, Exception):
                    pending = item
        except queue.Empty:
            pass
        return pending

    def close(self, timeout=5):
        """Stop the worker WITHOUT restarting it (``reset`` is
        stop-then-restart): signal stop, drain so a worker blocked on
        the full queue can exit, join with ``timeout``, and RE-RAISE any
        worker exception still pending in the queue — an error the
        consumer never observed must not vanish on teardown.  After
        ``close`` the iterator reports exhaustion until ``reset``; any
        inner iterators are left untouched for the caller to reuse."""
        self._stop.set()
        pending = self._drain(capture_error=True)
        t = self._thread
        if t is not None:
            t.join(timeout=timeout)
            if t.is_alive():
                import logging

                logging.warning("%s worker did not exit within %ss on "
                                "close()", type(self).__name__, timeout)
            self._thread = None
        pending = pending or self._drain(capture_error=True)
        self._exhausted = True
        if pending is not None and pending is not self._worker_error:
            self._worker_error = pending
            raise pending

    def _halt(self):
        """Stop the worker WITHOUT restarting it and clear queue/error
        state — the shared first half of ``reset()`` and ``seek()``.
        Drain so a worker blocked on a full queue can observe the stop
        and exit; it may still enqueue the batch it was holding, so
        drain again AFTER the join so no stale batch survives into the
        restarted stream."""
        self._stop.set()
        self._drain()
        if self._thread is not None:
            self._thread.join(timeout=5)
        self._drain()
        self._worker_error = None
        self._exhausted = False

    def seekable(self):
        return all(getattr(i, "seekable", lambda: False)()
                   for i in self.iters)

    def seek(self, epoch, nbatch):
        """Jump the whole pipeline: halt the staging worker, seek every
        inner iterator to ``(epoch, nbatch)``, restart streaming from
        the new position.  ``nbatch`` counts raw inner batches (the
        units ``fit`` checkpoints), independent of any pack factor."""
        if not self.seekable():
            raise MXNetError(
                "%s cannot seek: inner iterator(s) %s are not seekable"
                % (type(self).__name__,
                   [type(i).__name__ for i in self.iters]))
        self._halt()
        for i in self.iters:
            i.seek(epoch, nbatch)
        self._start()

    def __del__(self):
        self._stop.set()


class PrefetchingIter(_ThreadedPrefetchTeardown, DataIter):
    """Background-thread prefetcher over one or more iterators (reference
    ``PrefetchingIter``, ``io.py:341`` ≈ ``PrefetcherIter``/
    ``dmlc::ThreadedIter`` in C++).  Overlaps host batch prep with device
    compute — the double-buffered input pipeline the TPU step needs."""

    def __init__(self, iters, rename_data=None, rename_label=None,
                 prefetch_depth=2):
        iters = iters if isinstance(iters, list) else [iters]
        super().__init__(iters[0].batch_size)
        self.iters = iters
        self.rename_data = rename_data
        self.rename_label = rename_label
        self._queue = queue.Queue(maxsize=prefetch_depth)
        self._stop = threading.Event()
        self._thread = None
        self.current_batch = None
        self._worker_error = None
        self._exhausted = False
        self._start()

    @property
    def provide_data(self):
        if self.rename_data is None:
            return sum([i.provide_data for i in self.iters], [])
        return sum([[DataDesc(r[x.name], x.shape, x.dtype)
                     if isinstance(r, dict) else x
                     for x in i.provide_data]
                    for r, i in zip(self.rename_data, self.iters)], [])

    @property
    def provide_label(self):
        if self.rename_label is None:
            return sum([i.provide_label for i in self.iters], [])
        return sum([[DataDesc(r[x.name], x.shape, x.dtype)
                     if isinstance(r, dict) else x
                     for x in i.provide_label]
                    for r, i in zip(self.rename_label, self.iters)], [])

    def _worker(self):
        while not self._stop.is_set():
            if _fault_hook("prefetch", self._queue, self._stop):
                return
            try:
                batches = [i.next() for i in self.iters]
            except StopIteration:
                self._queue.put(None)
                return
            except Exception as exc:  # surface at next() like ThreadedIter
                if not self._stop.is_set():
                    self._queue.put(exc)
                return
            self._queue.put(batches)

    def _start(self):
        self._stop.clear()
        self._thread = threading.Thread(target=self._worker, daemon=True)
        self._thread.start()

    def reset(self):
        self._halt()
        for i in self.iters:
            i.reset()
        self._start()

    def iter_next(self):
        if self._worker_error is not None:
            # the worker died on this error; keep surfacing it (a fresh
            # reset() restarts the stream) instead of hanging on the
            # empty queue
            raise self._worker_error
        if self._exhausted:
            return False
        try:
            batches = _queue_get_or_die(self._queue, self._thread,
                                        type(self).__name__)
        except MXNetError as e:
            self._worker_error = e  # dead worker: fail every later call
            raise
        if batches is None:
            self._exhausted = True
            return False
        if isinstance(batches, Exception):
            self._worker_error = batches
            raise batches
        self.current_batch = DataBatch(
            data=sum([b.data for b in batches], []),
            label=sum([(b.label or []) for b in batches], []),
            pad=batches[0].pad, index=batches[0].index)
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


class DevicePrefetchIter(_ThreadedPrefetchTeardown, DataIter):
    """Async *device*-staging prefetcher: the second pipeline stage on top
    of :class:`PrefetchingIter`'s host double-buffer.

    A background thread pulls host batches from the inner iterator(s),
    issues the host→device transfer (``jax.device_put``) into a ring of
    ``prefetch_depth`` (≥2) in-flight device buffers, and *waits for the
    copy on the staging thread* — so by the time ``Module.fit`` asks for
    batch N+1, its bytes are already resident and the consumer thread
    never blocks on the link.  This is what keeps a fresh-buffer
    ``device_put`` (tens of MB per image batch) out of the step loop;
    what it saves on a directly attached chip is not measured.

    Sharding-aware: under a ``mesh`` the batch is placed with the proper
    batch ``NamedSharding`` up front (``parallel.sharding.shard_batch``),
    so DP/FSDP meshes consume pre-sharded arrays with no re-layout in the
    fused step.  Without a mesh, batches land on ``context``'s device (or
    the default device).

    ``steps_per_call=K`` packs K consecutive batches into one super-batch
    with a leading K axis — one transfer and one dispatch feed K
    ``lax.scan``'d updates (:class:`~mxnet_tpu.fused.TrainStep` with
    ``steps_per_call=K``).  The trailing ``len(epoch) % K`` batches of an
    epoch are dropped (a partial pack would recompile the scanned step);
    ``provide_data``/``provide_label`` keep the *per-step* shapes.

    Emitted batches carry ``staged=True`` so consumers skip their own
    placement pass.
    """

    def __init__(self, iters, prefetch_depth=2, mesh=None, context=None,
                 steps_per_call=1):
        iters = iters if isinstance(iters, list) else [iters]
        super().__init__(iters[0].batch_size)
        if prefetch_depth < 1:
            raise MXNetError("prefetch_depth must be >= 1")
        if steps_per_call < 1:
            raise MXNetError("steps_per_call must be >= 1")
        self.iters = iters
        self.mesh = mesh
        self.context = context
        self._pack = int(steps_per_call)
        self._queue = queue.Queue(maxsize=prefetch_depth)
        self._stop = threading.Event()
        self._thread = None
        self.current_batch = None
        self._worker_error = None
        self._warned_drop = False
        self._exhausted = False
        # consumer-side staging-wait accounting: how long next() blocked
        # on the ring vs how many batches it delivered.  When the ratio
        # is high the pipeline is INPUT-bound (decode/transfer cannot
        # keep up with the device); bench_fit.py reports the attribution
        self.stage_wait_s = 0.0
        self.batches_delivered = 0
        self._start()

    @property
    def provide_data(self):
        return sum([i.provide_data for i in self.iters], [])

    @property
    def provide_label(self):
        return sum([i.provide_label for i in self.iters], [])

    # -- staging --------------------------------------------------------
    def _placement(self):
        """(fn: host/np/jax array -> committed device array) resolved
        lazily so constructing the iterator never initializes a backend
        the process does not use."""
        import jax

        if self.mesh is not None:
            from .parallel.sharding import shard_batch

            leading = 1 if self._pack > 1 else 0
            return lambda v: shard_batch(self.mesh, v, leading=leading)
        if self.context is not None:
            dev = self.context.jax_device
        else:
            dev = jax.local_devices()[0]
        return lambda v: jax.device_put(v, dev)

    @staticmethod
    def _host_array(arr):
        if isinstance(arr, NDArray):
            return np.asarray(arr._data)
        return np.asarray(arr)

    def _stage_group(self, group):
        """group: list (length pack) of per-iter batch lists -> one staged
        DataBatch.  Runs on the worker thread: the device_put AND the wait
        for transfer completion both happen here, off the consumer."""
        import jax

        place = self._placement()
        first = group[0]
        n_data = [len(b.data) for b in first]
        n_label = [len(b.label or []) for b in first]

        def stage_slot(get_arrays, counts):
            staged = []
            for it_idx, n in enumerate(counts):
                for j in range(n):
                    if self._pack == 1:
                        arr = get_arrays(group[0][it_idx])[j]
                        v = arr._data if isinstance(arr, NDArray) \
                            else np.asarray(arr)
                    else:
                        v = np.stack([
                            self._host_array(get_arrays(g[it_idx])[j])
                            for g in group])
                    out = place(v)
                    ctx = self.context
                    staged.append(NDArray(out, ctx) if ctx is not None
                                  else NDArray(out))
            return staged

        data = stage_slot(lambda b: b.data, n_data)
        label = stage_slot(lambda b: b.label or [], n_label)
        # eat the h2d latency HERE so the consumer never does
        jax.block_until_ready([a._data for a in data + label])
        batch = DataBatch(data=data, label=label,
                          pad=first[0].pad if self._pack == 1 else 0,
                          index=first[0].index if self._pack == 1 else None,
                          bucket_key=first[0].bucket_key,
                          provide_data=first[0].provide_data,
                          provide_label=first[0].provide_label)
        batch.staged = True
        return batch

    # -- worker ---------------------------------------------------------
    def _worker(self):
        while not self._stop.is_set():
            if _fault_hook("device_prefetch", self._queue, self._stop):
                return
            group = []
            try:
                for _ in range(self._pack):
                    group.append([i.next() for i in self.iters])
            except StopIteration:
                if group and not self._warned_drop:
                    self._warned_drop = True
                    import logging

                    logging.warning(
                        "DevicePrefetchIter(steps_per_call=%d): dropping "
                        "%d trailing batch(es) that do not fill a pack",
                        self._pack, len(group))
                self._queue.put(None)
                return
            except Exception as exc:  # surface at next() like ThreadedIter
                if not self._stop.is_set():
                    self._queue.put(exc)
                return
            try:
                staged = self._stage_group(group)
            except Exception as exc:
                if not self._stop.is_set():
                    self._queue.put(exc)
                return
            self._queue.put(staged)

    def _start(self):
        self._stop.clear()
        self._thread = threading.Thread(target=self._worker, daemon=True)
        self._thread.start()

    def reset(self):
        self._halt()
        for i in self.iters:
            i.reset()
        self._start()

    def reset_stage_stats(self):
        self.stage_wait_s = 0.0
        self.batches_delivered = 0

    def iter_next(self):
        if self._worker_error is not None:
            # worker died on this error; keep surfacing it (reset()
            # restarts the stream) instead of hanging on an empty queue
            raise self._worker_error
        if self._exhausted:
            # keep returning False (the worker is gone — a fresh get()
            # would block forever); reset() restarts the stream
            return False
        t0 = time.perf_counter()
        try:
            batch = _queue_get_or_die(self._queue, self._thread,
                                      "DevicePrefetchIter")
        except MXNetError as e:
            self._worker_error = e  # dead worker: fail every later call
            raise
        if batch is None:
            self._exhausted = True
            return False
        if isinstance(batch, Exception):
            self._worker_error = batch
            raise batch
        self.stage_wait_s += time.perf_counter() - t0
        self.batches_delivered += 1
        self.current_batch = batch
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


def prefetch_to_device(iters, prefetch_depth=2, mesh=None, context=None,
                       steps_per_call=1):
    """Wrap an iterator (or list of iterators) in a
    :class:`DevicePrefetchIter` — idempotent: an iterator that is already
    device-staging is returned as-is (same pack), so callers can apply it
    unconditionally."""
    if isinstance(iters, DevicePrefetchIter) and \
            iters._pack == steps_per_call:
        return iters
    return DevicePrefetchIter(iters, prefetch_depth=prefetch_depth,
                              mesh=mesh, context=context,
                              steps_per_call=steps_per_call)


class CSVIter(NDArrayIter):
    """CSV source (reference ``src/io/iter_csv.cc``; here parsed with
    numpy, feeding the same batching machinery)."""

    def __init__(self, data_csv, data_shape, label_csv=None, label_shape=(1,),
                 batch_size=1, **kwargs):
        data = np.loadtxt(data_csv, delimiter=",", dtype="float32")
        data = data.reshape((-1,) + tuple(data_shape))
        label = None
        if label_csv is not None:
            label = np.loadtxt(label_csv, delimiter=",", dtype="float32")
            label = label.reshape((-1,) + tuple(label_shape))
            if label.shape[1:] == (1,):
                label = label.ravel()
        super().__init__(data, label, batch_size=batch_size, **kwargs)


class LibSVMIter(DataIter):
    """LibSVM-format source yielding CSR data batches (reference
    ``src/io/iter_libsvm.cc``): lines of ``label idx:val idx:val ...``.
    ``data_libsvm`` may also carry sparse labels (``label_libsvm`` for a
    separate label file).  Batches pad the tail like NDArrayIter
    (``batch.pad`` rows repeated from the front)."""

    def __init__(self, data_libsvm, data_shape, batch_size,
                 label_libsvm=None, label_shape=None, data_name="data",
                 label_name="softmax_label", part_index=0, num_parts=1,
                 **kwargs):
        super().__init__(batch_size)
        from .ndarray.sparse import csr_matrix

        self._data_name = data_name
        self._label_name = label_name
        ncol = int(data_shape[-1] if isinstance(
            data_shape, (tuple, list)) else data_shape)
        vals, cols, indptr, labels = self._parse(data_libsvm, ncol)
        if label_libsvm is not None:
            lcol = int(label_shape[-1] if isinstance(
                label_shape, (tuple, list)) else (label_shape or 1))
            lv, lc, lp, _ = self._parse(label_libsvm, lcol)
            dense_lab = np.zeros((len(lp) - 1, lcol), "float32")
            for r in range(len(lp) - 1):
                dense_lab[r, lc[lp[r]:lp[r + 1]]] = lv[lp[r]:lp[r + 1]]
            labels = dense_lab.squeeze()
        n = len(indptr) - 1
        if num_parts > 1:  # sharded reading, same contract as the C iter
            per = n // num_parts
            lo, hi = part_index * per, (part_index + 1) * per \
                if part_index < num_parts - 1 else n
            sel = range(lo, hi)
            vals, cols, indptr, labels = self._take(vals, cols, indptr,
                                                    labels, sel)
            n = len(indptr) - 1
        self._vals, self._cols, self._indptr = vals, cols, indptr
        self._labels = np.asarray(labels, "float32")
        self._ncol = ncol
        self._num = n
        self._csr = csr_matrix
        self.reset()

    @staticmethod
    def _parse(path, ncol):
        vals, cols, indptr, labels = [], [], [0], []
        with open(path) as f:
            for line in f:
                parts = line.split()
                if not parts:
                    continue
                labels.append(float(parts[0]))
                for tok in parts[1:]:
                    i, v = tok.split(":")
                    cols.append(int(i))
                    vals.append(float(v))
                indptr.append(len(cols))
        return (np.asarray(vals, "float32"), np.asarray(cols, "int32"),
                np.asarray(indptr, "int64"), np.asarray(labels, "float32"))

    @staticmethod
    def _take(vals, cols, indptr, labels, rows):
        nv, nc, np_ = [], [], [0]
        for r in rows:
            nv.extend(vals[indptr[r]:indptr[r + 1]])
            nc.extend(cols[indptr[r]:indptr[r + 1]])
            np_.append(len(nc))
        return (np.asarray(nv, "float32"), np.asarray(nc, "int32"),
                np.asarray(np_, "int64"), labels[list(rows)])

    @property
    def provide_data(self):
        return [DataDesc(self._data_name, (self.batch_size, self._ncol))]

    @property
    def provide_label(self):
        lshape = (self.batch_size,) + tuple(self._labels.shape[1:])
        return [DataDesc(self._label_name, lshape)]

    def reset(self):
        self._cursor = 0

    def next(self):
        if self._cursor >= self._num:
            raise StopIteration
        rows = [(self._cursor + i) % self._num
                for i in range(self.batch_size)]
        pad = max(0, self._cursor + self.batch_size - self._num)
        vals, cols, indptr, labels = self._take(
            self._vals, self._cols, self._indptr, self._labels, rows)
        data = self._csr((vals, cols, indptr),
                         shape=(self.batch_size, self._ncol))
        from .ndarray import array

        self._cursor += self.batch_size
        return DataBatch(data=[data], label=[array(labels)], pad=pad)


class MNISTIter(NDArrayIter):
    """MNIST source (reference ``src/io/iter_mnist.cc``).  Reads the
    canonical idx-format files if present; raises otherwise (no network in
    the build environment)."""

    def __init__(self, image="train-images-idx3-ubyte",
                 label="train-labels-idx1-ubyte", batch_size=128,
                 shuffle=True, flat=False, **kwargs):
        import gzip
        import os
        import struct

        def read_idx(path):
            opener = gzip.open if path.endswith(".gz") else open
            if not os.path.exists(path) and os.path.exists(path + ".gz"):
                path, opener = path + ".gz", gzip.open
            with opener(path, "rb") as f:
                magic = struct.unpack(">I", f.read(4))[0]
                ndim = magic & 0xFF
                dims = struct.unpack(">" + "I" * ndim, f.read(4 * ndim))
                return np.frombuffer(f.read(), dtype=np.uint8).reshape(dims)

        images = read_idx(image).astype("float32") / 255.0
        labels = read_idx(label).astype("float32")
        if flat:
            images = images.reshape(images.shape[0], -1)
        else:
            images = images.reshape(images.shape[0], 1,
                                    images.shape[1], images.shape[2])
        super().__init__(images, labels, batch_size=batch_size,
                         shuffle=shuffle, **kwargs)


def ImageRecordIter(path_imgrec, data_shape, batch_size, path_imgidx=None,
                    label_width=1, shuffle=False, part_index=0, num_parts=1,
                    resize=0, rand_crop=False, rand_mirror=False,
                    mean_r=0.0, mean_g=0.0, mean_b=0.0,
                    std_r=0.0, std_g=0.0, std_b=0.0,
                    max_random_contrast=0, max_random_illumination=0,
                    preprocess_threads=4, prefetch_buffer=2,
                    data_name="data", label_name="softmax_label",
                    num_workers=None, seed=None, **kwargs):
    """RecordIO-backed image iterator (reference C iterator
    ``ImageRecordIter``, ``src/io/iter_image_recordio_2.cc:513`` + the
    default augmenter chain ``src/io/image_aug_default.cc``).

    Factory with the C iterator's parameter surface.  Two backends:

    * ``num_workers > 0`` (or ``MXNET_DATA_WORKERS``): the sharded
      deterministic data service — a :class:`DataServiceIter` over a
      picklable :class:`~mxnet_tpu.image.RecordImageLoader` with a
      multiprocess decode pool, cross-host global shuffle from ``seed``
      (``rank::nproc`` striding via ``part_index``/``num_parts``), and
      O(1) ``seek`` resume.
    * otherwise the classic :class:`~mxnet_tpu.image.ImageIter` with the
      matching augmenter list (resize -> crop -> mirror -> jitter ->
      normalize), threaded decode, and contiguous
      ``part_index``/``num_parts`` sharding.

    Either backend is wrapped in :class:`PrefetchingIter` so host-side
    batch assembly overlaps device steps.
    """
    from . import image as img_mod

    mean = None
    if mean_r or mean_g or mean_b:
        mean = np.array([mean_r, mean_g, mean_b], np.float32)
    std = None
    if std_r or std_g or std_b:
        std = np.array([std_r or 1.0, std_g or 1.0, std_b or 1.0],
                       np.float32)
    aug_list = img_mod.CreateAugmenter(
        data_shape, resize=resize, rand_crop=rand_crop,
        rand_mirror=rand_mirror, mean=mean, std=std,
        contrast=max_random_contrast, brightness=max_random_illumination)
    workers = int(num_workers if num_workers is not None
                  else get_env("MXNET_DATA_WORKERS", 0, int))
    if workers > 0:
        from . import recordio as rec_mod
        from .image import RecordImageLoader

        idx_path = path_imgidx or os.path.splitext(path_imgrec)[0] + ".idx"
        record = rec_mod.MXIndexedRecordIO(idx_path, path_imgrec, "r")
        loader = RecordImageLoader(
            data_shape, record=record, aug_list=aug_list,
            label_width=label_width, data_name=data_name,
            label_name=label_name)
        svc = DataServiceIter(
            loader, batch_size, seed=seed, shuffle=shuffle,
            num_workers=workers, rank=part_index, nproc=num_parts)
        return PrefetchingIter(svc, prefetch_depth=prefetch_buffer)
    inner = img_mod.ImageIter(
        batch_size, data_shape, label_width=label_width,
        path_imgrec=path_imgrec, path_imgidx=path_imgidx, shuffle=shuffle,
        part_index=part_index, num_parts=num_parts, aug_list=aug_list,
        data_name=data_name, label_name=label_name,
        num_threads=preprocess_threads, seed=seed, **kwargs)
    return PrefetchingIter(inner, prefetch_depth=prefetch_buffer)


def ImageDetRecordIter(path_imgrec, data_shape, batch_size,
                       max_objects=16, preprocess_threads=4,
                       prefetch_buffer=2, **kwargs):
    """Detection RecordIO iterator (reference C iterator
    ``ImageDetRecordIter``, ``src/io/iter_image_det_recordio.cc``):
    factory over :class:`mxnet_tpu.image_detection.ImageDetIter` with the
    det augmenter chain, threaded decode, and background prefetch —
    same pipeline contract as :func:`ImageRecordIter`."""
    from .image_detection import CreateDetAugmenter, ImageDetIter

    aug_kwargs = {k: kwargs.pop(k) for k in list(kwargs)
                  if k in ("resize", "rand_crop", "rand_pad",
                           "rand_mirror", "mean", "std", "brightness",
                           "contrast", "saturation", "inter_method",
                           "min_object_covered", "aspect_ratio_range",
                           "area_range", "pad_val")}
    aug_list = CreateDetAugmenter(data_shape, **aug_kwargs)
    inner = ImageDetIter(batch_size=batch_size, data_shape=data_shape,
                         path_imgrec=path_imgrec,
                         max_objects=max_objects, aug_list=aug_list,
                         num_threads=preprocess_threads, **kwargs)
    return PrefetchingIter(inner, prefetch_depth=prefetch_buffer)


# the data-service layer builds on the iterator ABC above; imported last
# to avoid a circular import, re-exported here so the data plane has one
# front door (``mxnet_tpu.io``)
from .data_service import (DataServiceIter, epoch_permutation,  # noqa: E402
                           fold_in)
