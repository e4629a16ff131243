"""Async double-buffered device feed.

Reference analog: src/io/iter_prefetcher.h double-buffers HOST batches;
the reference's GPU copy then overlaps via CUDA streams inside the
engine.  XLA has no implicit H2D overlap for python-side ``device_put``
— every step in the old path paid a blocking host->HBM transfer after
``next()`` returned.  ``DeviceFeedIter`` closes that gap: a background
thread pulls host batches from any iterator and ``device_put``s them
(mesh-sharded when the consuming step is SPMD) so up to ``depth``
batches are already resident in HBM while the current step runs.
Host assembly AND the H2D transfer overlap compute; the consumer's
``next()`` returns device-committed arrays.

Wired in by default (``MXNET_DEVICE_FEED``): ``gluon.data.DataLoader``
wraps its per-epoch iterator, ``Module.fit`` wraps ``train_data``, and
``bench.py`` feeds its measured steps through one.  Works with any
source: ``DataIter`` subclasses (DataBatch items), ``DataLoader``
iterators (lists of NDArrays), or plain generators of numpy arrays.
"""
from __future__ import annotations

import contextlib
import queue
import threading
import time

from .. import ndarray as nd
from ..base import MXNetError
from ..telemetry import tracing
from .io import DataBatch, DataIter

__all__ = ["DeviceFeedIter", "as_device_batch", "batch_nbytes",
           "device_feed_enabled"]

_END = object()


class _Err:
    def __init__(self, exc):
        self.exc = exc


def _q_put(q, stop, item):
    while not stop.is_set():
        try:
            q.put(item, timeout=0.1)
            return True
        except queue.Full:
            continue
    return False


def _watch(ready, stats):
    """Stamp when each batch is on the device, off the producing path:
    ``device_put`` returns before the layout change and the copy are
    done, and a producer that waited for them itself would keep the
    copy from overlapping its next ``next(source)``.  ``ready`` gives
    ``(when the host batch was in hand, its device arrays)`` in the
    producer's order and ``None`` at its end; busy is the union of
    those intervals, since the next copy may start before this one
    ends."""
    last = 0.0
    for t1, arrays in iter(ready.get, None):
        for a in arrays:
            try:
                a.block_until_ready()
            except RuntimeError:
                pass  # consumed and donated already: it was there
        del arrays  # hold no batch while waiting for the next
        now = time.perf_counter()
        stats["producer_busy_s"] += now - max(t1, last)
        last = now


def _produce(base, q, stop, stats, sharding, device, n_shards, ctx):
    """Producer loop (module-level on purpose: it must not hold a
    reference to the DeviceFeedIter, or an abandoned iterator could
    never be garbage-collected and its finalizer never fire).  ``ctx``
    is the trace context of the thread that started the feed: the
    producer's spans parent onto it."""
    from ..resilience import faultsim
    from ..resilience.retry import retry_call

    ready = queue.SimpleQueue()
    threading.Thread(target=_watch, args=(ready, stats),
                     name="DeviceFeedIter-ready", daemon=True).start()
    bound = tracing.use(ctx) if ctx is not None \
        else contextlib.nullcontext()
    with bound:
        try:
            src = iter(base)
            # the how-manieth batch, in the consumer's count: a RunLog
            # copies the spans of its sampled ones
            n = stats["batches"]
            while not stop.is_set():
                t0 = time.perf_counter()
                with tracing.region("mx_feed_source", nth=n):
                    item = next(src, _END)
                if item is _END:
                    _q_put(q, stop, _END)
                    return
                t1 = time.perf_counter()
                stats["source_wait_s"] += t1 - t0
                attempts = [0]

                def put_batch(it=item):
                    # feed.h2d: the injection point for transfer faults;
                    # transient failures (injected or OS-level) retry with
                    # bounded backoff instead of killing the epoch
                    attempts[0] += 1
                    faultsim.inject("feed.h2d")
                    return as_device_batch(it, sharding, device, n_shards)

                with tracing.region("mx_feed_h2d", nth=n) as r:
                    out = retry_call(
                        put_batch,
                        retry_on=(faultsim.FaultInjected, OSError),
                        attempts=3, base_delay=0.02, max_delay=0.5)
                    nbytes = batch_nbytes(out)
                    meta = {"bytes": nbytes}
                    if attempts[0] > 1:
                        meta["attempt"] = attempts[0]
                    r.set_metadata(**meta)
                ready.put((t1, [a for a in _arrays(out)
                                if hasattr(a, "block_until_ready")]))
                stats["h2d_bytes"] += nbytes
                n += 1
                if not _q_put(q, stop, out):
                    return
        except BaseException as e:  # noqa: BLE001 — surfaced on next()
            _q_put(q, stop, _Err(e))
        finally:
            ready.put(None)


def device_feed_enabled():
    from ..config import get_env

    return bool(get_env("MXNET_DEVICE_FEED"))


def _batch_sharding(mesh, data_axis):
    if mesh is None:
        return None
    from jax.sharding import NamedSharding, PartitionSpec as P

    return NamedSharding(mesh, P(data_axis))


def _put_array(v, sharding, device, n_shards):
    import jax

    if sharding is not None and getattr(v, "ndim", 0) >= 1 \
            and v.shape[0] % n_shards == 0:
        return jax.device_put(v, sharding)
    if device is not None:
        return jax.device_put(v, device)
    return jax.device_put(v)


def as_device_batch(item, sharding=None, device=None, n_shards=1):
    """Recursively move a batch's arrays to the device: NDArrays stay
    NDArrays (committed), numpy arrays become committed NDArrays, raw
    jax arrays stay raw; DataBatch structure/pad/index are preserved."""
    import numpy as onp

    import jax

    if item is None:
        return None
    if isinstance(item, DataBatch):
        return DataBatch(
            data=as_device_batch(item.data, sharding, device, n_shards),
            label=as_device_batch(item.label, sharding, device,
                                  n_shards),
            pad=item.pad, index=item.index, bucket_key=item.bucket_key,
            provide_data=item.provide_data,
            provide_label=item.provide_label)
    if isinstance(item, (list, tuple)):
        mapped = [as_device_batch(x, sharding, device, n_shards)
                  for x in item]
        return type(item)(mapped) if isinstance(item, tuple) else mapped
    if isinstance(item, nd.NDArray):
        return nd.NDArray(_put_array(item._data, sharding, device,
                                     n_shards))
    if isinstance(item, onp.ndarray):
        return nd.NDArray(_put_array(item, sharding, device, n_shards))
    if isinstance(item, jax.Array):
        return _put_array(item, sharding, device, n_shards)
    return item


def _arrays(item):
    """The arrays of a batch, unwrapped (an NDArray's ``_data``)."""
    if item is None:
        return
    if isinstance(item, DataBatch):
        yield from _arrays(item.data)
        yield from _arrays(item.label)
    elif isinstance(item, (list, tuple)):
        for x in item:
            yield from _arrays(x)
    else:
        yield item._data if isinstance(item, nd.NDArray) else item


def batch_nbytes(item):
    """Total array bytes in a (device) batch — the per-batch H2D
    transfer volume ``stats()['h2d_bytes']`` accumulates and telemetry
    step records report as deltas."""
    return sum(int(getattr(a, "nbytes", None) or 0)
               for a in _arrays(item))


class DeviceFeedIter(DataIter):
    """Wrap any batch iterator; keep ``depth`` batches device-resident
    ahead of the consumer (mesh-sharded over ``data_axis`` when a mesh
    is given).

    ``reset()`` restarts the producer and resets the wrapped source, so
    the wrapper drops into ``Module.fit``'s epoch loop in place of the
    raw iterator.  ``stats()`` reports, all as plain numbers that only
    grow: ``consumer_wait_s`` (``next()`` waiting for a batch),
    ``source_wait_s`` (the producer inside ``next(source)``),
    ``producer_busy_s`` (from the host batch in hand to the batch on
    the device, stamped by a watching thread a moment after it is
    there), ``depth_sum`` (batches queued at each ``next()``: mean
    depth is ``depth_sum / batches``), ``h2d_bytes``, ``batches``,
    ``epochs``.
    """

    def __init__(self, base, depth=None, mesh=None, data_axis="data",
                 device=None):
        from ..config import get_env

        super().__init__(getattr(base, "batch_size", 0))
        self._base = base
        self._depth = max(1, int(depth if depth is not None
                                 else get_env("MXNET_DEVICE_FEED_DEPTH")))
        self._sharding = _batch_sharding(mesh, data_axis)
        self._n_shards = int(mesh.devices.size) if mesh is not None else 1
        self._device = device
        self._stats = {"batches": 0, "epochs": 0,
                       "consumer_wait_s": 0.0, "producer_busy_s": 0.0,
                       "h2d_bytes": 0, "source_wait_s": 0.0,
                       "depth_sum": 0}
        self._thread = None
        self._done = False
        self._closed = False
        self._start()

    # --------------------------------------------------------- producer
    def _start(self):
        import weakref

        self._stop = threading.Event()
        self._q = queue.Queue(maxsize=self._depth)
        # the thread closes over the queue/event/stats — NOT self — so
        # an abandoned wrapper (consumer broke out of the epoch and
        # dropped it) stays collectible; the GC finalizer then releases
        # the producer instead of leaking a thread + `depth` device
        # batches for the life of the process
        self._thread = threading.Thread(
            target=_produce,
            args=(self._base, self._q, self._stop, self._stats,
                  self._sharding, self._device, self._n_shards,
                  tracing.current_context()),
            name="DeviceFeedIter", daemon=True)
        self._finalizer = weakref.finalize(self, self._stop.set)
        self._thread.start()

    def _halt(self, timeout=None):
        """Stop the producer with a BOUNDED join: a wedged producer
        (stuck inside a native H2D call) is abandoned as a daemon
        after the timeout instead of hanging fit teardown — the stop
        event keeps it from ever touching the queue again.  Returns
        True when the thread actually exited."""
        if self._thread is None:
            return True
        self._stop.set()
        while True:  # unblock a producer stuck on a full queue
            try:
                self._q.get_nowait()
            except queue.Empty:
                break
        if timeout is None:
            from ..config import get_env

            timeout = float(get_env("MXNET_FEED_JOIN_TIMEOUT_SEC"))
        t = self._thread
        t.join(timeout=timeout)
        joined = not t.is_alive()
        if not joined:
            import logging

            logging.warning(
                "DeviceFeedIter: producer did not join within %.1fs; "
                "abandoning daemon thread", timeout)
        self._thread = None
        return joined

    # --------------------------------------------------------- consumer
    def __iter__(self):
        return self

    def __len__(self):
        # generator bases have no length; raise the TypeError len()
        # itself would, so try/except-len consumers (tqdm et al.) fall
        # back exactly as they would on the unwrapped iterator
        if getattr(type(self._base), "__len__", None) is None:
            raise TypeError(
                "DeviceFeedIter: wrapped source has no length")
        return len(self._base)

    def next(self):
        if self._done:  # exhausted: don't block on a dead producer
            raise StopIteration
        t0 = time.perf_counter()
        depth = self._q.qsize()
        with tracing.region("mx_feed_wait", nth=self._stats["batches"],
                            depth=depth):
            while True:
                try:
                    item = self._q.get(timeout=0.5)
                    break
                except queue.Empty:
                    if not self._thread.is_alive():
                        raise MXNetError(
                            "DeviceFeedIter: producer thread died "
                            "without a sentinel")
        self._stats["consumer_wait_s"] += time.perf_counter() - t0
        if item is _END:
            self._done = True
            raise StopIteration
        if isinstance(item, _Err):
            self._done = True
            raise item.exc
        self._stats["batches"] += 1
        self._stats["depth_sum"] += depth
        return item

    def reset(self):
        self._halt()
        if hasattr(self._base, "reset"):
            self._base.reset()
        self._stats["epochs"] += 1
        self._done = False
        self._closed = False
        self._start()

    def close(self):
        """Stop the producer WITHOUT touching the wrapped source.  An
        owner that wrapped someone else's iterator (Module.fit) must
        close before handing the source back — a live producer keeps
        consuming from it and would race the next consumer.

        Idempotent, and the producer join is bounded
        (MXNET_FEED_JOIN_TIMEOUT_SEC) so a preemption drain can never
        hang in teardown; after close(), next() raises StopIteration
        until reset() revives the wrapper."""
        if self._closed:
            return
        self._closed = True
        self._done = True
        self._halt()

    @property
    def base(self):
        return self._base

    def stats(self):
        return dict(self._stats)

    # ------------------------------------------------- passthrough meta
    @property
    def provide_data(self):
        return getattr(self._base, "provide_data", None)

    @property
    def provide_label(self):
        return getattr(self._base, "provide_label", None)
