"""Device infeed: double-buffered host->device staging.

The BASELINE metric is input-stall % / TPU duty cycle: the device must never
wait for the host. ``prefetch_to_device`` keeps ``size`` batches in flight —
``jax.device_put`` is asynchronous, so transfer of batch N+1 overlaps compute
on batch N (the classic double-buffering at size=2).

Replaces the reference's ``tf.data`` prefetch / torch pin_memory+workers combo.
"""

from __future__ import annotations

from collections import deque

import numpy as np

from petastorm_tpu import observability as obs

#: numpy dtype kinds that can live on device; everything else (strings, objects,
#: datetimes) stays host-side numpy
JAX_COMPATIBLE_KINDS = ('b', 'i', 'u', 'f', 'c')


def stage_batch(batch, target):
    """Recursively move numeric arrays of a (possibly nested) batch dict onto
    ``target`` — a ``jax.Device`` (device_put) or a ``jax.sharding.Sharding``
    (global array assembled from this process's local shard). The single
    canonical host->device staging routine, shared by :class:`JaxDataLoader`,
    :func:`prefetch_to_device`, and ``parallel.make_global_batch``."""
    import jax
    from jax.sharding import Sharding

    def put(x):
        if isinstance(x, dict):
            return {k: put(v) for k, v in x.items()}
        if isinstance(x, np.ndarray) and x.dtype.kind in JAX_COMPATIBLE_KINDS:
            if isinstance(target, Sharding):
                global_shape = (x.shape[0] * jax.process_count(),) + x.shape[1:]
                return jax.make_array_from_process_local_data(target, x, global_shape)
            return jax.device_put(x, target)
        return x

    # per-batch stage timer: device_put is async, so this measures the HOST
    # cost of staging (buffer donation + transfer enqueue), the part that can
    # stall the input pipeline
    with obs.stage('infeed', cat='infeed'):
        return put(batch)


def prefetch_to_device(iterator, target=None, size=2, background=True):
    """Yield batches from ``iterator`` staged onto ``target`` (a device or a
    ``Sharding``; default: the default device), keeping ``size`` transfers in
    flight ahead of the consumer.

    ``background=True`` (default) pulls + stages on a dedicated thread, so the
    loader's batch assembly and the host-side cost of ``device_put`` overlap
    with whatever the consumer thread does between ``next()`` calls (dispatching
    the train step) — on a multi-core host the consumer's wait collapses to a
    queue pop when the pipeline keeps up. ``background=False`` keeps the
    original synchronous refill (deterministic single-thread execution, e.g.
    for profiling the pipeline itself).

    Checkpointing: ``JaxDataLoader.state_dict()`` is safe to call while this
    prefetcher is pumping (the loader serializes batch production against
    snapshots), but batches already staged into the prefetch queue count as
    delivered — a resume continues AFTER them, so a checkpoint taken mid-step
    skips up to ``size`` in-flight batches. Checkpoint at step boundaries with
    the queue drained (or use ``background=False, size=1``) for exact resume.

    :param iterator: iterable of batch dicts (possibly nested, e.g. NGram)
    :param target: ``jax.Device`` | ``jax.sharding.Sharding`` | None
    :param size: prefetch depth; 2 = double buffering
    """
    import jax

    if target is None:
        target = jax.devices()[0]
    if size < 1:
        raise ValueError('size must be >= 1')

    # ``infeed_wait`` times the consumer's wait for its next batch inside
    # next(): the queue take, or on the synchronous path the refill that
    # stages before the yield
    if not background:
        queue = deque()
        it = iter(iterator)
        exhausted = False
        try:
            while True:
                with obs.stage('infeed_wait', cat='infeed'):
                    while not exhausted and len(queue) < size:
                        try:
                            batch = next(it)
                        except StopIteration:
                            exhausted = True
                            break
                        # causal tracing: when fed a JaxDataLoader (not a bare
                        # generator) the infeed span joins the batch's tree
                        with obs.use_trace(getattr(iterator, 'last_trace', None)):
                            queue.append(stage_batch(batch, target))
                if not queue:
                    return
                yield queue.popleft()
        finally:
            queue.clear()
        return

    import queue as queue_mod
    import threading

    q = queue_mod.Queue(maxsize=size)
    stop = threading.Event()

    class _Final(object):  # private sentinel: no user batch can be this type
        def __init__(self, exc=None):
            self.exc = exc

    def _pump():
        try:
            for batch in iterator:
                # link the staging span to the batch's trace (loader inputs
                # carry last_trace; plain iterators stage unlinked)
                with obs.use_trace(getattr(iterator, 'last_trace', None)):
                    staged = stage_batch(batch, target)
                while not stop.is_set():
                    try:
                        q.put(staged, timeout=0.1)
                        break
                    except queue_mod.Full:
                        continue
                if stop.is_set():
                    return
            _put_final(_Final())
        except BaseException as exc:  # noqa: BLE001 - re-raised on the consumer thread
            _put_final(_Final(exc))

    def _put_final(item):
        while not stop.is_set():
            try:
                q.put(item, timeout=0.1)
                return
            except queue_mod.Full:
                continue

    thread = threading.Thread(target=_pump, daemon=True, name='pstpu-prefetch')
    thread.start()
    try:
        while True:
            with obs.stage('infeed_wait', cat='infeed'):
                item = q.get()
            if isinstance(item, _Final):
                if item.exc is not None:
                    raise item.exc
                return
            yield item
    finally:
        stop.set()
        thread.join(timeout=5)
