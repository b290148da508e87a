"""Thread pool: N daemon worker threads with a bounded results queue.

Parity: /root/reference/petastorm/workers_pool/thread_pool.py (worker exceptions
forwarded through the results queue and re-raised in the consumer :68-73,169-172;
per-item completion sentinel :63; stop-aware blocking put :200-214; optional
per-thread cProfile :41-49,190-198; ``diagnostics`` :219-221).

Threads are the right default on the TPU host: the hot work (Parquet decode,
image decode) happens in Arrow/OpenCV C++ which releases the GIL.

Item failures follow the pool-independent ``on_error``/``max_item_retries``
policy (``workers/supervision.py``): 'raise' forwards the first error to the
consumer (the historical behavior), 'retry' re-enqueues the item up to the
budget, 'skip' quarantines it after the budget so the epoch completes.
Threads cannot die the way processes can, so there is no heartbeat/respawn
machinery here — an exception IS the totality of a thread worker's failure
modes.
"""

from __future__ import annotations

import logging
import os
import pstats
import queue
import sys
import threading

from petastorm_tpu import faults, observability as obs
from petastorm_tpu.errors import EmptyResultError, WorkerTerminationRequested
from petastorm_tpu.native import image_codec
from petastorm_tpu.observability import blackbox
# in-process pools speak the same canonical message-kind vocabulary as the
# wire protocol (workers/protocol.py): results-queue records are
# (kind, seq, payload, dispatch_id, trace_ctx) tuples, dispatch ids are
# allocated by the shared monotonic allocator, and PT801 rejects local kind
# definitions. The trace_ctx slot carries the item's TraceContext on MSG_DATA
# — context rides the existing record, never an extra message
from petastorm_tpu.workers.protocol import MSG_DATA, MSG_DONE, MSG_ERROR, DispatchIds
from petastorm_tpu.workers.supervision import (ErrorPolicy, attach_remote_context,
                                               format_exception_tb, quarantine_record)

logger = logging.getLogger(__name__)

DEFAULT_RESULTS_QUEUE_SIZE = 50

#: task-queue sentinel consumed by exactly one worker thread, which then
#: exits its loop (the retire half of the autotuner's worker knob)
_RETIRE = object()


class ThreadPool(object):
    def __init__(self, workers_count, results_queue_size=DEFAULT_RESULTS_QUEUE_SIZE,
                 profiling_enabled=False, on_error='raise', max_item_retries=None,
                 protocol_monitor=None):
        self._workers_count = workers_count
        self._results_queue = queue.Queue(maxsize=results_queue_size)
        self._profiling_enabled = profiling_enabled
        self._profiles = []
        self._task_queue = queue.Queue()
        self._stop_event = threading.Event()
        self._threads = []
        self._ventilator = None
        self._ventilated_items = 0
        self._completed_items = 0
        self._items_requeued = 0
        self._quarantined = []
        self._policy = (on_error if isinstance(on_error, ErrorPolicy)
                        else ErrorPolicy(on_error, **({} if max_item_retries is None
                                                      else {'max_item_retries': max_item_retries})))
        self._counter_lock = threading.Lock()
        self._next_worker_id = workers_count  # ids for runtime-grown slots
        self._dispatch_ids = DispatchIds()
        self._tls = threading.local()  # per-worker-thread current item seq
        # opt-in protocol conformance monitor (docs/protocol.md; lazy import so
        # the default path never loads the analysis stack)
        self.protocol_monitor = None
        if protocol_monitor or (protocol_monitor is None and
                                os.environ.get('PSTPU_PROTOCOL_MONITOR', '') not in ('', '0')):
            from petastorm_tpu.analysis.protocol.monitor import monitor_from_env
            self.protocol_monitor = monitor_from_env(protocol_monitor, 'thread-pool')
        # checkpoint plumbing: seq of the payload last returned by get_results,
        # and an optional callback fired when an item's completion sentinel is
        # consumed (used by results-queue readers to mark empty items delivered)
        self.last_result_seq = None
        self.done_callback = None
        # trace linkage: virtual-root TraceContext of the item whose payload
        # get_results last returned (None below spans level)
        self.last_result_trace = None

    @property
    def workers_count(self):
        return self._workers_count

    def start(self, worker_class, worker_setup_args=None, ventilator=None):
        if self._threads:
            raise RuntimeError('Pool already started')
        # flight recorder (docs/observability.md): threads share the consumer
        # process, so one recorder covers pool + consumer
        flight = blackbox.maybe_enable('consumer')
        if flight is not None:
            flight.register_lock('thread_pool.counter_lock', self._counter_lock)
            flight.watch('pool_completed', lambda: self._completed_items)
        # kept for runtime slot growth (add_worker_slot spawns identical workers)
        self._worker_class = worker_class
        self._worker_setup_args = worker_setup_args
        for worker_id in range(self._workers_count):
            worker = worker_class(worker_id, self._publish, worker_setup_args)
            thread = threading.Thread(target=self._worker_loop, args=(worker,), daemon=True)
            thread.start()
            self._threads.append(thread)
        if ventilator is not None:
            self._ventilator = ventilator
            self._ventilator.start()

    # -- runtime slot grow/retire (the autotuner's worker knob) --------------

    def add_worker_slot(self):
        """Start one additional worker thread at runtime. Returns the new
        ``workers_count``. Safe at any point: the new worker pulls from the
        shared task queue exactly like the original ones."""
        if not self._threads:
            raise RuntimeError('Pool not started')
        with self._counter_lock:
            worker_id = self._next_worker_id
            self._next_worker_id += 1
            self._workers_count += 1
        worker = self._worker_class(worker_id, self._publish, self._worker_setup_args)
        thread = threading.Thread(target=self._worker_loop, args=(worker,), daemon=True)
        thread.start()
        self._threads.append(thread)
        logger.info('thread pool grew to %d workers', self._workers_count)
        return self._workers_count

    def retire_worker_slot(self):
        """Retire one worker thread at runtime (never below 1). The retire
        rides the task queue as a sentinel, so the exiting thread finishes
        its current item first — no item is ever abandoned. Returns the new
        ``workers_count``."""
        with self._counter_lock:
            if self._workers_count <= 1:
                return self._workers_count
            self._workers_count -= 1
        self._task_queue.put(_RETIRE)
        logger.info('thread pool retiring one worker (target %d)', self._workers_count)
        return self._workers_count

    def ventilate(self, *args, **kwargs):
        seq = kwargs.pop('_seq', None)
        # ventilate runs inside the ventilator's mint block, so the active
        # context here IS this item's identity; it rides the existing task
        # tuple — no extra queue traffic (the structural-overhead guard in
        # tests/test_tracing.py counts on this)
        ctx = obs.current_trace()
        with self._counter_lock:
            self._ventilated_items += 1
            d = self._dispatch_ids.next()
            if self.protocol_monitor is not None:
                # under the lock: allocation + dispatch event must be atomic
                # or concurrent ventilates report ids out of order
                self.protocol_monitor.on_dispatch(d, seq)
        self._task_queue.put((d, seq, args, kwargs, 0, ctx))

    def get_results(self):
        """Block until a result is available; raise :class:`EmptyResultError` when
        all ventilated items are processed and no more will be ventilated."""
        # the pool-wait stage timer is what the stall report decomposes the
        # loader's reader_wait_s against (docs/observability.md)
        with obs.stage('pool_wait', cat='pool') as sp:
            payload = self._get_results()
            # the item is only known once its frame arrives, so the wait span
            # joins its tree retroactively
            sp.link(self.last_result_trace)
            return payload

    def _get_results(self):
        while True:
            try:
                kind, seq, payload, d, ctx = self._results_queue.get(block=False)
            except queue.Empty:
                if self._all_done():
                    if self.protocol_monitor is not None and not self._stop_event.is_set():
                        with self._counter_lock:
                            ventilated, completed = (self._ventilated_items,
                                                     self._completed_items)
                        self.protocol_monitor.on_drained(ventilated, completed)
                    raise EmptyResultError()
                try:
                    kind, seq, payload, d, ctx = self._results_queue.get(timeout=0.05)
                except queue.Empty:
                    continue
            if kind == MSG_DATA:
                if self.protocol_monitor is not None:
                    self.protocol_monitor.on_message('data', d, live=True)
                self.last_result_seq = seq
                self.last_result_trace = obs.root_of(ctx)
                return payload
            elif kind == MSG_DONE:
                if self.protocol_monitor is not None:
                    self.protocol_monitor.on_message('done', d, live=True)
                # MSG_DONE payload is the delivered flag: quarantined/raised
                # items complete undelivered but still carry their real seq
                # for tenant-aware ventilator budget release
                self._count_completed(seq, d, delivered=bool(payload))
            elif kind == MSG_ERROR:
                if self.protocol_monitor is not None and d is not None:
                    self.protocol_monitor.on_message('error', d, live=True)
                raise payload
            else:
                # PT800-exhaustive: protocol.py declares no other in-process
                # kind; reaching this is a framing bug, never a silent drop
                raise RuntimeError('unknown results-queue kind {!r}'.format(kind))

    def _count_completed(self, seq=None, dispatch=None, delivered=True):
        with self._counter_lock:
            self._completed_items += 1
            if self.protocol_monitor is not None and dispatch is not None:
                self.protocol_monitor.on_complete(dispatch, delivered=delivered)
        if self._ventilator is not None:
            self._ventilator.processed_item(seq)
        if delivered and seq is not None and self.done_callback is not None:
            self.done_callback(seq)

    def _all_done(self):
        # completed() MUST be read before the counters: once it is true the
        # ventilated count is final, so a subsequent counter read cannot be
        # stale. The reverse order is a termination race — a whole epoch can
        # ventilate between a counters read of (0, 0) and completed()
        # flipping true, and the reader gives up with every item in flight
        # (found by the schedule explorer, docs/analysis.md).
        if self._ventilator is not None and not self._ventilator.completed():
            return False
        with self._counter_lock:
            outstanding = self._ventilated_items > self._completed_items
        if outstanding or not self._results_queue.empty():
            return False
        return True

    def stop(self):
        if self._ventilator is not None:
            self._ventilator.stop()
        self._stop_event.set()

    def join(self):
        if not self._stop_event.is_set():
            raise RuntimeError('join() must be called after stop()')
        # drain the results queue so workers blocked on a full queue can exit
        for thread in self._threads:
            while thread.is_alive():
                try:
                    while True:
                        self._results_queue.get(block=False)
                except queue.Empty:
                    pass
                thread.join(timeout=0.05)
        self._threads = []
        if self._profiling_enabled and self._profiles:
            stats = pstats.Stats(*self._profiles)
            stats.sort_stats('cumulative').print_stats()

    @property
    def quarantined_items(self):
        """Structured records of quarantined items (``on_error='skip'``)."""
        with self._counter_lock:
            return list(self._quarantined)

    @property
    def diagnostics(self):
        """The unified pool diagnostics schema (docs/observability.md): every
        pool type reports the same keys and units. ``worker_restarts`` is
        always 0 here — threads fail by exception, never by death."""
        with self._counter_lock:
            ventilated = self._ventilated_items
            completed = self._completed_items
            requeued = self._items_requeued
            quarantined = len(self._quarantined)
        out = {'workers_count': self._workers_count,
               'items_ventilated': ventilated,
               'items_completed': completed,
               'items_in_flight': ventilated - completed,
               'results_queue_depth': self._results_queue.qsize(),
               'worker_restarts': 0,
               'items_requeued': requeued,
               'items_quarantined': quarantined}
        # the lifetime_* family is process-global (chunkstore mirrors, serve
        # blobs): surfaced by every pool type for one uniform schema
        from petastorm_tpu.native.lifetime import registry as lifetime_registry
        out.update(lifetime_registry().counters())
        return out

    def telemetry_snapshots(self):
        """Worker metrics already live in this process's registry."""
        return []

    @property
    def results_qsize(self):
        return self._results_queue.qsize()

    # -- worker side --------------------------------------------------------

    def _publish(self, data):
        self._tls.published = True
        self._stop_aware_put((MSG_DATA, getattr(self._tls, 'seq', None), data,
                              getattr(self._tls, 'dispatch', None),
                              getattr(self._tls, 'trace', None)))

    def _stop_aware_put(self, item):
        """Bounded put that aborts when the pool is stopping, so workers never
        deadlock against a full results queue (reference thread_pool.py:200-214)."""
        while not self._stop_event.is_set():
            try:
                self._results_queue.put(item, timeout=0.05)
                return
            except queue.Full:
                continue
        raise WorkerTerminationRequested()

    def _handle_item_failure(self, worker, d, seq, args, kwargs, attempts, ctx):
        """Apply the on_error policy to one failed item, on the worker thread.
        ``attempts`` counts this failure. May raise WorkerTerminationRequested
        (propagated by the loop)."""
        exc = sys.exc_info()[1]
        if getattr(self._tls, 'published', False) and self._policy.on_error != 'raise':
            # the item already published into the results queue — requeueing
            # would run it (and its publishes) again, delivering rows twice;
            # it completes delivered instead, like a crash after publish on
            # the process pool (the protocol model checker's
            # requeue_published counterexample)
            logger.warning('Worker %d failed on item seq=%s AFTER publishing; '
                           'completing the item rather than re-running it: %s',
                           worker.worker_id, seq, exc)
            self._stop_aware_put((MSG_DONE, seq, True, d, None))
            return
        if self._policy.should_retry_error(attempts):
            logger.warning('Worker %d failed on item seq=%s (attempt %d/%d); requeueing: %s',
                           worker.worker_id, seq, attempts,
                           self._policy.max_item_retries + 1, exc)
            with self._counter_lock:
                self._items_requeued += 1
                nd = self._dispatch_ids.next()
                if self.protocol_monitor is not None:
                    self.protocol_monitor.on_requeue(d, nd)
            obs.count('items_requeued')
            # the retry keeps the original TraceContext: it is the same item,
            # and its (eventual) spans must land in the same tree
            self._task_queue.put((nd, seq, args, kwargs, attempts, ctx))
            return
        if self._policy.quarantines():
            record = quarantine_record(seq, attempts, 'error', error=exc,
                                       tb=format_exception_tb(exc),
                                       worker_id=worker.worker_id,
                                       item={'args': args, 'kwargs': kwargs})
            with self._counter_lock:
                self._quarantined.append(record)
            obs.count('items_quarantined')
            logger.error('Quarantining item seq=%s after %d failed attempts: %s',
                         seq, attempts, record['error'])
            # undelivered completion sentinel: the item counts complete for
            # epoch/flow-control/tenant-budget accounting but is never marked
            # delivered (the delivered flag, not a dropped seq, encodes that)
            self._stop_aware_put((MSG_DONE, seq, False, d, None))
            return
        logger.exception('Worker %d failed processing an item', worker.worker_id)
        attach_remote_context(exc, format_exception_tb(exc),
                              worker_id=worker.worker_id, seq=seq)
        self._stop_aware_put((MSG_ERROR, None, exc, d, None))
        # undelivered sentinel: flow control counts the item but it is
        # NOT marked delivered — a checkpoint will re-read it
        self._stop_aware_put((MSG_DONE, seq, False, d, None))

    def _worker_loop(self, worker):
        profiler = None
        if self._profiling_enabled:
            import cProfile
            profiler = cProfile.Profile()
        try:
            while not self._stop_event.is_set():
                try:
                    task = self._task_queue.get(timeout=0.05)
                except queue.Empty:
                    continue
                if task is _RETIRE:
                    return  # deliberate slot retire (worker.shutdown in finally)
                d, seq, args, kwargs, attempts, ctx = task
                # the pool's workers decode at once: each takes its share of
                # the native image-decode budget (read per item, so a resized
                # pool re-divides it), as the process pool does at bootstrap
                image_codec.set_thread_share(
                    max(1, image_codec._default_threads() // self._workers_count))
                self._tls.seq = seq
                self._tls.dispatch = d
                self._tls.published = False
                self._tls.trace = ctx
                try:
                    if profiler is not None:
                        profiler.enable()
                    try:
                        faults.on_item(kwargs)
                        # worker stages (read/decode/transform) open under the
                        # item's context and land in its span tree
                        with obs.use_trace(ctx):
                            worker.process(*args, **kwargs)
                    finally:
                        if profiler is not None:
                            profiler.disable()
                    self._stop_aware_put((MSG_DONE, seq, True, d, None))
                except WorkerTerminationRequested:
                    return
                except Exception:  # noqa: BLE001 - routed through the error policy
                    try:
                        self._handle_item_failure(worker, d, seq, args, kwargs,
                                                  attempts + 1, ctx)
                    except WorkerTerminationRequested:
                        return
        finally:
            if profiler is not None:
                self._profiles.append(pstats.Stats(profiler))
            worker.shutdown()
