"""JaxDataLoader: reader rows -> fixed-size batches of (sharded) jax Arrays.

Functional parity with the reference's ``pytorch.DataLoader`` (pytorch.py:94-215):
dtype sanitization, client-side shuffling buffer (row-wise transposition of
batched readers' columnar output, :163-175), fixed-``batch_size`` accumulation,
drain-then-final-batch on exhaustion (:182-192), context-manager stop (:209-215).

TPU-first differences:
  * static shapes by default (``drop_last=True``): XLA recompiles on shape
    change, so ragged final batches are dropped unless asked for;
  * output is a dict of numpy arrays, optionally converted to ``jax.Array``s
    (single device or a ``Sharding``) — non-numeric columns stay numpy;
  * NGram windows batch time-major: offset -> field -> ``[B, ...]`` arrays.
"""

from __future__ import annotations

import logging
import threading
import time
from decimal import Decimal

import numpy as np

from petastorm_tpu import observability as obs
from petastorm_tpu.errors import PetastormTpuError
from petastorm_tpu.observability import blackbox
from petastorm_tpu.jax.infeed import stage_batch
from petastorm_tpu.shuffling_buffer import default_min_after, make_shuffling_buffer_factory

logger = logging.getLogger(__name__)


def _sanitize_value(value, field_name):
    """numpy-ify one row value; Decimal -> float64 (reference pytorch.py:36-66
    promotes torch-hostile dtypes similarly)."""
    if isinstance(value, Decimal):
        return np.float64(value)
    if isinstance(value, np.datetime64):
        return value.astype('datetime64[ns]').astype(np.int64)  # ns ticks
    return value


def collate_rows(rows, field_names=None):
    """Stack a list of row dicts/namedtuples into a dict of [B, ...] arrays.

    Fields with non-uniform shapes raise with guidance (pad/crop in a
    TransformSpec); string/object fields become object arrays (host-only).
    """
    if not rows:
        raise PetastormTpuError('Cannot collate an empty batch')
    # per-row normalization: a batch may mix namedtuples with plain dicts
    # (e.g. checkpoint-restored buffer rows next to freshly-read rows)
    rows = [r._asdict() if hasattr(r, '_asdict') else r for r in rows]
    names = field_names or list(rows[0].keys())
    batch = {}
    for name in names:
        values = [_sanitize_value(r[name], name) for r in rows]
        v0 = values[0]
        if v0 is None or isinstance(v0, (str, bytes)):
            arr = np.empty(len(values), dtype=object)
            arr[:] = values
            batch[name] = arr
            continue
        try:
            batch[name] = np.stack(values)
        except ValueError:
            shapes = {np.shape(v) for v in values}
            if len(shapes) > 1:
                raise PetastormTpuError(
                    'Field {!r} has non-uniform shapes {} within a batch. For '
                    'variable-length sequences, pass collate_spec=CollateSpec('
                    '{{{!r}: PadSpec(...)}}) for per-batch ragged padding '
                    '(petastorm_tpu.sequence, docs/sequence.md); otherwise use a '
                    'TransformSpec to crop/pad to a fixed shape, or exclude the '
                    'field via schema_fields.'.format(name, sorted(shapes), name))
            raise
    return batch


def _sanitize_batch_columns(batch):
    """Column-at-a-time dtype sanitization for the columnar fast path — the
    batch analog of :func:`_sanitize_value`: datetime columns -> int64 ns
    ticks, Decimal object columns -> float64. ``None`` cells (nullable fields)
    are preserved, exactly as the row path's per-value sanitizer preserves
    them — columns containing nulls stay object-typed and host-side."""
    for name in batch:
        col = batch[name]
        if not isinstance(col, np.ndarray):
            continue
        if col.dtype.kind == 'M':
            batch[name] = col.astype('datetime64[ns]').astype(np.int64)
        elif col.dtype == object and col.size:
            v0 = next((v for v in col if v is not None), None)
            has_none = any(v is None for v in col)
            if isinstance(v0, Decimal):
                converted = [None if v is None else np.float64(v) for v in col]
            elif isinstance(v0, np.datetime64):
                converted = [None if v is None
                             else v.astype('datetime64[ns]').astype(np.int64)
                             for v in col]
            else:
                continue
            if has_none:
                out = np.empty(len(converted), dtype=object)
                out[:] = converted
                batch[name] = out
            else:
                batch[name] = np.array(converted)
    return batch


def _flatten_ngram_block(nested):
    """Nested window block {offset: {field: col}} -> flat {(offset, field): col}
    so the columnar buffers (which only see dicts of equal-length columns) can
    shuffle/slice windows like any other rows."""
    return {(off, name): col for off, fields in nested.items()
            for name, col in fields.items()}


def _unflatten_ngram_batch(flat):
    out = {}
    for (off, name), col in flat.items():
        out.setdefault(off, {})[name] = col
    return out


def _rows_from_columnar_batch(batch_namedtuple):
    """Transpose a batched reader's columnar output into row dicts
    (reference pytorch.py:163-175)."""
    d = batch_namedtuple._asdict()
    n = len(next(iter(d.values())))
    return [{k: v[i] for k, v in d.items()} for i in range(n)]


def _to_plain_row(row):
    """Checkpoint-friendly row: schema namedtuple classes are created
    dynamically and do not unpickle, so store plain dicts (collate accepts
    both). NGram windows are dicts of offset -> namedtuple."""
    if hasattr(row, '_asdict'):
        return row._asdict()
    if isinstance(row, dict):
        return {k: (v._asdict() if hasattr(v, '_asdict') else v) for k, v in row.items()}
    return row


class JaxDataLoader(object):
    """
    :param reader: a :class:`petastorm_tpu.reader.Reader` (row or batch oriented)
    :param batch_size: rows per emitted batch
    :param shuffling_queue_capacity: >0 enables a client-side
        :class:`RandomShufflingBuffer` of that capacity
    :param min_after_retrieve: decorrelation floor of the shuffling buffer
        (default capacity//2)
    :param seed: shuffling buffer RNG seed
    :param drop_last: drop the ragged final batch (default True: static shapes
        keep XLA from recompiling)
    :param to_device: ``None`` -> numpy host batches; a ``jax.Device`` -> arrays
        committed to it; a ``jax.sharding.Sharding`` -> global sharded arrays
        (multi-host: each process feeds its local shard)
    :param resume_state: dict from :meth:`state_dict`. Restores the rows that
        were buffered client-side at checkpoint time; construct the underlying
        reader with its own ``resume_state=state['reader']``.
    :param collate_spec: a :class:`petastorm_tpu.sequence.CollateSpec` —
        ragged collation for variable-length fields (docs/sequence.md): each
        batch pads the named fields to a per-batch length (``pad_to``
        rounding / ``buckets`` ladder / ``max_length`` cap), emits
        ``<field>_lengths`` companions, and tracks padding waste
        (``diagnostics['padding_waste_fraction']``). Row-oriented readers
        only; not supported with ngram windows.
    :param bucket_boundaries: with ``collate_spec``, batch by length bucket:
        rows are routed to length buckets and released only in same-bucket
        runs of ``batch_size``, so each padded batch mixes near-equal
        lengths. Deterministic and checkpoint-compatible (``seed`` drives the
        within-bucket shuffle); replaces the shuffling buffer — pass
        ``shuffling_queue_capacity=0``.
    """

    def __init__(self, reader, batch_size, shuffling_queue_capacity=0,
                 min_after_retrieve=None, seed=None, drop_last=True, to_device=None,
                 resume_state=None, collate_spec=None, bucket_boundaries=None):
        if batch_size < 1:
            raise ValueError('batch_size must be >= 1')
        self.reader = reader
        self.batch_size = batch_size
        self._drop_last = drop_last
        self._to_device = to_device
        self._ngram = getattr(reader, 'ngram', None)
        # serializes batch production against state_dict(): prefetch_to_device
        # (background=True) iterates this loader from a pump thread while a
        # checkpoint may be taken from the training thread
        self._state_lock = threading.Lock()
        # columnar fast path: readers that emit column blocks (make_batch_reader,
        # make_reader(output='columnar')) never materialize rows — batches are
        # numpy slices/gathers of whole blocks. NGram columnar readers emit
        # nested window blocks, buffered under flat (offset, field) keys.
        self._columnar = bool(reader.batched_output)
        self._columnar_ngram = self._columnar and self._ngram is not None
        # ragged collation + bucket-by-length batching (docs/sequence.md)
        self._collate_spec = collate_spec
        self._bucket_boundaries = tuple(bucket_boundaries) if bucket_boundaries else None
        self._pad_stats = {'real_tokens': 0, 'padded_tokens': 0}
        if collate_spec is not None:
            if self._columnar:
                raise ValueError(
                    "collate_spec requires a row-oriented reader (output='rows'): "
                    'ragged collation pads per-row cells, and columnar blocks are '
                    'already stacked')
            if self._ngram is not None:
                raise ValueError('collate_spec is not supported with ngram windows '
                                 '(windows collate per offset, not per ragged field)')
        if self._bucket_boundaries is not None:
            if collate_spec is None:
                raise ValueError('bucket_boundaries requires collate_spec: bucketing '
                                 "batches by the spec's length field")
            if shuffling_queue_capacity > 0:
                raise ValueError('bucket_boundaries replaces the shuffling buffer '
                                 '(seed drives the within-bucket shuffle); pass '
                                 'shuffling_queue_capacity=0')
        # shuffle knob state: _make_buffer reads these LIVE, so a runtime
        # set_shuffle_capacity (the autotuner's shuffle knob) applies to the
        # current buffer and to every buffer built for later epochs
        self._shuffle_capacity = shuffling_queue_capacity
        self._min_after_retrieve = min_after_retrieve
        self._shuffle_seed = seed
        self._buffer = None
        self._pending = []
        # diagnostics state exists from construction: the full key set is
        # emitted (as zeros) even before iteration starts, so consumers never
        # need .get guards (pre-fix, rows_emitted/reader_wait_* were absent
        # until the first __iter__)
        self._iter_start = None
        self._reader_wait_s = 0.0
        self._rows_out = 0
        # causal tracing (docs/observability.md): virtual-root TraceContext of
        # the most recent reader item folded into an emitted batch. A shuffled
        # batch mixes rows from many items; the collate/infeed spans link to
        # the LAST contributor — enough to walk one representative tree from
        # dispatch to device without per-row bookkeeping in the hot loop.
        self.last_trace = None
        if resume_state is not None:
            if not isinstance(resume_state, dict) or resume_state.get('version') != 1:
                raise ValueError('Unrecognized resume_state (expected a dict produced by '
                                 'JaxDataLoader.state_dict())')
            self._resume_rows = list(resume_state['rows'])
            self._resume_rng = resume_state.get('buffer_rng')
        else:
            self._resume_rows = None
            self._resume_rng = None
        # closed-loop autotuning (docs/autotune.md): an autotuned reader's
        # controller rebinds its evidence source to THIS loader (whose
        # diagnostics carry the consumer-side reader_wait signal) and gains
        # the shuffle-capacity knob
        tuner = getattr(reader, 'autotuner', None)
        if tuner is not None and hasattr(tuner, 'attach_loader'):
            tuner.attach_loader(self)
        # flight recorder (docs/observability.md): batches emitted are the
        # training loop's progress signal — the watchdog calls a run stalled
        # only when a stage is open AND this stops advancing
        if blackbox.maybe_enable('loader') is not None:
            blackbox.watch_progress('loader_batches', lambda: obs.get_registry()
                                    .counter('loader_batches_total').value)

    def _make_buffer(self):
        """Build the client-side buffer from the CURRENT shuffle knob values
        (one construction site for first iteration and every later epoch)."""
        capacity = self._shuffle_capacity
        if self._bucket_boundaries is not None:
            from petastorm_tpu.sequence.bucket import BucketBatchBuffer
            return BucketBatchBuffer(self._bucket_boundaries, self.batch_size,
                                     self._collate_spec.length_of,
                                     seed=self._shuffle_seed)
        if self._columnar:
            from petastorm_tpu.columnar import FifoColumnarBuffer, ShuffledColumnarBuffer
            if capacity > 0:
                floor = default_min_after(capacity, self._min_after_retrieve)
                return ShuffledColumnarBuffer(capacity, floor, self._shuffle_seed)
            return FifoColumnarBuffer()
        return make_shuffling_buffer_factory(
            capacity, self._min_after_retrieve, self._shuffle_seed,
            self.batch_size, batched_reader=self.reader.batched_output)()

    @property
    def shuffle_capacity(self):
        """The live shuffle-buffer capacity (0 = no shuffling buffer)."""
        return self._shuffle_capacity

    def set_shuffle_capacity(self, capacity):
        """Resize the client-side shuffling buffer at runtime (the autotuner's
        shuffle knob; ``docs/autotune.md``). Applies to the live buffer —
        buffered rows are kept — and to buffers built for later epochs. Only
        valid when the loader was constructed WITH a shuffling buffer
        (``shuffling_queue_capacity > 0``): switching shuffling on/off
        mid-iteration would change delivery semantics, not just performance."""
        capacity = int(capacity)
        if capacity < 2:
            raise ValueError('shuffle capacity must be >= 2 (the decorrelation '
                             'floor must stay below it)')
        if self._shuffle_capacity <= 0:
            raise RuntimeError('loader has no shuffling buffer (constructed with '
                               'shuffling_queue_capacity=0); the shuffle knob is '
                               'unavailable')
        with self._state_lock:
            self._shuffle_capacity = capacity
            # an explicit min_after_retrieve may exceed the new capacity:
            # re-derive the floor from the one shared definition
            self._min_after_retrieve = None
            buffer = self._buffer
            if buffer is not None and hasattr(buffer, 'resize'):
                buffer.resize(capacity, default_min_after(capacity))
        return capacity

    # -- iteration ----------------------------------------------------------

    def __iter__(self):
        # eager (not part of the generator body): a second iter() while rows
        # are in flight would rebind _buffer/_pending and silently drop the
        # first iterator's buffered rows from future state_dict() checkpoints.
        # Buffer creation and resume-row injection are ALSO eager — were they in
        # the generator body, two iter() calls before any next() would both pass
        # this guard, and advancing both would rebind _buffer and orphan the
        # first iterator's rows from checkpoints.
        if (self._buffer is not None and self._buffer.size) or self._pending:
            raise RuntimeError(
                'JaxDataLoader.__iter__ called again while a previous iteration still holds '
                'buffered rows; exhaust the previous iterator (or create a new loader) first.')
        buffer = self._buffer = self._make_buffer()
        self._pending = []
        if self._resume_rng is not None and hasattr(buffer, 'rng_state'):
            buffer.rng_state = self._resume_rng
        self._resume_rng = None
        if self._resume_rows:
            if self._columnar:
                from petastorm_tpu.columnar import rows_to_block
                buffer.add_block(rows_to_block(self._resume_rows))
            else:
                buffer.add_many(self._resume_rows)
        # clear even when empty: a leftover [] would permanently re-route
        # state_dict() to the (now stale) resume branch
        self._resume_rows = None
        gen = (self._iterate_columnar(buffer) if self._columnar
               else self._iterate(buffer, self._pending))
        return gen

    def _iterate_columnar(self, buffer):
        # Locking: the state lock is held only around buffer mutation + batch
        # extraction — NEVER across the blocking next(reader_it) — so a
        # state_dict() taken from another thread (background prefetch pumping
        # this loader) sees a consistent snapshot and cannot hang behind a
        # starved reader.
        #
        # Exactly ONE batch is extracted per yield: a batch leaves the buffer
        # only at the moment it is handed to the consumer. Extracting several
        # batches under the lock and yielding them lazily would park them in a
        # generator-local limbo that state_dict() cannot see — a checkpoint
        # taken then would silently lose those rows.
        self._iter_start = time.perf_counter()
        self._reader_wait_s = 0.0
        self._rows_out = 0
        bs = self.batch_size
        reader_it = iter(self.reader)
        exhausted = False
        while True:
            with self._state_lock:
                batch = None
                if not exhausted:
                    if buffer.can_emit(bs):
                        batch = self._emit_columnar(self._buffer_emit(buffer, bs))
                elif buffer.size >= bs:
                    batch = self._emit_columnar(self._buffer_emit(buffer, bs))
                elif buffer.size and not self._drop_last:
                    batch = self._emit_columnar(self._buffer_emit(buffer, buffer.size))
                else:
                    # drop_last leftovers are intentionally dropped — clear so
                    # an exhausted loader can be iterated again (multi-epoch)
                    buffer.clear()
                    return
            if batch is not None:
                yield batch
                continue
            w0 = time.perf_counter()
            try:
                item = next(reader_it)
            except StopIteration:
                self._reader_wait_s += time.perf_counter() - w0
                with self._state_lock:
                    buffer.finish()
                exhausted = True
                continue
            self._reader_wait_s += time.perf_counter() - w0
            with self._state_lock:
                # block granularity (one row group), never per row: the
                # counters-level overhead contract of the hot loop
                with obs.stage('shuffle_add', cat='loader') as sp:
                    if obs.spans_on():
                        sp.annotate(occupancy=buffer.size)
                    if self._columnar_ngram:
                        buffer.add_block(_flatten_ngram_block(item))
                    else:
                        buffer.add_block(dict(item._asdict()))
                obs.gauge_set('shuffle_buffer_occupancy', buffer.size)

    def _buffer_emit(self, buffer, count):
        """One shuffle-buffer batch extraction, timed; at spans level traced
        with its pre-emit occupancy (block granularity)."""
        with obs.stage('shuffle_emit', cat='loader') as sp:
            if obs.spans_on():
                sp.annotate(occupancy=buffer.size, rows=count)
            return buffer.emit(count)

    def _emit_columnar(self, batch):
        n = len(next(iter(batch.values()))) if batch else 0
        self._rows_out += n
        self.last_trace = getattr(self.reader, 'last_trace', None)
        with obs.stage('collate', cat='loader', rows=n) as sp:
            sp.link(self.last_trace)
            batch = _sanitize_batch_columns(batch)
            if self._columnar_ngram:
                batch = _unflatten_ngram_batch(batch)
        obs.count('loader_batches_total')
        if self._to_device is not None:
            with obs.use_trace(self.last_trace):
                batch = self._stage(batch)
        return batch

    def _iterate(self, buffer, pending):
        # One batch extracted per yield, same invariant (and for the same
        # checkpoint-correctness reason) as _iterate_columnar. The collate
        # happens under the lock BEFORE the yield: a state_dict() taken while
        # the consumer holds a batch must not count its rows as pending.
        self._iter_start = time.perf_counter()
        self._reader_wait_s = 0.0
        self._rows_out = 0
        bs = self.batch_size
        reader_it = iter(self.reader)
        exhausted = False
        while True:
            # one timer a batch around the per-row pulls, adds and draws (the
            # reader's pool_wait nests inside), never one a row
            with obs.stage('shuffle_fill', cat='loader'):
                exhausted = self._fill(buffer, pending, reader_it, exhausted)
            with self._state_lock:
                if len(pending) == bs or (pending and not self._drop_last):
                    batch = self._emit(list(pending))
                    pending.clear()
                else:
                    # drop_last leftovers are intentionally dropped — clear
                    # so an exhausted loader can be iterated again
                    pending.clear()
                    return
            yield batch

    def _fill(self, buffer, pending, reader_it, exhausted):
        """Draw rows into ``pending`` until it holds a batch or the reader is
        exhausted and the buffer drained. Returns ``exhausted``."""
        bs = self.batch_size
        while True:
            with self._state_lock:
                while buffer.can_retrieve() and len(pending) < bs:
                    pending.append(buffer.retrieve())
                if len(pending) == bs or exhausted:
                    return exhausted
            w0 = time.perf_counter()
            try:
                item = next(reader_it)
            except StopIteration:
                self._reader_wait_s += time.perf_counter() - w0
                with self._state_lock:
                    buffer.finish()
                exhausted = True
                continue
            self._reader_wait_s += time.perf_counter() - w0
            with self._state_lock:  # mutation only — never across the reader wait
                if self.reader.batched_output:
                    # occupancy at block granularity only: row-oriented readers
                    # land here once per ROW, and the hot-loop contract is no
                    # per-row telemetry work even at the counters level (the
                    # row path's gauge rides the per-batch emit instead)
                    buffer.add_many(_rows_from_columnar_batch(item))
                    obs.gauge_set('shuffle_buffer_occupancy', buffer.size)
                else:
                    buffer.add_many([item])

    # -- checkpoint ---------------------------------------------------------

    def state_dict(self):
        """Loader-level read-position checkpoint: the underlying reader's
        :meth:`Reader.state_dict` plus every row currently buffered client-side
        (shuffling buffer + partial batch), so no yielded-to-loader row is
        lost, and the shuffling buffer's RNG state, so a seeded resume
        reproduces the exact pre-checkpoint stream. Note the state embeds the
        buffered rows — with a large ``shuffling_queue_capacity`` it is
        correspondingly large. Resume with::

            reader = make_reader(url, ..., resume_state=state['reader'])
            loader = JaxDataLoader(reader, ..., resume_state=state)
        """
        with self._state_lock:
            if self._resume_rows is not None:
                # resume-constructed but not yet iterated: the restored rows/RNG
                # still await injection — re-checkpoint them, don't lose them
                rows = list(self._resume_rows)
                rng = self._resume_rng
            else:
                rows = []
                if self._buffer is not None:
                    if self._columnar:
                        rows.extend(self._buffer.snapshot_rows())
                    else:
                        rows.extend(getattr(self._buffer, '_items', []))
                rows.extend(self._pending)
                rng = getattr(self._buffer, 'rng_state', None)
            return {'version': 1,
                    'reader': self.reader.state_dict(),
                    'buffer_rng': rng,
                    'rows': [_to_plain_row(r) for r in rows]}

    def _emit(self, rows):
        self._rows_out += len(rows)
        self.last_trace = getattr(self.reader, 'last_trace', None)
        with obs.stage('collate', cat='loader', rows=len(rows)) as sp:
            sp.link(self.last_trace)
            if self._ngram is not None:
                batch = self._collate_ngram(rows)
            elif self._collate_spec is not None:
                from petastorm_tpu.sequence.collate import (collate_ragged_rows,
                                                            padding_waste_fraction)
                batch = collate_ragged_rows(rows, self._collate_spec, self._pad_stats)
                obs.gauge_set('padding_waste_fraction',
                              padding_waste_fraction(self._pad_stats))
            else:
                batch = collate_rows(rows)
        obs.count('loader_batches_total')
        if self._buffer is not None:
            obs.gauge_set('shuffle_buffer_occupancy', self._buffer.size)
        if self._to_device is not None:
            with obs.use_trace(self.last_trace):
                batch = self._stage(batch)
        return batch

    @property
    def diagnostics(self):
        """Host-side input-pipeline counters (SURVEY.md §5: the reference only
        exposes queue depths; the BASELINE metric is input-stall, so the loader
        tracks it): rows emitted, seconds blocked waiting on the reader, the
        wait fraction of wall time since iteration started, plus the underlying
        reader's diagnostics (unified pool schema + telemetry registry view).

        The loader key set is ALWAYS present — before iteration starts the
        values are zero, never absent, so consumers need no ``.get`` guards.
        Feed this dict to :func:`petastorm_tpu.observability.stall_report` to
        decompose ``reader_wait_s`` into per-stage contributions."""
        out = dict(self.reader.diagnostics)
        if self._iter_start is not None:
            elapsed = max(time.perf_counter() - self._iter_start, 1e-9)
            wait_fraction = round(self._reader_wait_s / elapsed, 4)
        else:
            wait_fraction = 0.0
        if self._collate_spec is not None:
            from petastorm_tpu.sequence.collate import padding_waste_fraction
            waste = padding_waste_fraction(self._pad_stats)
        else:
            waste = 0.0
        out.update({
            'rows_emitted': self._rows_out,
            'reader_wait_s': round(self._reader_wait_s, 4),
            'reader_wait_fraction': wait_fraction,
            'padding_waste_fraction': waste,
        })
        # zero-copy borrow accounting (docs/native.md): the loader's shuffle
        # buffer and prefetched batches are exactly the borrows that keep
        # shm-ring slots / blob maps pinned, so the live count belongs next
        # to the stall metrics. Refreshed here in case the reader's own
        # diagnostics did not carry the family (e.g. a bare facade).
        from petastorm_tpu.native.lifetime import registry as lifetime_registry
        out.update(lifetime_registry().counters())
        return out

    @property
    def quarantined_items(self):
        """Structured records of row groups quarantined under
        ``on_error='skip'`` — passthrough of
        :attr:`petastorm_tpu.reader.Reader.quarantined_items`, surfaced here
        so training loops can log data-quality incidents next to their step
        metrics (docs/robustness.md)."""
        return getattr(self.reader, 'quarantined_items', [])

    def _collate_ngram(self, windows):
        """windows: list of dicts offset -> namedtuple. Returns
        offset -> field -> [B, ...]."""
        out = {}
        for offset in windows[0]:
            out[offset] = collate_rows([w[offset] for w in windows])
        return out

    def _stage(self, batch):
        return stage_batch(batch, self._to_device)

    # -- lifecycle ----------------------------------------------------------

    def stop(self):
        # stamp the final stall attribution into the flight ring so a
        # post-mortem can report the last-known bottleneck without the
        # process's diagnostics surface (which dies with it)
        if blackbox.get_recorder() is not None:
            try:
                blackbox.record_stall(obs.stall_report(self.diagnostics))
            except Exception:  # noqa: BLE001 - teardown forensics must never mask stop()
                pass
            blackbox.unwatch_progress('loader_batches')
        self.reader.stop()

    def join(self):
        self.reader.join()

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc_value, tb):
        self.stop()
        self.join()


def stack_ngram_time_axis(ngram_batch):
    """Collapse a collated NGram batch (offset -> field -> [B, ...]) into
    field -> [B, T, ...] arrays, T being the window length in offset order.

    This is the bridge from the reader's windowed sequence readout to
    sequence-sharded training: the result can be staged with a
    ``NamedSharding(mesh, P('data', 'seq', ...))`` and consumed by
    context-parallel ops (``petastorm_tpu.ops.ring_attention``). Fields absent
    from some timesteps (NGram allows per-timestep field sets) are skipped.
    """
    offsets = sorted(ngram_batch)
    common = set(ngram_batch[offsets[0]])
    for off in offsets[1:]:
        common &= set(ngram_batch[off])
    out = {}
    for name in sorted(common):
        cols = [ngram_batch[off][name] for off in offsets]
        try:
            out[name] = np.stack(cols, axis=1)
        except ValueError:
            shapes = sorted({np.shape(c) for c in cols})
            raise PetastormTpuError(
                'NGram field {!r} has non-uniform shapes across timesteps '
                '{}: {}. Pad/crop it to a fixed shape with a TransformSpec, or '
                'collate ragged fields via petastorm_tpu.sequence '
                '(docs/sequence.md) before stacking the time axis.'.format(
                    name, offsets, shapes))
    return out


def make_jax_dataset(reader, batch_size, **loader_kwargs):
    """Generator of batches — the ``make_petastorm_dataset`` analog
    (reference tf_utils.py:348-402)."""
    loader = JaxDataLoader(reader, batch_size, **loader_kwargs)
    return iter(loader)
