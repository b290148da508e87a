"""Pipeline telemetry: metrics registry, span tracing, stall attribution.

The measurement substrate every perf PR reports against (ROADMAP: the
BASELINE north-star is input-stall fraction). Three levels, selected with
``make_reader(telemetry=...)`` or :func:`configure`:

* ``'off'`` — every instrumentation helper returns after one int compare;
  no counters, no spans, no per-row work anywhere.
* ``'counters'`` (default) — named counters/gauges/histograms updated at
  block/batch granularity; the ``diagnostics`` surfaces become views over
  the registry; stall attribution works.
* ``'spans'`` (opt-in) — additionally records one Chrome-trace event per
  pipeline stage execution into a bounded ring, exportable with
  :func:`export_chrome_trace` and viewable in Perfetto.

The level and registries are **per-process** (worker processes receive the
config through the pool's setup args and ship snapshots/events back over the
results channel). Instrument with::

    from petastorm_tpu import observability as obs

    with obs.stage('decode', cat='worker'):       # timer + (at spans) an event
        ...
    obs.count('rows_decoded_total', n)            # block-granularity counter
    obs.gauge_set('shuffle_occupancy', size)

``stage``/``span`` must be closed on all paths — use them as context
managers; lint rule PT700 (``petastorm_tpu.analysis``) enforces this.

See ``docs/observability.md`` for the metric catalog and span taxonomy.
"""

from __future__ import annotations

import time as _time

from petastorm_tpu.observability import blackbox as _blackbox
from petastorm_tpu.observability import metrics as _metrics
from petastorm_tpu.observability import trace as _trace
from petastorm_tpu.observability.blackbox import (FlightRecorder,  # noqa: F401
                                                  format_postmortem, load_flight,
                                                  postmortem_report)
from petastorm_tpu.observability.critical_path import (critical_path,  # noqa: F401
                                                       critical_path_summary,
                                                       format_critical_path,
                                                       format_slowest_batches,
                                                       format_span_tree,
                                                       slowest_batches, span_tree,
                                                       stage_breakdown, traces_in)
from petastorm_tpu.observability.exporters import (JsonlExporter,  # noqa: F401
                                                   host_identity,
                                                   to_prometheus_text, write_prometheus)
from petastorm_tpu.observability.history import (HistoryRecorder,  # noqa: F401
                                                 detect_regression, history_windows,
                                                 load_history, window_delta,
                                                 windowed_stall_report)
from petastorm_tpu.observability.metrics import (counters_on, flatten_snapshot,  # noqa: F401
                                                 get_registry, merge_snapshots, spans_on)
from petastorm_tpu.observability.podagg import (format_pod_report,  # noqa: F401
                                                load_host_series, load_pod,
                                                pod_report)
from petastorm_tpu.observability.report import (decode_collate_share,  # noqa: F401
                                                format_stall_report, stall_report)
from petastorm_tpu.observability.trace import (TraceContext, chrome_trace,  # noqa: F401
                                               current_trace, export_chrome_trace,
                                               get_ring, instant, mint_trace,
                                               root_of, span, trace_root, use_trace)

_LEVELS = ('off', 'counters', 'spans')


class TelemetryConfig(object):
    """Picklable telemetry description, shipped into worker processes.

    :param level: 'off' | 'counters' | 'spans'
    :param trace_capacity: span ring size (events); oldest rotate out
    """

    def __init__(self, level='counters', trace_capacity=_trace.DEFAULT_TRACE_CAPACITY):
        if level not in _LEVELS:
            raise ValueError("telemetry level must be one of {}, got {!r}".format(
                _LEVELS, level))
        if trace_capacity < 1:
            raise ValueError('trace_capacity must be >= 1')
        self.level = level
        self.trace_capacity = trace_capacity

    def _key(self):
        return (self.level, self.trace_capacity)

    def __eq__(self, other):
        return type(self) is type(other) and self._key() == other._key()

    def __hash__(self):
        return hash(self._key())

    def __repr__(self):
        return 'TelemetryConfig(level={!r}, trace_capacity={})'.format(
            self.level, self.trace_capacity)


def resolve_telemetry(telemetry):
    """Normalize the ``make_reader`` kwarg: ``None`` -> None (keep the current
    process configuration), a level string -> config, a config -> itself."""
    if telemetry is None:
        return None
    if isinstance(telemetry, TelemetryConfig):
        return telemetry
    if isinstance(telemetry, str):
        return TelemetryConfig(level=telemetry)
    raise ValueError("telemetry must be None, 'off'/'counters'/'spans', or a "
                     'TelemetryConfig, got {!r}'.format(telemetry))


def configure(telemetry):
    """Apply a telemetry config (or level string) to THIS process. ``None`` is
    a no-op. Returns the effective :class:`TelemetryConfig`."""
    config = resolve_telemetry(telemetry)
    if config is not None:
        _metrics.set_level(config.level)
        _trace.get_ring().set_capacity(config.trace_capacity)
    return current_config()


def current_config():
    """The process's effective config (what a Reader ships to its workers when
    no explicit ``telemetry=`` was given)."""
    return TelemetryConfig(level=_metrics.level_name(),
                           trace_capacity=_trace.get_ring().capacity)


# -- instrumentation helpers (each starts with the one-int-compare fast path) --

class _StageTimer(object):
    """Counter + (at spans level) trace event for one pipeline-stage
    execution. Accumulates into ``stage_<name>_s``.

    At spans level the timer participates in trace-context propagation
    exactly like :class:`petastorm_tpu.observability.trace._Span`: it stamps
    ``trace``/``span``/``parent`` from the thread's active
    :class:`TraceContext` and parents anything nested. :meth:`link` attaches
    the span to a context discovered only mid-flight (``pool_wait``)."""

    __slots__ = ('name', 'cat', 'args', '_t0', '_wall0', '_spans', '_ctx',
                 '_link', '_sid', '_pushed', '_act', '_act_prev', '_annotation')

    def __init__(self, name, cat, args, spans):
        self.name = name
        self.cat = cat
        self.args = args
        self._spans = spans
        self._link = None
        self._pushed = False

    def __enter__(self):
        # flight-recorder activity slot (docs/observability.md, "Flight
        # recorder"): one load + None compare when recording is off
        act = _blackbox._ACTIVITY
        self._act = act
        if act is not None:
            self._act_prev = act.enter(self.cat + '.' + self.name)
        if self._spans:
            self._wall0 = _time.time()
            ctx = _trace.current_trace()
            self._ctx = ctx
            if ctx is not None:
                self._sid = _trace.next_span_id()
                _trace._push_trace(_trace.TraceContext(ctx.trace, self._sid))
                self._pushed = True
            else:
                self._sid = None
        self._annotation = _trace.open_annotation(self.cat, self.name)
        self._t0 = _time.perf_counter()
        return self

    def link(self, ctx):
        """Adopt ``ctx`` as this span's parent context (no-op below spans
        level or when ``ctx`` is None)."""
        if self._spans and ctx is not None:
            self._link = ctx

    def annotate(self, **args):
        """Add args learnt mid-flight to the event (no-op below spans level;
        guard the call with :func:`spans_on` where the args cost work)."""
        if self._spans:
            self.args = dict(self.args or (), **args)

    def __exit__(self, exc_type, exc_value, tb):
        dur = _time.perf_counter() - self._t0
        _metrics.get_registry().stage_timer(self.name).record(dur)
        if self._act is not None:
            self._act.exit(self._act_prev)
        args = None
        if self._spans:
            if self._pushed:
                _trace._pop_trace()
            args = _trace.stamp_trace_args(self.args, self._link or self._ctx, self._sid)
            _trace.record_span(self.name, self.cat, self._wall0, dur, args)
        if self._annotation is not None:
            _trace.close_annotation(self._annotation, args)
        return False


def stage(name, cat='pipeline', **args):
    """Time one execution of a named pipeline stage: accumulates the
    ``stage_<name>_s``/``stage_<name>_count`` counters and, at level
    ``'spans'``, records a Chrome-trace event. While a ``jax.profiler``
    session records, it also opens the host annotation ``<cat>.<name>``
    (with ``args`` at ``'spans'``). No-op at ``'off'``. Use as a context
    manager (PT700)."""
    if not _metrics.counters_on():
        return _trace._NOOP_SPAN
    return _StageTimer(name, cat, args or None, _metrics.spans_on())


def count(name, n=1):
    """Increment a counter (no-op at level 'off')."""
    if _metrics.counters_on():
        _metrics.get_registry().counter(name).inc(n)


def add_seconds(name, seconds):
    """Accumulate a float counter (no-op at level 'off')."""
    if _metrics.counters_on():
        _metrics.get_registry().counter(name).add(seconds)


def gauge_set(name, value):
    """Set a gauge (no-op at level 'off')."""
    if _metrics.counters_on():
        _metrics.get_registry().gauge(name).set(value)


def observe(name, value, buckets=_metrics.DEFAULT_BUCKETS):
    """Observe into a histogram (no-op at level 'off')."""
    if _metrics.counters_on():
        _metrics.get_registry().histogram(name, buckets).observe(value)


def snapshot():
    """This process's structured metrics snapshot (picklable)."""
    return _metrics.get_registry().snapshot()


def drain_trace_events():
    """Drain the process span ring (worker -> main shipping)."""
    return _trace.get_ring().drain()


def absorb_trace_events(events):
    """Merge span events shipped from another process into this ring."""
    if events:
        _trace.get_ring().extend(events)


__all__ = [
    'FlightRecorder', 'HistoryRecorder',
    'JsonlExporter', 'TelemetryConfig', 'TraceContext', 'absorb_trace_events',
    'add_seconds', 'chrome_trace', 'configure', 'count', 'counters_on',
    'critical_path', 'critical_path_summary', 'current_config', 'current_trace',
    'decode_collate_share', 'detect_regression', 'drain_trace_events',
    'export_chrome_trace', 'flatten_snapshot', 'format_critical_path',
    'format_pod_report', 'format_postmortem', 'format_slowest_batches',
    'format_span_tree', 'load_flight', 'postmortem_report',
    'format_stall_report', 'gauge_set', 'get_registry', 'get_ring',
    'history_windows', 'host_identity', 'instant', 'load_history',
    'load_host_series', 'load_pod', 'merge_snapshots', 'mint_trace', 'observe',
    'pod_report', 'resolve_telemetry', 'root_of', 'slowest_batches', 'snapshot',
    'span', 'span_tree', 'spans_on', 'stage', 'stage_breakdown', 'stall_report',
    'to_prometheus_text', 'trace_root', 'traces_in', 'use_trace',
    'window_delta', 'windowed_stall_report', 'write_prometheus',
]
