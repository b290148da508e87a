"""Pipeline telemetry tests: metrics registry, cross-process aggregation,
trace ring bounding + Chrome trace schema, stall attribution, exporters, the
unified pool diagnostics schema, and the telemetry-off overhead guard."""

import json
import time

import numpy as np
import pytest

from petastorm_tpu import make_reader
from petastorm_tpu import observability as obs
from petastorm_tpu.jax.loader import JaxDataLoader
from petastorm_tpu.observability.metrics import MetricsRegistry, merge_snapshots
from petastorm_tpu.observability.trace import TraceRing


@pytest.fixture(autouse=True)
def _isolated_telemetry():
    """Telemetry state is process-global: save/restore the level and clear
    registry + ring around every test so tests neither pollute nor depend on
    each other."""
    saved = obs.current_config()
    obs.get_registry().reset()
    obs.get_ring().clear()
    yield
    obs.configure(saved)
    obs.get_registry().reset()
    obs.get_ring().clear()


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

def test_counter_gauge_histogram_basics():
    reg = MetricsRegistry()
    reg.counter('rows').inc(3)
    reg.counter('rows').inc()
    reg.counter('wait_s').add(0.25)
    reg.gauge('depth').set(7)
    reg.histogram('lat', buckets=(0.1, 1.0)).observe(0.05)
    reg.histogram('lat', buckets=(0.1, 1.0)).observe(0.5)
    reg.histogram('lat', buckets=(0.1, 1.0)).observe(5.0)
    snap = reg.snapshot()
    assert snap['counters']['rows'] == 4
    assert snap['counters']['wait_s'] == pytest.approx(0.25)
    assert snap['gauges']['depth'] == 7
    assert snap['histograms']['lat']['count'] == 3
    assert snap['histograms']['lat']['counts'] == [1, 1, 1]
    flat = obs.flatten_snapshot(snap)
    assert flat['rows'] == 4 and flat['lat_count'] == 3


def test_metric_kind_conflict_raises():
    reg = MetricsRegistry()
    reg.counter('x')
    with pytest.raises(TypeError):
        reg.gauge('x')


def test_merge_snapshots_sums_across_processes():
    a = {'counters': {'rows': 3}, 'gauges': {'occ': 2},
         'histograms': {'lat': {'bounds': [1.0], 'counts': [1, 0], 'sum': 0.5, 'count': 1}}}
    b = {'counters': {'rows': 5, 'other': 1}, 'gauges': {'occ': 4},
         'histograms': {'lat': {'bounds': [1.0], 'counts': [0, 2], 'sum': 4.0, 'count': 2}}}
    merged = merge_snapshots([a, b])
    assert merged['counters'] == {'rows': 8, 'other': 1}
    assert merged['gauges'] == {'occ': 6}
    assert merged['histograms']['lat']['counts'] == [1, 2]
    assert merged['histograms']['lat']['count'] == 3


def test_telemetry_config_resolution():
    assert obs.resolve_telemetry(None) is None
    cfg = obs.resolve_telemetry('spans')
    assert cfg.level == 'spans'
    assert obs.resolve_telemetry(cfg) is cfg
    with pytest.raises(ValueError):
        obs.resolve_telemetry('loud')
    with pytest.raises(ValueError):
        obs.TelemetryConfig(level='bogus')


# ---------------------------------------------------------------------------
# trace ring
# ---------------------------------------------------------------------------

def test_trace_ring_bounded_rotation():
    ring = TraceRing(capacity=8)
    for i in range(3 * 8):
        ring.add({'name': 'e{}'.format(i), 'ph': 'X', 'ts': i, 'dur': 1,
                  'pid': 1, 'tid': 1})
    assert len(ring) == 8
    events = ring.snapshot()
    # oldest rotated out: only the last 8 remain, in order
    assert [e['name'] for e in events] == ['e{}'.format(i) for i in range(16, 24)]
    assert ring.dropped == 16


def test_trace_ring_drain_and_absorb():
    ring = TraceRing(capacity=4)
    ring.add({'name': 'a'})
    drained = ring.drain()
    assert [e['name'] for e in drained] == ['a']
    assert len(ring) == 0
    ring.extend(drained)
    assert len(ring) == 1


def test_span_noop_below_spans_level():
    obs.configure('counters')
    with obs.span('invisible'):
        pass
    assert len(obs.get_ring()) == 0
    obs.configure('spans')
    with obs.span('visible'):
        pass
    assert [e['name'] for e in obs.get_ring().snapshot()] == ['visible']


def test_chrome_trace_export_schema(tmp_path):
    obs.configure('spans')
    with obs.stage('decode', cat='worker', rows=10):
        time.sleep(0.001)
    obs.instant('chunk_hit', cat='chunkstore')
    out = tmp_path / 'trace.json'
    n = obs.export_chrome_trace(str(out))
    assert n == 2
    doc = json.loads(out.read_text())  # loads == the Perfetto-parseable bar
    events = doc['traceEvents']
    assert len(events) == 2
    for event in events:
        assert {'ph', 'ts', 'dur', 'pid', 'tid', 'name'} <= set(event)
        assert event['ph'] == 'X'
    decode = next(e for e in events if e['name'] == 'decode')
    assert decode['dur'] >= 1000  # µs
    assert decode['args']['rows'] == 10


# ---------------------------------------------------------------------------
# end-to-end: counters through the reader/loader, per pool type
# ---------------------------------------------------------------------------

def _drain_loader(reader, batch_size=20):
    with JaxDataLoader(reader, batch_size=batch_size, drop_last=False) as loader:
        total = 0
        for batch in loader:
            first = next(iter(batch.values()))
            total += len(first)
        return total, loader.diagnostics


def test_counters_flow_thread_pool(synthetic_dataset):
    reader = make_reader(synthetic_dataset.url, schema_fields=['id'],
                         reader_pool_type='thread', workers_count=2,
                         output='columnar', telemetry='counters')
    total, diag = _drain_loader(reader)
    assert total == 100
    assert diag['worker_rows_decoded_total'] == 100
    # the id column rides the fused native pass (one stage for read+decode);
    # either attribution route must carry the worker's busy seconds
    assert (diag.get('stage_fused_decode_s', 0) > 0
            or (diag['stage_read_s'] > 0 and diag['stage_decode_s'] > 0))
    assert diag['stage_pool_wait_s'] > 0
    assert diag['stage_ventilate_count'] == diag['items_completed'] == 10
    assert diag['rows_emitted'] == 100


def test_cross_process_counter_aggregation(synthetic_dataset):
    """Worker-side stage counters recorded in SPAWNED processes must surface
    in the main process's diagnostics — they travel the results channel as
    cumulative snapshots, the same route the payloads ride."""
    reader = make_reader(synthetic_dataset.url, schema_fields=['id'],
                         reader_pool_type='process', workers_count=2,
                         output='columnar', telemetry='counters')
    try:
        total, diag = _drain_loader(reader)
    finally:
        pass  # _drain_loader's context stopped the reader already
    assert total == 100
    # these counters are only ever incremented inside the worker processes
    assert diag['worker_rows_decoded_total'] == 100
    assert (diag.get('stage_fused_decode_s', 0) > 0
            or (diag['stage_read_s'] > 0 and diag['stage_decode_s'] > 0))
    # and they arrived as per-pid snapshots, not via this process's registry
    assert obs.get_registry().snapshot()['counters'].get(
        'worker_rows_decoded_total') is None


def test_loader_diagnostics_full_keyset_before_iteration(synthetic_dataset):
    """Regression: pre-fix, rows_emitted/reader_wait_* were simply absent
    until the first __iter__, forcing .get guards on every consumer."""
    reader = make_reader(synthetic_dataset.url, schema_fields=['id'],
                         reader_pool_type='dummy', telemetry='counters')
    with JaxDataLoader(reader, batch_size=10) as loader:
        diag = loader.diagnostics
        assert diag['rows_emitted'] == 0
        assert diag['reader_wait_s'] == 0.0
        assert diag['reader_wait_fraction'] == 0.0


def test_unified_pool_diagnostics_schema():
    """Every pool type reports the same diagnostics keys and units."""
    from petastorm_tpu.workers import DummyPool, ProcessPool, ThreadPool
    expected = {'workers_count', 'items_ventilated', 'items_completed',
                'items_in_flight', 'results_queue_depth',
                'worker_restarts', 'items_requeued', 'items_quarantined',
                # process-global shared-plane borrow accounting
                # (docs/native.md): one family across every pool type
                'lifetime_live_borrows', 'lifetime_blocked_reclaims',
                'lifetime_guard_faults'}
    pools = [DummyPool(), ThreadPool(2), ProcessPool(2)]
    for pool in pools:
        # the process pool additionally reports its delivery mode
        extras = {'zero_copy'} if isinstance(pool, ProcessPool) else set()
        assert set(pool.diagnostics) == expected | extras, type(pool).__name__
        assert pool.telemetry_snapshots() == []
        assert all(isinstance(v, int) for v in pool.diagnostics.values())


# ---------------------------------------------------------------------------
# stall attribution
# ---------------------------------------------------------------------------

def test_stall_report_unit_decomposition():
    diag = {'reader_wait_s': 1.0, 'reader_wait_fraction': 0.5,
            'stage_pool_wait_s': 0.8, 'stage_read_s': 0.1,
            'stage_decode_s': 0.7, 'stage_transform_s': 0.0}
    report = obs.stall_report(diag)
    assert report['coverage'] == pytest.approx(1.0)
    # assembly = wait - pool_wait; worker split proportional to busy seconds
    assert report['stages']['consumer.assembly'] == pytest.approx(0.2)
    assert report['stages']['worker.decode'] == pytest.approx(0.8 * 0.7 / 0.8)
    assert report['bottleneck'] == 'worker.decode'
    text = obs.format_stall_report(report)
    assert 'worker.decode' in text and 'bottleneck' in text


def test_stall_report_chunk_fetch_not_double_counted():
    # chunk fetches happen INSIDE the read stage: the report must subtract
    # them from read IO, never count the same second twice
    diag = {'reader_wait_s': 1.0, 'stage_pool_wait_s': 1.0,
            'stage_read_s': 0.6, 'stage_chunk_fetch_s': 0.5,
            'stage_decode_s': 0.0}
    report = obs.stall_report(diag)
    assert report['worker_busy_s']['read_io'] == pytest.approx(0.1)
    assert report['worker_busy_s']['chunk_fetch'] == pytest.approx(0.5)
    assert report['bottleneck'] == 'worker.chunk_fetch'
    assert sum(report['stages'].values()) == pytest.approx(1.0, abs=1e-6)


def test_stall_report_unattributed_when_workers_untimed():
    report = obs.stall_report({'reader_wait_s': 1.0, 'stage_pool_wait_s': 0.9})
    assert report['stages']['pool.unattributed'] == pytest.approx(0.9)
    assert report['coverage'] == pytest.approx(1.0)


def _slow_batched_transform(batch):
    time.sleep(0.02)
    return batch


def test_stall_attribution_names_synthetic_slow_stage(synthetic_dataset):
    """A deliberately slow worker transform must dominate the measured worker
    busy time AND the report must attribute >=90% of the wait to named
    stages (the acceptance bar)."""
    from petastorm_tpu.transform import TransformSpec
    spec = TransformSpec(_slow_batched_transform, batched=True)
    reader = make_reader(synthetic_dataset.url, schema_fields=['id'],
                         reader_pool_type='thread', workers_count=1,
                         output='columnar', transform_spec=spec,
                         telemetry='counters')
    total, diag = _drain_loader(reader)
    assert total == 100
    report = obs.stall_report(diag)
    assert report['coverage'] >= 0.9
    busy = report['worker_busy_s']
    assert busy['transform'] > max(busy['read_io'], busy['decode'], busy['chunk_fetch'])
    assert report['bottleneck'] == 'worker.transform'


# ---------------------------------------------------------------------------
# telemetry off: near-zero overhead, no per-row work
# ---------------------------------------------------------------------------

def test_telemetry_off_records_nothing(synthetic_dataset):
    reader = make_reader(synthetic_dataset.url, schema_fields=['id'],
                         reader_pool_type='thread', workers_count=1,
                         output='columnar', telemetry='off')
    total, diag = _drain_loader(reader)
    assert total == 100
    snap = obs.get_registry().snapshot()
    assert snap['counters'] == {} and snap['gauges'] == {}
    assert len(obs.get_ring()) == 0
    # the loader's own wait accounting is independent of the telemetry level
    assert diag['rows_emitted'] == 100


def test_counters_level_no_per_row_calls(synthetic_dataset, monkeypatch):
    """The hot-loop contract: telemetry work happens at block/batch
    granularity. Count every observability entry point call during a full
    100-row read — the total must scale with blocks+batches (10+5 here), not
    rows."""
    calls = {'n': 0}

    def counting(fn):
        def wrapper(*a, **k):
            calls['n'] += 1
            return fn(*a, **k)
        return wrapper

    for name in ('stage', 'span', 'count', 'gauge_set', 'instant', 'observe',
                 'add_seconds'):
        monkeypatch.setattr(obs, name, counting(getattr(obs, name)))
    reader = make_reader(synthetic_dataset.url, schema_fields=['id'],
                         reader_pool_type='thread', workers_count=1,
                         output='columnar', telemetry='counters')
    total, _ = _drain_loader(reader, batch_size=20)
    assert total == 100
    # 10 blocks + 5 batches, ~11 instrumentation points each => ~110 calls of
    # block-level budget. ONE per-row call site would add >= 100 on top, so
    # 150 cleanly separates block-granularity from per-row regressions.
    assert calls['n'] <= 150, calls['n']


# ---------------------------------------------------------------------------
# exporters
# ---------------------------------------------------------------------------

def test_prometheus_exposition_format():
    reg = obs.get_registry()
    reg.counter('rows_total').inc(42)
    reg.gauge('occupancy').set(3)
    reg.histogram('wait', buckets=(0.1, 1.0)).observe(0.05)
    text = obs.to_prometheus_text()
    assert '# TYPE pstpu_rows_total counter' in text
    assert 'pstpu_rows_total 42' in text
    assert '# TYPE pstpu_occupancy gauge' in text
    assert 'pstpu_wait_bucket{le="0.1"} 1' in text
    assert 'pstpu_wait_bucket{le="+Inf"} 1' in text
    assert 'pstpu_wait_count 1' in text


def test_jsonl_exporter_flushes(tmp_path):
    obs.get_registry().counter('rows_total').inc(7)
    path = tmp_path / 'metrics.jsonl'
    with obs.JsonlExporter(str(path), interval_s=0.05):
        time.sleep(0.12)
    lines = [json.loads(line) for line in path.read_text().splitlines()]
    assert len(lines) >= 2  # at least one interval flush + the stop flush
    assert all('ts' in rec and rec['metrics']['rows_total'] == 7 for rec in lines)


def test_diagnose_cli_smoke(synthetic_dataset, tmp_path, capsys):
    from petastorm_tpu.observability.diagnose import main as diagnose_main
    trace = tmp_path / 'diag_trace.json'
    rc = diagnose_main([synthetic_dataset.url, '--batches', '3', '--batch-size', '10',
                        '-p', 'dummy', '-w', '1', '--trace-out', str(trace),
                        '--prom-out', str(tmp_path / 'm.prom')])
    assert rc == 0
    out = capsys.readouterr().out
    assert 'stall report' in out and 'diagnostics:' in out
    doc = json.loads(trace.read_text())
    assert doc['traceEvents'], 'spans level must record events'
    assert (tmp_path / 'm.prom').read_text().startswith('# TYPE')


def test_spans_level_records_pipeline_stages(synthetic_dataset):
    reader = make_reader(synthetic_dataset.url, schema_fields=['id'],
                         reader_pool_type='thread', workers_count=1,
                         output='columnar', telemetry='spans')
    total, _ = _drain_loader(reader)
    assert total == 100
    names = {e['name'] for e in obs.get_ring().snapshot()}
    assert {'ventilate', 'pool_wait', 'collate'} <= names
    # the worker's read+decode seconds live in ONE fused span on fused
    # stores, or in the classic read/decode pair on the Arrow path
    assert 'fused_decode' in names or {'read', 'decode'} <= names


# ---------------------------------------------------------------------------
# the step loop's wait and the pump thread's shuffle work, counted
# ---------------------------------------------------------------------------

@pytest.mark.parametrize('background', [True, False])
def test_infeed_wait_counts_each_batch_taken(background):
    import jax

    from petastorm_tpu.jax import prefetch_to_device
    obs.configure('counters')
    batches = ({'x': np.full((4, 3), i, np.float32)} for i in range(10))
    it = prefetch_to_device(batches, jax.devices()[0], size=2, background=background)
    taken = [int(next(it)['x'][0, 0]) for _ in range(6)]
    it.close()
    assert taken == list(range(6))
    counters = obs.snapshot()['counters']
    assert counters['stage_infeed_wait_count'] == 6
    assert counters['stage_infeed_wait_s'] >= 0.0
    # each batch was staged exactly once, whichever thread staged it
    assert counters['stage_infeed_count'] >= 6


def test_shuffle_stages_are_timed_and_traced(synthetic_dataset):
    reader = make_reader(synthetic_dataset.url, schema_fields=['id'],
                         reader_pool_type='thread', workers_count=1,
                         output='columnar', telemetry='counters')
    with JaxDataLoader(reader, batch_size=20, shuffling_queue_capacity=30, seed=1) as loader:
        assert sum(len(b['id']) for b in loader) == 100
    counters = obs.snapshot()['counters']
    assert counters['stage_shuffle_add_count'] == 10   # one a row group
    assert counters['stage_shuffle_emit_count'] == 5   # one a batch
    assert len(obs.get_ring()) == 0
    obs.configure('spans')
    reader = make_reader(synthetic_dataset.url, schema_fields=['id'],
                         reader_pool_type='thread', workers_count=1,
                         output='columnar', telemetry='spans')
    with JaxDataLoader(reader, batch_size=20, shuffling_queue_capacity=30, seed=1) as loader:
        assert sum(len(b['id']) for b in loader) == 100
    events = obs.get_ring().snapshot()
    emits = [e for e in events if e['name'] == 'shuffle_emit']
    adds = [e for e in events if e['name'] == 'shuffle_add']
    assert len(emits) == 5 and len(adds) == 10
    assert all(e['cat'] == 'loader' for e in emits + adds)
    assert [e['args']['rows'] for e in emits] == [20] * 5
    assert all(e['args']['occupancy'] >= 20 for e in emits)
    assert all('occupancy' in e['args'] for e in adds)


def test_row_path_fill_is_timed_once_a_batch(synthetic_dataset):
    reader = make_reader(synthetic_dataset.url, schema_fields=['id'],
                         reader_pool_type='thread', workers_count=1, telemetry='counters')
    with JaxDataLoader(reader, batch_size=20, shuffling_queue_capacity=30, seed=1) as loader:
        assert sum(len(b['id']) for b in loader) == 100
    counters = obs.snapshot()['counters']
    # one a batch, and the last that finds the reader exhausted and the
    # buffer drained; the per-row pulls, adds and draws are not timed apart
    assert counters['stage_shuffle_fill_count'] == 6
    assert 'stage_shuffle_add_count' not in counters
    # the reader's pool_wait runs inside the fill, on the same thread
    assert counters['stage_shuffle_fill_s'] >= counters['stage_pool_wait_s'] > 0.0
