"""End-to-end causal tracing tests: TraceContext minting and propagation,
the zero-extra-messages structural guard, cross-process span-tree
completeness (thread pool, process pool, served reader), critical-path
attribution (timeline sweep + seeded slow stage), pod aggregation and
straggler naming, and the host-stamped/rotating JSONL exporter.

See docs/observability.md ("Causal tracing") for the span taxonomy these
tests pin down.
"""

import json
import os
import sys
import time

import pytest

from petastorm_tpu import make_reader
from petastorm_tpu import observability as obs
from petastorm_tpu.jax.loader import JaxDataLoader
from petastorm_tpu.test_util.stub_workers import IdentityWorker
from petastorm_tpu.transform import TransformSpec
from petastorm_tpu.workers import ConcurrentVentilator, EmptyResultError, ThreadPool


@pytest.fixture(autouse=True)
def _isolated_telemetry():
    """Telemetry state is process-global: save/restore the level and clear
    registry + ring around every test (same contract as
    tests/test_observability.py)."""
    saved = obs.current_config()
    obs.get_registry().reset()
    obs.get_ring().clear()
    yield
    obs.configure(saved)
    obs.get_registry().reset()
    obs.get_ring().clear()


def _drain_loader(reader, batch_size=20):
    with JaxDataLoader(reader, batch_size=batch_size, drop_last=False) as loader:
        total = 0
        for batch in loader:
            first = next(iter(batch.values()))
            total += len(first)
        return total, loader.last_trace


def _tree_names(tree):
    names = []
    stack = [tree]
    while stack:
        node = stack.pop()
        if node['name'] != '<root>':
            names.append(node['name'])
        stack.extend(node['children'])
    return names


def _tree_pids(tree):
    pids = set()
    stack = [tree]
    while stack:
        node = stack.pop()
        if node['name'] != '<root>':
            pids.add(node['pid'])
        stack.extend(node['children'])
    return pids


def _assert_causally_linked(events, tree):
    """Every event of the trace must have landed in the tree (no orphans cut
    loose), and every non-root node's parent must be a span that exists."""
    ids = {tree['span']}
    stack = [tree]
    count = 0
    while stack:
        node = stack.pop()
        if node['name'] != '<root>':
            count += 1
            ids.add(node['span'])
        stack.extend(node['children'])
    stack = [tree]
    while stack:
        node = stack.pop()
        for child in node['children']:
            assert child['parent'] in ids or child['parent'] is None
        stack.extend(node['children'])
    stamped = [e for e in events
               if (e.get('args') or {}).get('trace') == tree['trace']]
    assert count == len(stamped)


# ---------------------------------------------------------------------------
# trace-context primitives
# ---------------------------------------------------------------------------

def test_trace_context_minting_and_nesting():
    obs.configure('spans')
    assert obs.current_trace() is None
    with obs.mint_trace('abcd1234', 7):
        ctx = obs.current_trace()
        assert ctx.trace == 'abcd1234:7'
        # the freshly minted context IS the virtual root
        assert ctx.span == ctx.trace
        with obs.stage('ventilate', cat='ventilator'):
            inner = obs.current_trace()
            assert inner.trace == ctx.trace and inner.span != ctx.span
        assert obs.root_of(obs.current_trace()) == obs.trace_root('abcd1234', 7)
    assert obs.current_trace() is None
    # the stage recorded its identity stamps
    (ev,) = [e for e in obs.get_ring().snapshot() if e.get('name') == 'ventilate']
    assert ev['args']['trace'] == 'abcd1234:7'
    assert ev['args']['parent'] == 'abcd1234:7'


def test_trace_context_free_below_spans_level():
    obs.configure('counters')
    with obs.mint_trace('abcd1234', 1):
        assert obs.current_trace() is None
        with obs.stage('ventilate', cat='ventilator'):
            pass
    assert len(obs.get_ring()) == 0


# ---------------------------------------------------------------------------
# propagation: zero extra messages, existing channels only
# ---------------------------------------------------------------------------

def _run_counted_pool(level, items=24):
    """Run one tagged-ventilator workload through a ThreadPool, counting every
    task-queue and results-queue put and recording the tuple arities."""
    obs.configure(level)
    pool = ThreadPool(2)
    counts = {'task': 0, 'results': 0}
    arities = {'task': set(), 'results': set()}
    orig_task_put = pool._task_queue.put
    orig_results_put = pool._results_queue.put

    def task_put(item, *a, **k):
        counts['task'] += 1
        if isinstance(item, tuple):
            arities['task'].add(len(item))
        return orig_task_put(item, *a, **k)

    def results_put(item, *a, **k):
        counts['results'] += 1
        if isinstance(item, tuple):
            arities['results'].add(len(item))
        return orig_results_put(item, *a, **k)

    pool._task_queue.put = task_put
    pool._results_queue.put = results_put
    vent = ConcurrentVentilator(pool.ventilate,
                                [{'value': i} for i in range(items)],
                                tag_items=True)
    pool.start(IdentityWorker, ventilator=vent)
    got = []
    while len(got) < items:
        try:
            got.append(pool.get_results())
        except EmptyResultError:
            time.sleep(0.01)
    pool.stop()
    pool.join()
    assert sorted(got) == list(range(items))
    return counts, arities


def test_tracing_adds_zero_queue_messages():
    """The structural guard: the TraceContext rides the EXISTING task/result
    tuples. Turning spans on must not change the number of queue messages or
    the tuple shapes — only the value in the reserved context slot."""
    off_counts, off_arities = _run_counted_pool('off')
    on_counts, on_arities = _run_counted_pool('spans')
    assert on_counts == off_counts
    assert on_arities == off_arities


def test_telemetry_off_reader_is_trace_free(synthetic_dataset):
    obs.configure('off')
    reader = make_reader(synthetic_dataset.url, schema_fields=['id'],
                         reader_pool_type='thread', workers_count=1,
                         output='columnar')
    total, last_trace = _drain_loader(reader)
    assert total == 100
    assert last_trace is None
    assert reader.last_trace is None
    assert len(obs.get_ring()) == 0


# ---------------------------------------------------------------------------
# span-tree completeness across processes
# ---------------------------------------------------------------------------

def test_thread_pool_batch_span_tree(synthetic_dataset):
    obs.configure('spans')
    reader = make_reader(synthetic_dataset.url, schema_fields=['id'],
                         reader_pool_type='thread', workers_count=2,
                         output='columnar')
    total, last_trace = _drain_loader(reader)
    assert total == 100
    assert last_trace is not None
    events = obs.get_ring().snapshot()
    tree = obs.span_tree(events, last_trace.trace)
    assert tree is not None
    names = _tree_names(tree)
    # dispatch -> worker decode -> consumer wait -> loader collate: the whole
    # batch journey, >= 4 causally linked stages
    assert 'ventilate' in names
    assert 'pool_wait' in names
    assert 'collate' in names
    assert any(n in names for n in ('fused_decode', 'decode', 'read'))
    assert len(set(names)) >= 4
    _assert_causally_linked(events, tree)


def test_process_pool_batch_span_tree_crosses_processes(synthetic_dataset):
    obs.configure('spans')
    reader = make_reader(synthetic_dataset.url, schema_fields=['id'],
                         reader_pool_type='process', workers_count=2,
                         output='columnar')
    total, last_trace = _drain_loader(reader)
    assert total == 100
    assert last_trace is not None
    events = obs.get_ring().snapshot()
    tree = obs.span_tree(events, last_trace.trace)
    assert tree is not None
    names = set(_tree_names(tree))
    assert len(names) >= 4
    # worker spans were recorded in a different process and shipped home on
    # the metrics piggyback: the tree must span >= 2 pids
    assert len(_tree_pids(tree)) >= 2
    _assert_causally_linked(events, tree)


def test_served_reader_batch_span_tree_crosses_processes(tmp_path, synthetic_dataset):
    obs.configure('spans')
    svc_dir = str(tmp_path / 'svc')
    reader = make_reader(synthetic_dataset.url, serve=svc_dir, seed=0,
                         shuffle_row_groups=False, workers_count=2)
    try:
        rows = [r for r in reader]
        assert len(rows) == 100
        last_trace = reader.last_trace
        assert last_trace is not None
        # absorb the daemon-side spans into the local ring, then reconstruct
        fetched = reader.service_trace_events()
        assert fetched
    finally:
        reader.stop()
        reader.join()
    events = obs.get_ring().snapshot()
    tree = obs.span_tree(events, last_trace.trace)
    assert tree is not None
    assert len(set(_tree_names(tree))) >= 4
    # daemon pid (ventilate/decode) + this process (pool_wait on the ring)
    assert len(_tree_pids(tree)) >= 2
    _assert_causally_linked(events, tree)
    from petastorm_tpu.serve.client import connect_service
    conn = connect_service(svc_dir)
    conn.send({'op': 'shutdown'})
    conn.recv()
    conn.close()


# ---------------------------------------------------------------------------
# critical-path attribution
# ---------------------------------------------------------------------------

def _ev(name, cat, ts, dur, span, parent, trace='t:1', pid=1):
    return {'name': name, 'cat': cat, 'ph': 'X', 'ts': ts, 'dur': dur,
            'pid': pid, 'tid': 1,
            'args': {'trace': trace, 'span': span, 'parent': parent}}


def test_critical_path_sweep_covers_makespan_exactly():
    """Async handoff shape: the ventilate span closes long before the worker
    subtree it caused even starts. The sweep must attribute every instant —
    segments sum exactly to the makespan, the handoff gap surfaces as
    '<untraced>', and the dominant stage is the decode, not the parent that
    merely contains it."""
    events = [
        _ev('ventilate', 'ventilator', 0, 100, 'v', 't:1'),
        # worker starts 50us after ventilate ended: an untraced gap
        _ev('decode', 'worker', 150, 800, 'd', 'v', pid=2),
        _ev('pool_wait', 'pool', 950, 250, 'w', 't:1'),
    ]
    tree = obs.span_tree(events, 't:1')
    assert tree['dur'] == 1200
    path = obs.critical_path(tree)
    assert sum(seg['dur_us'] for seg in path) == tree['dur']
    names = [seg['name'] for seg in path]
    assert names == ['ventilate', '<untraced>', 'decode', 'pool_wait']
    dominant = max(path, key=lambda s: s['dur_us'])
    assert dominant['name'] == 'decode' and dominant['pid'] == 2


def test_critical_path_deepest_span_owns_the_instant():
    """A child doing the actual work owns the time over the stage containing
    it, and self time nets out the nesting."""
    events = [
        _ev('read', 'worker', 0, 1000, 'r', 't:1'),
        _ev('arrow_decode', 'native', 200, 600, 'a', 'r'),
    ]
    tree = obs.span_tree(events, 't:1')
    path = obs.critical_path(tree)
    assert [s['name'] for s in path] == ['read', 'arrow_decode', 'read']
    assert sum(s['dur_us'] for s in path) == 1000
    breakdown = obs.stage_breakdown(tree)
    assert breakdown == {'read': 400, 'arrow_decode': 600}


def test_orphan_spans_attach_to_virtual_root():
    """A span whose parent rotated out of the ring must still appear in the
    tree (attached to the root), never silently vanish."""
    events = [_ev('decode', 'worker', 0, 500, 'd', 'gone-parent')]
    tree = obs.span_tree(events, 't:1')
    assert [c['name'] for c in tree['children']] == ['decode']
    assert tree['dur'] == 500


def test_critical_path_names_seeded_slow_stage(synthetic_dataset):
    """Seed a deliberately slow transform; the slowest batch's critical path
    must name it as the dominant stage — the per-batch answer the flat stall
    report cannot give."""
    obs.configure('spans')

    def slow(row):
        time.sleep(0.005)
        return row

    reader = make_reader(synthetic_dataset.url,
                         reader_pool_type='thread', workers_count=1,
                         transform_spec=TransformSpec(slow))
    with reader:
        for _, _row in zip(range(30), reader):
            pass
    events = obs.get_ring().snapshot()
    # the first-dispatched item hits an idle worker: no queue wait, so its
    # dispatch-to-delivery time is genuinely transform-bound
    first = next(t for t in obs.traces_in(events) if t.endswith(':0'))
    tree = obs.span_tree(events, first)
    dominant = max(obs.critical_path(tree), key=lambda s: s['dur_us'])
    assert dominant['name'] == 'transform'
    assert obs.stage_breakdown(tree).get('transform', 0) >= 5000  # >= 5 ms
    # later items queued behind the single busy worker: that wait must not
    # vanish — it surfaces as '<untraced>' on the slowest batch's path
    worst = obs.slowest_batches(events, top=1)[0]
    assert worst['stages'].get('transform', 0) >= 5000
    assert any(s['name'] == '<untraced>' for s in worst['critical_path'])


def test_critical_path_summary_schema(synthetic_dataset):
    obs.configure('spans')
    reader = make_reader(synthetic_dataset.url, schema_fields=['id'],
                         reader_pool_type='thread', workers_count=1,
                         output='columnar')
    total, _ = _drain_loader(reader)
    assert total == 100
    summary = obs.critical_path_summary(top=2)
    assert summary['traced_batches'] >= 10  # one trace per ventilated item
    assert 0 < len(summary['slowest']) <= 2
    entry = summary['slowest'][0]
    assert {'trace', 'makespan_us', 'spans', 'processes', 'stages',
            'critical_path'} <= set(entry)
    # the summary must round-trip through JSON (bench harness embeds it)
    json.dumps(summary)


# ---------------------------------------------------------------------------
# pod aggregation + straggler naming
# ---------------------------------------------------------------------------

def _write_host_series(path, host, points, wait_per_point=0.0):
    """One exporter-style JSONL file: ``points`` is [(ts, rows_emitted)]."""
    ident = obs.host_identity(host)
    with open(path, 'w') as f:
        for i, (ts, rows) in enumerate(points):
            rec = {'ts': ts, 'host': ident,
                   'metrics': {'rows_emitted': rows,
                               'reader_wait_s': wait_per_point * i}}
            f.write(json.dumps(rec) + '\n')


def test_pod_report_names_throughput_straggler(tmp_path):
    pod = tmp_path / 'pod'
    pod.mkdir()
    _write_host_series(str(pod / 'a.jsonl'), 'host0',
                       [(100.0, 0), (110.0, 10000)])
    _write_host_series(str(pod / 'b.jsonl'), 'host1',
                       [(100.0, 0), (110.0, 9000)])
    _write_host_series(str(pod / 'c.jsonl'), 'host2',
                       [(100.0, 0), (110.0, 2000)])
    report = obs.pod_report(str(pod))
    assert len(report['hosts']) == 3
    assert report['straggler'] is not None
    assert report['straggler']['host'] == 'host2'
    assert report['straggler']['reason'] == 'throughput'
    assert report['throughput_skew'] == pytest.approx(0.2)
    text = obs.format_pod_report(report)
    assert 'STRAGGLER host2' in text


def test_pod_report_names_stall_straggler(tmp_path):
    """Equal throughput, but one host spends most of its wall time starving:
    the stall-skew check catches what the throughput check cannot."""
    pod = tmp_path / 'pod'
    pod.mkdir()
    _write_host_series(str(pod / 'a.jsonl'), 'host0',
                       [(100.0, 0), (110.0, 5000)], wait_per_point=0.5)
    _write_host_series(str(pod / 'b.jsonl'), 'host1',
                       [(100.0, 0), (110.0, 5000)], wait_per_point=0.5)
    _write_host_series(str(pod / 'c.jsonl'), 'host2',
                       [(100.0, 0), (110.0, 5000)], wait_per_point=8.0)
    report = obs.pod_report(str(pod))
    assert report['straggler'] is not None
    assert report['straggler']['host'] == 'host2'
    assert report['straggler']['reason'] == 'stall'


def test_pod_report_balanced_pod_has_no_straggler(tmp_path):
    pod = tmp_path / 'pod'
    pod.mkdir()
    for i in range(3):
        _write_host_series(str(pod / 'h{}.jsonl'.format(i)), 'host{}'.format(i),
                           [(100.0, 0), (110.0, 5000 + 100 * i)])
    report = obs.pod_report(str(pod))
    assert report['straggler'] is None
    assert 'no straggler' in obs.format_pod_report(report)


def test_pod_report_merges_rotated_and_restarted_series(tmp_path):
    """A host's rotated backup (.jsonl.1) and a same-key second file must fold
    into one series, and a single-snapshot host reports but does not crash."""
    pod = tmp_path / 'pod'
    pod.mkdir()
    _write_host_series(str(pod / 'a.jsonl.1'), 'host0', [(100.0, 0)])
    # note: load_host_series reads path+'.1' first, then path
    _write_host_series(str(pod / 'a.jsonl'), 'host0', [(110.0, 10000)])
    _write_host_series(str(pod / 'b.jsonl'), 'host1', [(105.0, 500)])
    report = obs.pod_report(str(pod))
    by_host = {h['host']: h for h in report['hosts']}
    assert by_host['host0']['rows_per_s'] == pytest.approx(1000.0)
    assert by_host['host1']['rows_per_s'] is None  # 1 snapshot: no window


def test_diagnose_pod_cli(tmp_path, capsys):
    from petastorm_tpu.observability.diagnose import main as diagnose_main
    pod = tmp_path / 'pod'
    pod.mkdir()
    _write_host_series(str(pod / 'a.jsonl'), 'host0', [(100.0, 0), (110.0, 10000)])
    _write_host_series(str(pod / 'b.jsonl'), 'host1', [(100.0, 0), (110.0, 1000)])
    rc = diagnose_main(['--pod', str(pod)])
    assert rc == 0
    out = capsys.readouterr().out
    assert 'host0' in out and 'STRAGGLER host1' in out


def test_diagnose_batch_cli(synthetic_dataset, capsys):
    from petastorm_tpu.observability.diagnose import main as diagnose_main
    rc = diagnose_main([synthetic_dataset.url, '--batches', '3',
                        '--batch-size', '10', '-p', 'thread', '-w', '1',
                        '--batch', 'slowest'])
    assert rc == 0
    out = capsys.readouterr().out
    assert 'dominant stage' in out
    assert 'critical path:' in out
    assert 'makespan' in out


# ---------------------------------------------------------------------------
# host identity + exporter rotation
# ---------------------------------------------------------------------------

def test_host_identity_fields():
    ident = obs.host_identity()
    assert set(ident) == {'host', 'process_index', 'hostname', 'pid', 'boot_ts'}
    assert ident['pid'] == os.getpid()
    assert isinstance(ident['boot_ts'], float)
    assert obs.host_identity('host7')['host'] == 'host7'
    # the default key is stable within a process
    assert obs.host_identity()['host'] == ident['host']


def test_jsonl_exporter_stamps_host(tmp_path):
    obs.get_registry().counter('rows_total').inc(3)
    path = tmp_path / 'metrics.jsonl'
    with obs.JsonlExporter(str(path), interval_s=60, host_key='hostX'):
        pass  # the stop flush writes one line
    (rec,) = [json.loads(line) for line in path.read_text().splitlines()]
    assert rec['host']['host'] == 'hostX'
    assert rec['host']['pid'] == os.getpid()
    assert rec['metrics']['rows_total'] == 3


def test_jsonl_exporter_rotation_bounds_disk_and_counts_drops(tmp_path):
    obs.configure('counters')
    pad = {'counters': {'pad': 1, 'filler': 12345678}, 'gauges': {},
           'histograms': {}}
    path = tmp_path / 'metrics.jsonl'
    cap = 600
    exporter = obs.JsonlExporter(str(path), interval_s=60, max_bytes=cap,
                                 snapshot_fn=lambda: pad, host_key='h')
    for _ in range(40):
        exporter._flush()
    assert os.path.exists(str(path) + '.1')
    # one backup generation: on-disk use stays under ~2x the cap
    total = os.path.getsize(path) + os.path.getsize(str(path) + '.1')
    line_len = len(path.read_text().splitlines()[0]) + 1
    assert total <= 2 * cap + line_len
    dropped = obs.get_registry().snapshot()['counters'].get(
        'telemetry_export_dropped_total', 0)
    assert dropped > 0
    # every surviving line still parses and carries the stamp
    for line in path.read_text().splitlines():
        assert json.loads(line)['host']['host'] == 'h'


def test_jsonl_exporter_rotated_series_still_loads(tmp_path):
    """The pod loader reads backup + live file as one series."""
    pad = {'counters': {'rows_emitted': 100}, 'gauges': {}, 'histograms': {}}
    path = tmp_path / 'h.jsonl'
    exporter = obs.JsonlExporter(str(path), interval_s=60, max_bytes=400,
                                 snapshot_fn=lambda: pad, host_key='h0')
    for _ in range(10):
        exporter._flush()
    series = obs.load_host_series(str(path))
    assert series['host'] == 'h0'
    live = len(path.read_text().splitlines())
    backup = len((tmp_path / 'h.jsonl.1').read_text().splitlines())
    assert len(series['snapshots']) == live + backup


# ---------------------------------------------------------------------------
# the profiler bridge: stages on the device trace's clock
# ---------------------------------------------------------------------------

def _program_lines(trace_dir):
    """``{label: {line index, ...}}`` of the program's annotations in the
    newest xplane under ``trace_dir``, as the benchmark's reduction reads
    them."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmark.program_trace import program_events
    lines = {}
    for name, line, _, _ in program_events(trace_dir):
        lines.setdefault(name, set()).add(line)
    return lines


@pytest.mark.parametrize('output,shuffle', [
    ('columnar', ('loader.shuffle_add', 'loader.shuffle_emit')),   # a block at a time
    ('rows', ('loader.shuffle_fill',)),                            # one timer a batch
])
def test_pipeline_stages_land_on_the_profiler_trace(synthetic_dataset, tmp_path, output,
                                                    shuffle):
    import jax

    from petastorm_tpu.jax import prefetch_to_device
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    reader = make_reader(synthetic_dataset.url, schema_fields=['id', 'matrix'],
                         reader_pool_type='thread', workers_count=2,
                         output=output, num_epochs=1)
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        with reader:
            loader = JaxDataLoader(reader, batch_size=10, shuffling_queue_capacity=30,
                                   seed=1)
            taken = sum(1 for _ in prefetch_to_device(loader, size=2))
    finally:
        jax.profiler.stop_trace()
    assert taken == 10
    lines = _program_lines(str(tmp_path))
    for label in ('worker.decode', 'loader.collate', 'infeed.infeed',
                  'infeed.infeed_wait') + shuffle:
        assert label in lines, sorted(lines)
    # the consumer's wait sits on the thread that called next(); the pool's
    # stages on the worker threads', the shuffling, collate and staging on
    # the prefetch thread's
    assert not lines['worker.decode'] & lines['infeed.infeed_wait']
    assert not lines['infeed.infeed'] & lines['infeed.infeed_wait']
    for label in ('loader.collate',) + shuffle:
        assert lines[label] == lines['infeed.infeed']


class _CountingAnnotation(object):
    """Stands in for ``jax.profiler.TraceAnnotation``: counts what opens."""

    enabled = False
    opened = []

    def __init__(self, label):
        self.label = label
        self.metadata = {}
        _CountingAnnotation.opened.append(self)

    @staticmethod
    def is_enabled():
        return _CountingAnnotation.enabled

    def set_metadata(self, **kwargs):
        self.metadata.update(kwargs)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


@pytest.mark.parametrize('level,recording,opened', [
    ('counters', False, 0),   # no session: one is_enabled() and nothing more
    ('spans', False, 0),
    ('off', True, 0),         # off: the no-op, whatever the profiler does
    ('counters', True, 2),
    ('spans', True, 3),       # spans level adds the trace-only span
])
def test_stage_opens_an_annotation_only_under_a_recording_session(monkeypatch, level,
                                                                  recording, opened):
    from petastorm_tpu.observability import trace as trace_mod
    monkeypatch.setattr(trace_mod, '_annotation_cls', _CountingAnnotation)
    monkeypatch.setattr(_CountingAnnotation, 'enabled', recording)
    monkeypatch.setattr(_CountingAnnotation, 'opened', [])
    obs.configure(level)
    with obs.stage('decode', cat='worker', rows=4):
        with obs.stage('shuffle_emit', cat='loader') as sp:
            sp.annotate(occupancy=9)
    with obs.span('serve.admit', cat='serve', tenant='t'):
        pass
    got = _CountingAnnotation.opened
    assert len(got) == opened
    if got:
        assert [a.label for a in got][:2] == ['worker.decode', 'loader.shuffle_emit']
    if level == 'spans' and recording:
        assert got[0].metadata == {'rows': 4}
        assert got[1].metadata == {'occupancy': 9}
        assert got[2].label == 'serve.serve.admit'
    elif got:
        # below spans level the name alone: no args reach the annotation
        assert all(a.metadata == {} for a in got)


def test_no_session_opens_no_real_annotation(monkeypatch):
    import jax

    from petastorm_tpu.observability import trace as trace_mod
    made = []

    class Counted(jax.profiler.TraceAnnotation):
        def __init__(self, *args, **kwargs):
            made.append(args)
            super().__init__(*args, **kwargs)

    monkeypatch.setattr(trace_mod, '_annotation_cls', Counted)
    obs.configure('spans')
    with obs.stage('infeed_wait', cat='infeed'):
        pass
    assert made == []
    assert obs.snapshot()['counters']['stage_infeed_wait_count'] == 1


def test_stage_never_imports_jax_into_a_jax_free_process():
    import subprocess
    code = ('import sys\n'
            'from petastorm_tpu import observability as obs\n'
            'for level in ("counters", "spans"):\n'
            '    obs.configure(level)\n'
            '    with obs.stage("decode", cat="worker", rows=1):\n'
            '        with obs.span("emit", cat="loader"):\n'
            '            pass\n'
            'assert obs.snapshot()["counters"]["stage_decode_count"] == 2\n'
            'print("jax" in sys.modules)\n')
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run([sys.executable, '-c', code], cwd=root, capture_output=True,
                         text=True, timeout=120, check=True)
    assert out.stdout.strip() == 'False'
