"""The reduction of the program's spans on the profiler's timeline."""

import pytest

from benchmark import program_trace

#: 10 ms: the hand-made trace's unit, so that its idle gaps reach the gap
#: report's 100 ms
U = 10000000


def _hand_made():
    # one device, window [0, 100) U. Busy [0, 10), [30, 60), [90, 100): idle
    # [10, 30) under the step loop's wait for a batch, [60, 90) under a
    # dispatch stall alone.
    return {
        'devices': {'/device:TPU:0': [['conv', 0, 10 * U], ['conv', 30 * U, 30 * U],
                                      ['conv', 90 * U, 10 * U]]},
        'host': [['window', 0, 100 * U], ['wait_for_batch', 8 * U, 24 * U],
                 ['dispatch_step', 32 * U, 1 * U], ['wait_for_batch', 33 * U, 1 * U],
                 ['dispatch_step', 58 * U, 34 * U]],
        # line 0: the step loop; line 1: the prefetch thread; line 2: a worker
        'program': [['infeed.infeed_wait', 0, 9 * U, 22 * U],
                    ['infeed.infeed_wait', 0, 33 * U, 1 * U],
                    ['pool.pool_wait', 1, 5 * U, 15 * U],
                    ['loader.shuffle_add', 1, 20 * U, 2 * U],
                    ['loader.collate', 1, 22 * U, 6 * U],
                    ['infeed.infeed', 1, 28 * U, 1 * U],
                    ['worker.decode', 2, 0, 40 * U],
                    ['worker.decode', 2, 60 * U, 10 * U]],
    }


def test_reduce_puts_input_idle_down_to_the_pump_stage():
    r = program_trace.reduce(_hand_made())
    # [10, 30) idle, covered by the wait [9, 31): 20 U; the stall's 30 U in
    # dispatch_step is covered by no wait and does not count
    assert r['input_idle_s'] == pytest.approx(0.20)
    assert r['steps'] == 2
    assert program_trace.input_idle_ms_per_step(r) == pytest.approx(100.0)
    # the prefetch thread's innermost stage: pool_wait [10, 20), shuffle_add
    # [20, 22), collate [22, 28), infeed [28, 29); at [29, 30) it has none
    # open, though a worker is decoding: that time is the pump's, untimed
    assert r['input_idle_by_pump'] == {
        'pool.pool_wait': pytest.approx(0.10), 'loader.shuffle_add': pytest.approx(0.02),
        'loader.collate': pytest.approx(0.06), 'infeed.infeed': pytest.approx(0.01),
        'pump:untimed': pytest.approx(0.01)}
    assert r['annotations_per_step'] == pytest.approx(4.0)
    first, stall = r['gaps']
    assert first['mark'] == 'wait_for_batch' and first['seconds'] == pytest.approx(0.20)
    assert stall['mark'] == 'dispatch_step' and stall['at_s'] == pytest.approx(0.60)
    assert stall['threads'] == {'worker:2': {'worker.decode': pytest.approx(0.10)}}
    assert set(first['threads']) == {'consumer:0', 'pump:1', 'worker:2'}


def test_idle_with_no_pump_stage_open_is_untimed():
    extracted = _hand_made()
    extracted['program'] = [['infeed.infeed_wait', 0, 9 * U, 22 * U],
                            ['infeed.infeed', 1, 40 * U, 1 * U]]
    r = program_trace.reduce(extracted)
    assert r['input_idle_by_pump'] == {'pump:untimed': pytest.approx(0.20)}
    # gaps of 20 U and 30 U, both past the report's 100 ms
    assert [g['seconds'] for g in r['gaps']] == [pytest.approx(0.20), pytest.approx(0.30)]


def test_gap_report_leaves_out_gaps_under_100_ms():
    extracted = _hand_made()
    extracted['devices']['/device:TPU:0'].append(['conv', 12 * U, 18 * U - 1])
    r = program_trace.reduce(extracted)
    # [10, 12) and the last ns before 30 stay idle: 20 ms, under the report's
    # 100 ms; the stall's [60, 90) is reported
    assert [g['mark'] for g in r['gaps']] == ['dispatch_step']


def test_a_trace_without_the_programs_spans_reads_no_input_idle():
    extracted = dict(_hand_made(), program=[])
    r = program_trace.reduce(extracted)
    assert r['input_idle_s'] == 0.0 and r['input_idle_by_pump'] == {}
    assert r['annotations_per_step'] == 0.0


def test_reduce_recorded_program_trace():
    """A 0.12 s piece of a traced window of ``jpeg-decode.1chip`` on one v5e
    chip: the first steps after a profiler stall, with the program's spans."""
    import gzip
    import json
    import os

    from benchmark import trace
    from conftest import ROOT
    with gzip.open(os.path.join(ROOT, 'benchmark', 'testdata', 'trace_program_small.json.gz'),
                   'rt') as f:
        extracted = json.load(f)
    r = program_trace.reduce(extracted)
    marks = trace.reduce(extracted)
    assert r['steps'] == 3
    # input idle is a part of the idle time, and all of it is put down to
    # some stage or to none
    idle = marks['window_s'] - marks['busy_s']
    assert 0.001 < r['input_idle_s'] < idle
    assert sum(r['input_idle_by_pump'].values()) == pytest.approx(r['input_idle_s'])
    assert 'loader.collate' in r['input_idle_by_pump']
    # the benchmark's marks charge the whole gap to the dispatch stall that
    # overlaps it most; the program's wait span sees the input's part of it
    assert marks['idle_by_host']['wait_for_batch'] < r['input_idle_s']
    # the step loop, the prefetch thread and the workers are lines of their own
    lines = {}
    for name, line, _, _ in extracted['program']:
        lines.setdefault(name.split('.')[0], set()).add(line)
    consumer = {line for name, line, _, _ in extracted['program']
                if name == program_trace.WAIT}
    pump = {line for name, line, _, _ in extracted['program']
            if name == program_trace.STAGE}
    assert len(consumer) == len(pump) == 1 and consumer != pump
    assert not lines['worker'] & (consumer | pump)


def test_program_events_reads_the_stages_of_a_cpu_trace(tmp_path):
    import threading

    import jax

    from petastorm_tpu import observability as obs
    saved = obs.current_config()
    obs.configure('counters')
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        def work():
            with obs.stage('decode', cat='worker'):
                pass
        thread = threading.Thread(target=work)
        thread.start()
        thread.join(timeout=10)
        with obs.stage('infeed_wait', cat='infeed'):
            pass
        with obs.stage('mine', cat='bench'):   # not a program category
            pass
    finally:
        jax.profiler.stop_trace()
        obs.configure(saved)
    events = program_trace.program_events(str(tmp_path))
    names = sorted(e[0] for e in events)
    assert names == ['infeed.infeed_wait', 'worker.decode']
    lines = {e[0]: e[1] for e in events}
    assert lines['infeed.infeed_wait'] != lines['worker.decode']
    assert all(e[3] >= 0 for e in events)
