"""One run of a cell: set-up, the measured window, the traced window's
reduction and the comparison that decides ``correct``.

The path driven is the one ``README.md`` documents for training on a mesh:
the store's feed (``benchmark/stores/<store>.py``) -> ``prefetch_to_device``
onto ``data_sharding(make_mesh(('data',)))`` -> the AOT-compiled train step
of the configuration's model module (``benchmark/models/<arch>.py``), which
donates its state. The mesh spans the cell's chips and is entered with
``jax.set_mesh`` on one chip too. What belongs to one configuration (the
records, the feed and its check, the state, the step, the batch it takes,
the plain reference, the throughput) is found through those two modules;
this file holds what every configuration shares.
"""

from __future__ import annotations

import json
import os
import shutil
import statistics
import sys
import time

import numpy as np

from benchmark import gaps, manifest

#: how many of the window's first steps a sampled batch is drawn from
SAMPLE_SPAN = 40


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def log(message):
    print('benchmark: ' + message, flush=True)


def devices_for(cell, require_tpu=True):
    import jax
    devices = jax.devices()
    if require_tpu and devices[0].platform != 'tpu':
        raise NoChip('needs a TPU; JAX found {} ({}) x{}'.format(
            devices[0].platform, devices[0].device_kind, len(devices)))
    if len(devices) < cell.chips:
        raise NoChip('cell {} needs {} chips; JAX found {} {} ({})'.format(
            cell.name, cell.chips, len(devices), devices[0].platform,
            devices[0].device_kind))
    return devices


def build_store(cell, store_module, root):
    from benchmark.stores.common import build_store as build
    t0 = time.perf_counter()
    path = build(manifest.store_path(cell.config, root), cell.config,
                 os.path.join(root, 'benchmark', '.store_cache'))
    files = [os.path.join(d, f) for d, _, fs in os.walk(path) for f in fs
             if f.endswith('.parquet')]
    size = sum(os.path.getsize(f) for f in files)
    records = store_module.records(cell.config)
    log('store {}: {} records, {} bytes in parquet files, {:.1f} bytes a record, '
        'ready in {:.2f} s; host cpu_count {}'.format(
            os.path.basename(path), records, size, size / records,
            time.perf_counter() - t0, os.cpu_count()))
    return path


def compile_step(step, *args):
    """AOT-compile ``step`` for ``args``: (compiled, seconds, cache hits)."""
    import jax

    hits = []

    def on_event(name, **kwargs):
        if name == '/jax/compilation_cache/cache_hits':
            hits.append(name)

    jax.monitoring.register_event_listener(on_event)
    try:
        t0 = time.perf_counter()
        compiled = step.lower(*args).compile()
        seconds = time.perf_counter() - t0
    finally:
        jax.monitoring.unregister_event_listener(on_event)
    return compiled, seconds, len(hits)


def _counters():
    from petastorm_tpu import observability as obs
    return dict(obs.snapshot().get('counters', {}))


def _delta(after, before):
    return {k: v - before.get(k, 0) for k, v in after.items()}


class Run(object):
    """What one run saw, for the metric readers and the comparison."""

    def __init__(self):
        self.window = {}
        self.counters = {}
        self.trace = None
        self.checks = {}
        self.failed = 0
        self.readings = None


def run(cell, seed, seconds, trace, t_start, root=manifest.ROOT, require_tpu=True,
        faults=None, readings=None):
    """One run of ``cell``. Returns the result line as a dict.

    ``faults`` (tests and ``benchmark/readings.py`` only) is a dict of
    planted faults: ``'step'`` replaces the train step factory the model
    module calls (the control is put in the program's place so too),
    ``'batches'`` wraps the device batch iterator.
    ``readings(**facts)`` (``benchmark/readings.py`` only) is called after the
    comparison with what it compared; its dict goes into the line under
    ``readings``."""
    import jax

    faults = faults or {}
    devices = devices_for(cell, require_tpu)[:cell.chips]
    config, traffic = cell.config, cell.traffic
    store_module = manifest.load_module(manifest.store_path(config, root))
    model = manifest.model_module(config, root)
    store = build_store(cell, store_module, root)

    import jax.numpy as jnp

    from petastorm_tpu import native
    from petastorm_tpu.jax import prefetch_to_device
    from petastorm_tpu.jax.compile_cache import use_persistent_compile_cache
    from petastorm_tpu.parallel import data_sharding, make_mesh

    cache_dir = use_persistent_compile_cache(root)
    global_batch = traffic['batch_per_chip'] * cell.chips
    check_steps = config['check_steps']
    rng = np.random.default_rng(seed)
    samples = set(rng.choice(SAMPLE_SPAN, traffic['sampled_batches'], replace=False).tolist())
    out = Run()

    mesh = make_mesh(('data',), devices=devices)
    with jax.set_mesh(mesh):
        state = model.make_state(config, seed, mesh)
        step = model.make_step(config, faults.get('step'))
        sharding = data_sharding(mesh)
        spec = model.batch_spec(config, global_batch)
        keys = [key for key, _, _ in spec]
        compiled, compile_s, hits = compile_step(
            step, state, *[jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=sharding)
                           for _, shape, dtype in spec])
        log('step compiled in {:.2f} s ({} compile-cache hits, cache {}); custom kernel '
            'in step: {}; native row-group kernel: {}'.format(
                compile_s, hits, cache_dir, 'tpu_custom_call' in compiled.as_text(),
                native.is_available()))
        start = jax.device_get(state.params)

        with store_module.feed(store, config, traffic, seed, global_batch) as loader:
            batches = prefetch_to_device(loader, sharding, size=traffic['prefetch'])
            if 'batches' in faults:
                batches = faults['batches'](batches)
            kept = []
            try:
                # the first steps: the reference follows them
                check_losses = []
                for k in range(check_steps):
                    batch = next(batches)
                    kept.append(batch)
                    state, metrics = compiled(state, *[batch[key] for key in keys])
                    check_losses.append(metrics['loss'])
                    if k == 0:
                        grad1 = jax.device_get(model.first_gradient(config, state))
                end = jax.device_get(state.params)
                check_losses = [float(v) for v in check_losses]
                for _ in range(traffic['warmup_steps']):
                    batch = next(batches)
                    state, metrics = compiled(state, *[batch[key] for key in keys])
                setup_s = time.perf_counter() - t_start
                state, sampled = _window(out, compiled, keys, state, batches, metrics, seconds,
                                         samples, trace, cell, seed, root)
                kept.extend(sampled)
            finally:
                batches.close()
        w = out.window
        w['chips'] = cell.chips
        w['global_batch'] = global_batch
        w['examples'] = w['steps'] * global_batch
        w['units'] = model.units(w.pop('step_metrics'), global_batch)
        # a TPU holds the programs' temporaries in memory it reserves apart
        # from the buffers it allocates: the peak is the sum of both peaks
        stats = [d.memory_stats() or {} for d in devices]
        peaks = [s.get('peak_bytes_in_use', 0) + s.get('peak_bytes_reserved', 0) for s in stats]
        memory_peak = max(peaks)
        analysis = compiled.memory_analysis()
        log('device memory statistics, fullest chip: {}; the compiled step: {}'.format(
            json.dumps(stats[peaks.index(memory_peak)]),
            json.dumps({k: getattr(analysis, k, None) for k in (
                'argument_size_in_bytes', 'output_size_in_bytes', 'alias_size_in_bytes',
                'temp_size_in_bytes', 'generated_code_size_in_bytes')})))
        delivered = {key: np.concatenate([np.asarray(jax.device_get(b[key])) for b in kept])
                     for key in kept[0]}
        del state, compiled, kept, batch, metrics

    # the comparison, with the program's state freed
    t0 = time.perf_counter()
    numbers, ref_inputs = store_module.check(store, config, delivered)
    # the reference step takes the check steps' rows of the inputs the
    # store's check gives: the delivered batches, held to the store
    rows = [slice(i * global_batch, (i + 1) * global_batch) for i in range(check_steps)]
    ref_batches = [tuple(a[r] for a in ref_inputs) for r in rows]
    ref = model.reference(config, seed, ref_batches, devices[0])
    program = {'losses': check_losses, 'grad1': grad1,
               'change': jax.tree_util.tree_map(lambda a, b: np.float64(a) - b, end, start)}
    numbers.update(gaps.training(program, ref))
    log('reference compared in {:.2f} s; worst leaves: first gradient {} {!r}, change {} '
        '{!r}'.format(time.perf_counter() - t0, numbers.pop('_grad1_leaf'),
                      numbers['grad1_worst_gap'], numbers.pop('_change_leaf'),
                      numbers['change_worst_gap']))
    out.checks = {name: {'value': numbers[name], 'limit': limit}
                  for name, limit in config['limits'].items()}
    if readings is not None:
        out.readings = dict(readings(
            config=config, seed=seed, program=program, ref=ref, ref_batches=ref_batches,
            delivered=delivered, device=devices[0], store=store, store_module=store_module,
            model=model), program=numbers)
    correct = (all(c['value'] <= c['limit'] for c in out.checks.values())
               and out.failed == 0 and out.window['steps'] > 0)
    return result_line(cell, model, out, correct, setup_s, memory_peak, trace, root)


def _steps(compiled, keys, state, batches, metrics, done, samples=()):
    """Steps from a step boundary until ``done(steps, seconds)``, then until
    the last step dispatched is ready. Each step takes the batch's ``keys``
    in order. Its completion is observed as a loop that logs its loss does:
    dispatch step i, then wait for step i-1's loss. Each step's metrics are
    kept on the device. Returns ``(state, facts)``."""
    import jax

    jax.block_until_ready(metrics['loss'])
    sampled, step_metrics = [], []
    wait_s = 0.0
    t0 = time.perf_counter()
    completions = [t0]
    prev = None
    i = 0
    while True:
        with jax.profiler.TraceAnnotation('wait_for_batch'):
            w0 = time.perf_counter()
            batch = next(batches)
            wait_s += time.perf_counter() - w0
        with jax.profiler.TraceAnnotation('dispatch_step'):
            state, metrics = compiled(state, *[batch[key] for key in keys])
        if i in samples:
            sampled.append(batch)
        step_metrics.append(metrics)
        if prev is not None:
            prev.block_until_ready()
            completions.append(time.perf_counter())
        prev = metrics['loss']
        i += 1
        if done(i, completions[-1] - t0):
            break
    prev.block_until_ready()
    completions.append(time.perf_counter())
    return state, {'seconds': completions[-1] - t0, 'steps': i, 'step_metrics': step_metrics,
                   'intervals': np.diff(completions).tolist(), 'loader_wait_s': wait_s,
                   'sampled': sampled, 'metrics': metrics}


def _window(out, compiled, keys, state, batches, metrics, seconds, samples, trace, cell,
            seed, root):
    """The measured window of ``seconds``. With ``trace``, a traced window of
    the traffic's ``trace_steps`` steps follows it: the profiler stalls the
    host's dispatch for most of a second every few steps (``PERF.md``), so
    no end-to-end time is taken under it."""
    import jax

    before = _counters()
    state, w = _steps(compiled, keys, state, batches, metrics,
                      lambda steps, elapsed: elapsed >= seconds, samples)
    after = _counters()
    w['step_metrics'] = jax.device_get(w['step_metrics'])
    losses = np.asarray([m['loss'] for m in w['step_metrics']])
    out.failed = int(np.sum(~np.isfinite(losses)))
    out.counters = _delta(after, before)
    sampled = w.pop('sampled')
    metrics = w.pop('metrics')
    out.window.update(w)
    if trace:
        from benchmark import trace as trace_reduce
        trace_dir = os.path.join(root, 'benchmark', '.trace', '{}-{}'.format(cell.name, seed))
        shutil.rmtree(trace_dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.host_tracer_level = 1   # the benchmark's annotations, not the runtime's
        options.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        try:
            with jax.profiler.TraceAnnotation(trace_reduce.WINDOW):
                steps = cell.traffic['trace_steps']
                state, _ = _steps(compiled, keys, state, batches, metrics,
                                  lambda n, elapsed: n >= steps)
        finally:
            jax.profiler.stop_trace()
        out.trace = trace_reduce.reduce(trace_reduce.extract(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
    return state, sampled


def percentile(values, q):
    """The ``q``-th percentile of ``values`` by linear interpolation."""
    return float(np.percentile(np.asarray(values, np.float64), q))


def result_line(cell, model, out, correct, setup_s, memory_peak, trace, root):
    import jax
    w = out.window
    devices = jax.devices()
    device = {'platform': devices[0].platform, 'kind': devices[0].device_kind,
              'count': len(devices), 'memory_peak_bytes': int(memory_peak)}
    log('window: {} steps, {} examples, {} units of {} in {:.4f} s; loader wait {:.4f} s; '
        'interval median {:.3f} ms'.format(
            w['steps'], w['examples'], w['units'], model.THROUGHPUT, w['seconds'],
            w['loader_wait_s'], 1000 * statistics.median(w['intervals'])))
    metrics = {}
    line = {'correct': bool(correct), 'attempted': w['steps'], 'failed': out.failed}
    if trace:
        log('stage counters over the window: {}'.format(json.dumps(
            {k: v for k, v in sorted(out.counters.items())
             if k.startswith('stage_') or k.endswith('_total')})))
        record = {'window': w, 'counters': out.counters, 'trace': out.trace,
                  'config': cell.config, 'root': root,
                  'peaks': manifest.peaks(devices[0].device_kind, root)}
        for metric in cell.per_layer:
            value = manifest.metric_reader(metric, root)(record)
            if value is not None:
                metrics[metric['name']] = {'value': value, 'unit': metric['unit']}
        device['busy_s'] = out.trace['busy_s']
        device['window_s'] = out.trace['window_s']
        line['breakdown'] = out.trace['breakdown']
    else:
        end_to_end = {
            model.THROUGHPUT: w['units'] / w['seconds'],
            'step_interval_p95_ms': 1000 * percentile(w['intervals'], 95),
            'setup_s': setup_s,
        }
        for metric in cell.end_to_end:
            metrics[metric['name']] = {'value': end_to_end[metric['name']],
                                       'unit': metric['unit']}
    line['metrics'] = metrics
    line['device'] = device
    if out.readings is not None:
        line['readings'] = out.readings
    line['checks'] = out.checks
    for name, c in out.checks.items():
        print('check {}: {!r} (limit {!r})'.format(name, c['value'], c['limit']),
              file=sys.stderr, flush=True)
    return line
