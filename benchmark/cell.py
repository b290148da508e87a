"""One run of a cell: set-up, the measured window, the traced window's
reduction and the comparison that decides ``correct``.

The path driven is the one ``README.md`` documents for training on a mesh:
``make_reader`` -> ``JaxDataLoader`` -> ``prefetch_to_device`` onto
``data_sharding(make_mesh(('data',)))`` -> the AOT-compiled
``make_train_step(preprocess_fn=device_preprocess)`` on ResNet in bfloat16,
which donates its state. The mesh spans the cell's chips and is entered
with ``jax.set_mesh`` on one chip too.
"""

from __future__ import annotations

import json
import os
import shutil
import statistics
import sys
import time

import numpy as np

from benchmark import manifest

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


def build_store(cell, root):
    from benchmark.stores.common import build_store as build
    t0 = time.perf_counter()
    path = build(manifest.store_path(cell.config, root), cell.config,
                 os.path.join(root, 'benchmark', '.store_cache'))
    files = [os.path.join(d, f) for d, _, fs in os.walk(path) for f in fs
             if f.endswith('.parquet')]
    size = sum(os.path.getsize(f) for f in files)
    log('store {}: {} records, {} bytes in parquet files, {:.1f} bytes a record, '
        'ready in {:.2f} s; host cpu_count {}'.format(
            os.path.basename(path), cell.config['images'], size,
            size / cell.config['images'], time.perf_counter() - t0, os.cpu_count()))
    return path


def make_model(model):
    import jax.numpy as jnp

    from petastorm_tpu.models.resnet import BottleneckBlock, ResNet
    return ResNet(stage_sizes=model['stage_sizes'], block_cls=BottleneckBlock,
                  num_filters=model['num_filters'], num_classes=model['num_classes'],
                  dtype=jnp.dtype(model['dtype']))


def make_state(config, seed, mesh):
    """The program's TrainState holding the benchmark's weights for ``seed``,
    made on the mesh in one jitted call."""
    import jax
    import optax

    from benchmark import reference
    from petastorm_tpu.models.train import TrainState, state_shardings
    model = make_model(config['model'])
    opt = config['optimizer']
    tx = optax.sgd(opt['learning_rate'], momentum=opt['momentum'])

    def build(key_data):
        params, stats = reference.init_variables(config['model'], config['init'], key_data)
        return TrainState.create(apply_fn=model.apply, params=params, batch_stats=stats, tx=tx)

    key_data = reference.seed_key(seed)
    shardings = state_shardings(jax.eval_shape(build, key_data), mesh)
    return jax.jit(build, out_shardings=shardings)(key_data)


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


def first_gradient(opt_state):
    """The gradient of the first step, as SGD with momentum keeps it: its
    trace, which starts at zero, equals the first gradient after one step."""
    return opt_state[0].trace


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
    planted faults: ``'step'`` replaces the train step factory (the control
    is put in the program's place so too), ``'batches'`` wraps the device
    batch iterator.
    ``readings(**facts)`` (``benchmark/readings.py`` only) is called after the
    comparison with what it compared; its dict goes into the line under
    ``readings``."""
    import jax

    faults = faults or {}
    devices = devices_for(cell, require_tpu)[:cell.chips]
    store = build_store(cell, root)

    import jax.numpy as jnp

    from benchmark import reference
    from examples.imagenet.jax_resnet_example import device_preprocess
    from petastorm_tpu import make_reader
    from petastorm_tpu.jax import JaxDataLoader, prefetch_to_device
    from petastorm_tpu.jax.compile_cache import use_persistent_compile_cache
    from petastorm_tpu.models.train import make_train_step
    from petastorm_tpu.parallel import data_sharding, make_mesh

    config, traffic = cell.config, cell.traffic
    cache_dir = use_persistent_compile_cache(root)
    store_module = manifest.load_module(manifest.store_path(config, root))
    size = config['image_size']
    global_batch = traffic['batch_per_chip'] * cell.chips
    check_steps = config['check_steps']
    rng = np.random.default_rng(seed)
    samples = set(rng.choice(SAMPLE_SPAN, traffic['sampled_batches'], replace=False).tolist())
    out = Run()

    mesh = make_mesh(('data',), devices=devices)
    with jax.set_mesh(mesh):
        state = make_state(config, seed, mesh)
        make_step = faults.get('step', make_train_step)
        step = make_step(preprocess_fn=device_preprocess,
                         preprocess_seed=config['model']['augment_seed'])
        sharding = data_sharding(mesh)
        compiled, compile_s, hits = compile_step(
            step, state,
            jax.ShapeDtypeStruct((global_batch, size, size, 3), jnp.uint8, sharding=sharding),
            jax.ShapeDtypeStruct((global_batch,), jnp.int32, sharding=sharding))
        from petastorm_tpu import native
        from petastorm_tpu.native import image_codec
        log('step compiled in {:.2f} s ({} compile-cache hits, cache {}); Pallas kernel '
            'in step: {}; native row-group kernel: {}; native image codec: {}'.format(
                compile_s, hits, cache_dir, 'tpu_custom_call' in compiled.as_text(),
                native.is_available(), image_codec.is_available()))
        start = jax.device_get(state.params)

        reader = make_reader('file://' + store, num_epochs=None, seed=seed,
                             shuffle_row_groups=traffic['shuffle_row_groups'],
                             reader_pool_type=traffic['reader_pool_type'],
                             workers_count=traffic['workers_count'],
                             transform_spec=store_module.transform(config))
        with reader:
            loader = JaxDataLoader(reader, global_batch,
                                   shuffling_queue_capacity=traffic['shuffling_queue_capacity'],
                                   seed=seed)
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
                    state, metrics = compiled(state, batch['image'], batch['label'])
                    check_losses.append(metrics['loss'])
                    if k == 0:
                        grad1 = jax.device_get(first_gradient(state.opt_state))
                end = jax.device_get(state.params)
                check_losses = [float(v) for v in check_losses]
                for _ in range(traffic['warmup_steps']):
                    batch = next(batches)
                    state, metrics = compiled(state, batch['image'], batch['label'])
                setup_s = time.perf_counter() - t_start
                state, sampled = _window(out, compiled, state, batches, metrics, seconds,
                                         samples, trace, cell, seed, root)
                kept.extend(sampled)
            finally:
                batches.close()
        out.window['chips'] = cell.chips
        out.window['global_batch'] = global_batch
        out.window['image_size'] = size
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
        delivered = [(np.asarray(jax.device_get(b['record_id'])),
                      np.asarray(jax.device_get(b['image'])),
                      np.asarray(jax.device_get(b['label']))) for b in kept]
        del state, compiled, kept, batch, metrics

    # the comparison, with the program's state freed
    t0 = time.perf_counter()
    ids = np.concatenate([d[0] for d in delivered])
    ref_images, ref_labels = store_module.reference(store, ids, config)
    delivered_images = np.concatenate([d[1] for d in delivered])
    delivered_labels = np.concatenate([d[2] for d in delivered])
    # the reference step takes the batch the step took, with the labels of
    # the stored records; the images are held to the plain decode above
    rows = [slice(i * global_batch, (i + 1) * global_batch) for i in range(check_steps)]
    ref_batches = [(delivered_images[r], ref_labels[r]) for r in rows]
    ref = reference.train(config, seed, ref_batches, device=devices[0])
    program = {'losses': check_losses, 'grad1': grad1,
               'change': jax.tree_util.tree_map(lambda a, b: np.float64(a) - b, end, start)}
    numbers = compare(program, ref, delivered_images, ref_images, delivered_labels,
                      ref_labels)
    log('reference compared in {:.2f} s; worst leaves: first gradient {} {!r}, change {} '
        '{!r}'.format(time.perf_counter() - t0, numbers.pop('_grad1_leaf'),
                      numbers['grad1_worst_gap'], numbers.pop('_change_leaf'),
                      numbers['change_worst_gap']))
    out.checks = {name: {'value': numbers[name], 'limit': limit}
                  for name, limit in config['limits'].items()}
    if readings is not None:
        out.readings = dict(readings(
            config=config, seed=seed, program=program, ref=ref, ref_batches=ref_batches,
            delivered_images=delivered_images, ref_images=ref_images,
            delivered_labels=delivered_labels, ref_labels=ref_labels, device=devices[0],
            store=store, store_module=store_module, ids=ids),
            program=numbers)
    correct = (all(c['value'] <= c['limit'] for c in out.checks.values())
               and out.failed == 0 and out.window['steps'] > 0)
    return result_line(cell, out, correct, setup_s, memory_peak, trace, root)


def compare(program, ref, delivered_images, ref_images, delivered_labels, ref_labels):
    """Every number the configurations compare; each configuration holds
    those it gives a limit. ``loss_gap`` is the largest relative gap of the
    first steps' losses and ``loss1_gap`` that of the first step's;
    ``grad1_gap`` and ``change_gap`` are the median leaf's gap of norms of
    the first gradient and of the parameters' change over the first steps,
    ``grad1_worst_gap`` and ``change_worst_gap`` the worst leaf's, and
    ``grad1_diff`` and ``change_diff`` the median leaf's norm of the
    difference."""
    from benchmark import reference
    grad1, grad1_worst, grad1_leaf = reference.leaf_gaps(program['grad1'], ref['grad1'])
    change, change_worst, change_leaf = reference.leaf_gaps(program['change'], ref['change'])
    a = delivered_images.reshape(len(delivered_images), -1).astype(np.float64)
    b = ref_images.reshape(len(ref_images), -1).astype(np.float64)
    a -= a.mean(axis=1, keepdims=True)
    b -= b.mean(axis=1, keepdims=True)
    correlation = (a * b).sum(axis=1) / np.sqrt((a * a).sum(axis=1) * (b * b).sum(axis=1))
    return {
        'loss_gap': reference.loss_gap(program['losses'], ref['losses']),
        'loss1_gap': reference.loss_gap(program['losses'][:1], ref['losses'][:1]),
        'grad1_gap': grad1,
        'grad1_diff': reference.leaf_difference(program['grad1'], ref['grad1']),
        'change_diff': reference.leaf_difference(program['change'], ref['change']),
        'change_gap': change,
        'grad1_worst_gap': grad1_worst,
        'change_worst_gap': change_worst,
        'pixel_gap': float(np.max(1.0 - correlation)),
        'pixel_errors': int(np.sum(np.any(delivered_images != ref_images, axis=(1, 2, 3)))),
        'label_errors': int(np.sum(delivered_labels != ref_labels)),
        '_grad1_leaf': grad1_leaf,
        '_change_leaf': change_leaf,
    }


def _steps(compiled, state, batches, metrics, done, samples=()):
    """Steps from a step boundary until ``done(steps, seconds)``, then until
    the last step dispatched is ready. Each step's completion is observed as
    a loop that logs its loss does: dispatch step i, then wait for step
    i-1's loss. Returns ``(state, facts)``."""
    import jax

    jax.block_until_ready(metrics['loss'])
    sampled, losses = [], []
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
            state, metrics = compiled(state, batch['image'], batch['label'])
        if i in samples:
            sampled.append(batch)
        losses.append(metrics['loss'])
        if prev is not None:
            prev.block_until_ready()
            completions.append(time.perf_counter())
        prev = metrics['loss']
        i += 1
        if done(i, completions[-1] - t0):
            break
    prev.block_until_ready()
    completions.append(time.perf_counter())
    return state, {'seconds': completions[-1] - t0, 'steps': i, 'losses': losses,
                   'intervals': np.diff(completions).tolist(), 'loader_wait_s': wait_s,
                   'sampled': sampled, 'metrics': metrics}


def _window(out, compiled, state, batches, metrics, seconds, samples, trace, cell, seed,
            root):
    """The measured window of ``seconds``. With ``trace``, a traced window of
    the traffic's ``trace_steps`` steps follows it: the profiler stalls the
    host's dispatch for most of a second every few steps (``PERF.md``), so
    no end-to-end time is taken under it."""
    import jax

    before = _counters()
    state, w = _steps(compiled, state, batches, metrics,
                      lambda steps, elapsed: elapsed >= seconds, samples)
    after = _counters()
    losses = np.asarray(jax.device_get(w.pop('losses')))
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
                state, _ = _steps(compiled, state, batches, metrics,
                                  lambda n, elapsed: n >= steps)
        finally:
            jax.profiler.stop_trace()
        out.trace = trace_reduce.reduce(trace_reduce.extract(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
    return state, sampled


def percentile(values, q):
    """The ``q``-th percentile of ``values`` by linear interpolation."""
    return float(np.percentile(np.asarray(values, np.float64), q))


def result_line(cell, out, correct, setup_s, memory_peak, trace, root):
    import jax
    w = out.window
    devices = jax.devices()
    device = {'platform': devices[0].platform, 'kind': devices[0].device_kind,
              'count': len(devices), 'memory_peak_bytes': int(memory_peak)}
    images = w['steps'] * w['global_batch']
    log('window: {} steps, {} images in {:.4f} s; loader wait {:.4f} s; interval median '
        '{:.3f} ms'.format(w['steps'], images, w['seconds'], w['loader_wait_s'],
                          1000 * statistics.median(w['intervals'])))
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
            'images_per_s': images / w['seconds'],
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
