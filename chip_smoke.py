#!/usr/bin/env python
"""chip_smoke.py — the ImageNet -> ResNet-50 train path, once, on a TPU.

The quickest proof that the product path still starts on the chip. One
process drives what a user's training loop drives:

  generate_synthetic_imagenet (JPEG, 320-560 px, from --seed)
  -> make_reader(transform_spec=make_transform(224, 1000)), thread pool
  -> JaxDataLoader -> prefetch_to_device
  -> make_train_step(preprocess_fn=device_preprocess) on resnet50 bf16,
     batch 128 at 224x224, with the Pallas normalize inside the step
  -> one more batch through reader_pool_type='process' (spawn + shm ring)

It checks that the step holds the Pallas kernel (``tpu_custom_call``), that
its first loss equals the same step built on the jnp normalize, that every
loss is finite, and that the native kernels decoded the store. Earlier lines
report compile seconds, the compile cache, a smoke examples/s reading (not a
benchmark metric), peak device memory and the decode path.

``--chips 4`` runs only the data-parallel path instead: a ('data',) mesh over
four chips, a global batch of 256 staged with ``data_sharding``, the state
placed with ``shard_train_state``, one step, compared with the same step on
one device.

The last stdout line is ``{"ok": true, "device": {...}}``. Without a TPU the
script exits non-zero and prints no result.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time

REPO_ROOT = os.path.dirname(os.path.abspath(__file__))

#: the generated store lives in the checkout (listed in .gitignore) and is
#: removed when the run ends
STORE_DIR = os.path.join(REPO_ROOT, '.smoke_store')

#: relative loss and parameter-update tolerances for comparisons of two bf16
#: steps that differ only in kernel choice or in partitioning
LOSS_RTOL = 1e-2
UPDATE_RTOL = 5e-2


class SmokeCheckFailed(RuntimeError):
    pass


def check(ok, message):
    if not ok:
        raise SmokeCheckFailed(message)


def log(message):
    print('chip_smoke: ' + message, flush=True)


def preprocess_jnp(images, rng):
    """``device_preprocess`` with the jnp normalize: the reference the Pallas
    step's loss is compared with."""
    import jax.numpy as jnp

    from examples.imagenet.jax_resnet_example import IMAGENET_MEAN, IMAGENET_STD
    from petastorm_tpu import ops
    images = ops.random_flip(images, rng)
    return ops.normalize_images(images, IMAGENET_MEAN, IMAGENET_STD,
                                out_dtype=jnp.bfloat16, use_pallas=False)


def build_store(store_dir, images, min_dim, max_dim, seed):
    """ImageNet-shaped JPEG store from ``seed``: 32 images per synset."""
    from examples.imagenet.generate_petastorm_imagenet import generate_synthetic_imagenet
    shutil.rmtree(store_dir, ignore_errors=True)
    per_synset = 32
    generate_synthetic_imagenet('file://' + store_dir,
                                num_synsets=max(1, images // per_synset),
                                images_per_synset=per_synset, rows_per_row_group=16,
                                seed=seed, image_codec='jpeg',
                                min_dim=min_dim, max_dim=max_dim)
    return 'file://' + store_dir


def _init_state(model_name, num_classes, image_size, seed):
    import jax
    import jax.numpy as jnp

    from petastorm_tpu import models
    from petastorm_tpu.models.train import create_train_state
    model = getattr(models, model_name)(num_classes=num_classes, dtype=jnp.bfloat16)
    return create_train_state(model, jax.random.PRNGKey(seed),
                              jnp.zeros((1, image_size, image_size, 3), jnp.float32))


def _compile(step, *args):
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


def _decode_path(counters):
    """What decoded the store, from the reader's counters. Raises when the
    native kernels are missing or a column fell back for another reason than
    the two this store has by design: its string columns (``codec``) and the
    image column, whose resize hint sends its bytes through Arrow into the
    native JPEG decoder (``image-hints``)."""
    from petastorm_tpu import native
    from petastorm_tpu.native import image_codec

    check(native.is_available(), 'native row-group kernel did not load '
          '(build failed?): reads would take the pyarrow fallback')
    check(image_codec.is_available(), 'native image codec did not load '
          '(build failed?): JPEGs would decode through OpenCV')
    check(counters.get('worker_rows_decoded_total', 0) > 0, 'no rows decoded')
    fallbacks = {k.split(':', 1)[1]: v for k, v in counters.items()
                 if k.startswith('fused_fallback_column:') and v > 0}
    expected = {'image:image-hints', 'noun_id:codec', 'text:codec'}
    check(set(fallbacks) <= expected,
          'unexpected fused-decode fallbacks: {}'.format(sorted(set(fallbacks) - expected)))
    return {'native_kernel': True, 'native_image_codec': True,
            'arrow_fallback_columns_total': counters.get('arrow_fallback_columns_total', 0),
            'fused_fallback_columns': fallbacks}


def run_single_chip(store_dir, *, images=2048, min_dim=320, max_dim=560,
                    batch_size=128, image_size=224, num_classes=1000,
                    model='resnet50', warmup_steps=2, steps=10, seed=0):
    """The main path on ``jax.devices()[0]``; raises SmokeCheckFailed on any
    failed check that holds on every platform. Returns what it saw, including
    whether the compiled step holds a Pallas kernel (``pallas_in_step``),
    which only a TPU can make true."""
    import jax
    import numpy as np

    from examples.imagenet.jax_resnet_example import device_preprocess
    from examples.imagenet.transform import make_transform
    from petastorm_tpu import make_reader
    from petastorm_tpu import observability as obs
    from petastorm_tpu.jax import JaxDataLoader, prefetch_to_device
    from petastorm_tpu.models.train import make_train_step

    device = jax.devices()[0]
    report = {}
    t0 = time.perf_counter()
    url = build_store(store_dir, images, min_dim, max_dim, seed)
    report['store_s'] = time.perf_counter() - t0
    report['store_bytes'] = sum(os.path.getsize(os.path.join(d, f))
                                for d, _, fs in os.walk(store_dir) for f in fs)
    log('store: {} JPEGs at {}-{} px, {} bytes, built in {:.1f} s'.format(
        images, min_dim, max_dim, report['store_bytes'], report['store_s']))

    state = jax.device_put(_init_state(model, num_classes, image_size, seed), device)
    step = make_train_step(preprocess_fn=device_preprocess, preprocess_seed=seed)
    ref_step = make_train_step(donate=False, preprocess_fn=preprocess_jnp,
                               preprocess_seed=seed)
    transform = make_transform(image_size, num_classes)
    counters_before = obs.snapshot().get('counters', {})
    losses = []
    with make_reader(url, num_epochs=None, seed=seed, transform_spec=transform) as reader:
        loader = JaxDataLoader(reader, batch_size, shuffling_queue_capacity=2 * batch_size,
                               seed=seed)
        batches = prefetch_to_device(loader, device, size=2)
        try:
            first = next(batches)
            check(first['image'].shape == (batch_size, image_size, image_size, 3)
                  and first['image'].dtype == np.uint8,
                  'bad image batch {} {}'.format(first['image'].shape, first['image'].dtype))
            check(first['label'].shape == (batch_size,), 'bad label batch')
            args = (state, first['image'], first['label'])
            compiled, report['compile_s'], report['cache_hits'] = _compile(step, *args)
            report['pallas_in_step'] = 'tpu_custom_call' in compiled.as_text()
            report['step_temp_bytes'] = compiled.memory_analysis().temp_size_in_bytes
            ref_compiled, report['ref_compile_s'], _ = _compile(ref_step, *args)
            log('compile: step {:.2f} s ({} cache hits), jnp reference step {:.2f} s; '
                'step temporaries {} bytes'.format(
                    report['compile_s'], report['cache_hits'], report['ref_compile_s'],
                    report['step_temp_bytes']))

            # the reference does not donate, so it runs first on the shared state
            _, ref_metrics = ref_compiled(*args)
            state, metrics = compiled(*args)
            loss, ref_loss = float(metrics['loss']), float(ref_metrics['loss'])
            report['first_loss'], report['ref_loss'] = loss, ref_loss
            log('first-batch loss: Pallas step {!r}, jnp step {!r}'.format(loss, ref_loss))
            check(abs(loss - ref_loss) <= LOSS_RTOL * max(1.0, abs(ref_loss)),
                  'Pallas step loss {} != jnp step loss {}'.format(loss, ref_loss))
            losses.append(loss)

            for _ in range(warmup_steps):
                b = next(batches)
                state, metrics = compiled(state, b['image'], b['label'])
                losses.append(float(jax.block_until_ready(metrics)['loss']))
            t0 = time.perf_counter()
            for _ in range(steps):
                b = next(batches)
                state, metrics = compiled(state, b['image'], b['label'])
                jax.block_until_ready((state, metrics))
                losses.append(float(metrics['loss']))
            report['steps_s'] = time.perf_counter() - t0
        finally:
            batches.close()
    counters = obs.snapshot().get('counters', {})
    delta = {k: v - counters_before.get(k, 0) for k, v in counters.items()}
    report['decode'] = _decode_path(delta)
    report['steps'] = steps
    report['smoke_examples_per_s'] = steps * batch_size / report['steps_s']
    log('{} timed steps in {:.3f} s: {:.1f} examples/s (smoke reading, not a '
        'benchmark metric)'.format(steps, report['steps_s'], report['smoke_examples_per_s']))
    log('decode path: {}'.format(json.dumps(report['decode'], sort_keys=True)))

    # one batch through spawned workers: they must never touch the chip
    with make_reader(url, reader_pool_type='process', workers_count=2,
                     num_epochs=None, seed=seed, transform_spec=transform) as reader:
        b = next(iter(JaxDataLoader(reader, batch_size, to_device=device)))
        state, metrics = compiled(state, b['image'], b['label'])
        losses.append(float(metrics['loss']))
    report['process_pool_loss'] = losses[-1]
    log('process-pool batch consumed: loss {!r}'.format(losses[-1]))

    report['losses'] = losses
    check(all(np.isfinite(losses)), 'non-finite loss: {}'.format(losses))
    stats = device.memory_stats() or {}
    report['peak_bytes_in_use'] = stats.get('peak_bytes_in_use')
    log('peak device memory: {} bytes'.format(report['peak_bytes_in_use']))
    return report


def _relative_update_diff(params_a, params_b, params_before):
    """|update_a - update_b| / |update_b| over the whole parameter tree."""
    import jax
    import numpy as np
    before = jax.device_get(params_before)
    a = jax.tree_util.tree_leaves(jax.device_get(params_a))
    b = jax.tree_util.tree_leaves(jax.device_get(params_b))
    old = jax.tree_util.tree_leaves(before)
    diff = sum(float(np.sum(np.square(np.float64(x) - y))) for x, y in zip(a, b))
    norm = sum(float(np.sum(np.square(np.float64(y) - o))) for y, o in zip(b, old))
    return float(np.sqrt(diff / max(norm, 1e-60)))


def run_data_parallel(store_dir, devices, *, per_device_batch=64, images=512,
                      min_dim=320, max_dim=560, image_size=224, num_classes=1000,
                      model='resnet50', seed=0):
    """One data-parallel step over ``devices`` against the same step on one
    device at the same global batch. Raises SmokeCheckFailed on a mismatch."""
    import jax

    from examples.imagenet.jax_resnet_example import device_preprocess
    from examples.imagenet.transform import make_transform
    from petastorm_tpu import make_reader
    from petastorm_tpu.jax import JaxDataLoader, prefetch_to_device
    from petastorm_tpu.models.train import make_train_step, shard_train_state
    from petastorm_tpu.parallel import data_sharding, make_mesh

    n = len(devices)
    global_batch = per_device_batch * n
    url = build_store(store_dir, images, min_dim, max_dim, seed)
    mesh = make_mesh(('data',), devices=devices)
    report = {'devices': n, 'global_batch': global_batch}

    with make_reader(url, num_epochs=None, seed=seed,
                     transform_spec=make_transform(image_size, num_classes)) as reader:
        batches = prefetch_to_device(JaxDataLoader(reader, global_batch, seed=seed),
                                     data_sharding(mesh), size=2)
        try:
            batch = next(batches)
        finally:
            batches.close()
    for name in ('image', 'label'):
        shards = batch[name].addressable_shards
        rows = sorted(s.data.shape[0] for s in shards)
        placed = {s.device for s in shards}
        log('{} shards: {} devices, rows {}'.format(name, len(placed), rows))
        check(len(placed) == n and rows == [per_device_batch] * n,
              '{} not split over {} devices: {} devices, rows {}'.format(
                  name, n, len(placed), rows))

    state = _init_state(model, num_classes, image_size, seed)
    step = make_train_step(donate=False, preprocess_fn=device_preprocess,
                           preprocess_seed=seed)
    with jax.set_mesh(mesh):
        dp_state = shard_train_state(state, mesh)
        dp_args = (dp_state, batch['image'], batch['label'])
        dp_compiled, report['dp_compile_s'], _ = _compile(step, *dp_args)
        dp_new, dp_metrics = dp_compiled(*dp_args)
    report['dp_pallas_in_step'] = 'tpu_custom_call' in dp_compiled.as_text()
    log('compile: data-parallel step {:.2f} s, Pallas kernel in it: {}'.format(
        report['dp_compile_s'], report['dp_pallas_in_step']))

    one = devices[0]
    one_args = (jax.device_put(state, one), jax.device_put(batch['image'], one),
                jax.device_put(batch['label'], one))
    one_compiled, report['one_compile_s'], _ = _compile(step, *one_args)
    mem = one_compiled.memory_analysis()
    need = mem.temp_size_in_bytes + mem.argument_size_in_bytes + mem.output_size_in_bytes
    limit = (one.memory_stats() or {}).get('bytes_limit')
    report['one_device_bytes'] = need
    log('one-device step at batch {}: compiled in {:.2f} s, {} bytes of '
        'arguments+outputs+temporaries, device limit {}'.format(
            global_batch, report['one_compile_s'], need, limit))
    check(limit is None or need < limit, 'one-device reference does not fit the device')
    one_new, one_metrics = one_compiled(*one_args)

    dp_loss, one_loss = float(dp_metrics['loss']), float(one_metrics['loss'])
    update_rel = _relative_update_diff(dp_new.params, one_new.params, state.params)
    report.update(dp_loss=dp_loss, one_loss=one_loss, update_rel_diff=update_rel)
    log('data-parallel loss {!r}, one-device loss {!r}, relative parameter-update '
        'difference {!r}'.format(dp_loss, one_loss, update_rel))
    check(abs(dp_loss - one_loss) <= LOSS_RTOL * max(1.0, abs(one_loss)),
          'data-parallel loss {} != one-device loss {}'.format(dp_loss, one_loss))
    check(update_rel <= UPDATE_RTOL,
          'data-parallel update differs from one-device update by {}'.format(update_rel))
    return report


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split('\n\n')[0])
    parser.add_argument('--chips', type=int, choices=(1, 4), default=1,
                        help='4: run only the data-parallel path over four chips')
    parser.add_argument('--seed', type=int, default=0)
    args = parser.parse_args(argv)

    import jax
    devices = jax.devices()
    platform, kind = devices[0].platform, devices[0].device_kind
    if platform != 'tpu':
        print('chip_smoke: needs a TPU; JAX found {} ({})'.format(platform, kind),
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print('chip_smoke: --chips {} but JAX found {} devices'.format(
            args.chips, len(devices)), file=sys.stderr)
        return 1
    from petastorm_tpu.jax.compile_cache import use_persistent_compile_cache
    cache_dir = use_persistent_compile_cache(REPO_ROOT)
    log('device {} x{}, compile cache {}'.format(kind, len(devices), cache_dir))

    try:
        if args.chips == 4:
            report = run_data_parallel(STORE_DIR, devices[:4], seed=args.seed)
            check(report['dp_pallas_in_step'],
                  'data-parallel step holds no tpu_custom_call')
        else:
            report = run_single_chip(STORE_DIR, seed=args.seed)
            log('Pallas kernel in compiled step: {}'.format(report['pallas_in_step']))
            check(report['pallas_in_step'],
                  'compiled step holds no tpu_custom_call: the Pallas normalize '
                  'gave way to the jnp path')
    finally:
        shutil.rmtree(STORE_DIR, ignore_errors=True)
    print(json.dumps({'ok': True, 'device': {'platform': platform, 'kind': kind,
                                             'count': len(devices)}}), flush=True)
    return 0


if __name__ == '__main__':
    sys.exit(main())
