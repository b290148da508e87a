"""bench_duty.py — the north-star duty-cycle benchmark as one command.

Builds a synthetic ImageNet-Parquet store (photo-like PNGs), runs a REAL jitted
ResNet-50 bf16 train step on the TPU, and measures how much wall time the step
loop spends blocked on input (`pipeline_duty_cycle`, BASELINE.md methodology).
It refuses to run without a TPU: a CPU number is not a duty cycle. Variants
isolate where the host budget goes:

  png        PNG decode + resize transform on the host (the baseline config)
  jpeg       realistic-size (320-560px) JPEG store, scaled DCT decode to
             ~target resolution + small resize — the format real ImageNet
             pipelines actually run
  raw        pre-resized uint8 RawTensorCodec store (zero-copy columnar
             decode) — the decode-free ceiling
  png_cached second epoch with a pre-filled local-disk cache (cache stores
             decoded rows, so PNG decode is skipped; resize still runs)

Emits one JSON line per variant:
  {"metric": "duty_cycle_<variant>", "examples_per_sec": ..,
   "input_stall_fraction": .., "host_cores": .., "device": ..,
   "device_kind": .., "device_count": ..}

Usage: python bench_duty.py [--steps 30] [--batch-size 64] [--image-size 160]
                            [--variants png,raw,png_cached] [--num-classes 1000]
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile

import numpy as np

# bump when build_raw_store's on-disk layout changes (reused --keep-dir stores
# are rebuilt instead of silently benchmarked under the new label)
RAW_STORE_FORMAT = 'v3-flba-pagescan'

REPO_ROOT = os.path.dirname(os.path.abspath(__file__))
if REPO_ROOT not in sys.path:
    sys.path.insert(0, REPO_ROOT)


def build_png_store(url, rows, seed=0, image_codec='png', min_dim=64, max_dim=160):
    from examples.imagenet.generate_petastorm_imagenet import generate_synthetic_imagenet
    images_per_synset = 32
    generate_synthetic_imagenet(url, num_synsets=max(1, rows // images_per_synset),
                                images_per_synset=images_per_synset,
                                rows_per_row_group=16, seed=seed, image_codec=image_codec,
                                min_dim=min_dim, max_dim=max_dim)


def build_raw_store(url, rows, image_size, num_classes, seed=0):
    """Pre-resized uint8 tensors + integer labels: zero host decode work.
    RawTensorCodec stores headerless cells, so whole-column decode is a
    zero-copy view of the Arrow buffer (~2.4x the NdarrayCodec block rate)."""
    from examples.imagenet.generate_petastorm_imagenet import synthetic_image
    from petastorm_tpu.codecs import RawTensorCodec, ScalarCodec
    from petastorm_tpu.etl.dataset_metadata import materialize_dataset
    from petastorm_tpu.unischema import Unischema, UnischemaField

    schema = Unischema('RawImagenet', [
        UnischemaField('image', np.uint8, (image_size, image_size, 3), RawTensorCodec(), False),
        UnischemaField('label', np.int64, (), ScalarCodec(np.int64), False),
    ])
    rng = np.random.default_rng(seed)
    # uncompressed: the raw variant is the decode-free ceiling; snappy on raw
    # pixel tensors costs read-side decompression for a marginal size win
    with materialize_dataset(url, schema, rows_per_row_group=64, compression='none') as writer:
        for i in range(rows):
            writer.write({'image': synthetic_image(rng, image_size, image_size),
                          'label': int(i % num_classes)})
    return schema


def make_step(image_size, num_classes, seed=0, model_factory=None):
    import jax
    import jax.numpy as jnp

    from examples.imagenet.jax_resnet_example import device_preprocess
    from petastorm_tpu.models import resnet50
    from petastorm_tpu.models.train import create_train_state, make_train_step

    model = (model_factory or resnet50)(num_classes=num_classes, dtype=jnp.bfloat16)
    state = create_train_state(model, jax.random.PRNGKey(seed),
                               jnp.zeros((1, image_size, image_size, 3)))
    state = jax.device_put(state, jax.devices()[0])
    train_step = make_train_step(donate=False, preprocess_fn=device_preprocess,
                                 preprocess_seed=seed)
    holder = {'state': state}

    def step_fn(images, labels):
        holder['state'], metrics = train_step(holder['state'], images, labels)
        return metrics['loss']

    return step_fn


def measure_kwargs(args):
    """The one measurement configuration shared by the variant runs and the
    sweep — points from both stay comparable."""
    return ({'seed': 7, 'shuffle_row_groups': True, 'workers_count': args.workers},
            {'shuffling_queue_capacity': 512, 'seed': 7})


def run_variant(variant, args, png_url, raw_url, jpeg_url, tmpdir):
    from examples.imagenet.transform import make_transform
    from petastorm_tpu import make_reader
    from petastorm_tpu.tools.throughput import pipeline_duty_cycle

    step_fn = make_step(args.image_size, args.num_classes)
    reader_kwargs, loader_kwargs = measure_kwargs(args)
    batch_to_args = lambda b: (b['image'], b['label'])  # noqa: E731
    if variant in ('png', 'png_cached'):
        url = png_url
        reader_kwargs['transform_spec'] = make_transform(args.image_size, args.num_classes)
    elif variant == 'jpeg':
        url = jpeg_url
        reader_kwargs['transform_spec'] = make_transform(args.image_size, args.num_classes)
    elif variant == 'raw':
        url = raw_url
    else:
        raise ValueError(variant)

    if variant == 'png_cached':
        cache_dir = os.path.join(tmpdir, 'disk_cache')
        reader_kwargs.update({'cache_type': 'local-disk', 'cache_location': cache_dir,
                              'cache_size_limit': 10 << 30,
                              'cache_row_size_estimate': 200 << 10})
        # pre-fill: one full epoch populates the decoded-row cache, so the
        # measured pass below behaves like every epoch after the first
        with make_reader(url, num_epochs=1, **reader_kwargs) as reader:
            for _ in reader:
                pass

    res = pipeline_duty_cycle(
        url, step_fn, batch_to_args, batch_size=args.batch_size, steps=args.steps,
        warmup_steps=args.warmup_steps, reader_kwargs=reader_kwargs,
        loader_kwargs=loader_kwargs)
    return res


#: the --sweep ladder: step cost rises ~monotonically (deeper, then wider);
#: bytes/example stay CONSTANT, so the sweep isolates "can the fixed host+
#: staging budget hide under a growing step" — the duty-vs-step-cost curve
SWEEP_MODELS = (
    ('resnet18', 'resnet18', 1),
    ('resnet50', 'resnet50', 1),
    ('resnet101', 'resnet101', 1),
    ('resnet152', 'resnet152', 1),
    ('resnet152w2', 'resnet152', 2),  # double width = ~4x FLOPs vs resnet152
)


def measure_step_ms(step_fn, batch_size, image_size, repeats=10):
    """Device-only cost of one train step (median of ``repeats``), staged
    input, fully blocked — the x-axis of the duty-vs-step-cost curve."""
    import statistics
    import time

    import jax
    import jax.numpy as jnp

    images = jax.device_put(jnp.zeros((batch_size, image_size, image_size, 3),
                                      dtype=jnp.uint8))
    labels = jax.device_put(jnp.zeros((batch_size,), dtype=jnp.int64))
    jax.block_until_ready(step_fn(images, labels))  # compile + warm
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(step_fn(images, labels))
        times.append(time.perf_counter() - t0)
    return statistics.median(times) * 1000


def run_sweep(args, raw_url, device_fields):
    """The duty-vs-step-cost curve on the raw store: one point per ladder
    model. Emits a JSON line per point; the curve demonstrates (or refutes)
    that the loader hides input time once the step is heavy enough — the
    BASELINE north-star claim, measured instead of inferred."""
    import functools

    from petastorm_tpu import models as model_zoo
    from petastorm_tpu.tools.throughput import pipeline_duty_cycle

    reader_kwargs, loader_kwargs = measure_kwargs(args)
    ladder = SWEEP_MODELS
    if args.sweep_models:
        wanted = {m.strip() for m in args.sweep_models.split(',')}
        unknown = wanted - {m[0] for m in SWEEP_MODELS}
        if unknown:
            raise SystemExit('unknown --sweep-models: {}'.format(sorted(unknown)))
        ladder = [m for m in SWEEP_MODELS if m[0] in wanted]
    results = []
    for label, factory_name, width in ladder:
        base = getattr(model_zoo, factory_name)
        factory = functools.partial(base, num_filters=64 * width)
        step_fn = make_step(args.image_size, args.num_classes, model_factory=factory)
        step_ms = measure_step_ms(step_fn, args.batch_size, args.image_size)
        res = pipeline_duty_cycle(
            raw_url, step_fn, lambda b: (b['image'], b['label']),
            batch_size=args.batch_size, steps=args.steps,
            warmup_steps=args.warmup_steps,
            reader_kwargs=reader_kwargs, loader_kwargs=loader_kwargs)
        point = {
            'metric': 'duty_sweep',
            'model': label,
            'step_ms': round(step_ms, 2),
            'consumption_ex_per_s': round(args.batch_size / (step_ms / 1000), 1),
            'examples_per_sec': round(res.samples_per_second, 1),
            'input_stall_fraction': round(res.input_stall_fraction, 4),
            'duty_cycle': round(1 - res.input_stall_fraction, 4),
            'batch_size': args.batch_size,
            'image_size': args.image_size,
            'steps': args.steps,
            **device_fields,
        }
        print(json.dumps(point), flush=True)
        results.append(point)
    best = min(results, key=lambda p: p['input_stall_fraction'])
    print(json.dumps({'metric': 'duty_sweep_best', **{k: best[k] for k in
                      ('model', 'step_ms', 'input_stall_fraction', 'duty_cycle',
                       'examples_per_sec')}, **device_fields}), flush=True)
    return results


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument('--steps', type=int, default=30)
    parser.add_argument('--warmup-steps', type=int, default=5)
    parser.add_argument('--batch-size', type=int, default=64)
    parser.add_argument('--image-size', type=int, default=160)
    parser.add_argument('--num-classes', type=int, default=1000)
    parser.add_argument('--rows', type=int, default=256)
    parser.add_argument('--workers', type=int, default=max(1, os.cpu_count() or 1))
    parser.add_argument('--variants', default='png,jpeg,raw,png_cached')
    parser.add_argument('--sweep', action='store_true',
                        help='duty-vs-step-cost curve on the raw store across '
                             'the model ladder (instead of --variants)')
    parser.add_argument('--sweep-models', default=None,
                        help='comma-separated subset of the ladder '
                             '(default: all of {})'.format(
                                 ','.join(m[0] for m in SWEEP_MODELS)))
    parser.add_argument('--keep-dir', default=None,
                        help='reuse/keep the dataset dir (default: fresh tempdir)')
    args = parser.parse_args(argv)

    import jax
    devices = jax.devices()
    device = devices[0].platform
    if device != 'tpu':
        raise SystemExit('bench_duty.py needs a TPU; JAX found {} ({})'.format(
            device, devices[0].device_kind))
    from petastorm_tpu.jax.compile_cache import use_persistent_compile_cache
    use_persistent_compile_cache(REPO_ROOT)
    device_fields = {'device': device, 'device_kind': devices[0].device_kind,
                     'device_count': len(devices)}

    tmpdir = args.keep_dir or tempfile.mkdtemp(prefix='bench_duty_')
    png_dir = os.path.join(tmpdir, 'imagenet_png')
    raw_dir = os.path.join(tmpdir, 'imagenet_raw')
    jpeg_dir = os.path.join(tmpdir, 'imagenet_jpeg')
    png_url, raw_url = 'file://' + png_dir, 'file://' + raw_dir
    jpeg_url = 'file://' + jpeg_dir
    variants = ['raw'] if args.sweep else \
        [v.strip() for v in args.variants.split(',') if v.strip()]
    try:
        if not os.path.exists(png_dir) and any(v.startswith('png') for v in variants):
            build_png_store(png_url, args.rows)
        # format stamp: a reused --keep-dir store from before a layout change
        # (e.g. the NdarrayCodec -> RawTensorCodec switch) must be rebuilt, not
        # silently measured under the new label
        raw_stamp = os.path.join(raw_dir, '.format_stamp')
        # layout version + build params: a stale --keep-dir store (older codec
        # OR different rows/size/classes) is rebuilt, never silently measured
        raw_spec = '{}:rows={}:image_size={}:num_classes={}'.format(
            RAW_STORE_FORMAT, args.rows, args.image_size, args.num_classes)
        raw_fresh = (os.path.exists(raw_stamp) and
                     open(raw_stamp).read().strip() == raw_spec)
        if 'raw' in variants and not raw_fresh:
            shutil.rmtree(raw_dir, ignore_errors=True)
            build_raw_store(raw_url, args.rows, args.image_size, args.num_classes)
            with open(raw_stamp, 'w') as f:
                f.write(raw_spec)
        if not os.path.exists(jpeg_dir) and 'jpeg' in variants:
            # realistic ImageNet photo sizes; scaled DCT decode shines here
            build_png_store(jpeg_url, args.rows, image_codec='jpeg',
                            min_dim=320, max_dim=560)

        if args.sweep:
            run_sweep(args, raw_url, device_fields)
            return
        for variant in variants:
            res = run_variant(variant, args, png_url, raw_url, jpeg_url, tmpdir)
            print(json.dumps({
                'metric': 'duty_cycle_{}'.format(variant),
                'examples_per_sec': round(res.samples_per_second, 1),
                'input_stall_fraction': round(res.input_stall_fraction, 4),
                'duty_cycle': round(1 - res.input_stall_fraction, 4),
                'host_cores': os.cpu_count(),
                **device_fields,
                'batch_size': args.batch_size,
                'image_size': args.image_size,
                'steps': args.steps,
            }), flush=True)
    finally:
        if args.keep_dir is None:
            shutil.rmtree(tmpdir, ignore_errors=True)


if __name__ == '__main__':
    main()
