"""ResNet-50 on an ImageNet-Parquet dataset over a TPU mesh — the BASELINE.md
north-star configuration (ImageNet Parquet + shuffle_row_groups + local disk
cache feeding ResNet-50; sharded multi-host reading via cur_shard/shard_count).

Per-host flow: this host's reader consumes the row-group shard derived from
``jax.process_index()``; worker threads decode+resize; the loader collates and
stages global device arrays over the mesh; the pjit-sharded train step runs on
all chips. No inter-host traffic on the data path (share-nothing, like the
reference's reader.py:485-502) — gradient collectives ride ICI via XLA.
"""

from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from examples.imagenet.transform import make_transform
from petastorm_tpu import make_reader
from petastorm_tpu import ops
from petastorm_tpu.jax import JaxDataLoader
from petastorm_tpu.models import resnet50
from petastorm_tpu.models.train import (create_train_state, make_train_step,
                                        shard_train_state)
from petastorm_tpu.parallel import data_sharding, make_mesh


# per-channel ImageNet stats in 0-255 units (normalization happens on device)
IMAGENET_MEAN = np.array([123.675, 116.28, 103.53], np.float32)
IMAGENET_STD = np.array([58.395, 57.12, 57.375], np.float32)


def device_preprocess(images, rng):
    """Fused on-device input ops: random flip + uint8->bf16 normalize."""
    images = ops.random_flip(images, rng)
    return ops.normalize_images(images, IMAGENET_MEAN, IMAGENET_STD,
                                out_dtype=jnp.bfloat16)


def train(dataset_url, batch_size=64, steps=100, image_size=160, num_classes=1000,
          cache_location=None, seed=0):
    mesh = make_mesh(('data',))
    sharding = data_sharding(mesh)

    model = resnet50(num_classes=num_classes, dtype=jnp.bfloat16)
    state = create_train_state(model, jax.random.PRNGKey(seed),
                               jnp.zeros((1, image_size, image_size, 3)))
    cache_kwargs = {}
    if cache_location:
        cache_kwargs = {'cache_type': 'local-disk', 'cache_location': cache_location,
                        'cache_size_limit': 10 << 30, 'cache_row_size_estimate': 200 << 10}

    with jax.set_mesh(mesh):
        state = shard_train_state(state, mesh)
        train_step = make_train_step(preprocess_fn=device_preprocess,
                                     preprocess_seed=seed)
        with make_reader(dataset_url, num_epochs=None, seed=seed,
                         shuffle_row_groups=True,
                         transform_spec=make_transform(image_size, num_classes),
                         cur_shard=jax.process_index(), shard_count=jax.process_count(),
                         **cache_kwargs) as reader:
            loader = JaxDataLoader(reader, batch_size, shuffling_queue_capacity=1024,
                                   seed=seed, to_device=sharding)
            for step, batch in enumerate(loader):
                state, metrics = train_step(state, batch['image'], batch['label'])
                if step % 10 == 0:
                    print('step {}: loss={:.4f}'.format(step, float(metrics['loss'])))
                if step + 1 >= steps:
                    break
    return state


def main():
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument('--dataset-url', default='file:///tmp/imagenet_dataset')
    parser.add_argument('--batch-size', type=int, default=64)
    parser.add_argument('--steps', type=int, default=100)
    parser.add_argument('--image-size', type=int, default=160)
    parser.add_argument('--num-classes', type=int, default=1000)
    parser.add_argument('--cache-location', default=None)
    args = parser.parse_args()
    train(args.dataset_url, args.batch_size, args.steps, args.image_size,
          args.num_classes, args.cache_location)


if __name__ == '__main__':
    main()
