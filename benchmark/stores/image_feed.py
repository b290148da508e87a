"""What the two ImageNet stores share: the feed the step takes its batches
from, and the feed's check against the store's plain read.

Each image store module defines ``transform(config)`` and
``reference(path, record_ids, config, fault=None)``, the plainly read
images and labels of the records, and hands its ``feed`` and ``check`` to
the functions here.
"""

from __future__ import annotations

import contextlib

import numpy as np


@contextlib.contextmanager
def feed(store, config, traffic, seed, global_batch, transform):
    """``make_reader`` over the store with the traffic's pool and shuffling,
    then ``JaxDataLoader`` with its shuffling buffer: host batches of
    ``image``, ``label`` and ``record_id``."""
    from benchmark.cell import log
    from petastorm_tpu import make_reader
    from petastorm_tpu.jax import JaxDataLoader
    from petastorm_tpu.native import image_codec
    log('native image codec: {}'.format(image_codec.is_available()))
    with make_reader('file://' + store, num_epochs=None, seed=seed,
                     shuffle_row_groups=traffic['shuffle_row_groups'],
                     reader_pool_type=traffic['reader_pool_type'],
                     workers_count=traffic['workers_count'],
                     transform_spec=transform) as reader:
        yield JaxDataLoader(reader, global_batch,
                            shuffling_queue_capacity=traffic['shuffling_queue_capacity'],
                            seed=seed)


def numbers(delivered_images, ref_images, delivered_labels, ref_labels):
    """``pixel_gap``: the worst row's 1 - Pearson correlation of delivered
    and plainly read pixels; ``pixel_errors``: rows whose pixels differ at
    all; ``label_errors``: rows whose label differs."""
    a = delivered_images.reshape(len(delivered_images), -1).astype(np.float64)
    b = ref_images.reshape(len(ref_images), -1).astype(np.float64)
    a -= a.mean(axis=1, keepdims=True)
    b -= b.mean(axis=1, keepdims=True)
    correlation = (a * b).sum(axis=1) / np.sqrt((a * a).sum(axis=1) * (b * b).sum(axis=1))
    return {
        'pixel_gap': float(np.max(1.0 - correlation)),
        'pixel_errors': int(np.sum(np.any(delivered_images != ref_images, axis=(1, 2, 3)))),
        'label_errors': int(np.sum(delivered_labels != ref_labels)),
    }


def check(delivered, ref_images, ref_labels):
    """The feed's numbers for the ``delivered`` rows, with ``ref_images``
    and ``ref_labels`` read plainly for their ``record_id``s, and the inputs
    of the reference step: the delivered images, which the numbers hold to
    the plain read, with the stored records' labels."""
    return (numbers(delivered['image'], ref_images, delivered['label'], ref_labels),
            (delivered['image'], ref_labels))
