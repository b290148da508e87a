"""ImageNet stored pre-decoded at the training resolution, generated from the
configuration's ``data_seed``: the ``write_mode=raw`` layout of FFCV's
ImageNet writer at 224x224.

A copy of ``bench_duty.build_raw_store``: uint8 ``RawTensorCodec`` images,
uncompressed, and an int64 label, plus ``record_id``, the record's index.
The pixels come from the JPEG store's generator at the stored size. The
store is one fixed data set: the run's seed draws the order it is delivered
in, not the records.

This module imports no JAX: it runs in spawned store-building processes.
"""

from __future__ import annotations

import numpy as np

from benchmark.stores import image_feed, jpeg_imagenet
from benchmark.stores.jpeg_imagenet import read_records

VERSION = 'v2'

#: nothing to plant in a decode: the records are stored as the step takes them
FAULTS = ()


def schema(config):
    from petastorm_tpu.codecs import RawTensorCodec, ScalarCodec
    from petastorm_tpu.unischema import Unischema, UnischemaField
    size = config['image_size']
    return Unischema('RawImagenet', [
        UnischemaField('image', np.uint8, (size, size, 3), RawTensorCodec(), False),
        UnischemaField('label', np.int64, (), ScalarCodec(np.int64), False),
        UnischemaField('record_id', np.int64, (), ScalarCodec(np.int64), False),
    ])


def rows(config, start, stop):
    size = config['image_size']
    for i in range(start, stop):
        rng = np.random.default_rng((config['data_seed'], i))
        yield {'image': jpeg_imagenet.synthetic_image(rng, size, size, config['texture']),
               'label': i % config['model']['num_classes'], 'record_id': i}


def transform(config):
    """None: the records are stored as the step takes them."""
    return None


def reference(path, record_ids, config, fault=None):
    """Plain pyarrow read of the records: the stored bytes as uint8
    ``[N, S, S, 3]`` images and the stored labels."""
    size = config['image_size']
    cells = read_records(path, record_ids, ['image', 'label'])
    images = np.stack([np.frombuffer(cells[int(r)][0], np.uint8).reshape(size, size, 3)
                       for r in record_ids])
    labels = np.array([cells[int(r)][1] for r in record_ids], np.int64)
    return images, labels


def records(config):
    return config['images']


def feed(store, config, traffic, seed, global_batch):
    """The shared image feed (``benchmark/stores/image_feed.py``) with this
    store's transform."""
    return image_feed.feed(store, config, traffic, seed, global_batch, transform(config))


def check(store, config, delivered, fault=None):
    """The feed's check (``benchmark/stores/image_feed.py``) against ``reference``."""
    return image_feed.check(delivered, *reference(store, delivered['record_id'], config, fault))
