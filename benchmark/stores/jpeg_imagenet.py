"""ILSVRC-2012-shaped JPEG store, generated from the configuration's
``data_seed``.

A copy of ``examples/imagenet/generate_petastorm_imagenet.generate_synthetic_imagenet``
(schema ``noun_id``/``text``/``image``, JPEG at ``jpeg_quality``), changed so
that records come near the ILSVRC-2012 mean size: the image carries two
scales of noise over the smooth gradient, which gives JPEG the
high-frequency content of a photograph. Each record also carries
``record_id``, its index, as an ILSVRC record carries its file name.

The store is one fixed data set, as ImageNet is: the run's seed draws the
order the reader and the loader deliver it in, not the records.

This module imports no JAX: it runs in spawned store-building processes.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import zlib

import numpy as np

from benchmark.stores import image_feed

VERSION = 'v2'

#: what ``reference`` can plant in the decode in the program's place, for
#: ``benchmark/readings.py``: another resize filter, or another DCT scale
FAULTS = ('area_filter', 'nearest_filter', 'full_scale', 'finer_scale')

_SOURCE = os.path.join(os.path.dirname(os.path.abspath(__file__)), 'jpeg_scaled.c')
_BUILD_DIR = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), '.build')


def schema(config):
    from petastorm_tpu.codecs import CompressedImageCodec, ScalarCodec
    from petastorm_tpu.unischema import Unischema, UnischemaField
    return Unischema('ImagenetSchema', [
        UnischemaField('noun_id', np.str_, (), ScalarCodec(), False),
        UnischemaField('text', np.str_, (), ScalarCodec(), False),
        UnischemaField('image', np.uint8, (None, None, 3),
                       CompressedImageCodec('jpeg', quality=config['jpeg_quality']), False),
        UnischemaField('record_id', np.int64, (), ScalarCodec(), False),
    ])


def layout(config):
    """``(heights, widths, synsets)`` of every record, in store order."""
    n = config['images']
    sizes = np.random.default_rng(config['data_seed']).integers(
        config['min_dim'], config['max_dim'] + 1, size=(n, 2))
    return sizes[:, 0], sizes[:, 1], np.arange(n) % config['synsets']


def synthetic_image(rng, h, w, texture):
    """Smooth gradients plus coarse and fine noise, as uint8 RGB."""
    import cv2
    yy = np.linspace(0, 4 * np.pi, h, dtype=np.float32)[:, None, None]
    xx = np.linspace(0, 4 * np.pi, w, dtype=np.float32)[None, :, None]
    phase = rng.uniform(0, 2 * np.pi, 3).astype(np.float32)[None, None, :]
    image = np.sin(xx + phase) * 40 + np.cos(yy + phase * 0.5) * 30 + 128
    cell = texture['coarse_cell']
    coarse = rng.standard_normal((h // cell + 1, w // cell + 1, 3), dtype=np.float32)
    image += cv2.resize(coarse, (w, h), interpolation=cv2.INTER_LINEAR) * texture['coarse_sigma']
    image += rng.standard_normal((h, w, 3), dtype=np.float32) * texture['fine_sigma']
    return np.clip(image, 0, 255).astype(np.uint8)


def rows(config, start, stop):
    heights, widths, synsets = layout(config)
    for i in range(start, stop):
        rng = np.random.default_rng((config['data_seed'], i))
        yield {'noun_id': 'n{:08d}'.format(synsets[i]),
               'text': 'synthetic synset {}'.format(synsets[i]),
               'image': synthetic_image(rng, int(heights[i]), int(widths[i]), config['texture']),
               'record_id': i}


class _KeepRecordId(object):
    """The example's batched transform, with ``record_id`` carried along."""

    def __init__(self, inner):
        self.inner = inner

    def __call__(self, block):
        out = self.inner(block)
        out['record_id'] = block['record_id']
        return out


def transform(config):
    """``examples/imagenet/transform.make_transform`` (native decode at the
    DCT scale covering the training size, resize, label from the synset),
    keeping ``record_id``."""
    from examples.imagenet.transform import make_transform
    from petastorm_tpu import TransformSpec
    from petastorm_tpu.unischema import UnischemaField
    spec = make_transform(config['image_size'], config['model']['num_classes'])
    return TransformSpec(
        _KeepRecordId(spec.func),
        edit_fields=spec.edit_fields + [UnischemaField('record_id', np.int64, (), None, False)],
        removed_fields=spec.removed_fields, batched=spec.batched,
        image_resize=spec.image_resize)


def _decoder():
    """``scaled_decode`` of ``jpeg_scaled.c``, built once into
    ``benchmark/.build`` against the system libjpeg."""
    with open(_SOURCE, 'rb') as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:12]
    lib_path = os.path.join(_BUILD_DIR, 'jpeg_scaled-{}.so'.format(digest))
    if not os.path.exists(lib_path):
        os.makedirs(_BUILD_DIR, exist_ok=True)
        tmp = '{}.{}.tmp'.format(lib_path, os.getpid())
        subprocess.run(['cc', '-O2', '-shared', '-fPIC', _SOURCE, '-o', tmp, '-ljpeg'],
                       check=True)
        os.replace(tmp, lib_path)
    fn = ctypes.CDLL(lib_path).scaled_decode
    fn.restype = ctypes.c_int
    fn.argtypes = [ctypes.c_char_p, ctypes.c_ulong, ctypes.c_int, ctypes.c_int, ctypes.c_int,
                   ctypes.c_void_p, ctypes.POINTER(ctypes.c_int)]
    return fn


def decode(decoder, data, size, fault=None):
    """One stored JPEG as the training path states it: decoded at the
    smallest m/8 DCT scale covering ``size`` x ``size``, then resized to it
    by bilinear interpolation where no axis is cut by 2x or more, by area
    otherwise (``petastorm_tpu.codecs._mild_ratio``'s rule). ``fault``, one
    of ``FAULTS``, plants another filter or scale."""
    import cv2
    dims = (ctypes.c_int * 3)()
    if decoder(data, len(data), size, size, 0, None, dims) != 0:
        raise ValueError('not a JPEG')
    scale_num = {'full_scale': 8, 'finer_scale': min(8, dims[2] + 1)}.get(fault, 0)
    if scale_num:
        decoder(data, len(data), size, size, scale_num, None, dims)
    width, height = dims[0], dims[1]
    rgb = np.empty((height, width, 3), np.uint8)
    if decoder(data, len(data), size, size, scale_num, rgb.ctypes.data, dims) != 0:
        raise ValueError('not a JPEG')
    mild = height < 2 * size and width < 2 * size or height < size or width < size
    interpolation = {'area_filter': cv2.INTER_AREA, 'nearest_filter': cv2.INTER_NEAREST}.get(
        fault, cv2.INTER_LINEAR if mild else cv2.INTER_AREA)
    return cv2.resize(rgb, (size, size), interpolation=interpolation)


def reference(path, record_ids, config, fault=None):
    """Plain read of the records ``record_ids`` (pyarrow), plain decode
    (``decode``) and the label the training path derives:
    ``crc32(noun_id) % num_classes``. Returns ``(images uint8 [N, S, S, 3],
    labels int64 [N])``."""
    size, num_classes = config['image_size'], config['model']['num_classes']
    decoder = _decoder()
    cells = read_records(path, record_ids, ['image', 'noun_id'])
    images = np.empty((len(record_ids), size, size, 3), np.uint8)
    labels = np.empty(len(record_ids), np.int64)
    for k, rid in enumerate(record_ids):
        image, noun_id = cells[int(rid)]
        images[k] = decode(decoder, image, size, fault)
        labels[k] = zlib.crc32(noun_id.encode()) % num_classes
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


def read_records(path, record_ids, columns):
    """``{record_id: tuple of the columns' cells}`` for the wanted records,
    read with pyarrow alone."""
    import pyarrow.dataset as ds
    table = ds.dataset(path, format='parquet').to_table(
        columns=['record_id'] + columns,
        filter=ds.field('record_id').isin(sorted({int(r) for r in record_ids})))
    ids = table.column('record_id').to_pylist()
    cols = [table.column(c).to_pylist() for c in columns]
    return {rid: tuple(c[k] for c in cols) for k, rid in enumerate(ids)}
