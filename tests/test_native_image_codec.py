"""Batched native PNG/JPEG decode (native/image_codec.cpp) vs the OpenCV path.

The native decoder must be bit-exact with ``CompressedImageCodec.decode`` for
every flavor it claims (PNG gray/RGB 8/16-bit, JPEG gray/RGB) and must cleanly
reject — so the codec falls back to OpenCV — everything else (palette/alpha
PNG, corrupt bytes). Reference behavior being matched:
/root/reference/petastorm/codecs.py:92-111 (per-image decode, RGB output).
"""

import io

import numpy as np
import pytest

from petastorm_tpu.codecs import CompressedImageCodec
from petastorm_tpu.native import image_codec
from petastorm_tpu.unischema import UnischemaField

cv2 = pytest.importorskip('cv2')

pytestmark = pytest.mark.skipif(not image_codec.is_available(),
                                reason='native image codec not built')

rng = np.random.default_rng(7)


def _png(arr):
    ok, buf = cv2.imencode('.png', arr if arr.ndim == 2 else cv2.cvtColor(arr, cv2.COLOR_RGB2BGR))
    assert ok
    return buf.tobytes()


def _jpeg(arr, quality=85):
    ok, buf = cv2.imencode('.jpeg', arr if arr.ndim == 2 else cv2.cvtColor(arr, cv2.COLOR_RGB2BGR),
                           [int(cv2.IMWRITE_JPEG_QUALITY), quality])
    assert ok
    return buf.tobytes()


def _cv2_decode(blob):
    img = cv2.imdecode(np.frombuffer(blob, np.uint8), cv2.IMREAD_UNCHANGED)
    if img.ndim == 3 and img.shape[2] == 3:
        img = cv2.cvtColor(img, cv2.COLOR_BGR2RGB)
    return img


@pytest.mark.parametrize('shape,dtype,fmt', [
    ((37, 53, 3), np.uint8, 'png'),
    ((64, 64), np.uint8, 'png'),
    ((21, 33), np.uint16, 'png'),
    ((40, 56, 3), np.uint16, 'png'),
    ((37, 53, 3), np.uint8, 'jpeg'),
    ((64, 64), np.uint8, 'jpeg'),
    ((1, 1, 3), np.uint8, 'png'),
    ((1, 7), np.uint8, 'png'),
])
def test_native_matches_cv2(shape, dtype, fmt):
    hi = 65536 if dtype == np.uint16 else 256
    img = rng.integers(0, hi, shape, dtype=dtype)
    blob = _png(img) if fmt == 'png' else _jpeg(img)
    (out,) = image_codec.decode_images([blob])
    np.testing.assert_array_equal(out, _cv2_decode(blob))


def test_natural_content_filtered_rows():
    # smooth content makes the encoder choose Sub/Up/Average/Paeth filters —
    # exercises every unfilter branch including the SSE2 Paeth path
    x = np.linspace(0, 6 * np.pi, 96)
    img = np.clip(np.sin(x)[None, :, None] * 90 + np.cos(x)[:, None, None] * 90 + 128
                  + rng.normal(0, 5, (96, 96, 3)), 0, 255).astype(np.uint8)
    blob = _png(img)
    (out,) = image_codec.decode_images([blob])
    np.testing.assert_array_equal(out, _cv2_decode(blob))


def test_interlaced_png_via_libpng_fallback():
    from PIL import Image

    img = rng.integers(0, 256, (48, 32, 3), np.uint8)
    buf = io.BytesIO()
    Image.fromarray(img).save(buf, format='png', interlace=True)
    blob = buf.getvalue()
    (out,) = image_codec.decode_images([blob])  # fast path bails; libpng path
    np.testing.assert_array_equal(out, img)


def test_mixed_batch_sizes_and_formats():
    imgs = [rng.integers(0, 256, s, np.uint8)
            for s in [(16, 24, 3), (50, 10), (33, 47, 3)]]
    blobs = [_png(imgs[0]), _png(imgs[1]), _jpeg(imgs[2])]
    outs = image_codec.decode_images(blobs)
    np.testing.assert_array_equal(outs[0], imgs[0])
    np.testing.assert_array_equal(outs[1], imgs[1])
    np.testing.assert_array_equal(outs[2], _cv2_decode(blobs[2]))


def test_memoryview_input():
    img = rng.integers(0, 256, (20, 20, 3), np.uint8)
    blob = _png(img)
    (out,) = image_codec.decode_images([memoryview(blob)])
    np.testing.assert_array_equal(out, img)


def test_threads_fanout_matches_single():
    imgs = [rng.integers(0, 256, (31 + i, 17 + i, 3), np.uint8) for i in range(20)]
    blobs = [_png(im) for im in imgs]
    single = image_codec.decode_images(blobs, threads=1)
    fanned = image_codec.decode_images(blobs, threads=4)
    for s, f in zip(single, fanned):
        np.testing.assert_array_equal(s, f)


@pytest.mark.parametrize('bad', [
    b'not an image at all',
    b'\x89PNG\r\n\x1a\n' + b'\x00' * 20,  # corrupt header
])
def test_unsupported_raises_native_decode_error(bad):
    with pytest.raises(image_codec.NativeDecodeError):
        image_codec.decode_images([bad])


def test_rgba_png_rejected_natively():
    rgba = rng.integers(0, 256, (12, 12, 4), np.uint8)
    ok, buf = cv2.imencode('.png', rgba)
    assert ok
    with pytest.raises(image_codec.NativeDecodeError) as info:
        image_codec.decode_images([buf.tobytes()])
    assert info.value.index == 0


def test_codec_decode_batch_equals_decode_and_handles_none():
    codec = CompressedImageCodec('png')
    field = UnischemaField('im', np.uint8, (None, None, 3), codec, True)
    imgs = [rng.integers(0, 256, (14 + i, 9, 3), np.uint8) for i in range(4)]
    cells = [codec.encode(field, im) for im in imgs]
    cells.insert(2, None)  # nullable cell
    out = codec.decode_batch(field, cells)
    assert out[2] is None
    expect = [codec.decode(field, c) for c in cells if c is not None]
    got = [o for o in out if o is not None]
    for e, g in zip(expect, got):
        np.testing.assert_array_equal(e, g)


def test_codec_decode_batch_falls_back_on_unsupported():
    # an alpha png in the column forces the whole-column OpenCV fallback;
    # results must still match per-image decode of the supported cells
    codec = CompressedImageCodec('png')
    field = UnischemaField('im', np.uint8, None, codec, False)
    rgb = rng.integers(0, 256, (10, 11, 3), np.uint8)
    rgba = rng.integers(0, 256, (10, 11, 4), np.uint8)
    ok, rgba_blob = cv2.imencode('.png', rgba)
    assert ok
    cells = [codec.encode(field, rgb), rgba_blob.tobytes()]
    out = codec.decode_batch(field, cells)
    np.testing.assert_array_equal(out[0], rgb)
    np.testing.assert_array_equal(out[1], cv2.imdecode(np.frombuffer(cells[1], np.uint8),
                                                       cv2.IMREAD_UNCHANGED))


def test_uint16_rgb_png_roundtrip_through_codec():
    codec = CompressedImageCodec('png')
    field = UnischemaField('im', np.uint16, (18, 22, 3), codec, False)
    img = rng.integers(0, 65536, (18, 22, 3), np.uint16)
    (out,) = codec.decode_batch(field, [codec.encode(field, img)])
    np.testing.assert_array_equal(out, img)


# -- scaled JPEG decode (round 3) --------------------------------------------

def _jpeg_bytes(h, w, quality=85, seed=0):
    import cv2
    rng = np.random.default_rng(seed)
    img = rng.integers(0, 255, (h, w, 3), dtype=np.uint8)
    ok, enc = cv2.imencode('.jpeg', img, [int(cv2.IMWRITE_JPEG_QUALITY), quality])
    assert ok
    return enc.tobytes()


@pytest.mark.skipif(not image_codec.is_available(), reason='native codec unavailable')
def test_scaled_jpeg_dims_cover_min_size():
    enc = _jpeg_bytes(1200, 900)
    out = image_codec.decode_images([enc], min_size=(160, 160))[0]
    # smallest m/8 covering 160: m=2 -> ceil(1200*2/8)=300, ceil(900*2/8)=225
    assert out.shape == (300, 225, 3)
    assert out.shape[0] >= 160 and out.shape[1] >= 160


@pytest.mark.skipif(not image_codec.is_available(), reason='native codec unavailable')
def test_scaled_jpeg_small_image_stays_full_size():
    enc = _jpeg_bytes(100, 80)
    out = image_codec.decode_images([enc], min_size=(160, 160))[0]
    assert out.shape == (100, 80, 3)  # cannot upscale; full size


@pytest.mark.skipif(not image_codec.is_available(), reason='native codec unavailable')
def test_scaled_decode_png_ignores_hint():
    import cv2
    img = np.random.default_rng(1).integers(0, 255, (400, 300, 3), dtype=np.uint8)
    ok, enc = cv2.imencode('.png', img)
    out = image_codec.decode_images([enc.tobytes()], min_size=(100, 100))[0]
    assert out.shape == (400, 300, 3)


@pytest.mark.skipif(not image_codec.is_available(), reason='native codec unavailable')
def test_scaled_jpeg_approximates_area_resize():
    import cv2
    enc = _jpeg_bytes(800, 600, seed=3)
    full = image_codec.decode_images([enc])[0]
    scaled = image_codec.decode_images([enc], min_size=(160, 160))[0]
    ref = cv2.resize(full, (scaled.shape[1], scaled.shape[0]),
                     interpolation=cv2.INTER_AREA)
    diff = np.abs(scaled.astype(int) - ref.astype(int)).mean()
    assert diff < 20  # DCT scaling ~= area resampling (random noise is worst case)


@pytest.mark.skipif(not image_codec.is_available(), reason='native codec unavailable')
def test_scaled_mixed_batch_per_image_scales():
    encs = [_jpeg_bytes(640, 480, seed=4), _jpeg_bytes(120, 90, seed=5),
            _jpeg_bytes(1600, 1200, seed=6)]
    outs = image_codec.decode_images(encs, min_size=(160, 160))
    assert outs[0].shape == (240, 180, 3)   # m=3
    assert outs[1].shape == (120, 90, 3)    # smaller than min: full
    assert outs[2].shape == (400, 300, 3)   # m=2 (m=1 would give width 150 < 160)


def test_codec_decode_batch_min_size_passthrough():
    codec = CompressedImageCodec('jpeg')
    field = UnischemaField('im', np.uint8, (None, None, 3), codec, False)
    enc = _jpeg_bytes(800, 600, seed=7)
    outs = codec.decode_batch(field, [enc, None], min_size=(160, 160))
    assert outs[1] is None
    assert outs[0].shape[0] >= 160 and outs[0].shape[0] < 800


def test_transform_decode_hints_end_to_end(tmp_path):
    """A jpeg dataset read with TransformSpec(image_decode_hints=...) resizes
    through scaled decode and still yields exact target shapes."""
    import cv2
    from examples.imagenet.generate_petastorm_imagenet import generate_synthetic_imagenet
    from examples.imagenet.jax_resnet_example import make_transform
    from petastorm_tpu import make_reader
    url = 'file://' + str(tmp_path / 'jpg_ds')
    generate_synthetic_imagenet(url, num_synsets=2, images_per_synset=8,
                                rows_per_row_group=8, image_codec='jpeg',
                                min_dim=200, max_dim=400)
    with make_reader(url, reader_pool_type='dummy', output='columnar',
                     shuffle_row_groups=False,
                     transform_spec=make_transform(96, 10)) as reader:
        blocks = [b._asdict() for b in reader]
    images = np.concatenate([b['image'] for b in blocks])
    assert images.shape == (16, 96, 96, 3)
    labels = np.concatenate([b['label'] for b in blocks])
    assert set(labels.tolist()) <= set(range(10))


# -- decode_images_block: whole-column decode into one allocation ------------

def test_block_decode_matches_per_image():
    rng = np.random.default_rng(11)
    imgs = [rng.integers(0, 255, (40, 56, 3), dtype=np.uint8) for _ in range(7)]
    blobs = [_png(im) for im in imgs[:4]] + [_jpeg(im) for im in imgs[4:]]
    block = image_codec.decode_images_block(blobs)
    singles = image_codec.decode_images(blobs)
    assert block.shape == (7, 40, 56, 3) and block.dtype == np.uint8
    for i in range(7):
        np.testing.assert_array_equal(block[i], singles[i])


def test_block_decode_mixed_dims_returns_none():
    rng = np.random.default_rng(12)
    blobs = [_png(rng.integers(0, 255, (16, 16, 3), dtype=np.uint8)),
             _png(rng.integers(0, 255, (16, 20, 3), dtype=np.uint8))]
    assert image_codec.decode_images_block(blobs) is None


def test_block_decode_grayscale():
    rng = np.random.default_rng(13)
    imgs = [rng.integers(0, 255, (24, 24), dtype=np.uint8) for _ in range(3)]
    block = image_codec.decode_images_block([_png(im) for im in imgs])
    assert block.shape == (3, 24, 24)
    for i, im in enumerate(imgs):
        np.testing.assert_array_equal(block[i], im)


def test_block_decode_bad_cell_raises():
    with pytest.raises(image_codec.NativeDecodeError):
        image_codec.decode_images_block([b'not an image'])


def test_codec_decode_column_matches_batch():
    import pyarrow as pa
    rng = np.random.default_rng(14)
    codec = CompressedImageCodec('png')
    field = UnischemaField('im', np.uint8, (18, 22, 3), codec, False)
    imgs = [rng.integers(0, 255, (18, 22, 3), dtype=np.uint8) for _ in range(5)]
    cells = [codec.encode(field, im) for im in imgs]
    column = pa.chunked_array([pa.array(cells, type=pa.binary())])
    block = codec.decode_column(field, column)
    assert block.shape == (5, 18, 22, 3)
    for i, im in enumerate(imgs):
        np.testing.assert_array_equal(block[i], im)


def test_codec_decode_column_nulls_defer():
    import pyarrow as pa
    codec = CompressedImageCodec('png')
    field = UnischemaField('im', np.uint8, (8, 8, 3), codec, True)
    cells = [codec.encode(field, np.zeros((8, 8, 3), np.uint8)), None]
    column = pa.chunked_array([pa.array(cells, type=pa.binary())])
    assert codec.decode_column(field, column) is None


def test_codec_decode_column_scaled_jpeg_hint():
    import pyarrow as pa
    codec = CompressedImageCodec('jpeg')
    field = UnischemaField('im', np.uint8, (None, None, 3), codec, False)
    cells = [_jpeg_bytes(400, 600, seed=i) for i in range(3)]
    column = pa.chunked_array([pa.array(cells, type=pa.binary())])
    block = codec.decode_column(field, column, min_size=(100, 150))
    assert block is not None
    n, h, w, c = block.shape
    assert 100 <= h < 400 and 150 <= w < 600  # decoded at a reduced DCT scale


def test_auto_decode_mixed_dims_returns_per_image_list():
    rng = np.random.default_rng(15)
    imgs = [rng.integers(0, 255, (16, 16, 3), dtype=np.uint8),
            rng.integers(0, 255, (16, 20, 3), dtype=np.uint8)]
    out = image_codec.decode_images_auto([_png(im) for im in imgs])
    assert isinstance(out, list) and len(out) == 2
    for got, want in zip(out, imgs):
        np.testing.assert_array_equal(got, want)


def test_codec_decode_column_mixed_dims_single_probe_object_column():
    import pyarrow as pa
    rng = np.random.default_rng(16)
    codec = CompressedImageCodec('png')
    field = UnischemaField('im', np.uint8, (None, None, 3), codec, False)
    imgs = [rng.integers(0, 255, (10, 12, 3), dtype=np.uint8),
            rng.integers(0, 255, (14, 12, 3), dtype=np.uint8)]
    cells = [codec.encode(field, im) for im in imgs]
    column = pa.chunked_array([pa.array(cells, type=pa.binary())])
    out = codec.decode_column(field, column)
    assert out is not None and out.dtype == object
    for got, want in zip(out, imgs):
        np.testing.assert_array_equal(got, want)


# -- fused decode+resize (TransformSpec.image_resize) ------------------------

def test_decode_images_resized_matches_cv2_area():
    rng = np.random.default_rng(17)
    imgs = [rng.integers(0, 255, (90, 120, 3), dtype=np.uint8) for _ in range(4)]
    out = image_codec.decode_images_resized([_png(im) for im in imgs], (32, 48))
    assert out.shape == (4, 32, 48, 3) and out.dtype == np.uint8
    for got, src in zip(out, imgs):
        ref = cv2.resize(src, (48, 32), interpolation=cv2.INTER_AREA)
        assert np.abs(got.astype(int) - ref.astype(int)).max() <= 1


def test_decode_images_resized_grayscale_and_identity():
    rng = np.random.default_rng(18)
    img = rng.integers(0, 255, (20, 24), dtype=np.uint8)
    out = image_codec.decode_images_resized([_png(img)], (20, 24))
    assert out.shape == (1, 20, 24)
    np.testing.assert_array_equal(out[0], img)  # identity resize = plain decode


@pytest.fixture(scope='module')
def mixed_size_png_dataset(tmp_path_factory):
    from petastorm_tpu.codecs import CompressedImageCodec, ScalarCodec
    from petastorm_tpu.etl.dataset_metadata import write_petastorm_dataset
    from petastorm_tpu.unischema import Unischema, UnischemaField

    path = tmp_path_factory.mktemp('mixed_png_store')
    url = 'file://' + str(path)
    schema = Unischema('MixedPng', [
        UnischemaField('id', np.int64, (), ScalarCodec(), False),
        UnischemaField('image', np.uint8, (None, None, 3), CompressedImageCodec('png'), False),
    ])
    rng = np.random.default_rng(19)
    data = [{'id': i,
             'image': rng.integers(0, 255, (40 + 8 * (i % 4), 50 + 4 * (i % 3), 3),
                                   dtype=np.uint8)}
            for i in range(24)]
    write_petastorm_dataset(url, schema, iter(data), rows_per_row_group=8)
    return url, data


def _resize_ref(img, size):
    # the shared policy: bilinear under 2x decimation, area at >= 2x
    from petastorm_tpu.codecs import _mild_ratio
    interp = cv2.INTER_LINEAR if _mild_ratio(img.shape[0], img.shape[1], size[0], size[1]) \
        else cv2.INTER_AREA
    return cv2.resize(img, (size[1], size[0]), interpolation=interp)


def test_image_resize_end_to_end_row_reader(mixed_size_png_dataset):
    from petastorm_tpu import TransformSpec, make_reader
    url, data = mixed_size_png_dataset
    by_id = {r['id']: r['image'] for r in data}
    spec = TransformSpec(image_resize={'image': (32, 32)})
    n = 0
    with make_reader(url, reader_pool_type='dummy', shuffle_row_groups=False,
                     transform_spec=spec) as reader:
        for row in reader:
            assert row.image.shape == (32, 32, 3)
            ref = _resize_ref(by_id[row.id], (32, 32))
            assert np.abs(row.image.astype(int) - ref.astype(int)).max() <= 1
            n += 1
    assert n == len(data)


def test_image_resize_end_to_end_columnar_uniform_blocks(mixed_size_png_dataset):
    from petastorm_tpu import TransformSpec, make_reader
    url, data = mixed_size_png_dataset
    spec = TransformSpec(image_resize={'image': (28, 36)})
    ids = []
    with make_reader(url, reader_pool_type='dummy', output='columnar',
                     shuffle_row_groups=False, transform_spec=spec) as reader:
        for block in reader:
            assert block.image.shape[1:] == (28, 36, 3)  # one uniform block
            assert block.image.dtype == np.uint8
            ids.extend(block.id.tolist())
    assert sorted(ids) == [r['id'] for r in data]


def test_image_resize_opencv_fallback_same_contract(mixed_size_png_dataset, monkeypatch):
    from petastorm_tpu import TransformSpec, make_reader
    url, data = mixed_size_png_dataset
    monkeypatch.setattr(image_codec, '_load_failed', True)  # native codec "absent"
    monkeypatch.setattr(image_codec, '_lib', None)
    assert not image_codec.is_available()
    spec = TransformSpec(image_resize={'image': (32, 32)})
    by_id = {r['id']: r['image'] for r in data}
    with make_reader(url, reader_pool_type='dummy', shuffle_row_groups=False,
                     transform_spec=spec) as reader:
        for row in reader:
            assert row.image.shape == (32, 32, 3)
            ref = _resize_ref(by_id[row.id], (32, 32))
            np.testing.assert_array_equal(row.image, ref)  # same cv2 path = exact


def test_image_resize_transform_schema_autoedit():
    from petastorm_tpu import TransformSpec
    from petastorm_tpu.codecs import CompressedImageCodec
    from petastorm_tpu.transform import transform_schema
    from petastorm_tpu.unischema import Unischema, UnischemaField

    schema = Unischema('S', [
        UnischemaField('image', np.uint8, (None, None, 3), CompressedImageCodec('png'), False)])
    out = transform_schema(schema, TransformSpec(image_resize={'image': (64, 48)}))
    assert out.fields['image'].shape == (64, 48, 3)
    # explicit edit wins over the auto-derived shape
    out2 = transform_schema(schema, TransformSpec(
        image_resize={'image': (64, 48)},
        edit_fields=[UnischemaField('image', np.uint8, (10, 10, 3), None, False)]))
    assert out2.fields['image'].shape == (10, 10, 3)


def test_image_resize_rejects_bad_target():
    from petastorm_tpu import TransformSpec
    with pytest.raises(ValueError):
        TransformSpec(image_resize={'image': (0, 10)})
    with pytest.raises(ValueError):
        TransformSpec(image_resize={'image': (10,)})


def test_native_resize_area_image_matches_cv2():
    rng = np.random.default_rng(20)
    img = rng.integers(0, 255, (60, 80, 3), dtype=np.uint8)
    out = image_codec.resize_area_image(img, (30, 40))
    ref = cv2.resize(img, (40, 30), interpolation=cv2.INTER_AREA)
    assert np.abs(out.astype(int) - ref.astype(int)).max() <= 1


def test_image_resize_rejects_non_image_codec():
    from petastorm_tpu import TransformSpec
    from petastorm_tpu.codecs import NdarrayCodec
    from petastorm_tpu.transform import transform_schema
    from petastorm_tpu.unischema import Unischema, UnischemaField

    schema = Unischema('S', [
        UnischemaField('arr', np.uint8, (None, None, 3), NdarrayCodec(), False)])
    with pytest.raises(ValueError, match='does not support decode-time resize'):
        transform_schema(schema, TransformSpec(image_resize={'arr': (8, 8)}))
    with pytest.raises(ValueError, match='unknown field'):
        transform_schema(schema, TransformSpec(image_resize={'nope': (8, 8)}))


def test_decode_hint_overrides_resize_scale():
    # explicit image_decode_hints wins: jpeg decodes at a scale covering the
    # hint (2x supersample), not just the resize target
    blob = _jpeg_bytes(800, 1200, seed=3)
    small = image_codec.decode_images_resized([blob], (100, 150))
    big = image_codec.decode_images_resized([blob], (100, 150), min_size=(400, 600))
    assert small.shape == big.shape == (1, 100, 150, 3)
    # both valid; a supersampled source reduces aliasing so outputs differ
    assert not np.array_equal(small, big)


def test_cache_key_distinguishes_resize(tmp_path):
    from petastorm_tpu.row_worker import _cache_key

    class Piece:
        path = 'p.parquet'
        row_group = 0
    k_plain = _cache_key('/d', Piece, ['image'])
    k_hint = _cache_key('/d', Piece, ['image'], decode_hints={'image': (32, 32)})
    k_resize = _cache_key('/d', Piece, ['image'], decode_hints={'image': (32, 32)},
                          resize_hints={'image': (32, 32)})
    assert len({k_plain, k_hint, k_resize}) == 3


def test_image_resize_uint16_without_opencv_uses_numpy_fallback(tmp_path, monkeypatch):
    # 16-bit PNG column + image_resize on an OpenCV-less host: the native fast
    # path declines (depth != 8) and decode_batch's resize must fall back to
    # the numpy area resampler instead of crashing
    import petastorm_tpu.codecs as codecs_mod
    from petastorm_tpu import TransformSpec, make_reader
    from petastorm_tpu.codecs import CompressedImageCodec, ScalarCodec
    from petastorm_tpu.etl.dataset_metadata import write_petastorm_dataset
    from petastorm_tpu.unischema import Unischema, UnischemaField

    url = 'file://' + str(tmp_path)
    schema = Unischema('U16', [
        UnischemaField('id', np.int64, (), ScalarCodec(), False),
        UnischemaField('image', np.uint16, (None, None, 3), CompressedImageCodec('png'), False),
    ])
    rng = np.random.default_rng(21)
    data = [{'id': i, 'image': rng.integers(0, 65535, (20 + 4 * i, 24, 3), dtype=np.uint16)}
            for i in range(6)]
    write_petastorm_dataset(url, schema, iter(data), rows_per_row_group=3)

    def no_cv2():
        raise ImportError('cv2 disabled for test')
    monkeypatch.setattr(codecs_mod, '_import_cv2', no_cv2)

    spec = TransformSpec(image_resize={'image': (16, 16)})
    with make_reader(url, reader_pool_type='dummy', shuffle_row_groups=False,
                     transform_spec=spec) as reader:
        rows = list(reader)
    assert len(rows) == 6
    assert all(r.image.shape == (16, 16, 3) and r.image.dtype == np.uint16 for r in rows)


def test_numpy_area_resize_matches_cv2():
    from petastorm_tpu.codecs import _area_resize_numpy
    rng = np.random.default_rng(22)
    img = rng.integers(0, 255, (50, 70, 3), dtype=np.uint8)
    out = _area_resize_numpy(img, 25, 35)
    ref = cv2.resize(img, (35, 25), interpolation=cv2.INTER_AREA)
    assert np.abs(out.astype(int) - ref.astype(int)).max() <= 1


def test_native_resize_bilinear_matches_cv2_linear():
    rng = np.random.default_rng(21)
    for shape, target in [((60, 80, 3), (40, 56)), ((45, 45), (32, 32)),
                          ((33, 57, 3), (60, 70))]:  # down-mild and upscale
        img = rng.integers(0, 255, shape, dtype=np.uint8)
        out = image_codec.resize_bilinear_image(img, target)
        ref = cv2.resize(img, (target[1], target[0]), interpolation=cv2.INTER_LINEAR)
        assert np.abs(out.astype(int) - ref.astype(int)).max() <= 1, (shape, target)


def test_resize_policy_dispatch():
    """_resize_image must pick bilinear under 2x decimation and area at >= 2x,
    and the native fused path must follow the same split."""
    from petastorm_tpu.codecs import _mild_ratio, _resize_image
    rng = np.random.default_rng(22)
    # mild (1.5x): matches cv2 INTER_LINEAR
    img = rng.integers(0, 255, (48, 48, 3), dtype=np.uint8)
    got = _resize_image(img, 32, 32)
    ref = cv2.resize(img, (32, 32), interpolation=cv2.INTER_LINEAR)
    np.testing.assert_array_equal(got, ref)
    # real decimation (3x): matches cv2 INTER_AREA
    img2 = rng.integers(0, 255, (96, 96, 3), dtype=np.uint8)
    got2 = _resize_image(img2, 32, 32)
    ref2 = cv2.resize(img2, (32, 32), interpolation=cv2.INTER_AREA)
    np.testing.assert_array_equal(got2, ref2)
    assert _mild_ratio(48, 48, 32, 32) and not _mild_ratio(96, 96, 32, 32)
    assert not _mild_ratio(64, 40, 32, 32)  # boundary: exactly 2x is NOT mild
    # mixed down+up (h 3x down, w upscaled): bilinear on EVERY backend — the
    # same store must decode identically with or without OpenCV installed
    assert _mild_ratio(96, 24, 32, 32)
    img3 = rng.integers(0, 255, (96, 24, 3), dtype=np.uint8)
    got3 = _resize_image(img3, 32, 32)
    ref3 = cv2.resize(img3, (32, 32), interpolation=cv2.INTER_LINEAR)
    np.testing.assert_array_equal(got3, ref3)
    native3 = image_codec.resize_bilinear_image(img3, (32, 32))
    assert np.abs(native3.astype(int) - ref3.astype(int)).max() <= 1
    out3 = image_codec.decode_images_resized([_png(img3)], (32, 32))
    assert np.abs(out3[0].astype(int) - ref3.astype(int)).max() <= 1
    # fused native path agrees within rounding on the mild branch
    out = image_codec.decode_images_resized([_png(img)], (32, 32))
    assert np.abs(out[0].astype(int) - ref.astype(int)).max() <= 1


def test_thread_budget_cooperative_grants(monkeypatch):
    """threads=None callers share the process budget: the first concurrent
    caller gets the free budget, later ones get the floor of 1, and every
    grant is returned."""
    monkeypatch.setattr(image_codec, '_default_threads', lambda: 4)
    with image_codec._thread_grant(None) as g1:
        assert g1 == 4
        with image_codec._thread_grant(None) as g2:
            assert g2 == 1  # budget exhausted: floor keeps the caller moving
        with image_codec._thread_grant(None) as g3:
            assert g3 == 1
    with image_codec._thread_grant(None) as g4:
        assert g4 == 4  # fully returned
    assert image_codec._threads_in_use == 0
    # explicit request bypasses the accounting entirely
    with image_codec._thread_grant(2) as g5:
        assert g5 == 2
    assert image_codec._threads_in_use == 0


def test_thread_budget_decode_results_identical(monkeypatch):
    monkeypatch.setattr(image_codec, '_default_threads', lambda: 3)
    imgs = [rng.integers(0, 256, (30 + i, 20, 3), np.uint8) for i in range(12)]
    blobs = [_png(im) for im in imgs]
    budgeted = image_codec.decode_images(blobs)  # threads=None -> grant path
    single = image_codec.decode_images(blobs, threads=1)
    for b, s in zip(budgeted, single):
        np.testing.assert_array_equal(b, s)
    assert image_codec._threads_in_use == 0


def test_default_thread_budget_safety(monkeypatch):
    # garbage env degrades to the safe floor, never the full budget
    monkeypatch.setenv('PSTPU_IMG_THREADS', 'auto')
    assert image_codec._default_threads() == 1
    monkeypatch.setenv('PSTPU_IMG_THREADS', '')
    assert image_codec._default_threads() == 1
    monkeypatch.setenv('PSTPU_IMG_THREADS', '6')
    assert image_codec._default_threads() == 6
    # unset in a top-level process: CPU count
    monkeypatch.delenv('PSTPU_IMG_THREADS')
    import os as os_mod
    assert image_codec._default_threads() == max(1, os_mod.cpu_count() or 1)


def _child_budget(q):
    import os
    os.environ.pop('PSTPU_IMG_THREADS', None)
    from petastorm_tpu.native import image_codec as ic
    q.put(ic._default_threads())


def test_default_thread_budget_in_mp_child_is_one(monkeypatch):
    """A multiprocessing child NOT configured by our pool bootstrap defaults
    to 1 — N sibling processes each claiming cpu_count would oversubscribe."""
    import multiprocessing
    monkeypatch.delenv('PSTPU_IMG_THREADS', raising=False)
    ctx = multiprocessing.get_context('spawn')
    q = ctx.Queue()
    p = ctx.Process(target=_child_budget, args=(q,))
    p.start()
    assert q.get(timeout=60) == 1
    p.join()


def test_native_resamplers_fuzz_vs_cv2():
    """Random shapes (tiny, 1-px axes, extreme aspect) through both native
    resamplers stay within 1 LSB of the cv2 references. Bilinear everywhere;
    area wherever at least the promised regime applies (both axes downscale,
    or both upscale — cv2's MIXED down+up INTER_AREA is a non-separable
    special case that disagrees even with cv2's own two-step composition by
    ~100 LSB, so bit-parity there is not a meaningful contract; the shared
    resize policy never routes such shapes to area with cv2 absent AND
    present simultaneously anyway)."""
    fuzz = np.random.default_rng(99)
    checked_area = 0
    for _ in range(40):
        sh = int(fuzz.integers(1, 80))
        sw = int(fuzz.integers(1, 80))
        dh = int(fuzz.integers(1, 64))
        dw = int(fuzz.integers(1, 64))
        c = int(fuzz.choice([1, 3]))
        shape = (sh, sw) if c == 1 else (sh, sw, c)
        img = fuzz.integers(0, 256, shape, dtype=np.uint8)
        got_b = image_codec.resize_bilinear_image(img, (dh, dw))
        ref_b = cv2.resize(img, (dw, dh), interpolation=cv2.INTER_LINEAR)
        assert np.abs(got_b.astype(int) - ref_b.astype(int)).max() <= 1, \
            ('bilinear', shape, (dh, dw))
        both_down = dh <= sh and dw <= sw
        both_up = dh >= sh and dw >= sw
        if both_down or both_up:
            checked_area += 1
            got_a = image_codec.resize_area_image(img, (dh, dw))
            ref_a = cv2.resize(img, (dw, dh), interpolation=cv2.INTER_AREA)
            assert np.abs(got_a.astype(int) - ref_a.astype(int)).max() <= 1, \
                ('area', shape, (dh, dw))
    assert checked_area >= 10  # the area contract actually got exercised


# -- one native call per resized column, against the per-image cv2 path ------

def _textured(shape, seed):
    """Smooth gradients under noise: a photo-like texture whose JPEG decode
    and resize exercise every filter tap."""
    r = np.random.default_rng(seed)
    h, w = shape[:2]
    coarse = cv2.resize(r.normal(128, 40, (h // 4 + 1, w // 4 + 1) + shape[2:]), (w, h))
    coarse = coarse.reshape(shape)
    return np.clip(coarse + r.normal(0, 30, shape), 0, 255).astype(np.uint8)


def _image_column(blobs):
    import pyarrow as pa
    return pa.chunked_array([pa.array(blobs, type=pa.binary())])


def _counter(name):
    from petastorm_tpu import observability as obs
    return obs.snapshot().get('counters', {}).get(name, 0)


@pytest.fixture
def counters_level():
    from petastorm_tpu.observability import metrics
    level = metrics.level_name()
    metrics.set_level('counters')
    yield
    metrics.set_level(level)


@pytest.mark.parametrize('fmt,shapes,target', [
    # the JPEG store's shape: 320-560 px decoded at the DCT scale covering
    # 224, so mixed 240-280 px outputs at bilinear ratios
    ('jpeg', [(320, 560, 3), (447, 333, 3), (560, 512, 3), (401, 389, 3)], (224, 224)),
    # full-size PNG decode at area ratios, both axes shrinking 2x or more
    ('png', [(96, 140, 3), (120, 100, 3), (77, 200, 3)], (32, 40)),
    # area on one axis, the other shrinking under 2x
    ('png', [(100, 50, 3), (130, 45, 3)], (40, 32)),
    # grayscale, mixed bilinear and area ratios in one column
    ('png', [(48, 52), (130, 90), (40, 44)], (32, 32)),
    # an axis that enlarges sends the image to bilinear, even where the
    # other shrinks 2x or more: area never meets an enlarging axis
    ('png', [(100, 20, 3), (24, 90, 3)], (32, 32)),
], ids=['jpeg-bilinear', 'png-area', 'png-area-one-axis-mild', 'gray-mixed',
        'enlarging-axis-bilinear'])
def test_resized_column_one_call_matches_per_image_cv2(fmt, shapes, target, counters_level):
    """decode_column(resize=...) serves the column through one native
    decode+resize call; the per-image path (native decode, then cv2.resize by
    the shared policy) stays within 1 LSB and 1 - Pearson <= 1e-4 a row."""
    channels = 3 if len(shapes[0]) == 3 else 1
    codec = CompressedImageCodec(fmt)
    field = UnischemaField('im', np.uint8, (None, None) + ((3,) if channels == 3 else ()),
                           codec, False)
    imgs = [_textured(s, seed) for seed, s in enumerate(shapes)]
    blobs = [codec.encode(field, im) for im in imgs]
    fused0, fallback0 = (_counter('image_columns_fused_total'),
                         _counter('image_columns_fallback_total'))

    block = codec.decode_column(field, _image_column(blobs), resize=target)

    assert _counter('image_columns_fused_total') == fused0 + 1
    assert _counter('image_columns_fallback_total') == fallback0
    assert block.shape == (len(imgs),) + target + ((3,) if channels == 3 else ())
    assert block.dtype == np.uint8
    per_image = codec.decode_batch(field, blobs, resize=target)
    for got, want in zip(block, per_image):
        gap = np.abs(got.astype(int) - want.astype(int))
        assert gap.max() <= 1
        assert 1 - np.corrcoef(got.ravel(), want.ravel())[0, 1] <= 1e-4


def test_resized_column_opens_image_decode_stage(counters_level):
    codec = CompressedImageCodec('png')
    field = UnischemaField('im', np.uint8, (None, None, 3), codec, False)
    blobs = [codec.encode(field, _textured((40, 50, 3), i)) for i in range(3)]
    before = _counter('stage_image_decode_count')
    codec.decode_column(field, _image_column(blobs), resize=(16, 16))
    assert _counter('stage_image_decode_count') == before + 1


@pytest.mark.parametrize('case', ['mixed-channels', 'uint16'])
def test_resized_column_fallback_to_per_image_path(case, counters_level):
    """Columns the one call cannot hold in one uint8 block (mixed channel
    counts, 16-bit) return None, count one fallback, and the per-image path
    still delivers every image at the target size."""
    if case == 'mixed-channels':
        dtype = np.uint8
        imgs = [_textured((40, 50, 3), 1), _textured((36, 44), 2)]
    else:
        dtype = np.uint16
        imgs = [rng.integers(0, 65535, (40, 50, 3), dtype=np.uint16),
                rng.integers(0, 65535, (30, 34, 3), dtype=np.uint16)]
    codec = CompressedImageCodec('png')
    field = UnischemaField('im', dtype, (None, None, None), codec, False)
    blobs = [_png(im) for im in imgs]
    fused0, fallback0 = (_counter('image_columns_fused_total'),
                         _counter('image_columns_fallback_total'))

    assert codec.decode_column(field, _image_column(blobs), resize=(16, 16)) is None

    assert _counter('image_columns_fallback_total') == fallback0 + 1
    assert _counter('image_columns_fused_total') == fused0
    per_image = codec.decode_batch(field, blobs, resize=(16, 16))
    assert [im.shape[:2] for im in per_image] == [(16, 16)] * len(imgs)
    assert all(im.dtype == dtype for im in per_image)


def test_unresized_column_counts_nothing(counters_level):
    codec = CompressedImageCodec('png')
    field = UnischemaField('im', np.uint8, (None, None, 3), codec, False)
    blobs = [codec.encode(field, _textured((20, 24, 3), i)) for i in range(2)]
    fused0, fallback0 = (_counter('image_columns_fused_total'),
                         _counter('image_columns_fallback_total'))
    assert codec.decode_column(field, _image_column(blobs)).shape == (2, 20, 24, 3)
    assert (_counter('image_columns_fused_total'),
            _counter('image_columns_fallback_total')) == (fused0, fallback0)
