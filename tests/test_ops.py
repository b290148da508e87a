"""Device-side ops: fused normalize (Pallas kernel vs reference math), augment."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from petastorm_tpu.ops import normalize_images, random_crop, random_flip

MEAN = np.array([123.675, 116.28, 103.53], np.float32)
STD = np.array([58.395, 57.12, 57.375], np.float32)


def _reference(images, mean, std):
    return (images.astype(np.float32) - mean) / std


@pytest.mark.parametrize('shape', [
    (4, 32, 32, 3),     # W*C = 96 < one lane block (masked edge)
    (2, 17, 224, 3),    # W*C = 672: non-divisible by 512 lanes, odd rows
    (1, 8, 128, 1),     # single channel
])
def test_normalize_pallas_matches_reference(shape, rng):
    images = rng.integers(0, 256, shape, dtype=np.uint8)
    c = shape[-1]
    mean, std = MEAN[:c], STD[:c]
    out = normalize_images(jnp.asarray(images), mean, std, out_dtype=jnp.float32,
                           interpret=True)
    np.testing.assert_allclose(np.asarray(out), _reference(images, mean, std),
                               rtol=1e-5, atol=1e-5)


def test_normalize_pallas_float_input_not_truncated(rng):
    # Regression: the kernel used to widen through int32 unconditionally,
    # flattening fractional float inputs to -1.0 (advisor finding r1).
    images = rng.random((2, 8, 128, 3)).astype(np.float32)  # values in [0, 1)
    out = normalize_images(jnp.asarray(images), 0.5, 0.5, out_dtype=jnp.float32,
                           interpret=True)
    np.testing.assert_allclose(np.asarray(out), _reference(images, 0.5, 0.5),
                               rtol=1e-5, atol=1e-5)


def test_normalize_jnp_fallback_matches_reference(rng):
    images = rng.integers(0, 256, (3, 16, 24, 3), dtype=np.uint8)
    out = normalize_images(jnp.asarray(images), MEAN, STD, out_dtype=jnp.float32,
                           use_pallas=False)
    np.testing.assert_allclose(np.asarray(out), _reference(images, MEAN, STD),
                               rtol=1e-5, atol=1e-5)


def test_normalize_bfloat16_output_and_scalar_stats(rng):
    images = rng.integers(0, 256, (2, 8, 16, 3), dtype=np.uint8)
    out = normalize_images(jnp.asarray(images), 127.5, 127.5, interpret=True)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               _reference(images, 127.5, 127.5), rtol=2e-2, atol=2e-2)


def test_normalize_single_image_and_validation(rng):
    img = rng.integers(0, 256, (8, 16, 3), dtype=np.uint8)
    out = normalize_images(jnp.asarray(img), MEAN, STD, out_dtype=jnp.float32,
                           use_pallas=False)
    assert out.shape == (8, 16, 3)
    with pytest.raises(ValueError, match='std must be non-zero'):
        normalize_images(jnp.asarray(img), MEAN, 0.0)
    with pytest.raises(ValueError, match='mean must be'):
        normalize_images(jnp.asarray(img), np.ones(4), STD)


def test_normalize_pallas_runs_per_device_under_mesh(rng):
    """Regression (chip bring-up, PR 21): XLA cannot partition a Mosaic
    kernel, so the sharded data-parallel train step failed to compile on a
    four-chip mesh. Under ``jax.set_mesh`` the kernel now runs per device and
    the batch stays split: no device gathers the whole batch."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    mesh = Mesh(np.array(jax.devices()[:4]), ('data',))
    images = rng.integers(0, 256, (8, 4, 16, 3), dtype=np.uint8)
    x = jax.device_put(images, NamedSharding(mesh, P('data')))
    with jax.set_mesh(mesh):
        fn = jax.jit(lambda a: normalize_images(a, MEAN, STD, out_dtype=jnp.float32,
                                                interpret=True))
        out = fn(x)
        hlo = fn.lower(x).compile().as_text()
    np.testing.assert_allclose(np.asarray(out), _reference(images, MEAN, STD),
                               rtol=1e-5, atol=1e-5)
    shards = out.addressable_shards
    assert len({s.device for s in shards}) == 4
    assert all(s.data.shape[0] == 2 for s in shards)
    assert 'all-gather' not in hlo


def test_normalize_pallas_under_mesh_rejects_unsplittable_batch(rng):
    from jax.sharding import Mesh
    mesh = Mesh(np.array(jax.devices()[:4]), ('data',))
    images = jnp.asarray(rng.integers(0, 256, (6, 4, 16, 3), dtype=np.uint8))
    with jax.set_mesh(mesh), pytest.raises(ValueError, match='does not split'):
        normalize_images(images, MEAN, STD, interpret=True)


def test_normalize_jits_inside_train_step(rng):
    # the op must compose with jit (static shapes, no python control flow)
    images = jnp.asarray(rng.integers(0, 256, (2, 8, 16, 3), dtype=np.uint8))

    @jax.jit
    def step(x):
        return normalize_images(x, MEAN, STD, out_dtype=jnp.float32,
                                use_pallas=False).sum()

    assert np.isfinite(float(step(images)))


def test_random_flip_values_and_determinism(rng):
    images = jnp.asarray(rng.integers(0, 256, (8, 4, 6, 3), dtype=np.uint8))
    key = jax.random.key(0)
    out1 = random_flip(images, key)
    out2 = random_flip(images, key)
    np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))
    # every output image is either the original or its horizontal mirror
    img_np, out_np = np.asarray(images), np.asarray(out1)
    n_flipped = 0
    for i in range(img_np.shape[0]):
        same = np.array_equal(out_np[i], img_np[i])
        mirrored = np.array_equal(out_np[i], img_np[i, :, ::-1, :])
        assert same or mirrored
        n_flipped += int(mirrored and not same)
    assert 0 < n_flipped < img_np.shape[0]  # prob=0.5 over 8 images


def test_random_crop_shape_and_content(rng):
    images = jnp.asarray(rng.integers(0, 256, (4, 10, 12, 3), dtype=np.uint8))
    out = random_crop(images, jax.random.key(1), 6, 8)
    assert out.shape == (4, 6, 8, 3)
    # each crop must be a contiguous window of its source image
    img_np, out_np = np.asarray(images), np.asarray(out)
    for i in range(4):
        found = any(
            np.array_equal(out_np[i], img_np[i, y:y + 6, x:x + 8])
            for y in range(5) for x in range(5))
        assert found
    with pytest.raises(ValueError, match='larger than image'):
        random_crop(images, jax.random.key(2), 20, 8)


# -- ring attention (context parallelism over a virtual mesh) ----------------

def _reference_attention(q, k, v, causal):
    d = q.shape[-1]
    s = np.einsum('bhqd,bhkd->bhqk', q, k) / np.sqrt(d)
    if causal:
        t = q.shape[2]
        mask = np.tril(np.ones((t, t), bool))
        s = np.where(mask[None, None], s, -1e30)
    p = np.exp(s - s.max(-1, keepdims=True))
    p = p / p.sum(-1, keepdims=True)
    return np.einsum('bhqk,bhkd->bhqd', p, v)


@pytest.mark.parametrize('causal', [False, True])
@pytest.mark.parametrize('ring', [2, 8])
def test_ring_attention_matches_full_attention(causal, ring, rng):
    from jax.sharding import Mesh
    from petastorm_tpu.ops.ring_attention import make_ring_attention

    b, h, t, d = 2, 3, 32, 8
    q = rng.standard_normal((b, h, t, d), dtype=np.float32)
    k = rng.standard_normal((b, h, t, d), dtype=np.float32)
    v = rng.standard_normal((b, h, t, d), dtype=np.float32)

    mesh = Mesh(np.array(jax.devices()[:ring]), ('seq',))
    attn = make_ring_attention(mesh, seq_axis='seq', causal=causal)
    out = np.asarray(attn(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v)))
    expected = _reference_attention(q, k, v, causal)
    np.testing.assert_allclose(out, expected, rtol=2e-4, atol=2e-4)


def test_ring_attention_with_data_and_seq_axes(rng):
    from jax.sharding import Mesh
    from petastorm_tpu.ops.ring_attention import make_ring_attention

    b, h, t, d = 4, 2, 16, 4
    q = rng.standard_normal((b, h, t, d), dtype=np.float32)
    k = rng.standard_normal((b, h, t, d), dtype=np.float32)
    v = rng.standard_normal((b, h, t, d), dtype=np.float32)
    mesh = Mesh(np.asarray(jax.devices()[:8]).reshape(2, 4), ('data', 'seq'))
    attn = make_ring_attention(mesh, seq_axis='seq', batch_axis='data', causal=True)
    out = np.asarray(attn(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v)))
    np.testing.assert_allclose(out, _reference_attention(q, k, v, True),
                               rtol=2e-4, atol=2e-4)


# -- Ulysses all-to-all sequence parallelism ---------------------------------

@pytest.mark.parametrize('causal', [False, True])
@pytest.mark.parametrize('shards', [2, 4])
def test_ulysses_attention_matches_full_attention(causal, shards, rng):
    from jax.sharding import Mesh
    from petastorm_tpu.ops.ulysses_attention import make_ulysses_attention

    b, h, t, d = 2, 4, 32, 8  # h divisible by both shard counts
    q = rng.standard_normal((b, h, t, d), dtype=np.float32)
    k = rng.standard_normal((b, h, t, d), dtype=np.float32)
    v = rng.standard_normal((b, h, t, d), dtype=np.float32)

    mesh = Mesh(np.array(jax.devices()[:shards]), ('seq',))
    attn = make_ulysses_attention(mesh, seq_axis='seq', causal=causal)
    out = np.asarray(attn(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v)))
    np.testing.assert_allclose(out, _reference_attention(q, k, v, causal),
                               rtol=2e-4, atol=2e-4)


def test_ulysses_matches_ring_attention(rng):
    # the two context-parallel strategies are interchangeable: same math,
    # different data movement
    from jax.sharding import Mesh
    from petastorm_tpu.ops.ring_attention import make_ring_attention
    from petastorm_tpu.ops.ulysses_attention import make_ulysses_attention

    b, h, t, d = 2, 8, 64, 4
    q = rng.standard_normal((b, h, t, d), dtype=np.float32)
    k = rng.standard_normal((b, h, t, d), dtype=np.float32)
    v = rng.standard_normal((b, h, t, d), dtype=np.float32)
    mesh = Mesh(np.array(jax.devices()[:8]), ('seq',))
    ring = make_ring_attention(mesh, causal=True)
    uly = make_ulysses_attention(mesh, causal=True)
    np.testing.assert_allclose(
        np.asarray(uly(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))),
        np.asarray(ring(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))),
        rtol=2e-4, atol=2e-4)


def test_ulysses_attention_with_data_axis_and_chunking(rng):
    from jax.sharding import Mesh
    from petastorm_tpu.ops.ulysses_attention import make_ulysses_attention

    b, h, t, d = 4, 4, 32, 4
    q = rng.standard_normal((b, h, t, d), dtype=np.float32)
    k = rng.standard_normal((b, h, t, d), dtype=np.float32)
    v = rng.standard_normal((b, h, t, d), dtype=np.float32)
    mesh = Mesh(np.asarray(jax.devices()[:8]).reshape(2, 4), ('data', 'seq'))
    attn = make_ulysses_attention(mesh, seq_axis='seq', batch_axis='data',
                                  causal=True, kv_chunk=4)
    out = np.asarray(attn(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v)))
    np.testing.assert_allclose(out, _reference_attention(q, k, v, True),
                               rtol=2e-4, atol=2e-4)


def test_ulysses_rejects_indivisible_heads(rng):
    from jax.sharding import Mesh
    from petastorm_tpu.ops.ulysses_attention import make_ulysses_attention

    mesh = Mesh(np.array(jax.devices()[:4]), ('seq',))
    attn = make_ulysses_attention(mesh)
    x = jnp.zeros((1, 3, 16, 4))  # 3 heads, 4-way seq axis
    with pytest.raises(ValueError, match='divisible'):
        attn(x, x, x)


# -- pipeline parallelism (GPipe over a mesh axis) ---------------------------

def _pipeline_stage(params, act):
    w, b = params
    return jax.nn.gelu(act @ w + b)


def _stacked_stage_params(n_stages, dim, rng):
    w = jnp.asarray(rng.standard_normal((n_stages, dim, dim)).astype(np.float32) * 0.3)
    b = jnp.asarray(rng.standard_normal((n_stages, dim)).astype(np.float32) * 0.1)
    return w, b


def _sequential_ref(params, x):
    w, b = params
    for s in range(w.shape[0]):
        x = jax.nn.gelu(x @ w[s] + b[s])
    return x


@pytest.mark.parametrize('stages,microbatches', [(2, 4), (4, 8), (8, 8)])
def test_pipeline_matches_sequential(stages, microbatches, rng):
    from jax.sharding import Mesh
    from petastorm_tpu.parallel import make_pipelined_apply

    mesh = Mesh(np.array(jax.devices()[:stages]), ('stage',))
    params = _stacked_stage_params(stages, 16, rng)
    apply = make_pipelined_apply(mesh, _pipeline_stage, num_microbatches=microbatches)
    x = jnp.asarray(rng.standard_normal((32, 16)).astype(np.float32))
    with mesh:
        y = apply(params, x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(_sequential_ref(params, x)),
                               rtol=2e-5, atol=2e-5)


def test_pipeline_grads_match_sequential(rng):
    from jax.sharding import Mesh
    from petastorm_tpu.parallel import make_pipelined_apply

    stages = 4
    mesh = Mesh(np.array(jax.devices()[:stages]), ('stage',))
    params = _stacked_stage_params(stages, 8, rng)
    apply = make_pipelined_apply(mesh, _pipeline_stage, num_microbatches=stages)
    x = jnp.asarray(rng.standard_normal((16, 8)).astype(np.float32))
    with mesh:
        g = jax.grad(lambda p, xx: jnp.sum(apply(p, xx) ** 2))(params, x)
    ref = jax.grad(lambda p, xx: jnp.sum(_sequential_ref(p, xx) ** 2))(params, x)
    for a, b in zip(jax.tree_util.tree_leaves(g), jax.tree_util.tree_leaves(ref)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-5)


def test_pipeline_rejects_indivisible_batch(rng):
    from jax.sharding import Mesh
    from petastorm_tpu.parallel import make_pipelined_apply

    mesh = Mesh(np.array(jax.devices()[:2]), ('stage',))
    params = _stacked_stage_params(2, 8, rng)
    apply = make_pipelined_apply(mesh, _pipeline_stage, num_microbatches=4)
    with mesh, pytest.raises(ValueError, match='divisible'):
        apply(params, jnp.zeros((6, 8)))


def test_pipeline_rejects_wrong_stage_count(rng):
    # a 4-stage stack over a 2-device axis would silently keep stages 0 and 2
    from jax.sharding import Mesh
    from petastorm_tpu.parallel import make_pipelined_apply

    mesh = Mesh(np.array(jax.devices()[:2]), ('stage',))
    params = _stacked_stage_params(4, 8, rng)
    apply = make_pipelined_apply(mesh, _pipeline_stage, num_microbatches=2)
    with mesh, pytest.raises(ValueError, match='one stage per device'):
        apply(params, jnp.zeros((4, 8)))


def test_mixup_blend_and_labels(rng):
    from petastorm_tpu.ops import mixup

    images = jnp.asarray(rng.integers(0, 255, (8, 6, 6, 3), dtype=np.uint8))
    labels = jnp.asarray(rng.integers(0, 5, (8,)))
    key = jax.random.PRNGKey(3)
    out, soft = jax.jit(lambda i, l, k: mixup(i, l, k, num_classes=5))(images, labels, key)
    assert out.shape == images.shape and out.dtype == images.dtype
    assert soft.shape == (8, 5)
    np.testing.assert_allclose(np.asarray(soft).sum(axis=1), 1.0, atol=1e-5)
    # deterministic under the same key
    out2, soft2 = mixup(images, labels, key, num_classes=5)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(out2))
    # lam >= 0.5: the original image dominates every blend
    orig = images.astype(np.float32)
    assert np.abs(np.asarray(out).astype(np.float32) - orig).max() <= 255 * 0.5 + 1
    # already-soft labels pass through the same blend
    _, soft3 = mixup(images, jax.nn.one_hot(labels, 5), key)
    np.testing.assert_allclose(np.asarray(soft3), np.asarray(soft), atol=1e-6)
    with pytest.raises(ValueError, match='num_classes'):
        mixup(images, labels, key)  # int labels need num_classes


def test_cutmix_box_and_label_fraction(rng):
    from petastorm_tpu.ops import cutmix

    images = jnp.asarray(rng.integers(0, 255, (6, 16, 16, 3), dtype=np.uint8))
    labels = jnp.asarray(rng.integers(0, 4, (6,)))
    key = jax.random.PRNGKey(11)
    out, soft = jax.jit(lambda i, l, k: cutmix(i, l, k, num_classes=4))(images, labels, key)
    assert out.shape == images.shape and out.dtype == images.dtype
    np.testing.assert_allclose(np.asarray(soft).sum(axis=1), 1.0, atol=1e-5)
    # every pixel comes from either the original or SOME other batch image
    out_np, img_np = np.asarray(out), np.asarray(images)
    from_self = (out_np == img_np).all(axis=3)
    changed_frac = 1.0 - from_self.mean()
    # the label fraction and the pixel fraction agree (same realized box);
    # soft rows are lam*self + (1-lam)*partner, so off-own-class mass = 1-lam
    own = np.take_along_axis(np.asarray(soft), np.asarray(labels)[:, None], axis=1).ravel()
    # box fraction bound: pixels equal by coincidence can only OVERSTATE
    # from_self, so changed_frac <= 1-lam_adj
    assert changed_frac <= (1.0 - own.min()) + 1e-6
