"""Fused on-device image normalization (uint8 -> float, mean/std).

Replaces the host-side half of the reference's image path: there,
``CompressedImageCodec.decode`` hands numpy uint8 to user TransformSpecs that
cast and normalize on CPU (reference codecs.py:92-111), quadrupling the bytes
shipped to the accelerator. Here the reader ships uint8 and this op performs
cast + mean-subtract + std-divide in one pass on the TPU.

The Pallas kernel views an NHWC batch as a 2-D (N*H, W*C) array — elementwise
math has no layout semantics, so the only thing that matters is hardware
tiling: lanes of 128 along W*C, sublane blocks along rows. The per-channel
mean/std become a (1, W*C) row (the channel pattern repeats with period C)
broadcast down the block. One read of uint8, one write of bf16/f32: the
fusion XLA would need three ops and an f32 intermediate for.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

# rows per block: multiple of every dtype's sublane minimum (uint8 needs 32)
_BLOCK_ROWS = 256
_BLOCK_COLS = 512  # lanes: multiple of 128


def _kernel(img_ref, mean_ref, inv_std_ref, out_ref):
    x = img_ref[:]
    if jnp.issubdtype(x.dtype, jnp.integer):
        # Mosaic has no direct uint8->f32 cast; widen through int32 first.
        # Float inputs must NOT take this path — int32 would truncate them.
        x = x.astype(jnp.int32)
    x = x.astype(jnp.float32)
    out_ref[:] = ((x - mean_ref[:]) * inv_std_ref[:]).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=('out_dtype', 'interpret'))
def _normalize_pallas(flat, mean_row, inv_std_row, out_dtype, interpret=False):
    n, m = flat.shape
    grid = (pl.cdiv(n, _BLOCK_ROWS), pl.cdiv(m, _BLOCK_COLS))
    return pl.pallas_call(
        _kernel,
        out_shape=jax.ShapeDtypeStruct((n, m), out_dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((_BLOCK_ROWS, _BLOCK_COLS), lambda i, j: (i, j),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, _BLOCK_COLS), lambda i, j: (0, j),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, _BLOCK_COLS), lambda i, j: (0, j),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((_BLOCK_ROWS, _BLOCK_COLS), lambda i, j: (i, j),
                               memory_space=pltpu.VMEM),
        interpret=interpret,
    )(flat, mean_row, inv_std_row)


def _normalize_nhwc(images, mean_row, inv_std_row, out_dtype, interpret):
    b, h, w, c = images.shape
    out = _normalize_pallas(images.reshape(b * h, w * c), mean_row, inv_std_row,
                            out_dtype, interpret=interpret)
    return out.reshape(b, h, w, c)


def _as_channel_row(values, channels, width, name):
    arr = np.asarray(values, dtype=np.float32)
    if arr.ndim == 0:
        arr = np.full(channels, float(arr), np.float32)
    if arr.shape != (channels,):
        raise ValueError('{} must be a scalar or shape ({},), got {}'.format(
            name, channels, arr.shape))
    return np.tile(arr, width)[None, :]  # (1, W*C): channel pattern repeated


def normalize_images(images, mean, std, out_dtype=jnp.bfloat16, use_pallas=None,
                     interpret=False):
    """``(images - mean) / std`` with cast, fused on device.

    :param images: ``(B, H, W, C)`` (or ``(H, W, C)``) uint8/integer/float array
    :param mean/std: scalar or per-channel ``(C,)`` values, in the same units
        as ``images`` (e.g. 0-255 for uint8 ImageNet stats)
    :param out_dtype: output dtype (default bfloat16, the TPU matmul input type)
    :param use_pallas: force the Pallas kernel on/off; default: on when the
        default backend is TPU, else a pure-jnp path (identical math). Under a
        multi-device ``jax.set_mesh`` the kernel runs per device on the batch
        rows split over every mesh axis (``shard_map``); a sharded batch
        outside such a context cannot take the kernel
    :param interpret: run the Pallas kernel in interpreter mode (tests)
    """
    squeeze = images.ndim == 3
    if squeeze:
        images = images[None]
    if images.ndim != 4:
        raise ValueError('images must be (B, H, W, C) or (H, W, C), got shape {}'.format(
            images.shape))
    b, h, w, c = images.shape
    mean_row = _as_channel_row(mean, c, w, 'mean')
    std_row = _as_channel_row(std, c, w, 'std')
    if np.any(std_row == 0):
        raise ValueError('std must be non-zero')
    inv_std_row = 1.0 / std_row

    if use_pallas is None:
        use_pallas = jax.default_backend() == 'tpu'

    if use_pallas or interpret:
        kernel = functools.partial(_normalize_nhwc, out_dtype=jnp.dtype(out_dtype),
                                   interpret=interpret)
        mesh = jax.sharding.get_abstract_mesh()
        if not mesh.empty and mesh.size > 1:
            # XLA cannot partition a Mosaic kernel: under a multi-device mesh
            # (``jax.set_mesh``) each device normalizes its own batch rows
            if b % mesh.size:
                raise ValueError('batch {} does not split over the {} devices of the '
                                 'mesh'.format(b, mesh.size))
            rows = P(tuple(mesh.axis_names))
            kernel = jax.shard_map(kernel, mesh=mesh, in_specs=(rows, P(), P()),
                                   out_specs=rows, check_vma=False)
        out = kernel(images, jnp.asarray(mean_row), jnp.asarray(inv_std_row))
    else:
        mean_a = jnp.asarray(mean_row.reshape(w, c), jnp.float32)
        inv_a = jnp.asarray(inv_std_row.reshape(w, c), jnp.float32)
        out = ((images.astype(jnp.float32) - mean_a) * inv_a).astype(out_dtype)
    return out[0] if squeeze else out
