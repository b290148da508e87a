"""Ulysses-style all-to-all sequence parallelism: the second context-parallel
attention strategy (sibling of :mod:`petastorm_tpu.ops.ring_attention`).

Where ring attention keeps every device on its own sequence shard and rotates
key/value shards around the mesh ring (n-1 ``ppermute`` steps, O(T/n) memory,
communication overlapped with compute), Ulysses redistributes ONCE: an
``all_to_all`` converts the sequence-sharded layout [B, H, T/n, D] into a
head-sharded layout [B, H/n, T, D], each device runs exact attention for its
own heads over the FULL sequence with zero further communication, and a second
``all_to_all`` restores the sequence sharding. Public recipe: DeepSpeed-Ulysses
(arXiv:2309.14509).

Trade-offs (why both exist):
  * Ulysses needs ``num_heads % ring_size == 0`` and holds full-length K/V for
    its head subset — O(T) memory per device, so it suits moderate T with many
    heads; ring attention holds O(T/n) and scales to extreme T.
  * Ulysses communicates in 2 all-to-all phases (4 ``all_to_all`` ops: q, k, v
    forward + the output back — XLA is free to fuse/overlap the forward
    three); ring does n-1 ppermute rotations but overlaps them with block
    compute.

The local per-head attention reuses the same online-softmax block update as
ring attention (one implementation of the math), scanning k/v chunks so the
[T, T] score matrix never materializes.

Pure JAX: ``lax.all_to_all`` + ``shard_map``, collectives ride ICI. No
reference counterpart — the reference has no model-side sequence code at all
(SURVEY.md §2.9/§5); this exists because BASELINE-scale long-context training
needs the data pipeline's time-major sequence batches consumed by a
context-parallel op.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from petastorm_tpu.ops.ring_attention import _NEG_INF, _block_update


def _chunked_full_attention(q, k, v, causal, kv_chunk):
    """Exact attention of q [B,H,T,D] over full-length k/v [B,H,T,D], scanning
    k/v in chunks of ``kv_chunk`` with the shared online-softmax update."""
    b, h, t, d = q.shape
    scale = 1.0 / (d ** 0.5)
    num_chunks = t // kv_chunk
    q32 = q.astype(jnp.float32)
    m = q32[..., 0] * 0 + _NEG_INF
    l = q32[..., 0] * 0
    acc = q32 * 0
    q_pos = jnp.arange(t)

    k_chunks = k.reshape(b, h, num_chunks, kv_chunk, d).transpose(2, 0, 1, 3, 4)
    v_chunks = v.reshape(b, h, num_chunks, kv_chunk, d).transpose(2, 0, 1, 3, 4)

    def step(carry, inputs):
        m, l, acc = carry
        c, k_blk, v_blk = inputs
        if causal:
            k_pos = c * kv_chunk + jnp.arange(kv_chunk)
            mask = q_pos[:, None] >= k_pos[None, :]
        else:
            mask = jnp.ones((t, kv_chunk), bool)
        m, l, acc = _block_update(q32, k_blk.astype(jnp.float32), v_blk, mask,
                                  m, l, acc, scale)
        return (m, l, acc), None

    (m, l, acc), _ = jax.lax.scan(
        step, (m, l, acc), (jnp.arange(num_chunks), k_chunks, v_chunks))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.astype(q.dtype)


def ulysses_attention(q, k, v, axis_name, causal=False, kv_chunk=None):
    """Exact attention over a sequence sharded on ``axis_name`` via head
    redistribution.

    Call under ``shard_map`` with q/k/v local sequence shards [B, H, T_local, D]
    laid out contiguously (shard i holds positions [i*T_local, (i+1)*T_local) —
    how the loader stages time-major sequence batches). Requires
    ``H % axis_size == 0``. Returns the local output shard in q's dtype.

    ``kv_chunk`` bounds the score-block width of the local attention
    (default: T_local, the natural chunking).
    """
    n = jax.lax.psum(1, axis_name)  # axis size: static under shard_map
    h, t_local = q.shape[1], q.shape[2]
    if h % n:
        # guard at the op so EVERY entry point (including direct
        # make_sharded_ulysses_attention use) fails loudly, not with a cryptic
        # all_to_all split-axis error from inside shard_map
        raise ValueError('ulysses attention needs num_heads ({}) divisible by the '
                         '{!r} axis size ({}); use ring attention otherwise'.format(
                             h, axis_name, n))
    # all_to_all(tiled): split the head axis n ways, concatenate the received
    # pieces along the sequence axis -> [B, H/n, T, D] with the full sequence
    # in device order (contiguous layout preserved)
    seq_to_heads = functools.partial(jax.lax.all_to_all, axis_name=axis_name,
                                     split_axis=1, concat_axis=2, tiled=True)
    q_full, k_full, v_full = seq_to_heads(q), seq_to_heads(k), seq_to_heads(v)

    t = t_local * n
    chunk = t_local if kv_chunk is None else int(kv_chunk)
    if chunk < 1 or t % chunk:
        raise ValueError('kv_chunk ({}) must be a positive divisor of the full sequence '
                         'length ({})'.format(kv_chunk, t))
    out = _chunked_full_attention(q_full, k_full, v_full, causal, chunk)

    # inverse redistribution: split the sequence axis, concatenate heads back
    return jax.lax.all_to_all(out, axis_name=axis_name,
                              split_axis=2, concat_axis=1, tiled=True)


def make_sharded_ulysses_attention(mesh, seq_axis='seq', batch_axis=None,
                                   causal=False, kv_chunk=None):
    """The un-jitted shard_map'd ``(q, k, v) -> out`` on [B, H, T, D] with the
    sequence axis sharded over ``mesh[seq_axis]`` — composable inside a larger
    jitted computation (drop-in for ``make_sharded_ring_attention``)."""
    spec = P(batch_axis, None, seq_axis, None)

    @functools.partial(
        shard_map, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)
    def _sharded(q, k, v):
        return ulysses_attention(q, k, v, seq_axis, causal=causal, kv_chunk=kv_chunk)

    return _sharded


def make_ulysses_attention(mesh, seq_axis='seq', batch_axis=None, causal=False,
                           kv_chunk=None):
    """A jitted ``(q, k, v) -> out`` computing exact attention with the
    sequence axis sharded over ``mesh[seq_axis]`` via all-to-all head
    redistribution. Inputs/outputs are global [B, H, T, D] arrays; the head
    count must be divisible by the ``seq_axis`` size."""
    from jax.sharding import NamedSharding

    spec = P(batch_axis, None, seq_axis, None)
    fn = jax.jit(make_sharded_ulysses_attention(mesh, seq_axis, batch_axis,
                                                causal, kv_chunk))

    def apply(q, k, v):
        if q.shape[1] % mesh.shape[seq_axis]:
            raise ValueError(
                'ulysses attention needs num_heads ({}) divisible by the {} axis '
                'size ({}); use ring attention otherwise'.format(
                    q.shape[1], seq_axis, mesh.shape[seq_axis]))
        sharding = NamedSharding(mesh, spec)
        q, k, v = (jax.device_put(x, sharding) for x in (q, k, v))
        return fn(q, k, v)

    return apply
