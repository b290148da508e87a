"""Ring attention: context-parallel attention over a mesh axis.

The reference's only long-sequence feature is NGram windowed readout on the
data side (reference ngram.py; SURVEY.md §5 "long-context"). On TPU the
framework also has to FEED long-context training, where the sequence axis is
sharded across devices ("context parallelism"). This module supplies the
model-side op that consumes such sequence-sharded batches: blockwise (online
softmax) attention where key/value shards rotate around the mesh axis ring via
``jax.lax.ppermute``, so each device only ever holds ``T / ring_size`` keys —
memory per device is O(T/n) while computing exact full attention.

Pure JAX + XLA collectives (psum/ppermute ride ICI), composed with
``jax.shard_map`` — no hand-rolled communication runtime, per the platform's
compilation model. The blockwise accumulation is the standard public
flash/ring-attention recipe (log-sum-exp running max).

Use :func:`ring_attention` under ``shard_map`` yourself, or
:func:`make_ring_attention` for a ready-made sharded callable on a mesh.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P


_NEG_INF = -1e30


def _block_update(q, k_blk, v_blk, mask, m, l, acc, scale):
    """One online-softmax accumulation step.

    q: [B,H,Tq,D]; k_blk/v_blk: [B,H,Tk,D]; mask: [Tq,Tk] bool (True = keep);
    m/l: [B,H,Tq] running max / normalizer; acc: [B,H,Tq,D] running numerator.
    """
    s = jnp.einsum('bhqd,bhkd->bhqk', q, k_blk,
                   preferred_element_type=jnp.float32) * scale
    s = jnp.where(mask[None, None], s, _NEG_INF)
    m_new = jnp.maximum(m, s.max(axis=-1))
    p = jnp.exp(s - m_new[..., None])
    correction = jnp.exp(m - m_new)
    l_new = l * correction + p.sum(axis=-1)
    acc_new = acc * correction[..., None] + jnp.einsum(
        'bhqk,bhkd->bhqd', p, v_blk.astype(jnp.float32),
        preferred_element_type=jnp.float32)
    return m_new, l_new, acc_new


def ring_attention(q, k, v, axis_name, causal=False):
    """Exact attention over a sequence sharded on ``axis_name``.

    Call under ``shard_map`` with q/k/v sharded on their sequence axis:
    q: [B, H, Tq_local, D], k/v: [B, H, Tk_local, D] (local shards).
    Returns the local output shard [B, H, Tq_local, D] in q's dtype.

    ``causal`` masks with GLOBAL positions: query global index >= key global
    index. Shards must be laid out contiguously (shard i holds positions
    [i*T_local, (i+1)*T_local)), which is how the loader stages time-major
    sequence batches.
    """
    n = jax.lax.psum(1, axis_name)
    my_idx = jax.lax.axis_index(axis_name)
    b, h, tq, d = q.shape
    tk = k.shape[2]
    scale = 1.0 / (d ** 0.5)

    q32 = q.astype(jnp.float32)
    # derive the accumulators from q (zeroed) rather than fresh constants:
    # under shard_map the scan carry's device-varying axes must match the
    # body's outputs, and q already varies over every mesh axis in play
    m = q32[..., 0] * 0 + _NEG_INF
    l = q32[..., 0] * 0
    acc = q32 * 0

    q_pos = my_idx * tq + jnp.arange(tq)

    def step(carry, t):
        k_blk, v_blk, m, l, acc = carry
        # device i holds k-shard (i - t) mod n at ring step t
        blk_idx = jnp.mod(my_idx - t, n)
        if causal:
            k_pos = blk_idx * tk + jnp.arange(tk)
            mask = q_pos[:, None] >= k_pos[None, :]
        else:
            mask = jnp.ones((tq, tk), bool)
        m, l, acc = _block_update(q32, k_blk.astype(jnp.float32),
                                  v_blk, mask, m, l, acc, scale)
        # rotate k/v shards one step around the ring
        perm = [(i, (i + 1) % n) for i in range(n)]
        k_next = jax.lax.ppermute(k_blk, axis_name, perm)
        v_next = jax.lax.ppermute(v_blk, axis_name, perm)
        return (k_next, v_next, m, l, acc), None

    (_, _, m, l, acc), _ = jax.lax.scan(step, (k, v, m, l, acc), jnp.arange(n))
    # fully-masked rows (never possible for causal with contiguous layout, but
    # cheap insurance): avoid 0/0
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.astype(q.dtype)


def make_sharded_ring_attention(mesh, seq_axis='seq', batch_axis=None, causal=False):
    """The un-jitted shard_map'd ``(q, k, v) -> out`` on [B, H, T, D] with the
    sequence axis sharded over ``mesh[seq_axis]`` — composable inside a larger
    jitted computation (e.g. a transformer's attention_fn). The ONE place the
    partition spec + shard_map wiring lives."""
    spec = P(batch_axis, None, seq_axis, None)

    @functools.partial(
        shard_map, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)
    def _sharded(q, k, v):
        return ring_attention(q, k, v, seq_axis, causal=causal)

    return _sharded


def make_ring_attention(mesh, seq_axis='seq', batch_axis=None, causal=False):
    """A jitted ``(q, k, v) -> out`` computing exact attention with the
    sequence axis sharded over ``mesh[seq_axis]`` (and optionally batch over
    ``batch_axis``). Inputs/outputs are global arrays of shape [B, H, T, D]."""
    from jax.sharding import NamedSharding

    spec = P(batch_axis, None, seq_axis, None)
    fn = jax.jit(make_sharded_ring_attention(mesh, seq_axis, batch_axis, causal))

    def apply(q, k, v):
        sharding = NamedSharding(mesh, spec)
        q, k, v = (jax.device_put(x, sharding) for x in (q, k, v))
        return fn(q, k, v)

    return apply
