"""Pipeline parallelism (pp): GPipe-style microbatched execution over a mesh
axis.

The last of the framework's parallelism strategies (with dp/tp/sp/ep): the
model's layers are split into S stages, one per device along ``stage_axis``;
the batch is split into M microbatches that flow through the stages in a
skewed schedule (stage s processes microbatch ``t - s`` at tick t), with
activations hopping stage-to-stage via ``lax.ppermute`` on ICI. After the
S + M - 1 fill-and-drain ticks every microbatch has traversed every stage.
Public recipe: GPipe (arXiv:1811.06965), expressed SPMD-style — all stages
run the same program under ``shard_map``, per-stage parameters are a stacked
``[S, ...]`` pytree sharded ``P(stage_axis)``, and validity masking replaces
control flow (XLA-friendly: one ``lax.fori_loop``, no data-dependent Python).

Bubble fraction is the usual (S-1)/(S+M-1) — raise ``num_microbatches`` to
amortize. Exactness: outputs equal running the stages sequentially (tested).

This module is the generic machinery; compose it with any per-stage function
(``stage_fn(stage_params, activation) -> activation``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P



def pipeline_spmd(stage_fn, stage_params, microbatches, axis_name):
    """Run the pipeline from INSIDE ``shard_map`` over ``axis_name``.

    :param stage_fn: ``(stage_params, act) -> act`` applied by every stage to
        its current microbatch activation (same shapes in and out).
    :param stage_params: THIS stage's parameter pytree (the shard_map-local
        slice of the stacked parameters, leading stage axis already squeezed).
    :param microbatches: ``[M, mb, ...]`` the full microbatched input
        (replicated across stages; stage 0 ingests microbatch t at tick t).
    :returns: ``[M, mb, ...]`` outputs (identical on every stage).
    """
    n_stages = jax.lax.psum(1, axis_name)
    stage = jax.lax.axis_index(axis_name)
    num_mb = microbatches.shape[0]
    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def tick(t, carry):
        act, out = carry
        # stage 0 ingests a fresh microbatch; later stages use the activation
        # that arrived from the previous stage on the last tick
        mb_t = jnp.clip(t, 0, num_mb - 1)
        inp = jnp.where(stage == 0, microbatches[mb_t], act)
        y = stage_fn(stage_params, inp)
        # stage s holds microbatch t - s at tick t; outside [0, M) it is
        # pipeline bubble — computed SPMD anyway, writes masked out
        mb_i = t - stage
        valid = jnp.logical_and(mb_i >= 0, mb_i < num_mb)
        mb_w = jnp.clip(mb_i, 0, num_mb - 1)
        write = jnp.logical_and(valid, stage == n_stages - 1)
        out = out.at[mb_w].set(jnp.where(write, y, out[mb_w]))
        act = jax.lax.ppermute(y, axis_name, perm)
        return act, out

    # the carries are updated with device-varying values inside the loop, so
    # their initial values must already be device-varying (shard_map rejects a
    # replicated->varying carry): derive them from axis_index, which varies
    varying_zero = (jax.lax.axis_index(axis_name) * 0).astype(microbatches.dtype)
    act0 = jnp.zeros_like(microbatches[0]) + varying_zero
    out0 = jnp.zeros_like(microbatches) + varying_zero
    _, out = jax.lax.fori_loop(0, n_stages + num_mb - 1, tick, (act0, out0))
    # results live on the last stage; psum of masked copies replicates them
    return jax.lax.psum(jnp.where(stage == n_stages - 1, out, jnp.zeros_like(out)),
                        axis_name)


def make_pipelined_apply(mesh, stage_fn, stage_axis='stage', num_microbatches=None):
    """A jitted ``(stacked_params, x) -> y`` running ``stage_fn`` as a
    pipeline over ``mesh[stage_axis]``.

    ``stacked_params``: pytree whose every leaf has a leading ``[S, ...]``
    stage axis (S = the mesh axis size) — sharded ``P(stage_axis)`` so each
    device holds only its own stage's parameters. ``x``: ``[B, ...]`` global
    batch with ``B`` divisible by ``num_microbatches`` (default S, the
    minimum that keeps every stage busy at steady state).
    """
    n_stages = mesh.shape[stage_axis]
    num_mb = num_microbatches or n_stages

    def _squeeze(tree):
        return jax.tree_util.tree_map(lambda leaf: leaf[0], tree)

    # P(stage_axis) is a pytree PREFIX: it applies to every parameter leaf
    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(P(stage_axis), P()), out_specs=P())
    def _run(stacked_params, microbatches):
        # shard_map hands each stage its [1, ...] parameter slice
        return pipeline_spmd(stage_fn, _squeeze(stacked_params), microbatches,
                             stage_axis)

    @jax.jit
    def apply(stacked_params, x):
        # shard_map would happily split a WRONG-but-divisible stage count
        # (e.g. 4 stacked stages over a 2-device axis keeps stages 0 and 2
        # and silently computes garbage) — reject anything but an exact match
        for path, leaf in jax.tree_util.tree_flatten_with_path(stacked_params)[0]:
            if leaf.shape[0] != n_stages:
                raise ValueError(
                    'stacked stage params leaf {} has leading dim {} but the {!r} mesh '
                    'axis has {} stages; one stage per device is required'.format(
                        jax.tree_util.keystr(path), leaf.shape[0], stage_axis, n_stages))
        b = x.shape[0]
        if b % num_mb:
            raise ValueError('batch ({}) must be divisible by num_microbatches '
                             '({})'.format(b, num_mb))
        mb = x.reshape((num_mb, b // num_mb) + x.shape[1:])
        out = _run(stacked_params, mb)
        return out.reshape((b,) + out.shape[2:])

    return apply
