"""A two-layer causal language model on packed token sequences, for the
harness's CPU tests: a configuration whose batch is not an image.

The program's side is a flax model (``nn.dot_product_attention`` under a
causal mask within each packed segment) trained by the program's
``TrainState`` with ``optax.adam``. The plain reference is written below in
``jax.numpy`` at float32 ``highest``, with Adam's equations by hand; the
weights are made from the seed by ``init_params`` and handed to both. Each
cell reports ``tokens_per_s``: the document tokens (not padding) each step
counts in its metrics.
"""

from __future__ import annotations

import itertools

#: the end-to-end metric of the window's throughput, and its unit counted
#: by ``units``
THROUGHPUT = 'tokens_per_s'

KEYS = ('tokens', 'segment_ids', 'positions')


def batch_spec(config, global_batch):
    return [(key, (global_batch, config['sequence_length']), 'int32') for key in KEYS]


def units(step_metrics, global_batch):
    """Document tokens trained by the steps whose metrics are ``step_metrics``."""
    return int(sum(int(m['tokens']) for m in step_metrics))


def init_params(model, sequence_length, key_data):
    """Every matrix normal with std ``init_std``; no biases. Jit-able."""
    import jax
    import jax.numpy as jnp
    width, std = model['d_model'], model['init_std']
    shapes = {'embed': {'embedding': (model['vocab_size'], width)},
              'position': {'embedding': (sequence_length, width)},
              'head': {'kernel': (width, model['vocab_size'])}}
    for i in range(model['num_layers']):
        shapes['block{}'.format(i)] = {
            'qkv': {'kernel': (width, 3 * width)}, 'out': {'kernel': (width, width)},
            'up': {'kernel': (width, model['mlp_dim'])},
            'down': {'kernel': (model['mlp_dim'], width)}}
    key = jax.random.wrap_key_data(key_data)
    leaves = itertools.count()

    def make(tree):
        return {name: make(sub) if isinstance(sub, dict) else std * jax.random.normal(
            jax.random.fold_in(key, next(leaves)), sub, jnp.float32)
            for name, sub in sorted(tree.items())}

    return make(shapes)


def _key_data(seed):
    import jax
    return jax.random.key_data(jax.random.key(seed))


def _optimizer(config):
    import optax
    opt = config['optimizer']
    return optax.adam(opt['learning_rate'], b1=opt['b1'], b2=opt['b2'], eps=opt['eps'])


# -- the program's side ------------------------------------------------------------

def _flax_model(config):
    import flax.linen as nn
    import jax.numpy as jnp
    model = config['model']
    dtype = jnp.dtype(model['dtype'])
    width, heads = model['d_model'], model['num_heads']

    class Block(nn.Module):
        @nn.compact
        def __call__(self, x, mask):
            b, t, _ = x.shape
            qkv = nn.Dense(3 * width, use_bias=False, dtype=dtype, name='qkv')(x)
            q, k, v = (a.reshape(b, t, heads, width // heads) for a in jnp.split(qkv, 3, -1))
            a = nn.dot_product_attention(q, k, v, mask=mask, dtype=dtype)
            x = x + nn.Dense(width, use_bias=False, dtype=dtype, name='out')(a.reshape(b, t, width))
            h = nn.relu(nn.Dense(model['mlp_dim'], use_bias=False, dtype=dtype, name='up')(x))
            return x + nn.Dense(width, use_bias=False, dtype=dtype, name='down')(h)

    class TinyLM(nn.Module):
        @nn.compact
        def __call__(self, tokens, segment_ids, positions):
            mask = nn.combine_masks(nn.make_attention_mask(segment_ids, segment_ids, jnp.equal),
                                    nn.make_causal_mask(tokens))
            x = (nn.Embed(model['vocab_size'], width, dtype=dtype, name='embed')(tokens)
                 + nn.Embed(config['sequence_length'], width, dtype=dtype,
                            name='position')(positions))
            for i in range(model['num_layers']):
                x = Block(name='block{}'.format(i))(x, mask)
            return nn.Dense(model['vocab_size'], use_bias=False, dtype=dtype, name='head')(x)

    return TinyLM()


def program_step(config, donate=True):
    """The jitted train step: next-token cross entropy within each segment."""
    import jax
    import jax.numpy as jnp
    import optax
    model = _flax_model(config)

    def step(state, tokens, segment_ids, positions):
        def loss_fn(params):
            logits = model.apply({'params': params}, tokens, segment_ids, positions)
            ce = optax.softmax_cross_entropy_with_integer_labels(
                logits[:, :-1].astype(jnp.float32), tokens[:, 1:])
            valid = (segment_ids[:, 1:] == segment_ids[:, :-1]) & (segment_ids[:, 1:] > 0)
            return jnp.sum(ce * valid) / jnp.maximum(jnp.sum(valid), 1)

        loss, grads = jax.value_and_grad(loss_fn)(state.params)
        return state.apply_gradients(grads=grads), {
            'loss': loss, 'tokens': jnp.sum(segment_ids > 0)}

    return jax.jit(step, donate_argnums=(0,) if donate else ())


def make_state(config, seed, mesh):
    import jax

    from petastorm_tpu.models.train import TrainState, state_shardings
    tx = _optimizer(config)

    def build(key_data):
        params = init_params(config['model'], config['sequence_length'], key_data)
        return TrainState.create(apply_fn=None, params=params, tx=tx)

    key_data = _key_data(seed)
    shardings = state_shardings(jax.eval_shape(build, key_data), mesh)
    return jax.jit(build, out_shardings=shardings)(key_data)


def make_step(config, factory=None):
    return (factory or program_step)(config)


def first_gradient(config, state):
    """Adam's first moment after one step is ``(1 - b1)`` times the first
    gradient."""
    import jax
    b1 = config['optimizer']['b1']
    return jax.tree_util.tree_map(lambda m: m / (1 - b1), state.opt_state[0].mu)


# -- the plain reference ------------------------------------------------------------

def forward(params, tokens, segment_ids, positions, model):
    import jax
    import jax.numpy as jnp
    highest = jax.lax.Precision.HIGHEST
    x = params['embed']['embedding'][tokens] + params['position']['embedding'][positions]
    b, t, width = x.shape
    heads = model['num_heads']
    allowed = ((segment_ids[:, :, None] == segment_ids[:, None, :])
               & jnp.tril(jnp.ones((t, t), bool))[None])
    for i in range(model['num_layers']):
        p = params['block{}'.format(i)]
        qkv = jnp.dot(x, p['qkv']['kernel'], precision=highest)
        q, k, v = (a.reshape(b, t, heads, width // heads) for a in jnp.split(qkv, 3, -1))
        scores = jnp.einsum('bqhd,bkhd->bhqk', q, k, precision=highest) / jnp.sqrt(width / heads)
        weights = jax.nn.softmax(jnp.where(allowed[:, None], scores, -jnp.inf), axis=-1)
        a = jnp.einsum('bhqk,bkhd->bqhd', weights, v, precision=highest).reshape(b, t, width)
        x = x + jnp.dot(a, p['out']['kernel'], precision=highest)
        h = jax.nn.relu(jnp.dot(x, p['up']['kernel'], precision=highest))
        x = x + jnp.dot(h, p['down']['kernel'], precision=highest)
    return jnp.dot(x, params['head']['kernel'], precision=highest)


def loss(params, tokens, segment_ids, positions, model):
    import jax
    import jax.numpy as jnp
    logp = jax.nn.log_softmax(forward(params, tokens, segment_ids, positions, model)[:, :-1])
    picked = jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)[..., 0]
    valid = (segment_ids[:, 1:] == segment_ids[:, :-1]) & (segment_ids[:, 1:] > 0)
    return -jnp.sum(picked * valid) / jnp.sum(valid)


def _reference_step(model, opt):
    import jax
    import jax.numpy as jnp

    def step(params, mu, nu, count, tokens, segment_ids, positions):
        value, grads = jax.value_and_grad(loss)(params, tokens, segment_ids, positions, model)
        count = count + 1
        mu = jax.tree_util.tree_map(lambda m, g: opt['b1'] * m + (1 - opt['b1']) * g, mu, grads)
        nu = jax.tree_util.tree_map(lambda n, g: opt['b2'] * n + (1 - opt['b2']) * g * g,
                                    nu, grads)
        params = jax.tree_util.tree_map(
            lambda p, m, n: p - opt['learning_rate'] * (m / (1 - opt['b1'] ** count))
            / (jnp.sqrt(n / (1 - opt['b2'] ** count)) + opt['eps']), params, mu, nu)
        return params, mu, nu, count, value, grads

    return jax.jit(step)


def reference(config, seed, batches, device):
    """The plain reference's steps of Adam on ``batches`` of (tokens,
    segment ids, positions): ``{'losses', 'grad1', 'change'}``."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    model, opt = config['model'], config['optimizer']
    step = _reference_step(model, opt)
    with jax.default_device(device), jax.default_matmul_precision('highest'):
        params = init_params(model, config['sequence_length'], _key_data(seed))
        start = jax.device_get(params)
        mu = jax.tree_util.tree_map(jnp.zeros_like, params)
        nu = jax.tree_util.tree_map(jnp.zeros_like, params)
        count = jnp.zeros((), jnp.int32)
        losses, grad1 = [], None
        for tokens, segment_ids, positions in batches:
            params, mu, nu, count, value, grads = step(
                params, mu, nu, count, jnp.asarray(tokens), jnp.asarray(segment_ids),
                jnp.asarray(positions))
            losses.append(float(value))
            if grad1 is None:
                grad1 = jax.device_get(grads)
        end = jax.device_get(params)
    change = jax.tree_util.tree_map(lambda a, b: np.float64(a) - b, end, start)
    return {'losses': losses, 'grad1': grad1, 'change': change}
