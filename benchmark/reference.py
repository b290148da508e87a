"""Plain reference of the train step, and the benchmark's weights.

Written from the published description of ResNet (He et al., CVPR 2016,
arXiv:1512.03385, bottleneck blocks with the stride on the 3x3 conv) and of
batch norm in training mode (Ioffe and Szegedy 2015), in float32 ``jax.numpy``
at ``highest`` matmul precision. It imports nothing of the program. The
parameter tree uses the program's names, so the same weights, made here from
the seed, can be handed to both.

``quant='fp8'`` computes in fp8 where the program computes in bfloat16, as
fp8 training does: every conv and dense layer takes per-tensor scaled
float8_e4m3 operands and passes float8_e5m2 output gradients back, and
every activation between them is held in scaled float8_e4m3. It is the
control: one precision below the bfloat16 the configuration states. ``half_batch=True`` takes the loss over the first
half of the batch alone: a planted fault.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


# -- the parameter tree -------------------------------------------------------

def _blocks(model):
    """``(name, in_channels, filters, strides)`` of every bottleneck block."""
    channels = model['num_filters']
    for i, count in enumerate(model['stage_sizes']):
        for j in range(count):
            filters = model['num_filters'] * 2 ** i
            yield ('stage{}_block{}'.format(i + 1, j), channels, filters,
                   2 if i > 0 and j == 0 else 1)
            channels = 4 * filters


def param_shapes(model):
    """``{module: {leaf: shape}}`` of the parameters and of the batch-norm
    statistics, in the program's naming."""
    nf = model['num_filters']
    params = {'conv_init': {'kernel': (7, 7, 3, nf)}}
    norms = {'bn_init': nf}
    for name, cin, f, strides in _blocks(model):
        block = {'conv1': {'kernel': (1, 1, cin, f)}, 'conv2': {'kernel': (3, 3, f, f)},
                 'conv3': {'kernel': (1, 1, f, 4 * f)}}
        block_norms = {'bn1': f, 'bn2': f, 'bn3': 4 * f}
        if cin != 4 * f or strides != 1:
            block['conv_proj'] = {'kernel': (1, 1, cin, 4 * f)}
            block_norms['bn_proj'] = 4 * f
        for bn, c in block_norms.items():
            block[bn] = {'scale': (c,), 'bias': (c,)}
        params[name] = block
        norms[name] = block_norms
    params['bn_init'] = {'scale': (nf,), 'bias': (nf,)}
    last = 4 * nf * 2 ** (len(model['stage_sizes']) - 1)
    params['head'] = {'kernel': (last, model['num_classes']), 'bias': (model['num_classes'],)}
    stats = {'bn_init': {'mean': (nf,), 'var': (nf,)}}
    for name, _, _, _ in _blocks(model):
        stats[name] = {bn: {'mean': (c,), 'var': (c,)} for bn, c in norms[name].items()}
    return params, stats


def _paths(tree, prefix=()):
    for key in sorted(tree):
        value = tree[key]
        if isinstance(value, dict):
            yield from _paths(value, prefix + (key,))
        else:
            yield prefix + (key,), value


def _set(tree, path, value):
    for key in path[:-1]:
        tree = tree.setdefault(key, {})
    tree[path[-1]] = value


def seed_key(seed):
    """The key data of ``seed``: an argument, so that no compiled program
    depends on the seed."""
    return jax.random.key_data(jax.random.key(seed))


def init_variables(model, init, key_data):
    """``(params, batch_stats)`` in float32 from the key data of the seed
    (``seed_key``): conv kernels normal with std sqrt(2 / fan_in), the head
    normal with std ``init['head_std']``, biases 0, batch-norm scales
    ``init['bn_scale']`` but ``init['residual_bn_scale']`` for the last
    batch norm of each residual branch (``bn3``, which the program's model
    initialises to zero), statistics mean 0 and variance 1. Jit-able."""
    shapes, stat_shapes = param_shapes(model)
    key = jax.random.wrap_key_data(key_data)
    params = {}
    for k, (path, shape) in enumerate(_paths(shapes)):
        leaf_key = jax.random.fold_in(key, k)
        if path[-1] == 'kernel' and path[0] == 'head':
            value = jax.random.normal(leaf_key, shape, jnp.float32) * init['head_std']
        elif path[-1] == 'kernel':
            fan_in = int(np.prod(shape[:-1]))
            value = jax.random.normal(leaf_key, shape, jnp.float32) * np.sqrt(2.0 / fan_in)
        elif path[-1] == 'scale':
            scale = init['residual_bn_scale'] if path[-2] == 'bn3' else init['bn_scale']
            value = jnp.full(shape, scale, jnp.float32)
        else:
            value = jnp.zeros(shape, jnp.float32)
        _set(params, path, value)
    stats = {}
    for path, shape in _paths(stat_shapes):
        _set(stats, path, jnp.zeros(shape, jnp.float32) if path[-1] == 'mean'
             else jnp.ones(shape, jnp.float32))
    return params, stats


# -- fp8 as fp8 training computes ---------------------------------------------

def _scaled_round(x, dtype):
    """``x`` rounded to ``dtype`` with one scale that maps its largest
    magnitude to the type's largest; clipped first, because float8_e4m3fn
    turns what lies past its largest value into NaN."""
    top = float(jnp.finfo(dtype).max)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
    return jnp.clip(x / scale, -top, top).astype(dtype).astype(jnp.float32) * scale


@jax.custom_vjp
def _fp8_operand(x):
    return _scaled_round(x, jnp.float8_e4m3fn)


def _fp8_operand_fwd(x):
    return _fp8_operand(x), None


def _fp8_operand_bwd(_, g):
    return (g,)


_fp8_operand.defvjp(_fp8_operand_fwd, _fp8_operand_bwd)


@jax.custom_vjp
def _fp8_output(y):
    return y


def _fp8_output_fwd(y):
    return y, None


def _fp8_output_bwd(_, g):
    return (_scaled_round(g, jnp.float8_e5m2),)


_fp8_output.defvjp(_fp8_output_fwd, _fp8_output_bwd)


# -- the forward pass ---------------------------------------------------------

def _conv(x, kernel, strides, padding, quant):
    if quant == 'fp8':
        x, kernel = _fp8_operand(x), _fp8_operand(kernel)
    y = jax.lax.conv_general_dilated(
        x, kernel, (strides, strides), padding,
        dimension_numbers=('NHWC', 'HWIO', 'NHWC'), precision=HIGHEST)
    return _fp8_output(y) if quant == 'fp8' else y


def _batch_norm(x, p, eps, quant):
    mean = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(x - mean), axis=(0, 1, 2))
    y = (x - mean) * jax.lax.rsqrt(var + eps) * p['scale'] + p['bias']
    return _fp8_operand(y) if quant == 'fp8' else y


def _bottleneck(x, p, strides, eps, quant):
    y = jax.nn.relu(_batch_norm(_conv(x, p['conv1']['kernel'], 1, 'SAME', quant), p['bn1'],
                                eps, quant))
    y = jax.nn.relu(_batch_norm(_conv(y, p['conv2']['kernel'], strides, 'SAME', quant),
                                p['bn2'], eps, quant))
    y = _batch_norm(_conv(y, p['conv3']['kernel'], 1, 'SAME', quant), p['bn3'], eps, quant)
    if 'conv_proj' in p:
        x = _batch_norm(_conv(x, p['conv_proj']['kernel'], strides, 'SAME', quant),
                        p['bn_proj'], eps, quant)
    y = jax.nn.relu(x + y)
    return _fp8_operand(y) if quant == 'fp8' else y


def flip_key(seed, step):
    """The key of step ``step``'s flips: ``fold_in(key(seed), step)``, where
    ``seed`` is the configuration's ``augment_seed``."""
    return jax.random.fold_in(jax.random.key(seed), step)


def preprocess(images, labels, key, model):
    """The step's input ops: a horizontal flip of each image with
    probability ``model['flip_prob']`` drawn from ``key``, then
    ``(x - mean) / std`` per channel, in float32."""
    flip = jax.random.bernoulli(key, model['flip_prob'], (images.shape[0],))
    images = jnp.where(flip[:, None, None, None], images[:, :, ::-1, :], images)
    mean = jnp.asarray(model['input_mean'], jnp.float32)
    std = jnp.asarray(model['input_std'], jnp.float32)
    return (images.astype(jnp.float32) - mean) / std, labels


def forward(params, x, model, quant=None):
    eps = model['bn_epsilon']
    x = _conv(x, params['conv_init']['kernel'], 2, [(3, 3), (3, 3)], quant)
    x = jax.nn.relu(_batch_norm(x, params['bn_init'], eps, quant))
    x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
                              ((0, 0), (1, 1), (1, 1), (0, 0)))
    for name, _, _, strides in _blocks(model):
        block = jax.checkpoint(functools.partial(_bottleneck, strides=strides, eps=eps,
                                                 quant=quant))
        x = block(x, params[name])
    x = jnp.mean(x, axis=(1, 2))
    head = params['head']
    if quant == 'fp8':
        return _fp8_output(jnp.dot(_fp8_operand(x), _fp8_operand(head['kernel']),
                                   precision=HIGHEST)) + head['bias']
    return jnp.dot(x, head['kernel'], precision=HIGHEST) + head['bias']


def loss(params, x, labels, model, quant=None, half_batch=False):
    if half_batch:
        x, labels = x[:x.shape[0] // 2], labels[:labels.shape[0] // 2]
    logits = forward(params, x, model, quant)
    logp = jax.nn.log_softmax(logits)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None].astype(jnp.int32), axis=1))


def step_model(model, optimizer):
    """``model`` with the optimizer's ``momentum`` and ``learning_rate``."""
    return dict(model, momentum=optimizer['momentum'], learning_rate=optimizer['learning_rate'])


def sgd_step(params, trace, images, labels, flip_key, model, quant=None, half_batch=False):
    """One step of SGD with momentum on uint8 ``images``; ``model`` is a
    ``step_model``. Returns ``(params, trace, loss)``."""
    with jax.default_matmul_precision('highest'):
        x, labels = preprocess(images, labels, flip_key, model)
        value, grads = jax.value_and_grad(loss)(params, x, labels, model, quant, half_batch)
        trace = jax.tree_util.tree_map(lambda g, t: g + model['momentum'] * t, grads, trace)
        params = jax.tree_util.tree_map(lambda p, t: p - model['learning_rate'] * t,
                                        params, trace)
    return params, trace, value


@functools.partial(jax.jit, static_argnames=('model_key', 'quant', 'half_batch'))
def _step(params, trace, images, labels, flip_key, model_key, quant, half_batch):
    return sgd_step(params, trace, images, labels, flip_key, dict(model_key), quant, half_batch)


def _model_key(model, optimizer):
    """The hashable static description the jitted step is keyed on."""
    items = step_model(model, optimizer)
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v) for k, v in items.items()))


def train(config, seed, batches, quant=None, half_batch=False, device=None):
    """The reference's first ``len(batches)`` steps of SGD with momentum from
    the benchmark's weights for ``seed``, on ``batches`` of (uint8 images,
    int labels). Returns ``{'losses', 'grad1', 'change'}`` on the host:
    each step's loss, the first step's gradient and the parameters' change
    over all steps, as float64 trees."""
    model, optimizer = config['model'], config['optimizer']
    device = device or jax.devices()[0]
    with jax.default_device(device):
        params, _ = jax.jit(functools.partial(init_variables, model, config['init']))(
            seed_key(seed))
        start = jax.device_get(params)
        trace = jax.tree_util.tree_map(jnp.zeros_like, params)
        key = _model_key(model, optimizer)
        losses, grad1 = [], None
        for step, (images, labels) in enumerate(batches):
            params, trace, value = _step(params, trace, jnp.asarray(images),
                                         jnp.asarray(labels, jnp.int32),
                                         flip_key(model['augment_seed'], step), key, quant,
                                         half_batch)
            losses.append(float(value))
            if step == 0:
                grad1 = jax.device_get(trace)
        end = jax.device_get(params)
    change = jax.tree_util.tree_map(lambda a, b: np.float64(a) - b, end, start)
    return {'losses': losses, 'grad1': grad1, 'change': change}
