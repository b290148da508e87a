"""ResNet on images: what a cell needs of the program's model and of its
plain reference, for configurations whose ``model.arch`` is ``resnet``.

The program's step is ``make_train_step(preprocess_fn=device_preprocess)``
on ``petastorm_tpu.models.resnet`` with SGD and momentum; the reference is
``benchmark/reference.py``. Each cell reports ``images_per_s``: a step
trains the global batch.
"""

from __future__ import annotations

#: the end-to-end metric of the window's throughput, and its unit counted
#: by ``units``
THROUGHPUT = 'images_per_s'


def batch_spec(config, global_batch):
    """``[(key, shape, dtype)]`` of the batch the compiled step takes, in
    the order it takes them."""
    size = config['image_size']
    return [('image', (global_batch, size, size, 3), 'uint8'),
            ('label', (global_batch,), 'int32')]


def units(step_metrics, global_batch):
    """Images trained by the steps whose metrics are ``step_metrics``."""
    return len(step_metrics) * global_batch


def make_model(model):
    import jax.numpy as jnp

    from petastorm_tpu.models.resnet import BottleneckBlock, ResNet
    return ResNet(stage_sizes=model['stage_sizes'], block_cls=BottleneckBlock,
                  num_filters=model['num_filters'], num_classes=model['num_classes'],
                  dtype=jnp.dtype(model['dtype']))


def make_state(config, seed, mesh):
    """The program's TrainState holding the benchmark's weights for ``seed``,
    made on the mesh in one jitted call."""
    import jax
    import optax

    from benchmark import reference
    from petastorm_tpu.models.train import TrainState, state_shardings
    model = make_model(config['model'])
    opt = config['optimizer']
    tx = optax.sgd(opt['learning_rate'], momentum=opt['momentum'])

    def build(key_data):
        params, stats = reference.init_variables(config['model'], config['init'], key_data)
        return TrainState.create(apply_fn=model.apply, params=params, batch_stats=stats, tx=tx)

    key_data = reference.seed_key(seed)
    shardings = state_shardings(jax.eval_shape(build, key_data), mesh)
    return jax.jit(build, out_shardings=shardings)(key_data)


def make_step(config, factory=None):
    """The jitted step from ``factory`` (the program's ``make_train_step``
    unless a fault or the control is put in its place), with the flips and
    the normalize inside it."""
    from examples.imagenet.jax_resnet_example import device_preprocess
    from petastorm_tpu.models.train import make_train_step
    factory = factory or make_train_step
    return factory(preprocess_fn=device_preprocess,
                   preprocess_seed=config['model']['augment_seed'])


def first_gradient(config, state):
    """The gradient of the first step, as SGD with momentum keeps it: its
    trace, which starts at zero, equals the first gradient after one step."""
    return state.opt_state[0].trace


def reference(config, seed, batches, device, **variant):
    """The plain reference's steps on ``batches`` of (uint8 images, labels):
    ``{'losses', 'grad1', 'change'}``. ``variant`` is ``quant='fp8'`` (the
    control) or ``half_batch=True`` (a planted fault)."""
    from benchmark import reference as plain
    return plain.train(config, seed, batches, device=device, **variant)


def control_step(config):
    """A factory with ``make_train_step``'s arguments whose jitted step is
    the control: the reference's step computed in fp8, on the program's
    state, flipped as the program flips (``preprocess_seed`` and the step
    counter)."""

    def factory(preprocess_fn=None, preprocess_seed=0, donate=True):
        import jax

        from benchmark import reference as plain
        model = plain.step_model(config['model'], config['optimizer'])

        def step(state, images, labels):
            key = jax.random.fold_in(jax.random.key(preprocess_seed), state.step)
            params, trace, loss = plain.sgd_step(
                state.params, state.opt_state[0].trace, images, labels, key, model, quant='fp8')
            opt_state = (state.opt_state[0]._replace(trace=trace),) + tuple(state.opt_state[1:])
            return (state.replace(step=state.step + 1, params=params, opt_state=opt_state),
                    {'loss': loss})

        return jax.jit(step, donate_argnums=(0,) if donate else ())

    return factory
