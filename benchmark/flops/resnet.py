"""Operations of a bottleneck ResNet, from its conv and dense shapes.

A multiply-add counts two operations. Batch norm, ReLU, pooling and the
loss are left out: they are a fraction of a percent of the total. Training
counts the forward pass three times (forward, and the two products of the
backward pass); nothing is recomputed.
"""

from __future__ import annotations


def _out(size, stride):
    return -(-size // stride)


def forward_flops_per_image(model, image_size):
    nf = model['num_filters']
    size = _out(image_size, 2)                      # 7x7 stride-2 stem
    flops = 2 * size * size * 7 * 7 * 3 * nf
    size = _out(size, 2)                            # 3x3 stride-2 max pool
    channels = nf
    for i, count in enumerate(model['stage_sizes']):
        filters = nf * 2 ** i
        for j in range(count):
            strides = 2 if i > 0 and j == 0 else 1
            out = _out(size, strides)
            flops += 2 * size * size * channels * filters        # conv1, 1x1
            flops += 2 * out * out * 9 * filters * filters        # conv2, 3x3, strided
            flops += 2 * out * out * filters * 4 * filters        # conv3, 1x1
            if channels != 4 * filters or strides != 1:
                flops += 2 * out * out * channels * 4 * filters   # projection, strided
            size, channels = out, 4 * filters
    flops += 2 * channels * model['num_classes']                 # dense head
    return flops


def train_flops_per_image(model, image_size):
    return 3 * forward_flops_per_image(model, image_size)


def train_flops_per_example(config):
    """Training FLOPs of one image of the configuration's size."""
    return train_flops_per_image(config['model'], config['image_size'])
