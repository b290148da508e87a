"""Operations of the two-layer token model, from its shapes: a multiply-add
counts two, training counts the forward pass three times, and attention
counts its two products over the whole causal square (what the step
computes, masked or not)."""

from __future__ import annotations


def train_flops_per_example(config):
    """Training FLOPs of one packed sequence of ``sequence_length`` tokens."""
    model, tokens = config['model'], config['sequence_length']
    width = model['d_model']
    per_layer = 3 * width * width + width * width + 2 * width * model['mlp_dim']
    matmuls = model['num_layers'] * per_layer + width * model['vocab_size']
    attention = model['num_layers'] * 2 * tokens * width
    return 3 * 2 * tokens * (matmuls + attention)
