"""The whole train step's share of the chips' peak, in %: training FLOPs per
example (``train_flops_per_example`` of ``benchmark/flops/<arch>.py``, from
the model's shapes) times the examples trained in the window, over the
window's seconds times the chips times the peak bf16 FLOP/s of the device
kind (``benchmark/peaks.json``)."""

from benchmark import manifest


def reduce(record):
    window, config = record['window'], record['config']
    flops = manifest.flops_module(config, record['root']).train_flops_per_example(config)
    examples = window['examples']
    peak = record['peaks']['bf16_flops_per_s'] * window['chips']
    if window['seconds'] <= 0 or examples <= 0:
        return None
    return 100.0 * flops * examples / (window['seconds'] * peak)
