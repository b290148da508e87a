"""The whole train step's share of the chips' peak, in %: training FLOPs per
image (``benchmark/flops/<arch>.py``, from the conv and dense shapes) times
the images trained in the window, over the window's seconds times the chips
times the peak bf16 FLOP/s of the device kind (``benchmark/peaks.json``)."""

from benchmark import manifest


def reduce(record):
    window, config = record['window'], record['config']
    flops = manifest.flops_module(config, record['root']).train_flops_per_image(
        config['model'], config['image_size'])
    images = window['steps'] * window['global_batch']
    peak = record['peaks']['bf16_flops_per_s'] * window['chips']
    if window['seconds'] <= 0 or images <= 0:
        return None
    return 100.0 * flops * images / (window['seconds'] * peak)
