"""Busy time of the read-and-decode worker pool per delivered batch, in ms.

The window's deltas of the stage timers that the pool's threads run (read,
decode, fused decode, transform), summed over threads, over the batches the
loader collated in the same window.
"""

STAGES = ('stage_read_s', 'stage_decode_s', 'stage_fused_decode_s', 'stage_transform_s')


def reduce(record):
    counters = record['counters']
    batches = counters.get('loader_batches_total', 0)
    busy = sum(counters.get(name, 0.0) for name in STAGES)
    if batches <= 0 or busy <= 0:
        return None
    return 1000.0 * busy / batches
