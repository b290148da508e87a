"""The Pallas normalize kernel's share of its roofline, in %.

The kernel reads uint8 images and writes bfloat16 ones, so it is bound by
memory: its least time per call is the bytes it must move (1 + 2 bytes a
pixel channel of the chip's rows) over the peak HBM bytes/s. The share is
that least time, times the calls in the traced window, over the device time
of the kernel's path there: the kernel's own events and those of the copies
that bring its operands into the layout and memory it asks for and take its
result out of them. On a v5e chip those copies carry most of the bytes the
kernel's own event seems to move (``PERF.md``, section 5).

The kernel's events are the ops named ``KERNEL`` in the device trace (the
name XLA gives the ``tpu_custom_call`` from the jitted function).
"""

import re

KERNEL = '%_normalize_pallas'


def _name(op):
    return op.split(' = ', 1)[0]


def reduce(record):
    trace, window = record['trace'], record['window']
    if not trace:
        return None
    ops = trace['op_events']
    kernels = [op for op in ops if re.match(re.escape(KERNEL) + r'(\.\d+)? = ', op)]
    calls = sum(ops[op] for op in kernels)
    if calls == 0:
        return None
    names = {_name(op) for op in kernels}
    operands = {n for op in kernels for n in re.findall(r'%[\w.\-]+', op.split(' = ', 1)[1])}
    path = set(kernels)
    for op in ops:
        is_copy = re.match(r'%[\w.\-]+ = \S+ copy\(', op) is not None
        if is_copy and (_name(op) in operands
                        or any(re.search(re.escape(n) + r'[),]', op) for n in names)):
            path.add(op)
    seconds = sum(trace['op_seconds'][op] for op in path)
    rows = window['global_batch'] // window['chips']
    size = record['config']['image_size']
    bytes_per_call = rows * size * size * 3 * (1 + 2)
    least = calls * bytes_per_call / record['peaks']['hbm_bytes_per_s']
    return 100.0 * least / seconds
