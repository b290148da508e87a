"""Share of the measured window that the step loop spent blocked in
``next()`` on the prefetch iterator (the ``wait_for_batch`` annotation), by
the benchmark's clock."""


def reduce(record):
    window = record['window']
    if window['seconds'] <= 0:
        return None
    return window['loader_wait_s'] / window['seconds']
