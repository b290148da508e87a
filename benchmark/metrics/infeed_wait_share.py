"""Share of the measured window that the step loop waited, inside the
program, for its next batch: the window's delta of ``stage_infeed_wait_s``
(the timer ``prefetch_to_device`` keeps around the consumer's take) over the
window's seconds. ``loader_wait_share`` times the same wait from outside,
around ``next()``; this wait is nested inside that one.

A program without the timer gives nothing to read, and no value."""


def reduce(record):
    seconds = record['window']['seconds']
    wait = record['counters'].get('stage_infeed_wait_s')
    if wait is None or seconds <= 0:
        return None
    return wait / seconds
