"""Mean time of one ``JaxDataLoader`` collate in the window, in ms: the
window's deltas of ``stage_collate_s`` over ``stage_collate_count``."""


def reduce(record):
    counters = record['counters']
    count = counters.get('stage_collate_count', 0)
    if count <= 0:
        return None
    return 1000.0 * counters.get('stage_collate_s', 0.0) / count
