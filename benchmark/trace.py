"""Reduction of a profiler trace to the device's busy time, its operations
and its idle gaps.

``extract`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote into plain
lists: the operation events of each device plane's ``XLA Ops`` line, and
the host annotations the benchmark writes (``window``, ``wait_for_batch``,
``dispatch_step``). ``reduce`` works on those lists alone, so it can be
checked on a small recorded trace kept in ``benchmark/testdata``.
"""

from __future__ import annotations

import glob
import os

DEVICE_PLANE = '/device:TPU:'
OPS_LINE = 'XLA Ops'
WINDOW = 'window'
HOST_MARKS = ('wait_for_batch', 'dispatch_step')
TOP = 10


def extract(trace_dir):
    """``{'devices': {plane: [[op, start_ns, dur_ns], ...]},
    'host': [[mark, start_ns, dur_ns], ...]}`` from the newest trace under
    ``trace_dir``."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(trace_dir, '**', '*.xplane.pb'), recursive=True),
                   key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError('no .xplane.pb under {}'.format(trace_dir))
    data = ProfileData.from_file(paths[-1])
    devices, host = {}, []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[plane.name] = [[e.name, e.start_ns, e.duration_ns]
                                           for e in line.events]
        elif plane.name.startswith('/host:'):
            for line in plane.lines:
                host.extend([e.name, e.start_ns, e.duration_ns] for e in line.events
                            if e.name == WINDOW or e.name in HOST_MARKS)
    return {'devices': devices, 'host': host}


def _union(intervals):
    merged = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return merged


def _overlap(a0, a1, b0, b1):
    return max(0.0, min(a1, b1) - max(a0, b0))


def reduce(extracted):
    """Busy and idle time of each device over the host's ``window`` mark.

    Returns ``{'window_s', 'busy_s' (mean over devices), 'busy_by_device'
    ({plane: seconds}), 'idle_share' ({plane: share}), 'op_seconds' ({op: seconds summed over devices}),
    'op_events' ({op: count}), 'idle_by_host' ({mark: seconds, mean over
    devices}), 'breakdown'}``; ``breakdown`` lists the ten operations that
    took most device time (mean over devices) and the ten longest idle gaps,
    each named by the host mark that overlaps it most (``other`` where none
    does)."""
    windows = [(s, s + d) for name, s, d in extracted['host'] if name == WINDOW]
    if not windows or not extracted['devices']:
        raise ValueError('trace holds no window mark or no device operations')
    w0, w1 = windows[0]
    marks = [(name, s, s + d) for name, s, d in extracted['host'] if name in HOST_MARKS]
    marks.sort(key=lambda m: m[1])
    n = len(extracted['devices'])
    busy, idle_share, op_seconds, op_events = {}, {}, {}, {}
    idle_by_host = {m: 0.0 for m in HOST_MARKS + ('other',)}
    gaps = []
    for plane, events in sorted(extracted['devices'].items()):
        clipped = []
        for name, s, d in events:
            a, b = max(s, w0), min(s + d, w1)
            if b > a:
                clipped.append((a, b))
                op_seconds[name] = op_seconds.get(name, 0.0) + (b - a) * 1e-9
                op_events[name] = op_events.get(name, 0) + 1
        merged = _union(clipped)
        busy[plane] = sum(b - a for a, b in merged) * 1e-9
        idle_share[plane] = 1.0 - busy[plane] / ((w1 - w0) * 1e-9)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                label = _charge(a, b, marks)
                idle_by_host[label] += (b - a) * 1e-9 / n
                gaps.append([label, (b - a) * 1e-9])
    gaps.sort(key=lambda g: -g[1])
    top_ops = sorted(op_seconds.items(), key=lambda kv: -kv[1])[:TOP]
    return {
        'window_s': (w1 - w0) * 1e-9,
        'busy_s': sum(busy.values()) / n,
        'busy_by_device': busy,
        'idle_share': idle_share,
        'op_seconds': op_seconds,
        'op_events': op_events,
        'idle_by_host': idle_by_host,
        'breakdown': {'device_ops': [[short_name(name), sec / n] for name, sec in top_ops],
                      'idle_gaps': gaps[:TOP]},
    }


def short_name(op):
    """An operation's HLO name and result type, without its operands."""
    name, _, rest = op.partition(' = ')
    if not rest:
        return name
    return '{} = {}'.format(name, 'tuple' if rest.startswith('(') else rest.split('{', 1)[0])


def _charge(a, b, marks):
    """The host mark overlapping ``[a, b)`` most, or ``other``."""
    best, label = 0.0, 'other'
    for name, s, e in marks:
        if s >= b:
            break
        o = _overlap(a, b, s, e)
        if o > best:
            best, label = o, name
    return label
