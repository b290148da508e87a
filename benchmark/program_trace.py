"""The program's own spans on the profiler's timeline, and the device idle
time they account for.

While ``jax.profiler`` records, every stage timer of ``petastorm_tpu`` also
opens a host annotation ``<cat>.<name>`` on the clock of the device planes
(``docs/observability.md``, "On the profiler's timeline").
``program_events`` reads those events as ``[[name, line_index, start_ns,
dur_ns], ...]``, where ``line_index`` numbers the thread lines of the host
planes. ``reduce`` takes what ``benchmark.trace.extract`` reads with them
under ``'program'``, and puts the device's idle time in the ``window`` mark
down to them:

- ``input_idle_s``: idle time that an ``infeed.infeed_wait`` span covers,
  the step loop waiting inside the program for a batch; mean over devices.
  The profiler's own stalls fall in ``dispatch_step``, outside every such
  span, so they do not count here;
- ``steps``: the window's ``dispatch_step`` marks;
- ``input_idle_by_pump``: ``input_idle_s`` by the innermost stage open on the
  prefetch thread (the line that holds ``infeed.infeed``), or ``pump:untimed``
  where none is;
- ``annotations_per_step``: the program's annotations that start in the
  window, per step;
- ``gaps``: each idle gap of at least ``MIN_GAP_S``, with the benchmark's
  mark that overlaps it most and, for each thread line, the seconds of each
  program stage open in it.

``benchmark/cell.py`` discards its trace after its own reduction, so no
per-layer metric of ``BENCHMARK.json`` reads these yet (``PERF.md``, section
7). Until it does, this module run as a script runs one traced cell as
``run.py --trace 1`` does, keeps the extraction and prints the result line
with the reduction under ``program_trace``::

    python3 benchmark/program_trace.py --workload <name> --seed <n> --seconds <s> \\
        --out <file.json.gz>
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import glob  # noqa: E402
import gzip  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if sys.path[0] == os.path.dirname(os.path.abspath(__file__)):
    sys.path.pop(0)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import trace  # noqa: E402

#: the program's stage categories (``docs/observability.md``)
CATEGORIES = ('worker.', 'native.', 'pool.', 'loader.', 'infeed.', 'ventilator.',
              'chunkstore.')
WAIT = 'infeed.infeed_wait'
STAGE = 'infeed.infeed'
WORKER = ('worker.', 'native.')
UNTIMED = 'pump:untimed'
#: the shortest idle gap the gap report lists
MIN_GAP_S = 0.1


def program_events(trace_dir):
    """``[[name, line_index, start_ns, dur_ns], ...]`` of the program's
    annotations in the newest trace under ``trace_dir``."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(trace_dir, '**', '*.xplane.pb'), recursive=True),
                   key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError('no .xplane.pb under {}'.format(trace_dir))
    events, index = [], 0
    for plane in ProfileData.from_file(paths[-1]).planes:
        if plane.name.startswith('/host:'):
            for line in plane.lines:
                events.extend([e.name, index, e.start_ns, e.duration_ns] for e in line.events
                              if e.name.startswith(CATEGORIES))
                index += 1
    return events


def _idle(events, w0, w1):
    """The idle intervals of one device's ``[[op, start, dur], ...]`` in
    ``[w0, w1)``."""
    busy = trace._union([(max(s, w0), min(s + d, w1)) for _, s, d in events
                         if min(s + d, w1) > max(s, w0)])
    edges = [w0] + [x for iv in busy for x in iv] + [w1]
    return [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]


def _innermost(t, events):
    """The name of the latest-started event of ``events`` open at ``t``."""
    best = None
    for name, _, s, e in events:
        if s <= t < e and (best is None or s > best[1]):
            best = (name, s)
    return best and best[0]


def _by_stage(a, b, pump):
    """``{label: ns}`` over ``[a, b)``: the innermost stage open on the
    prefetch thread, or ``pump:untimed``."""
    pump = [ev for ev in pump if ev[2] < b and ev[3] > a]
    cuts = sorted({a, b} | {x for ev in pump for x in ev[2:] if a < x < b})
    out = {}
    for x, y in zip(cuts, cuts[1:]):
        label = _innermost((x + y) / 2, pump) or UNTIMED
        out[label] = out.get(label, 0) + (y - x)
    return out


def _open(a, b, program, roles):
    """``{'<role>:<line>': {stage: seconds}}`` of the program's stages
    overlapping ``[a, b)``."""
    out = {}
    for name, line, s, e in program:
        o = trace._overlap(a, b, s, e)
        if o > 0:
            stages = out.setdefault('{}:{}'.format(roles.get(line, 'other'), line), {})
            stages[name] = stages.get(name, 0.0) + o * 1e-9
    return out


def reduce(extracted):
    """The device's idle time in the window put down to the program's
    stages; see the module's docstring for the keys."""
    windows = [(s, s + d) for name, s, d in extracted['host'] if name == trace.WINDOW]
    if not windows or not extracted['devices']:
        raise ValueError('trace holds no window mark or no device operations')
    w0, w1 = windows[0]
    marks = sorted(((name, s, s + d) for name, s, d in extracted['host']
                    if name in trace.HOST_MARKS), key=lambda m: m[1])
    steps = sum(1 for name, s, _ in marks if name == 'dispatch_step' and w0 <= s < w1)
    program = [(name, line, s, s + d) for name, line, s, d in extracted['program']]
    waits = trace._union([(s, e) for name, _, s, e in program if name == WAIT])
    pump_lines = {line for name, line, _, _ in program if name == STAGE}
    roles = {line: 'worker' for name, line, _, _ in program if name.startswith(WORKER)}
    roles.update({line: 'consumer' for name, line, _, _ in program if name == WAIT})
    roles.update({line: 'pump' for line in pump_lines})
    pump = [ev for ev in program if ev[1] in pump_lines and ev[0] != WAIT]
    n = len(extracted['devices'])
    input_idle, by_pump, gaps = 0.0, {}, []
    for plane, events in sorted(extracted['devices'].items()):
        for a, b in _idle(events, w0, w1):
            for c, d in waits:
                o = trace._overlap(a, b, c, d)
                if o > 0:
                    input_idle += o * 1e-9 / n
                    for label, ns in _by_stage(max(a, c), min(b, d), pump).items():
                        by_pump[label] = by_pump.get(label, 0.0) + ns * 1e-9 / n
            if (b - a) * 1e-9 >= MIN_GAP_S:
                gaps.append({'device': plane, 'at_s': (a - w0) * 1e-9,
                             'seconds': (b - a) * 1e-9, 'mark': trace._charge(a, b, marks),
                             'threads': _open(a, b, program, roles)})
    annotations = sum(1 for _, _, s, _ in program if w0 <= s < w1)
    return {'input_idle_s': input_idle, 'steps': steps, 'input_idle_by_pump': by_pump,
            'annotations_per_step': annotations / steps if steps else None, 'gaps': gaps}


def input_idle_ms_per_step(reduced):
    """Device time lost to the input pipeline each traced step, in ms."""
    if not reduced['steps']:
        return None
    return 1000.0 * reduced['input_idle_s'] / reduced['steps']


def main(argv=None):
    """Run one traced cell and keep the program's events with its trace.
    Interim: this entry goes once ``benchmark/cell.py`` keeps the program's
    events in its own traced run (``PERF.md``, section 7)."""
    parser = argparse.ArgumentParser(description=__doc__.split('\n\n')[0])
    parser.add_argument('--workload', required=True)
    parser.add_argument('--seed', type=int, required=True)
    parser.add_argument('--seconds', type=float, required=True)
    parser.add_argument('--out', required=True, help='the extraction, as .json.gz')
    args = parser.parse_args(argv)

    from benchmark import cell as cell_run
    from benchmark import manifest
    cell = manifest.load_cell(args.workload, ROOT)
    kept = {}
    base_extract = trace.extract

    def extract_and_keep(trace_dir):
        kept.update(base_extract(trace_dir), program=program_events(trace_dir))
        return kept

    trace.extract = extract_and_keep
    try:
        line = cell_run.run(cell, args.seed, args.seconds, True, T_START, ROOT)
    except cell_run.NoChip as e:
        print('benchmark: {}'.format(e), file=sys.stderr)
        return 1
    finally:
        trace.extract = base_extract
    reduced = reduce(kept)
    reduced['input_idle_ms_per_step'] = input_idle_ms_per_step(reduced)
    line['program_trace'] = reduced
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with gzip.open(args.out, 'wt') as f:
        json.dump(kept, f)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == '__main__':
    sys.exit(main())
