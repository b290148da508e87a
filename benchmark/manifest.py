"""What a cell is made of, found by name from ``BENCHMARK.json``.

Everything that belongs to one configuration, traffic mix or per-layer
metric sits in a file of its own:

- ``benchmark/configs/<config>.json``: the configuration as it is run; its
  ``store`` names ``benchmark/stores/<store>.py`` (the generator, the feed
  and the feed's check) and its ``model.arch`` both
  ``benchmark/models/<arch>.py`` (the program's state and step, the batch
  the step takes, the plain reference and the throughput it reports) and
  ``benchmark/flops/<arch>.py`` (the FLOP count);
- ``benchmark/traffic/<traffic>.json``: batch, pool and shuffling;
- ``benchmark/metrics/<metric>.py``: the reader of one per-layer metric.

A new cell, configuration, mix or metric is added by adding files and
entries; nothing here names one.
"""

from __future__ import annotations

import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


class Cell(object):
    """One entry of ``workloads`` with what it refers to."""

    def __init__(self, workload, config, traffic, end_to_end, per_layer):
        self.name = workload['name']
        self.chips = workload['chips']
        self.workload = workload
        self.config = config
        self.traffic = traffic
        self.end_to_end = end_to_end
        self.per_layer = per_layer


def load_json(path):
    with open(path) as f:
        return json.load(f)


def load_module(path):
    """Import a file of the benchmark by its path."""
    rel = os.path.relpath(path, ROOT)
    name = os.path.splitext(rel)[0].replace(os.sep, '.').replace('-', '_')
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _reports(metric, workload_name):
    return 'workloads' not in metric or workload_name in metric['workloads']


def load_cell(name, root=ROOT):
    """The cell ``name`` of ``<root>/BENCHMARK.json``; raises KeyError for an
    unknown name."""
    manifest = load_json(os.path.join(root, 'BENCHMARK.json'))
    workload = {w['name']: w for w in manifest['workloads']}[name]
    config_entry = {c['name']: c for c in manifest['configs']}[workload['config']]
    config = load_json(os.path.join(root, config_entry['file']))
    traffic = load_json(os.path.join(root, 'benchmark', 'traffic',
                                     workload['traffic'] + '.json'))
    end_to_end = [m for m in manifest['end_to_end'] if _reports(m, name)]
    per_layer = [m for m in manifest['per_layer'] if _reports(m, name)]
    return Cell(workload, config, traffic, end_to_end, per_layer)


def metric_reader(metric, root=ROOT):
    """The ``reduce(record)`` function of a per-layer metric."""
    return load_module(os.path.join(root, 'benchmark', 'metrics',
                                    metric['name'] + '.py')).reduce


def store_path(config, root=ROOT):
    return os.path.join(root, 'benchmark', 'stores', config['store'] + '.py')


def model_module(config, root=ROOT):
    return load_module(os.path.join(root, 'benchmark', 'models',
                                    config['model']['arch'] + '.py'))


def flops_module(config, root=ROOT):
    return load_module(os.path.join(root, 'benchmark', 'flops',
                                    config['model']['arch'] + '.py'))


def peaks(device_kind, root=ROOT):
    """The peak table's entry for ``device_kind``; an unknown kind is an error."""
    table = load_json(os.path.join(root, 'benchmark', 'peaks.json'))['devices']
    if device_kind not in table:
        raise KeyError('no peaks for device kind {!r} in benchmark/peaks.json'.format(
            device_kind))
    return table[device_kind]
