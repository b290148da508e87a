"""The benchmark's CPU tests: JAX on the CPU, and a small copy of the
benchmark (``tiny_root``) whose cells run in seconds."""

import json
import os
import shutil
import sys

os.environ['JAX_PLATFORMS'] = 'cpu'

import pytest  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

#: what the small copy changes in every configuration and traffic mix
TINY_CONFIG = {'images': 256, 'image_size': 32, 'min_dim': 40, 'max_dim': 72,
               'rows_per_row_group': 16, 'synsets': 10}
TINY_MODEL = {'stage_sizes': [1, 1, 1, 1], 'num_filters': 8, 'num_classes': 10}
TINY_TRAFFIC = {'batch_per_chip': 8, 'workers_count': 2, 'shuffling_queue_capacity': 32,
                'warmup_steps': 2}


def make_tiny_root(path):
    """A copy of ``BENCHMARK.json`` and ``benchmark/`` at ``path`` with every
    configuration and traffic mix cut to the small sizes above."""
    shutil.copy(os.path.join(ROOT, 'BENCHMARK.json'), path)
    shutil.copytree(os.path.join(ROOT, 'benchmark'), os.path.join(path, 'benchmark'),
                    ignore=shutil.ignore_patterns('.store_cache', '.trace', '.readings',
                                                  '__pycache__', 'tests'))
    for sub, changes in (('configs', TINY_CONFIG), ('traffic', TINY_TRAFFIC)):
        folder = os.path.join(path, 'benchmark', sub)
        for name in os.listdir(folder):
            with open(os.path.join(folder, name)) as f:
                data = json.load(f)
            data.update({k: v for k, v in changes.items() if k in data})
            if 'model' in data:
                data['model'].update(TINY_MODEL)
            with open(os.path.join(folder, name), 'w') as f:
                json.dump(data, f)
    return str(path)


@pytest.fixture(scope='session')
def tiny_root(tmp_path_factory):
    return make_tiny_root(tmp_path_factory.mktemp('tiny'))
