"""``correct`` on the CPU at a small size: a sound run passes, and the run
fails with each fault the cells can have planted under the timed path, and
with the control (the reference in fp8) in the program's place.

The program's model runs in float32 here; its input still reaches it in
bfloat16 (``device_preprocess``), and at this size the three steps of SGD
amplify that rounding, so the limits are looser than the chip's. The chip's
limits are set in the configurations from the chip's readings
(``benchmark/readings.py``).
"""

import json
import os
import time

import pytest

from benchmark import cell as cell_run
from benchmark import manifest, readings
from conftest import make_tiny_root

LIMITS = {'loss_gap': 5e-4, 'grad1_worst_gap': 0.2, 'change_worst_gap': 0.5, 'grad1_diff': 0.2, 'pixel_errors': 0,
          'label_errors': 0}
WORKLOAD = 'raw-feed.1chip'
SEED = 2 ** 31 + 17


@pytest.fixture(scope='module')
def root(tmp_path_factory):
    root = make_tiny_root(tmp_path_factory.mktemp('float32'))
    path = os.path.join(root, 'benchmark', 'configs', 'imagenet-raw224-resnet50.json')
    with open(path) as f:
        config = json.load(f)
    config['model'].update(dtype='float32', num_filters=16)
    config['image_size'] = 64
    config['limits'] = LIMITS
    with open(path, 'w') as f:
        json.dump(config, f)
    path = os.path.join(root, 'benchmark', 'traffic', 'train-b128.json')
    with open(path) as f:
        traffic = json.load(f)
    traffic['batch_per_chip'] = 32
    with open(path, 'w') as f:
        json.dump(traffic, f)
    return root


def _run(root, **kwargs):
    cell = manifest.load_cell(WORKLOAD, root)
    return cell_run.run(cell, SEED, 0.5, False, time.perf_counter(), root,
                        require_tpu=False, **kwargs)


def _unchanged_state(**kwargs):
    import jax

    from petastorm_tpu.models.train import make_train_step
    real = make_train_step(donate=False, **kwargs)

    def step(state, images, labels):
        return state, real(state, images, labels)[1]

    return jax.jit(step)


def _half_batch(**kwargs):
    import jax

    from petastorm_tpu.models.train import make_train_step
    real = make_train_step(donate=False, **kwargs)

    def step(state, images, labels):
        half = images.shape[0] // 2
        return real(state, images[:half], labels[:half])

    return jax.jit(step)


def _altered_answer(batches):
    for batch in batches:
        yield dict(batch, image=batch['image'].at[0].set(batch['image'][1]))


def test_sound_run_is_correct(root):
    line = _run(root)
    assert line['correct'], line['checks']
    assert list(line)[-1] == 'checks'


@pytest.mark.parametrize('faults', [{'step': _unchanged_state}, {'step': _half_batch},
                                    {'batches': _altered_answer}],
                         ids=['state-unchanged', 'half-batch', 'answer-altered'])
def test_planted_fault_is_not_correct(root, faults):
    line = _run(root, faults=faults)
    assert not line['correct'], line['checks']


def test_control_in_the_programs_place_is_not_correct(root):
    config = manifest.load_cell(WORKLOAD, root).config
    line = _run(root, faults={'step': readings.control_step(config)})
    assert not line['correct'], line['checks']


def test_readings_report_the_variants_and_the_feed_faults(root):
    line = _run(root, readings=readings.readings_of(['fault_half_batch']))
    got = line['readings']
    assert got['program']['loss_gap'] <= LIMITS['loss_gap']
    assert got['fault_half_batch']['loss_gap'] > LIMITS['loss_gap']
    assert got['fault_wrong_record']['pixel_errors'] > 0
    assert got['fault_wrong_record']['label_errors'] > 0
