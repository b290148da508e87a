"""A configuration whose batch is not an image joins the benchmark by new
files and entries alone: a token store packed by ``PackedSequenceLoader``
feeding a two-layer language model (``token_cell/``), added to a small copy
of the benchmark and run through ``cell.run`` on the CPU."""

import hashlib
import json
import os
import shutil
import time

import pytest

from benchmark import cell as cell_run
from benchmark import manifest
from conftest import make_tiny_root

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)), 'token_cell')
WORKLOAD = 'tiny-tokens.1chip'
SEED = 2 ** 31 + 29


def _digests(root):
    return {os.path.relpath(os.path.join(d, f), root):
            hashlib.sha256(open(os.path.join(d, f), 'rb').read()).hexdigest()
            for d, _, fs in os.walk(root) for f in fs}


@pytest.fixture(scope='module')
def root(tmp_path_factory):
    root = make_tiny_root(tmp_path_factory.mktemp('tokens'))
    before = _digests(root)
    with open(os.path.join(root, 'BENCHMARK.json')) as f:
        old = json.load(f)
    added = []
    for sub in sorted(os.listdir(FIXTURE)):
        for name in os.listdir(os.path.join(FIXTURE, sub)):
            target = os.path.join(root, 'benchmark', sub, name)
            assert not os.path.exists(target), target
            os.makedirs(os.path.dirname(target), exist_ok=True)
            shutil.copy(os.path.join(FIXTURE, sub, name), target)
            added.append(target)
    bench = json.loads(json.dumps(old))
    bench['configs'].append({
        'name': 'tiny-tokens-lm', 'source': 'https://arxiv.org/abs/2101.00027',
        'file': 'benchmark/configs/tiny-tokens-lm.json', 'reduced': [],
        'why': 'token documents packed into sequences for a two-layer causal model'})
    bench['workloads'].append({
        'name': WORKLOAD, 'config': 'tiny-tokens-lm', 'traffic': 'packed-b4', 'chips': 1,
        'why': 'packed sequences of heavy-tailed documents: packing, infeed and the step'})
    bench['end_to_end'].append({
        'name': 'tokens_per_s', 'unit': 'tokens/s', 'better': 'higher', 'bound': 0.03,
        'source': 'host_clock', 'workloads': [WORKLOAD]})
    with open(os.path.join(root, 'BENCHMARK.json'), 'w') as f:
        json.dump(bench, f)
    # every file the copy had is as it was, and the manifest only gained entries
    after = _digests(root)
    assert {k: v for k, v in after.items() if k in before and k != 'BENCHMARK.json'} == \
        {k: v for k, v in before.items() if k != 'BENCHMARK.json'}
    assert set(after) - set(before) == {os.path.relpath(p, root) for p in added}
    for key, entries in old.items():
        if isinstance(entries, list) and key != 'paths' and key != 'command':
            assert bench[key][:len(entries)] == entries
    return root


def _run(root, **kwargs):
    cell = manifest.load_cell(WORKLOAD, root)
    return cell_run.run(cell, SEED, 0.5, False, time.perf_counter(), root,
                        require_tpu=False, **kwargs)


def _unchanged_state(root):
    import jax
    model = manifest.model_module(manifest.load_cell(WORKLOAD, root).config, root)

    def factory(config):
        real = model.program_step(config, donate=False)
        return jax.jit(lambda state, *batch: (state, real(state, *batch)[1]))

    return factory


def test_token_cell_is_correct_and_reports_tokens(root):
    line = _run(root)
    assert line['correct'], line['checks']
    assert set(line['metrics']) == {'tokens_per_s', 'step_interval_p95_ms', 'setup_s'}
    assert line['metrics']['tokens_per_s']['value'] > 0
    assert line['checks']['token_errors']['value'] == 0
    assert line['checks']['layout_errors']['value'] == 0
    assert list(line)[-1] == 'checks'


def _altered_token(batches):
    for batch in batches:
        tokens = batch['tokens']
        yield dict(batch, tokens=tokens.at[0, 0].set((tokens[0, 0] + 1) % 64))


@pytest.mark.parametrize('fault,check', [('state-unchanged', 'grad1_worst_gap'),
                                         ('token-altered', 'token_errors')])
def test_token_cell_with_a_planted_fault_is_not_correct(root, fault, check):
    faults = ({'step': _unchanged_state(root)} if fault == 'state-unchanged'
              else {'batches': _altered_token})
    line = _run(root, faults=faults)
    assert not line['correct'], line['checks']
    assert line['checks'][check]['value'] > line['checks'][check]['limit']
