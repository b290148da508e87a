"""chip_smoke.py's bodies at a tiny size on CPU, and its refusal to run
without a chip. On CPU the step takes the jnp normalize, so the Pallas check
(``pallas_in_step``) is the one check left to the chip run."""

import os
import pickle
import subprocess
import sys

import jax
import numpy as np
import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)

import chip_smoke  # noqa: E402

TINY = dict(images=64, min_dim=40, max_dim=80, image_size=32, num_classes=10,
            model='resnet18')


def test_single_chip_body_through_both_pools(tmp_path):
    report = chip_smoke.run_single_chip(str(tmp_path / 'store'), batch_size=4,
                                        warmup_steps=1, steps=2, **TINY)
    # thread-pool steps, then one process-pool batch, all finite
    assert len(report['losses']) == 1 + 1 + 2 + 1
    assert np.isfinite(report['losses']).all()
    assert report['process_pool_loss'] == report['losses'][-1]
    assert report['first_loss'] == pytest.approx(report['ref_loss'], rel=chip_smoke.LOSS_RTOL)
    assert report['pallas_in_step'] is False  # CPU: the jnp normalize
    decode = report['decode']
    assert decode['native_kernel'] and decode['native_image_codec']
    assert set(decode['fused_fallback_columns']) <= {
        'image:image-hints', 'noun_id:codec', 'text:codec'}
    assert report['compile_s'] > 0 and report['smoke_examples_per_s'] > 0


def test_data_parallel_body_matches_one_device(tmp_path):
    report = chip_smoke.run_data_parallel(str(tmp_path / 'store'), jax.devices()[:4],
                                          per_device_batch=2, **TINY)
    assert report['devices'] == 4 and report['global_batch'] == 8
    assert report['dp_loss'] == pytest.approx(report['one_loss'], rel=chip_smoke.LOSS_RTOL)
    assert report['update_rel_diff'] <= chip_smoke.UPDATE_RTOL


def test_decode_path_rejects_unexpected_fallback():
    counters = {'worker_rows_decoded_total': 8, 'fused_fallback_column:image:image-hints': 2,
                'fused_fallback_column:image:image-codec-unavailable': 1}
    with pytest.raises(chip_smoke.SmokeCheckFailed, match='image-codec-unavailable'):
        chip_smoke._decode_path(counters)
    # a reason another reader counted before this run (zero in the delta)
    counters['fused_fallback_column:image:image-codec-unavailable'] = 0
    assert chip_smoke._decode_path(counters)['fused_fallback_columns'] == {
        'image:image-hints': 2}


@pytest.mark.parametrize('script', ['chip_smoke.py', 'bench_duty.py'])
def test_chip_scripts_refuse_cpu(script):
    """No CPU branch: without a TPU both exit non-zero and print no result."""
    env = dict(os.environ, JAX_PLATFORMS='cpu')
    proc = subprocess.run([sys.executable, os.path.join(REPO_ROOT, script)],
                          env=env, capture_output=True, text=True, timeout=120,
                          cwd=REPO_ROOT)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert 'TPU' in proc.stderr


def test_image_transform_unpickles_without_jax():
    """Regression (chip bring-up, PR 21): process-pool workers unpickle the
    ImageNet TransformSpec, which used to live in the JAX example module, so
    every spawned worker imported JAX next to a parent holding the chip."""
    from examples.imagenet.transform import make_transform
    blob = pickle.dumps(make_transform(32, 10))
    code = ('import pickle, sys; pickle.loads(sys.stdin.buffer.read()); '
            "print('jax' in sys.modules)")
    proc = subprocess.run([sys.executable, '-c', code], input=blob, capture_output=True,
                          timeout=120, cwd=REPO_ROOT,
                          env=dict(os.environ, PYTHONPATH=REPO_ROOT))
    assert proc.returncode == 0, proc.stderr
    assert proc.stdout.strip() == b'False'
