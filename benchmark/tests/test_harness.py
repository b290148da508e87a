"""CPU tests of the benchmark harness: the trace reduction, the FLOP count,
the manifest, the store generators and ``run.py``'s refusal without a chip."""

import gzip
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from benchmark import manifest, trace
from benchmark.flops import resnet as resnet_flops
from conftest import ROOT

TESTDATA = os.path.join(ROOT, 'benchmark', 'testdata')


# -- trace reduction ------------------------------------------------------------

def test_reduce_hand_made_trace():
    ms = 1000000
    extracted = {
        'devices': {
            '/device:TPU:0': [['conv', 0, 4 * ms], ['fusion', 2 * ms, 4 * ms],
                              ['conv', 8 * ms, 1 * ms], ['late', 20 * ms, 5 * ms]],
            '/device:TPU:1': [['conv', 0, 10 * ms]],
        },
        'host': [['window', 0, 10 * ms], ['wait_for_batch', 6 * ms, 1 * ms],
                 ['dispatch_step', 7 * ms, 1 * ms]],
    }
    r = trace.reduce(extracted)
    assert r['window_s'] == pytest.approx(0.010)
    # TPU:0 busy on [0, 6) and [8, 9): 7 ms; TPU:1 the whole window
    assert r['idle_share']['/device:TPU:0'] == pytest.approx(0.3)
    assert r['idle_share']['/device:TPU:1'] == pytest.approx(0.0)
    assert r['busy_s'] == pytest.approx((0.007 + 0.010) / 2)
    assert r['op_seconds'] == pytest.approx({'conv': 0.015, 'fusion': 0.004})
    assert r['op_events'] == {'conv': 3, 'fusion': 1}
    # the gap [6, 8) ms overlaps each mark for 1 ms: the first found is kept;
    # the gap [9, 10) ms overlaps no mark
    assert r['breakdown']['idle_gaps'] == [['wait_for_batch', pytest.approx(0.002)],
                                           ['other', pytest.approx(0.001)]]
    assert r['breakdown']['device_ops'][0] == ['conv', pytest.approx(0.0075)]
    assert trace.short_name('%fusion.1 = bf16[8,4]{1,0:T(8,128)} fusion(%a)') == \
        '%fusion.1 = bf16[8,4]'


def test_reduce_recorded_trace():
    """A 0.12 s piece of a traced window of ``raw-feed.1chip`` on one v5e chip."""
    with gzip.open(os.path.join(TESTDATA, 'trace_small.json.gz'), 'rt') as f:
        extracted = json.load(f)
    r = trace.reduce(extracted)
    assert r['window_s'] == pytest.approx(0.12)
    assert 0 < r['busy_s'] <= r['window_s']
    assert len(r['breakdown']['device_ops']) == trace.TOP
    assert all(0.0 <= s <= 1.0 for s in r['idle_share'].values())
    assert sum(r['idle_by_host'].values()) == pytest.approx(r['window_s'] - r['busy_s'])
    assert any('convolution' in name or 'fusion' in name for name in r['op_seconds'])
    assert all(' = ' in name and '(' not in name and '{' not in name
               for name, _ in r['breakdown']['device_ops'])
    record = {'trace': r, 'window': {'global_batch': 128, 'chips': 1},
              'config': {'image_size': 224}, 'peaks': manifest.peaks('TPU v5 lite')}
    roofline = manifest.load_module(os.path.join(ROOT, 'benchmark', 'metrics',
                                                 'normalize_roofline.py')).reduce(record)
    # the kernel with the copies around it: about 150 us a call against 71 us
    assert 30 < roofline < 70


# -- FLOP count ------------------------------------------------------------------

def test_flops_of_one_bottleneck_block_by_hand():
    model = {'stage_sizes': [1], 'num_filters': 64, 'num_classes': 1000}
    stem = 2 * 112 * 112 * (7 * 7 * 3) * 64
    # the block after the max pool, at 56x56: 64 -> 64 -> 64 -> 256, and the
    # projection of its input, 64 -> 256
    block = (2 * 56 * 56 * 64 * 64 + 2 * 56 * 56 * (3 * 3 * 64) * 64
             + 2 * 56 * 56 * 64 * 256 + 2 * 56 * 56 * 64 * 256)
    head = 2 * 256 * 1000
    assert resnet_flops.forward_flops_per_image(model, 224) == stem + block + head
    assert resnet_flops.train_flops_per_image(model, 224) == 3 * (stem + block + head)


def test_resnet50_flops_match_the_published_count():
    """He et al. 2016, Table 1: 3.8e9 multiply-adds for ResNet-50 at 224."""
    model = {'stage_sizes': [3, 4, 6, 3], 'num_filters': 64, 'num_classes': 1000}
    macs = resnet_flops.forward_flops_per_image(model, 224) / 2
    assert 3.8e9 <= macs <= 4.2e9


# -- manifest ----------------------------------------------------------------------

def test_new_config_traffic_and_metric_are_found_by_name(tmp_path):
    from conftest import make_tiny_root
    root = make_tiny_root(tmp_path)
    with open(os.path.join(root, 'BENCHMARK.json')) as f:
        bench = json.load(f)
    with open(os.path.join(root, bench['configs'][0]['file'])) as f:
        config = json.load(f)
    config['name'] = 'new-config'
    with open(os.path.join(root, 'benchmark', 'configs', 'new-config.json'), 'w') as f:
        json.dump(config, f)
    with open(os.path.join(root, 'benchmark', 'traffic', 'new-mix.json'), 'w') as f:
        json.dump({'batch_per_chip': 4}, f)
    with open(os.path.join(root, 'benchmark', 'metrics', 'new_metric.py'), 'w') as f:
        f.write('def reduce(record):\n    return 42.0\n')
    bench['configs'].append(dict(bench['configs'][0], name='new-config',
                                 file='benchmark/configs/new-config.json'))
    bench['workloads'].append({'name': 'new.1chip', 'config': 'new-config',
                               'traffic': 'new-mix', 'chips': 1, 'why': 'test'})
    bench['per_layer'].append({'name': 'new_metric', 'unit': 'ms', 'better': 'lower',
                               'source': 'host_clock', 'layer': 'test',
                               'moves': 'images_per_s', 'workloads': ['new.1chip']})
    with open(os.path.join(root, 'BENCHMARK.json'), 'w') as f:
        json.dump(bench, f)

    cell = manifest.load_cell('new.1chip', root)
    assert cell.config['name'] == 'new-config'
    assert cell.traffic == {'batch_per_chip': 4}
    names = [m['name'] for m in cell.per_layer]
    assert 'new_metric' in names and 'worker_busy_ms_per_batch' not in names
    assert manifest.metric_reader(cell.per_layer[-1], root)({}) == 42.0
    # the cells already there keep theirs
    assert 'new_metric' not in [m['name'] for m in manifest.load_cell('raw-feed.1chip',
                                                                       root).per_layer]


def test_every_cell_and_metric_of_the_manifest_resolves():
    with open(os.path.join(ROOT, 'BENCHMARK.json')) as f:
        bench = json.load(f)
    for workload in bench['workloads']:
        cell = manifest.load_cell(workload['name'])
        assert os.path.exists(manifest.store_path(cell.config))
        assert cell.config['name'] == workload['config']
        # the model module's throughput is one of the cell's end-to-end metrics
        throughput = manifest.model_module(cell.config).THROUGHPUT
        assert throughput in [m['name'] for m in cell.end_to_end]
        for metric in cell.per_layer:
            assert callable(manifest.metric_reader(metric))
    assert manifest.peaks('TPU v5 lite')['bf16_flops_per_s'] == 197e12
    with pytest.raises(KeyError):
        manifest.peaks('no such chip')


# -- store generators -----------------------------------------------------------------

@pytest.mark.parametrize('config_name', ['imagenet-jpeg-resnet50', 'imagenet-raw224-resnet50'])
def test_store_rows_are_fixed_by_the_data_seed(tiny_root, config_name):
    config = manifest.load_json(os.path.join(tiny_root, 'benchmark', 'configs',
                                             config_name + '.json'))
    module = manifest.load_module(manifest.store_path(config, tiny_root))

    def rows(data_seed):
        return list(module.rows(dict(config, data_seed=data_seed), 0, 12))

    a, b, c = rows(2 ** 31 + 9), rows(2 ** 31 + 9), rows(4)
    for x, y in zip(a, b):
        assert x.keys() == y.keys()
        for k in x:
            assert np.array_equal(x[k], y[k])
    assert any(not np.array_equal(x['image'], y['image']) for x, y in zip(a, c))


def test_built_store_is_the_same_each_time(tmp_path, tiny_root):
    from benchmark.stores.common import build_store
    config = manifest.load_json(os.path.join(tiny_root, 'benchmark', 'configs',
                                             'imagenet-jpeg-resnet50.json'))
    path = manifest.store_path(config, tiny_root)
    a = build_store(path, config, str(tmp_path / 'a'), processes=2)
    b = build_store(path, config, str(tmp_path / 'b'), processes=2)
    assert build_store(path, config, str(tmp_path / 'a')) == a   # found, not rebuilt
    files = sorted(f for f in os.listdir(a) if f.endswith('.parquet'))
    assert files and files == sorted(f for f in os.listdir(b) if f.endswith('.parquet'))
    for name in files:
        with open(os.path.join(a, name), 'rb') as fa, open(os.path.join(b, name), 'rb') as fb:
            assert fa.read() == fb.read()
    module = manifest.load_module(path)
    images, labels = module.reference(a, list(range(config['images'])), config)
    assert images.shape == (config['images'], 32, 32, 3) and labels.shape == (config['images'],)


def _delivered(store, module, config):
    """Every record as the program's reader and transform deliver it."""
    from petastorm_tpu import make_reader
    ids, images = [], []
    with make_reader('file://' + store, num_epochs=1, reader_pool_type='dummy',
                     transform_spec=module.transform(config)) as reader:
        for row in reader:
            ids.append(int(row.record_id))
            images.append(row.image)
    return np.array(ids), np.stack(images)


def test_jpeg_reference_decodes_as_the_program_and_each_fault_apart(tmp_path, tiny_root):
    """The plain decode at the DCT scale and filter the transform states
    gives the program's pixels; each fault planted in it reads far off."""
    from benchmark.stores import image_feed
    from benchmark.stores.common import build_store
    config = manifest.load_json(os.path.join(tiny_root, 'benchmark', 'configs',
                                             'imagenet-jpeg-resnet50.json'))
    config['min_dim'], config['max_dim'] = 60, 140   # scales 2/8 to 5/8 of the image
    path = manifest.store_path(config, tiny_root)
    module = manifest.load_module(path)
    store = build_store(path, config, str(tmp_path), processes=2)
    ids, delivered = _delivered(store, module, config)
    labels = np.zeros(len(ids), np.int64)

    def gap(images):
        return image_feed.numbers(delivered, images, labels, labels)['pixel_gap']

    plain = gap(module.reference(store, ids, config)[0])
    faults = {f: gap(module.reference(store, ids, config, fault=f)[0]) for f in module.FAULTS}
    assert plain < 1e-4, plain
    assert min(faults.values()) > 100 * max(plain, 1e-5), faults


# -- run.py without a chip ---------------------------------------------------------------

def test_run_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS='cpu')
    proc = subprocess.run([sys.executable, os.path.join(ROOT, 'benchmark', 'run.py'),
                           '--workload', 'raw-feed.1chip', '--seed', str(2 ** 31 + 3),
                           '--seconds', '1', '--trace', '0'],
                          cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert '{' not in proc.stdout
    assert 'needs a TPU' in proc.stderr


def test_run_exits_nonzero_with_only_the_benchmark(tmp_path):
    shutil.copy(os.path.join(ROOT, 'BENCHMARK.json'), tmp_path)
    shutil.copytree(os.path.join(ROOT, 'benchmark'), tmp_path / 'benchmark',
                    ignore=shutil.ignore_patterns('.store_cache', '.trace', '__pycache__'))
    env = dict(os.environ, JAX_PLATFORMS='cpu', PYTHONPATH='')
    proc = subprocess.run([sys.executable, 'benchmark/run.py', '--workload', 'raw-feed.1chip',
                           '--seed', '1', '--seconds', '1', '--trace', '0'],
                          cwd=tmp_path, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert '{' not in proc.stdout
    assert "No module named 'petastorm_tpu'" in proc.stderr
