"""The main path's Pallas kernel, compiled for a described TPU v5e.

No chip is attached here: the TPU compiler builds for a topology it is told
about, which refuses what the chip would refuse (misaligned blocks, too much
fast memory, a kernel that cannot be partitioned). Only the module-scoped
fixture below describes the topology, never an import: each xdist worker
imports this file, and only the worker that runs it may load the TPU library.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from petastorm_tpu.ops import normalize_images
from petastorm_tpu.ops.preprocess import _normalize_pallas

MEAN = np.array([123.675, 116.28, 103.53], np.float32)
STD = np.array([58.395, 57.12, 57.375], np.float32)


@pytest.fixture(scope='module')
def topo():
    import os

    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault('TPU_LOG_DIR', 'disabled')
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache off around these tests
    was_enabled = jax.config.jax_enable_compilation_cache
    jax.config.update('jax_enable_compilation_cache', False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform='tpu', topology_name='v5e:2x2')
    except Exception as e:  # noqa: BLE001 - no TPU compiler in this install
        pytest.skip('no v5e:2x2 topology can be described here: {}'.format(e))
    finally:
        jax.config.update('jax_enable_compilation_cache', was_enabled)
        compilation_cache.reset_cache()


@pytest.fixture(scope='module')
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize('rows,cols,out_dtype', [
    (128 * 224, 672, jnp.bfloat16),  # ResNet-50 batch 128 at 224x224x3
    (64 * 160, 480, jnp.bfloat16),   # bench_duty's batch 64 at 160x160x3
    (128 * 224, 672, jnp.float32),
    (224, 672, jnp.bfloat16),        # fewer rows than one 256-row block
])
def test_normalize_kernel_compiles_for_v5e(one_chip, rows, cols, out_dtype):
    flat = jax.ShapeDtypeStruct((rows, cols), jnp.uint8, sharding=one_chip)
    row = jax.ShapeDtypeStruct((1, cols), jnp.float32, sharding=one_chip)
    compiled = _normalize_pallas.lower(flat, row, row, out_dtype=jnp.dtype(out_dtype),
                                       interpret=False).compile()
    assert 'tpu_custom_call' in compiled.as_text()


def test_normalize_kernel_compiles_per_device_on_four_chips(topo):
    """The data-parallel step's normalize: one kernel per chip on its own
    rows, with no gather of the global batch."""
    mesh = Mesh(np.array(topo.devices), ('data',))
    images = jax.ShapeDtypeStruct((256, 224, 224, 3), jnp.uint8,
                                  sharding=NamedSharding(mesh, P('data')))
    fn = jax.jit(lambda x: normalize_images(x, MEAN, STD, use_pallas=True))
    with jax.set_mesh(mesh):
        compiled = fn.lower(images).compile()
    text = compiled.as_text()
    assert 'tpu_custom_call' in text
    assert 'all-gather' not in text
