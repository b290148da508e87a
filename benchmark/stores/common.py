"""Store building shared by the store generators: rows are made in parallel
spawned processes that import no JAX, one part file per chunk, and the
petastorm metadata is written once over all part files.

A store is kept in the checkout under ``benchmark/.store_cache/<name>`` and
reused by every later run that asks for the same name (configuration,
generator version and data seed): only a checkout's first run of a
configuration builds it.
"""

from __future__ import annotations

import multiprocessing
import os
import shutil

ROWS_PER_CHUNK = 256


def _write_chunk(task):
    """One part file of rows ``[start, stop)``: runs in a spawned process."""
    module_path, config, start, stop, chunk_dir = task
    from benchmark.manifest import load_module
    from petastorm_tpu.etl.dataset_metadata import DatasetWriter
    module = load_module(module_path)
    schema = module.schema(config)
    with DatasetWriter('file://' + chunk_dir, schema,
                       rows_per_row_group=config['rows_per_row_group'],
                       compression=config.get('compression', 'snappy')) as writer:
        for row in module.rows(config, start, stop):
            writer.write(row)
    return writer.row_groups_per_file


def build_store(module_path, config, cache_dir, processes=None):
    """Path of the store for ``config``, built if missing.

    ``module_path`` is the generator's file: it defines ``VERSION``,
    ``records(config)``, the store's count of records, ``schema(config)``
    and ``rows(config, start, stop)``, a generator of row dicts that gives
    the same rows for the same arguments in any process."""
    from benchmark.manifest import load_module
    from petastorm_tpu.etl.dataset_metadata import _write_dataset_metadata, load_row_groups
    module = load_module(module_path)
    name = '{}-{}-{}'.format(config['name'], module.VERSION, config['data_seed'])
    path = os.path.join(cache_dir, name)
    if os.path.exists(os.path.join(path, '_common_metadata')):
        return path
    os.makedirs(cache_dir, exist_ok=True)
    partial = path + '.partial'
    shutil.rmtree(partial, ignore_errors=True)
    records = module.records(config)
    bounds = list(range(0, records, ROWS_PER_CHUNK)) + [records]
    tasks = [(module_path, config, lo, hi, os.path.join(partial, 'chunk{:05d}'.format(i)))
             for i, (lo, hi) in enumerate(zip(bounds[:-1], bounds[1:]))]
    processes = processes or min(len(tasks), os.cpu_count() or 1)
    ctx = multiprocessing.get_context('spawn')
    with ctx.Pool(processes) as pool:
        inventories = pool.map(_write_chunk, tasks, chunksize=1)
    row_groups_per_file = {}
    for i, inventory in enumerate(inventories):
        chunk_dir = tasks[i][-1]
        for relpath, counts in inventory.items():
            target = 'part-{:05d}-{}'.format(i, relpath)
            os.rename(os.path.join(chunk_dir, relpath), os.path.join(partial, target))
            row_groups_per_file[target] = counts
        shutil.rmtree(chunk_dir)
    _write_dataset_metadata('file://' + partial, module.schema(config), row_groups_per_file)
    os.rename(partial, path)
    if not load_row_groups('file://' + path):
        raise RuntimeError('store {} has no row groups'.format(path))
    return path


def store_bytes(path):
    return sum(os.path.getsize(os.path.join(d, f)) for d, _, fs in os.walk(path) for f in fs)
