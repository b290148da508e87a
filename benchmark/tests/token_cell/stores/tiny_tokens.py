"""A small store of token documents for the harness's CPU tests, generated
from the configuration's ``data_seed``: heavy-tailed (log-normal) lengths
capped at the sequence length, Zipf-distributed token ids, and each
document's ``record_id``.

The feed packs the documents into sequences with ``PackedSequenceLoader``;
a transform gives every token its document's id (``doc``), which the
packing carries along, so the check traces each packed segment to its
record.

This module imports no JAX: it runs in spawned store-building processes.
"""

from __future__ import annotations

import contextlib
import io

import numpy as np

VERSION = 'v1'

#: nothing to plant in a decode: tokens are stored as the step takes them
FAULTS = ()


def records(config):
    return config['documents']


def lengths(config):
    rng = np.random.default_rng(config['data_seed'])
    n = rng.lognormal(config['length_log_mean'], config['length_log_sigma'],
                      config['documents'])
    return np.clip(n.astype(np.int64), config['min_length'], config['sequence_length'])


def schema(config):
    from petastorm_tpu.codecs import NdarrayCodec, ScalarCodec
    from petastorm_tpu.unischema import Unischema, UnischemaField
    return Unischema('TinyTokens', [
        UnischemaField('tokens', np.int32, (None,), NdarrayCodec(), False),
        UnischemaField('record_id', np.int64, (), ScalarCodec(np.int64), False),
    ])


def rows(config, start, stop):
    n = lengths(config)
    vocab = config['model']['vocab_size']
    for i in range(start, stop):
        rng = np.random.default_rng((config['data_seed'], i))
        tokens = (rng.zipf(config['zipf_a'], int(n[i])) - 1) % vocab
        yield {'tokens': tokens.astype(np.int32), 'record_id': i}


def _with_doc(row):
    row['doc'] = np.full(len(row['tokens']), row['record_id'], np.int32)
    return row


def transform(config):
    from petastorm_tpu import TransformSpec
    return TransformSpec(_with_doc, edit_fields=[('doc', np.int32, (None,), False)])


@contextlib.contextmanager
def feed(store, config, traffic, seed, global_batch):
    """``make_reader`` over the store, packed by ``PackedSequenceLoader``
    into ``global_batch`` sequences of ``sequence_length`` tokens a batch."""
    from petastorm_tpu import make_reader
    from petastorm_tpu.sequence import PackedSequenceLoader
    with make_reader('file://' + store, num_epochs=None, seed=seed,
                     shuffle_row_groups=traffic['shuffle_row_groups'],
                     reader_pool_type=traffic['reader_pool_type'],
                     workers_count=traffic['workers_count'],
                     transform_spec=transform(config)) as reader:
        yield PackedSequenceLoader(reader, config['sequence_length'], ['tokens', 'doc'],
                                   slots_per_batch=global_batch,
                                   pool_rows=traffic['pool_rows'])


def read_documents(path, record_ids):
    """``{record_id: tokens}`` read with pyarrow and ``np.load`` alone."""
    import pyarrow.dataset as ds
    table = ds.dataset(path, format='parquet').to_table(
        columns=['record_id', 'tokens'],
        filter=ds.field('record_id').isin(sorted({int(r) for r in record_ids})))
    return {rid: np.load(io.BytesIO(cell), allow_pickle=False)
            for rid, cell in zip(table.column('record_id').to_pylist(),
                                 table.column('tokens').to_pylist())}


def check(store, config, delivered, fault=None):
    """``layout_errors``: packed segments that are not one whole stored
    document, in one contiguous run with positions from 0;
    ``token_errors``: segments whose tokens differ from the document's.
    The reference step takes the stored documents laid out as delivered."""
    tokens, doc = delivered['tokens'], delivered['doc']
    segments, positions = delivered['segment_ids'], delivered['positions']
    stored = read_documents(store, np.unique(doc[segments > 0]))
    ref_tokens = np.zeros_like(tokens)
    layout_errors = token_errors = 0
    for row in range(len(tokens)):
        for segment in range(1, int(segments[row].max()) + 1):
            where = np.flatnonzero(segments[row] == segment)
            document = stored.get(int(doc[row, where[0]])) if len(where) else None
            if (document is None or len(document) != len(where)
                    or where[-1] - where[0] + 1 != len(where)
                    or np.any(doc[row, where] != doc[row, where[0]])
                    or np.any(positions[row, where] != np.arange(len(where)))):
                layout_errors += 1
                continue
            ref_tokens[row, where] = document
            token_errors += int(np.any(tokens[row, where] != document))
    return ({'layout_errors': layout_errors, 'token_errors': token_errors},
            (ref_tokens, segments, positions))
