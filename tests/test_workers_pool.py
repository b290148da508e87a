"""Pool protocol tests across thread/process/dummy pools
(modeled on reference workers_pool/tests/test_workers_pool.py)."""

import os
import tempfile

import pytest

from petastorm_tpu.serializers import ArrowTableSerializer, PickleSerializer
from petastorm_tpu.test_util.stub_workers import (DoubleOutputWorker, ExceptionEveryNWorker,
                                                  IdentityWorker, SetupArgsEchoWorker,
                                                  SleepyIdentityWorker, ZeroOutputWorker)
from petastorm_tpu.workers import (ConcurrentVentilator, DummyPool, EmptyResultError, ProcessPool,
                                   ThreadPool)

ALL_POOLS = [lambda n=3: ThreadPool(n), lambda n=3: DummyPool(n)]
POOL_IDS = ['thread', 'dummy']


def _drain(pool):
    results = []
    while True:
        try:
            results.append(pool.get_results())
        except EmptyResultError:
            return results


@pytest.mark.parametrize('make_pool', ALL_POOLS, ids=POOL_IDS)
def test_identity_all_items(make_pool):
    pool = make_pool()
    pool.start(IdentityWorker)
    for i in range(50):
        pool.ventilate(i)
    results = _drain(pool)
    assert sorted(results) == list(range(50))
    pool.stop(); pool.join()


@pytest.mark.parametrize('make_pool', ALL_POOLS, ids=POOL_IDS)
def test_multiple_results_per_item(make_pool):
    pool = make_pool()
    pool.start(DoubleOutputWorker)
    for i in range(10):
        pool.ventilate(i)
    results = _drain(pool)
    assert len(results) == 20
    pool.stop(); pool.join()


@pytest.mark.parametrize('make_pool', ALL_POOLS, ids=POOL_IDS)
def test_zero_output_workers(make_pool):
    """Items that publish nothing still count as processed (reference :268-297)."""
    pool = make_pool()
    pool.start(ZeroOutputWorker)
    for i in range(20):
        pool.ventilate(i)
    assert _drain(pool) == []
    pool.stop(); pool.join()


def test_thread_pool_exception_propagates():
    pool = ThreadPool(2)
    pool.start(ExceptionEveryNWorker, worker_setup_args=1)  # fail on every item
    pool.ventilate(5)
    with pytest.raises(ValueError, match='stub failure on 5'):
        _drain(pool)
    pool.stop(); pool.join()


def test_thread_pool_continues_after_exception():
    pool = ThreadPool(1)
    pool.start(ExceptionEveryNWorker, worker_setup_args=5)
    for i in [1, 2, 5, 3]:
        pool.ventilate(i)
    results, errors = [], []
    while True:
        try:
            results.append(pool.get_results())
        except EmptyResultError:
            break
        except ValueError as e:
            errors.append(e)
    assert sorted(results) == [1, 2, 3]
    assert len(errors) == 1
    pool.stop(); pool.join()


def test_thread_pool_fifo_single_worker():
    pool = ThreadPool(1)
    pool.start(IdentityWorker)
    for i in range(30):
        pool.ventilate(i)
    assert _drain(pool) == list(range(30))
    pool.stop(); pool.join()


def test_stop_mid_work_does_not_hang():
    pool = ThreadPool(4, results_queue_size=2)
    pool.start(SleepyIdentityWorker)
    for i in range(100):
        pool.ventilate(i, sleep_s=0.005)
    # consume a few then stop: workers blocked on the full results queue must exit
    for _ in range(3):
        pool.get_results()
    pool.stop()
    pool.join()


def test_diagnostics():
    # the unified pool diagnostics schema (docs/observability.md): identical
    # key set and units for every pool type
    pool = ThreadPool(2)
    pool.start(IdentityWorker)
    diag = pool.diagnostics
    assert {'workers_count', 'items_ventilated', 'items_completed',
            'items_in_flight', 'results_queue_depth'} <= set(diag)
    assert diag['workers_count'] == 2
    pool.stop(); pool.join()


# ---------------------------------------------------------------------------
# Process pool (spawned subprocesses; heavier — keep the matrix small)
# ---------------------------------------------------------------------------

class TestProcessPool:
    def test_identity(self):
        pool = ProcessPool(2)
        pool.start(IdentityWorker)
        for i in range(20):
            pool.ventilate(i)
        results = _drain(pool)
        assert sorted(results) == list(range(20))
        pool.stop(); pool.join()

    def test_setup_args_survive_spawn(self):
        pool = ProcessPool(2)
        pool.start(SetupArgsEchoWorker, worker_setup_args={'key': [1, 2, 3]})
        pool.ventilate(7)
        value, args = pool.get_results()
        assert value == 7 and args == {'key': [1, 2, 3]}
        pool.stop(); pool.join()

    def test_exception_propagates(self):
        pool = ProcessPool(1)
        pool.start(ExceptionEveryNWorker, worker_setup_args=1)
        pool.ventilate(5)
        with pytest.raises(ValueError, match='stub failure on 5'):
            _drain(pool)
        pool.stop(); pool.join()

    @pytest.mark.parametrize('transport', ['shm', 'zmq'])
    def test_arrow_table_serializer(self, transport):
        import pyarrow as pa
        from petastorm_tpu.test_util.stub_workers import ArrowTableWorker

        pool = ProcessPool(1, serializer=ArrowTableSerializer(), transport=transport)
        pool.start(ArrowTableWorker)
        pool.ventilate(5)
        table = pool.get_results()
        assert isinstance(table, pa.Table)
        assert table.num_rows == 5
        pool.stop(); pool.join()


def test_serializers_roundtrip():
    import numpy as np
    import pyarrow as pa
    for s in (PickleSerializer(), ArrowTableSerializer()):
        assert s.deserialize(s.serialize({'a': 1})) == {'a': 1}
    s = ArrowTableSerializer()
    t = pa.table({'x': np.arange(10), 'y': ['a'] * 10})
    out = s.deserialize(s.serialize(t))
    assert out.equals(t)
    # The shm transport hands deserialize a memoryview, not bytes — both the
    # table and the pickle-fallback branches must still dispatch correctly.
    for payload in (t, {'a': 1}):
        blob = memoryview(s.serialize(payload))
        out = s.deserialize(blob)
        if isinstance(payload, pa.Table):
            assert out.equals(payload)
        else:
            assert out == payload


class TestProcessPoolTransports:
    """Both results transports (first-party C++ shm ring, reference-style zmq)
    must behave identically through the pool protocol."""

    @pytest.mark.parametrize('transport', ['shm', 'zmq'])
    def test_identity_roundtrip(self, transport):
        pool = ProcessPool(2, transport=transport)
        assert pool.transport == transport
        pool.start(IdentityWorker)
        for i in range(30):
            pool.ventilate(i)
        results = _drain(pool)
        assert sorted(results) == list(range(30))
        pool.stop(); pool.join()

    @pytest.mark.parametrize('transport', ['shm', 'zmq'])
    def test_exception_propagates(self, transport):
        pool = ProcessPool(1, transport=transport)
        pool.start(ExceptionEveryNWorker, worker_setup_args=1)
        pool.ventilate(3)  # 3 % 1 == 0 -> raises
        with pytest.raises(ValueError, match='stub failure'):
            pool.get_results()
        pool.stop(); pool.join()

    def test_shm_large_payload_backpressure(self):
        # payloads larger than the ring force the blocking-write path and the
        # never-fits error path
        from petastorm_tpu.native.shm_ring import ShmRing
        import os
        name = '/pstpu_bp_{}'.format(os.getpid())
        ring = ShmRing.create(name, 1 << 20)
        w = ShmRing.attach(name)
        payload = b'z' * (400 << 10)
        assert w.try_write(payload)
        assert w.try_write(payload)
        assert not w.try_write(payload)  # full: 2x400KB + headers in a 1MB ring
        assert ring.try_read() == payload
        assert w.try_write(payload)  # space reclaimed
        with pytest.raises(ValueError, match='exceeds ring capacity'):
            w.try_write(b'z' * (2 << 20))
        w.close(); ring.close()

    def test_unknown_transport_rejected(self):
        with pytest.raises(ValueError, match='transport'):
            ProcessPool(1, transport='carrier-pigeon')

    def test_shm_writev_gather_segments(self):
        """writev lands N segments as ONE message, byte-identical to their
        concatenation — including wrap-around and numpy (read-only) inputs."""
        import os

        import numpy as np

        from petastorm_tpu.native.shm_ring import ShmRing
        name = '/pstpu_wv_{}'.format(os.getpid())
        ring = ShmRing.create(name, 64 << 10)
        w = ShmRing.attach(name)
        arr = np.arange(777, dtype=np.uint8)
        arr.setflags(write=False)  # Arrow-buffer views are read-only too
        parts = [b'H' + b'\x01' * 8, arr, b'', np.full((3, 5), 7, np.int32)]
        expect = b''.join(bytes(p) if not isinstance(p, np.ndarray) else p.tobytes()
                          for p in parts)
        for spin in range(40):  # enough messages to wrap the 64KB ring
            assert w.writev(parts)
            got = ring.try_read()
            assert got == expect, 'mismatch at message {}'.format(spin)
        with pytest.raises(ValueError, match='exceeds ring capacity'):
            w.writev([np.zeros(128 << 10, np.uint8)])
        w.close(); ring.close()


class TestNumpyBlockSerializer:
    """Raw-buffer block serializer: the process-pool default (round 3)."""

    def _rt(self, obj):
        from petastorm_tpu.serializers import NumpyBlockSerializer
        s = NumpyBlockSerializer()
        return s.deserialize(s.serialize(obj))

    def test_numeric_block_roundtrip_values_and_dtypes(self):
        import numpy as np
        block = {'img': np.arange(2 * 3 * 4, dtype=np.uint8).reshape(2, 3, 4),
                 'f': np.linspace(0, 1, 5, dtype=np.float32),
                 'ts': np.array(['2024-01-01', '2024-01-02'], dtype='datetime64[ns]')}
        out = self._rt(block)
        assert set(out) == set(block)
        for k in block:
            np.testing.assert_array_equal(out[k], block[k])
            assert out[k].dtype == block[k].dtype

    def test_mixed_block_object_columns_via_pickle(self):
        import numpy as np
        ragged = np.empty(2, dtype=object)
        ragged[0], ragged[1] = np.ones(2), np.ones(5)
        block = {'a': np.arange(3), 'ragged': ragged, 's': np.array(['x', 'yy'], dtype=object)}
        out = self._rt(block)
        np.testing.assert_array_equal(out['a'], np.arange(3))
        assert out['ragged'][1].shape == (5,)
        assert out['s'].tolist() == ['x', 'yy']

    def test_ragged_object_column_rides_raw_buffers(self):
        """Uniform-dtype ndarray cells (variable-size decoded images) must ride
        the raw-buffer channel — one buffer per cell, shapes in the header —
        not a pickle copy of the pixels; None cells (nullable) pass through."""
        import numpy as np
        from petastorm_tpu.serializers import NumpyBlockSerializer
        rng = np.random.default_rng(5)
        ragged = np.empty(5, dtype=object)
        for i in range(4):
            ragged[i] = rng.integers(0, 255, (8 + i, 6, 3), dtype=np.uint8)
        ragged[4] = None
        strings = np.array(['a', 'bb'], dtype=object)  # non-ndarray cells: pickled
        block = {'img': ragged, 'label': np.arange(5), 's': strings}
        s = NumpyBlockSerializer()
        data = s.serialize(block)
        # the pixels appear as raw bytes exactly once (no embedded pickle copy)
        assert data.count(ragged[0].tobytes()) == 1
        out = s.deserialize(bytearray(data))
        for i in range(4):
            np.testing.assert_array_equal(out['img'][i], ragged[i])
            assert out['img'][i].flags.writeable
        assert out['img'][4] is None
        assert out['s'].tolist() == ['a', 'bb']
        # mixed-dtype cells cannot share a buffer framing: whole column pickles
        mixed = np.empty(2, dtype=object)
        mixed[0], mixed[1] = np.ones(2, np.float32), np.ones(2, np.int64)
        out2 = s.deserialize(bytearray(s.serialize({'m': mixed, 'x': np.arange(2)})))
        np.testing.assert_array_equal(out2['m'][1], np.ones(2, np.int64))

    def test_ragged_cells_writable_after_immutable_transport(self):
        """Over zmq the message arrives as immutable bytes, so np.frombuffer
        views over it are read-only; deserialize must hand out WRITABLE object
        cells regardless of transport (in-place image ops, torch.from_numpy)
        — the ADVICE r5 / PT500 known-positive. Writable transports (shm ring
        / blob) must keep the zero-copy view."""
        import numpy as np
        from petastorm_tpu.serializers import NumpyBlockSerializer
        s = NumpyBlockSerializer()
        ragged = np.empty(2, dtype=object)
        ragged[0] = np.arange(12, dtype=np.uint8).reshape(3, 4)
        ragged[1] = np.arange(6, dtype=np.uint8).reshape(2, 3)
        block = {'img': ragged, 'label': np.arange(2)}
        out = s.deserialize(bytes(s.serialize(block)))  # zmq-style immutable
        for i, cell in enumerate(out['img']):
            assert cell.flags.writeable
            cell += 1  # the consumer contract: in-place ops must not raise
            np.testing.assert_array_equal(cell, ragged[i] + 1)
        # writable message (ring/blob channel): cells stay zero-copy views
        out2 = s.deserialize(bytearray(s.serialize(block)))
        assert out2['img'][0].flags.writeable
        assert out2['img'][0].base is not None

    def test_serialize_parts_matches_serialize_framing(self):
        """The gather-write channel's concatenated segments must be
        byte-identical to serialize() output (one deserializer serves both)."""
        import numpy as np
        from petastorm_tpu.serializers import NumpyBlockSerializer
        rng = np.random.default_rng(6)
        ragged = np.empty(3, dtype=object)
        for i in range(3):
            ragged[i] = rng.integers(0, 255, (4 + i, 5), dtype=np.uint8)
        block = {'img': ragged, 'label': np.arange(3),
                 'ts': np.array(['2024-01-01'], dtype='datetime64[ns]')}
        s = NumpyBlockSerializer()
        parts = s.serialize_parts(block)
        joined = b''.join(bytes(p) if not isinstance(p, np.ndarray) else p.tobytes()
                          for p in parts)
        assert joined == s.serialize(block)
        assert s.serialize_parts([1, 2]) is None  # non-block: caller pickles

    def test_empty_block_roundtrip(self):
        """Zero-row blocks (a predicate filtering a row group to nothing) must
        serialize: memoryview.cast('B') rejects zeros in shape/strides, so the
        serializer routes empties through tobytes (r5 e2e-matrix regression)."""
        import numpy as np
        block = {'id': np.empty((0,), np.int64),
                 'img': np.empty((0, 4, 4, 3), np.uint8),
                 'f': np.arange(3, dtype=np.float32)}
        out = self._rt(block)
        assert out['id'].shape == (0,)
        assert out['img'].shape == (0, 4, 4, 3) and out['img'].dtype == np.uint8
        np.testing.assert_array_equal(out['f'], block['f'])

    def test_non_block_payloads_roundtrip(self):
        import numpy as np
        rows = [{'x': np.ones(2)}, {'x': np.zeros(2)}]  # ngram-style list
        out = self._rt(rows)
        assert isinstance(out, list) and len(out) == 2
        exc = self._rt(ValueError('boom'))
        assert isinstance(exc, ValueError)
        assert self._rt({}) == {}

    def test_views_reference_message_not_copies(self):
        import numpy as np
        from petastorm_tpu.serializers import NumpyBlockSerializer
        s = NumpyBlockSerializer()
        data = s.serialize({'a': np.arange(10, dtype=np.int64)})
        out = s.deserialize(data)
        assert out['a'].base is not None  # a view over the message, not a copy

    @pytest.mark.parametrize('serializer_name', ['numpy_block', 'pickle'])
    def test_process_pool_block_payloads(self, serializer_name, tmp_path):
        """A process-pool columnar read returns identical data under both the
        raw-buffer default and plain pickle (reference reader.py:269 analog)."""
        import numpy as np
        from petastorm_tpu import make_reader
        from petastorm_tpu import reader as reader_mod
        from petastorm_tpu.codecs import NdarrayCodec, ScalarCodec
        from petastorm_tpu.etl.dataset_metadata import write_petastorm_dataset
        from petastorm_tpu.serializers import NumpyBlockSerializer, PickleSerializer
        from petastorm_tpu.unischema import Unischema, UnischemaField

        schema = Unischema('S', [
            UnischemaField('id', np.int64, (), ScalarCodec(), False),
            UnischemaField('vec', np.float32, (4,), NdarrayCodec(), False),
        ])
        url = 'file://' + str(tmp_path / 'ds')
        rng = np.random.default_rng(0)
        expected = {i: rng.standard_normal(4).astype(np.float32) for i in range(40)}
        write_petastorm_dataset(url, schema, ({'id': i, 'vec': expected[i]}
                                              for i in range(40)), rows_per_row_group=10)

        serializer = NumpyBlockSerializer() if serializer_name == 'numpy_block' else PickleSerializer()
        orig = reader_mod._make_pool

        def patched(pool_type, workers, qsize, serializer_arg=None, **kwargs):
            return orig(pool_type, workers, qsize, serializer=serializer, **kwargs)

        reader_mod._make_pool = patched
        try:
            with make_reader(url, reader_pool_type='process', workers_count=2,
                             output='columnar', shuffle_row_groups=False) as reader:
                seen = {}
                for block in reader:
                    for i, row_id in enumerate(block.id.tolist()):
                        seen[int(row_id)] = np.asarray(block.vec[i])
        finally:
            reader_mod._make_pool = orig
        assert sorted(seen) == sorted(expected)
        for k in expected:
            np.testing.assert_array_equal(seen[k], expected[k])


@pytest.mark.skipif(
    not __import__('petastorm_tpu.native.shm_ring', fromlist=['is_available']).is_available(),
    reason='shm ring unavailable')
class TestShmRingStress:
    """Round-3 stress coverage of the default process-pool transport: ring
    wrap-around under sustained load, payloads exceeding ring capacity,
    worker crash mid-run, and /dev/shm exhaustion -> zmq fallback."""

    def test_wraparound_many_payloads_intact(self):
        from petastorm_tpu.test_util.stub_workers import BlobWorker
        # 30 items x 3 blobs x 200KB = ~18MB through a 1MB ring
        pool = ProcessPool(1, transport='shm', ring_bytes=1 << 20)
        pool.start(BlobWorker, {'size': 200 * 1024, 'count': 3})
        try:
            for i in range(30):
                pool.ventilate(i)
            got = []
            for _ in range(90):
                r = pool.get_results(timeout_s=60)
                assert r['blob'] == bytes([(r['item'] + r['j']) % 251]) * (200 * 1024)
                got.append((r['item'], r['j']))
            assert sorted(got) == [(i, j) for i in range(30) for j in range(3)]
        finally:
            pool.stop()
            pool.join()

    def test_payload_larger_than_ring_raises_not_hangs(self):
        from petastorm_tpu.test_util.stub_workers import BlobWorker
        pool = ProcessPool(1, transport='shm', ring_bytes=1 << 20)
        pool.start(BlobWorker, {'size': 2 << 20})  # 2MB > 1MB ring
        try:
            pool.ventilate(0)
            with pytest.raises(ValueError, match='exceeds ring capacity'):
                pool.get_results(timeout_s=60)
        finally:
            pool.stop()
            pool.join()

    def test_worker_crash_poison_item_raises_after_retries(self):
        """A crash-looping item is bounded by max_item_retries: the supervisor
        respawns + requeues, then surfaces PoisonItemError — no timeout, no
        hang (supervision replaced the old strand-until-timeout behavior)."""
        from petastorm_tpu.errors import PoisonItemError
        from petastorm_tpu.test_util.stub_workers import HardExitWorker
        pool = ProcessPool(1, transport='shm', ring_bytes=1 << 20, max_item_retries=1)
        pool.start(HardExitWorker, {'crash_on': 1})
        try:
            pool.ventilate(0)
            assert pool.get_results(timeout_s=60) == [0]
            pool.ventilate(1)  # kills every worker that touches it
            with pytest.raises(PoisonItemError, match='killed 2 consecutive worker'):
                while True:
                    pool.get_results(timeout_s=60)
            assert pool.diagnostics['worker_restarts'] >= 1
            assert pool.diagnostics['items_in_flight'] == 0
        finally:
            pool.stop()
            pool.join()

    def test_worker_crash_unsupervised_times_out_with_liveness_snapshot(self):
        """supervision=False restores the legacy behavior (a dead worker
        strands its items until the results timeout) — and the timeout message
        now carries the per-worker liveness snapshot."""
        from petastorm_tpu.test_util.stub_workers import HardExitWorker
        from petastorm_tpu.workers.process_pool import TimeoutWaitingForResultError
        pool = ProcessPool(1, transport='shm', ring_bytes=1 << 20, results_timeout_s=3,
                           supervision=False)
        pool.start(HardExitWorker, {'crash_on': 1})
        try:
            pool.ventilate(0)
            assert pool.get_results() == [0]
            pool.ventilate(1)  # worker dies here
            with pytest.raises(TimeoutWaitingForResultError) as exc_info:
                while True:
                    pool.get_results()
            msg = str(exc_info.value)
            assert 'items in flight' in msg
            assert 'Worker liveness' in msg and 'DEAD exitcode=13' in msg
            assert 'petastorm-tpu-diagnose' in msg
        finally:
            pool.stop()
            pool.join()

    @pytest.mark.parametrize('transport', ['shm', 'zmq'])
    def test_worker_crash_recovers_and_delivers_exactly_once(self, transport):
        """SIGKILL mid-item with a crash that does NOT repeat (the worker dies
        once, its replacement succeeds): every item is delivered exactly once
        and the restart is visible in diagnostics. Both transports: shm drains
        the dead worker's retired ring; zmq sweeps its lost dispatch pipe."""
        from petastorm_tpu.test_util.stub_workers import CrashOnceWorker
        pool = ProcessPool(2, transport=transport, ring_bytes=1 << 20)
        crash_flag = os.path.join(tempfile.mkdtemp(prefix='pstpu_crash_once_'), 'fired')
        pool.start(CrashOnceWorker, {'crash_on': 3, 'flag_path': crash_flag})
        try:
            for i in range(10):
                pool.ventilate(i)
            got = []
            while True:
                try:
                    got.append(pool.get_results(timeout_s=60))
                except EmptyResultError:
                    break
            assert sorted(got) == list(range(10))
            assert pool.diagnostics['worker_restarts'] >= 1
            assert pool.diagnostics['items_requeued'] >= 1
            assert pool.diagnostics['items_in_flight'] == 0
        finally:
            pool.stop()
            pool.join()

    def test_dev_shm_exhaustion_falls_back_to_zmq(self):
        from petastorm_tpu.test_util.stub_workers import IdentityWorker
        # absurd ring size: statvfs guard trips, pool degrades to zmq
        pool = ProcessPool(1, transport='shm', ring_bytes=1 << 45)
        pool.start(IdentityWorker)
        try:
            assert pool.transport == 'zmq'
            pool.ventilate(7)
            assert pool.get_results(timeout_s=30) == 7
        finally:
            pool.stop()
            pool.join()


class TestBlobSidechannel:
    """The large-payload /dev/shm blob path: single-copy serialize_into, COW
    mmap on read, unlink-on-read + sweep-on-join hygiene."""

    def test_serialize_into_bytes_match_serialize(self):
        import numpy as np
        from petastorm_tpu.serializers import NumpyBlockSerializer
        s = NumpyBlockSerializer()
        obj = {'a': np.arange(12, dtype=np.int64).reshape(3, 4),
               'b': np.ones((2, 5), np.float32),
               's': np.array(['x', 'y'], dtype=object)}
        regular = s.serialize(obj)
        buf = bytearray(len(regular))
        out = s.serialize_into(obj, lambda size: memoryview(buf)[:size])
        assert out is not None
        assert bytes(buf) == regular  # byte-identical framing
        back = s.deserialize(bytes(buf))
        np.testing.assert_array_equal(back['a'], obj['a'])
        np.testing.assert_array_equal(back['b'], obj['b'])
        assert back['s'].tolist() == ['x', 'y']

    def test_serialize_into_declines_small_and_nonblock(self):
        import numpy as np
        from petastorm_tpu.serializers import NumpyBlockSerializer
        s = NumpyBlockSerializer()
        called = []
        assert s.serialize_into({'a': np.arange(4)}, called.append, min_size=1 << 20) is None
        assert s.serialize_into(['not', 'a', 'block'], called.append) is None
        assert s.serialize_into({'only': np.array([None, 1], dtype=object)},
                                called.append) is None
        assert not called  # alloc never invoked on declined payloads

    @pytest.mark.skipif(not os.path.isdir('/dev/shm'), reason='needs /dev/shm')
    def test_process_pool_blob_payloads_roundtrip_and_cleanup(self, tmp_path):
        import glob
        import numpy as np
        from petastorm_tpu import make_reader
        from petastorm_tpu.codecs import RawTensorCodec, ScalarCodec
        from petastorm_tpu.etl.dataset_metadata import write_petastorm_dataset
        from petastorm_tpu.unischema import Unischema, UnischemaField

        schema = Unischema('S', [
            UnischemaField('id', np.int64, (), ScalarCodec(), False),
            UnischemaField('big', np.uint8, (64, 64, 3), RawTensorCodec(), False),
        ])
        url = 'file://' + str(tmp_path / 'ds')
        rng = np.random.default_rng(1)
        expected = {i: rng.integers(0, 255, (64, 64, 3), dtype=np.uint8) for i in range(30)}
        write_petastorm_dataset(url, schema, ({'id': i, 'big': expected[i]}
                                              for i in range(30)), rows_per_row_group=10)

        # 10 rows x 12KB > the tiny threshold: every block rides the blob path
        from petastorm_tpu import reader as reader_mod
        orig = reader_mod._make_pool

        def patched(pool_type, workers, qsize, serializer=None, **kwargs):
            pool = orig(pool_type, workers, qsize, serializer=serializer, **kwargs)
            if hasattr(pool, '_blob_threshold'):
                pool._blob_threshold = 1024
            return pool

        reader_mod._make_pool = patched
        try:
            with make_reader(url, reader_pool_type='process', workers_count=1,
                             output='columnar', shuffle_row_groups=False,
                             num_epochs=1) as reader:
                blob_dir = reader._pool._blob_dir
                assert blob_dir is not None
                seen = {}
                for block in reader:
                    for i, row_id in enumerate(block.id.tolist()):
                        seen[row_id] = np.array(block.big[i])
                    # consumed blobs are unlinked on read
                    assert len(glob.glob(os.path.join(blob_dir, '*'))) <= 2
        finally:
            reader_mod._make_pool = orig
        assert len(seen) == 30
        for i, arr in expected.items():
            np.testing.assert_array_equal(seen[i], arr)
        assert not os.path.exists(blob_dir)  # swept on join

    def test_parts_channel_blob_write_roundtrip(self):
        """The split-once publish path: serialize_parts -> write_parts_into a
        blob-style buffer -> deserialize, and join_parts for the in-band
        fallback — one classification, every channel byte-identical."""
        import numpy as np
        from petastorm_tpu.serializers import NumpyBlockSerializer
        s = NumpyBlockSerializer()
        big = {'a': np.zeros((1 << 18,), np.uint8)}
        parts = s.serialize_parts(big)
        total = s.parts_size(parts)
        buf = bytearray(total)
        s.write_parts_into(parts, memoryview(buf))
        np.testing.assert_array_equal(s.deserialize(bytes(buf))['a'], big['a'])
        assert bytes(buf) == s.join_parts(parts) == s.serialize(big)
        # non-block: no parts; the pickle channel serves it
        assert s.serialize_parts(['x']) is None
        assert s.deserialize(s.serialize(['x'])) == ['x']

    @pytest.mark.skipif(not os.path.isdir('/dev/shm'), reason='needs /dev/shm')
    @pytest.mark.parametrize('rows_per_group,label', [(30, 'blob'), (4, 'inband')])
    def test_blocks_writable_on_every_channel(self, tmp_path, rows_per_group, label):
        # the uniform contract: process-pool blocks are WRITABLE whichever
        # channel they rode (blob COW mmap / ring bytearray / zmq copies)
        import numpy as np
        from petastorm_tpu import make_reader
        from petastorm_tpu.codecs import RawTensorCodec, ScalarCodec
        from petastorm_tpu.etl.dataset_metadata import write_petastorm_dataset
        from petastorm_tpu.unischema import Unischema, UnischemaField

        schema = Unischema('S', [
            UnischemaField('id', np.int64, (), ScalarCodec(), False),
            UnischemaField('big', np.uint8, (128, 128, 3), RawTensorCodec(), False),
        ])
        url = 'file://' + str(tmp_path / 'ds')
        rng = np.random.default_rng(2)
        write_petastorm_dataset(url, schema, ({'id': i, 'big': rng.integers(
            0, 255, (128, 128, 3), dtype=np.uint8)} for i in range(30)),
            rows_per_row_group=rows_per_group)
        with make_reader(url, reader_pool_type='process', workers_count=1,
                         output='columnar', shuffle_row_groups=False, num_epochs=1) as r:
            block = next(iter(r))
            arr = block.big
            assert arr.flags.writeable, label
            arr[0, 0, 0, 0] = 7  # must not raise
            assert arr[0, 0, 0, 0] == 7


def test_dummy_pool_drops_pending_after_stop():
    # parity with ThreadPool: stop() discards ventilated-but-unprocessed items;
    # get_results after stop+join raises EmptyResultError, never AttributeError
    from petastorm_tpu.test_util.stub_workers import IdentityWorker
    pool = DummyPool()
    pool.start(IdentityWorker)
    pool.ventilate(1)
    pool.ventilate(2)
    assert pool.get_results() == 1
    pool.stop()
    pool.join()
    with pytest.raises(EmptyResultError):
        pool.get_results()


def test_dummy_pool_processes_on_consumer_thread():
    # the pool's reason to exist: worker code runs where a profiler sees it
    import threading
    from petastorm_tpu.workers.worker_base import WorkerBase

    class ThreadRecorder(WorkerBase):
        seen = []

        def process(self, x):
            ThreadRecorder.seen.append(threading.current_thread())
            self.publish(x)

    pool = DummyPool()
    pool.start(ThreadRecorder)
    pool.ventilate(1)
    assert pool.get_results() == 1
    assert ThreadRecorder.seen == [threading.main_thread()]
    pool.stop()
    pool.join()


@pytest.mark.skipif(not os.path.isdir('/dev/shm'), reason='needs /dev/shm')
def test_blob_allocation_failure_degrades_in_band(tmp_path):
    """A vanished blob dir (stand-in for tmpfs exhaustion; deletion works even
    under root, where chmod would be bypassed via CAP_DAC_OVERRIDE) must
    degrade every payload to the in-band channel — data complete and correct,
    no worker crash. Row groups are >= the 1MB blob threshold (1.38MB), so
    every payload genuinely attempts the blob path; mkdtemp is patched to
    hand the pool an already-deleted path, so the dir NEVER exists for any
    worker — no blob can land first, race-free. 4 failing groups also ride
    the worker through its self-disable threshold (3), though that flag is
    child-process state this test cannot observe directly."""
    import shutil
    import tempfile as tempfile_mod
    import numpy as np
    from petastorm_tpu import make_reader
    from petastorm_tpu.codecs import RawTensorCodec, ScalarCodec
    from petastorm_tpu.etl.dataset_metadata import write_petastorm_dataset
    from petastorm_tpu.unischema import Unischema, UnischemaField

    schema = Unischema('S', [
        UnischemaField('id', np.int64, (), ScalarCodec(), False),
        UnischemaField('big', np.uint8, (96, 96, 3), RawTensorCodec(), False),
    ])
    url = 'file://' + str(tmp_path / 'ds')
    rng = np.random.default_rng(5)
    expected = {i: rng.integers(0, 255, (96, 96, 3), dtype=np.uint8) for i in range(200)}
    write_petastorm_dataset(url, schema, ({'id': i, 'big': expected[i]}
                                          for i in range(200)), rows_per_row_group=50)

    real_mkdtemp = tempfile_mod.mkdtemp
    hijacked = []

    def fake_mkdtemp(*args, **kwargs):
        d = real_mkdtemp(*args, **kwargs)
        if str(kwargs.get('prefix', '')).startswith('pstpu_blobs_'):
            shutil.rmtree(d)  # the pool gets a path that never exists
            hijacked.append(d)
        return d

    tempfile_mod.mkdtemp = fake_mkdtemp
    try:
        with make_reader(url, reader_pool_type='process', workers_count=1,
                         output='columnar', shuffle_row_groups=False, num_epochs=1) as r:
            seen = {}
            for block in r:
                for i, row_id in enumerate(block.id.tolist()):
                    seen[row_id] = np.array(block.big[i])
    finally:
        tempfile_mod.mkdtemp = real_mkdtemp
    assert hijacked, 'blob dir was never requested: test did not cover the sidechannel'
    assert len(seen) == 200
    for i, a in expected.items():
        np.testing.assert_array_equal(seen[i], a)

def test_stale_blob_dirs_swept_on_pool_start(tmp_path):
    """Blob dirs orphaned by a hard-killed process (dead pid in the name, or a
    name with no parseable pid) are reaped by the next pool start once past
    the mtime grace; dirs owned by a live process — own pid, a real foreign
    live pid, or any fresh dir — survive (ADVICE r3)."""
    import os
    import subprocess
    import sys
    import time as time_mod
    from petastorm_tpu.workers.process_pool import _BLOB_SWEEP_GRACE_S, _sweep_stale_blob_dirs

    root = tmp_path / 'shm'
    root.mkdir()
    # find a pid that is certainly dead
    dead_pid = 999999
    while True:
        try:
            os.kill(dead_pid, 0)
            dead_pid -= 1
        except ProcessLookupError:
            break
        except PermissionError:
            dead_pid -= 1
    # a real foreign live process, to exercise the os.kill success branch
    child = subprocess.Popen([sys.executable, '-c', 'import time; time.sleep(60)'])
    try:
        stale = root / ('pstpu_blobs_%d_abc' % dead_pid)
        legacy = root / 'pstpu_blobs_legacyname'
        own = root / ('pstpu_blobs_%d_xyz' % os.getpid())
        foreign_live = root / ('pstpu_blobs_%d_qrs' % child.pid)
        fresh_dead = root / ('pstpu_blobs_%d_new' % dead_pid)
        weird = root / 'pstpu_blobs_²_x'  # non-ASCII digit: must not crash the sweep
        other = root / 'unrelated_dir'
        for d in (stale, legacy, own, foreign_live, fresh_dead, weird, other):
            d.mkdir()
            (d / 'blob').write_bytes(b'x' * 128)
        old = time_mod.time() - _BLOB_SWEEP_GRACE_S - 5
        for d in (stale, legacy, own, foreign_live, weird):
            os.utime(d, (old, old))  # past the grace period; fresh_dead stays fresh

        _sweep_stale_blob_dirs(str(root))

        assert not stale.exists()
        assert not legacy.exists()
        assert not weird.exists()  # unparseable pid + old: reaped, not crashed
        assert own.exists()
        assert foreign_live.exists()
        assert fresh_dead.exists()  # dead owner but inside the grace window
        assert other.exists()
    finally:
        child.kill()
        child.wait()

def test_process_pool_divides_image_thread_budget(monkeypatch):
    """Spawned workers cannot see each other's in-process decode-thread
    accounting, so each gets cpu_count // workers_count via the env var —
    unless the user pinned it, which children inherit untouched."""
    from petastorm_tpu.test_util.stub_workers import EnvEchoWorker

    monkeypatch.delenv('PSTPU_IMG_THREADS', raising=False)
    pool = ProcessPool(2)
    pool.start(EnvEchoWorker, worker_setup_args='PSTPU_IMG_THREADS')
    pool.ventilate(1)
    _, value = pool.get_results()
    pool.stop(); pool.join()
    expected = max(1, (os.cpu_count() or 1) // 2)
    assert value == str(expected)

    monkeypatch.setenv('PSTPU_IMG_THREADS', '7')
    pool = ProcessPool(2)
    pool.start(EnvEchoWorker, worker_setup_args='PSTPU_IMG_THREADS')
    pool.ventilate(1)
    _, value = pool.get_results()
    pool.stop(); pool.join()
    assert value == '7'  # explicit pin inherited as-is


# -- native image decode: a pool worker takes its share of the thread budget --

class _FanoutSpy(object):
    """Stands in for the native library: records the ``threads`` value each
    decode+resize call receives and forwards the call."""

    def __init__(self, lib):
        self._lib = lib
        self.fanouts = []

    def __getattr__(self, name):
        return getattr(self._lib, name)

    def pstpu_img_decode_resize_batch(self, n, datas, lens, outs, infos, threads, *rest):
        self.fanouts.append(threads)
        return self._lib.pstpu_img_decode_resize_batch(n, datas, lens, outs, infos, threads,
                                                       *rest)


def _png_column(n=3):
    import numpy as np
    import pyarrow as pa
    from petastorm_tpu.codecs import CompressedImageCodec
    from petastorm_tpu.unischema import UnischemaField
    codec = CompressedImageCodec('png')
    field = UnischemaField('im', np.uint8, (None, None, 3), codec, False)
    rng = np.random.default_rng(5)
    blobs = [codec.encode(field, rng.integers(0, 255, (30 + i, 40, 3), dtype=np.uint8))
             for i in range(n)]
    return codec, field, pa.chunked_array([pa.array(blobs, type=pa.binary())])


class _ResizedColumnWorker(IdentityWorker):
    """Decodes a resized PNG column per item, as a reader's row worker does."""

    def process(self, value):
        codec, field, column = _png_column()
        block = codec.decode_column(field, column, resize=(16, 16))
        self.publish((value, block.shape))


@pytest.fixture
def fanout_spy(monkeypatch):
    from petastorm_tpu.native import image_codec
    if not image_codec.is_available():
        pytest.skip('native image codec not built')
    spy = _FanoutSpy(image_codec._load_library())
    monkeypatch.setattr(image_codec, '_lib', spy)
    # a budget above 1, so a granted fan-out is told apart from the floor
    monkeypatch.setattr(image_codec, '_default_threads', lambda: 4)
    return spy


@pytest.mark.parametrize('width, share', [(2, 2), (3, 1), (10, 1)])
def test_thread_pool_worker_decodes_with_its_share(width, share, fanout_spy):
    """Each worker of a thread pool fans out over ``budget // width`` at most
    (budget 4 here): a narrow pool keeps a fan-out, a wide one decodes on the
    worker's own thread."""
    pool = ThreadPool(width)
    pool.start(_ResizedColumnWorker)
    for i in range(6):
        pool.ventilate(i)
    results = _drain(pool)
    pool.stop(); pool.join()
    assert sorted(v for v, _ in results) == list(range(6))
    assert all(shape == (3, 16, 16, 3) for _, shape in results)
    assert fanout_spy.fanouts == [share] * 6


@pytest.mark.parametrize('caller', ['own-thread', 'dummy-pool', 'one-worker-pool'])
def test_lone_caller_keeps_thread_grant(caller, fanout_spy):
    """Callers on their own (a script, the dummy pool, a pool of one worker)
    still fan the call out over the free budget."""
    if caller == 'own-thread':
        codec, field, column = _png_column()
        codec.decode_column(field, column, resize=(16, 16))
    else:
        pool = DummyPool() if caller == 'dummy-pool' else ThreadPool(1)
        pool.start(_ResizedColumnWorker)
        pool.ventilate(0)
        assert len(_drain(pool)) == 1
        pool.stop(); pool.join()
    assert fanout_spy.fanouts == [4]


def test_explicit_threads_win_inside_pool_worker(fanout_spy):
    from petastorm_tpu.columnar import column_cells
    from petastorm_tpu.native import image_codec

    class ExplicitThreadsWorker(IdentityWorker):
        def process(self, value):
            _, _, column = _png_column()
            image_codec.decode_images_resized(column_cells(column), (16, 16), threads=3)
            self.publish(value)

    pool = ThreadPool(2)
    pool.start(ExplicitThreadsWorker)
    pool.ventilate(0)
    assert _drain(pool) == [0]
    pool.stop(); pool.join()
    assert fanout_spy.fanouts == [3]


def test_thread_pool_share_follows_resized_pool(fanout_spy):
    """The share is read per item: after the pool grows, its workers divide
    the budget by the new width."""
    pool = ThreadPool(2)
    pool.start(_ResizedColumnWorker)
    pool.ventilate(0)
    assert len(_drain(pool)) == 1
    pool.add_worker_slot()
    pool.add_worker_slot()
    pool.ventilate(1)
    assert len(_drain(pool)) == 1
    pool.stop(); pool.join()
    assert fanout_spy.fanouts == [2, 1]


@pytest.mark.parametrize('pool_type, grant', [('thread', 2), ('dummy', 4)])
def test_fused_reader_image_decode_takes_pool_share(tmp_path, monkeypatch, pool_type, grant):
    """The fused row-group reader (fixed-shape image column, no resize) takes
    the same share as every other native decode made on a pool worker."""
    import contextlib

    import numpy as np

    from petastorm_tpu import make_reader
    from petastorm_tpu import native
    from petastorm_tpu import observability as obs
    from petastorm_tpu.codecs import CompressedImageCodec, ScalarCodec
    from petastorm_tpu.etl.dataset_metadata import write_petastorm_dataset
    from petastorm_tpu.native import image_codec
    from petastorm_tpu.observability import metrics
    from petastorm_tpu.unischema import Unischema, UnischemaField
    if not (native.is_available() and image_codec.is_available()):
        pytest.skip('native kernels not built')
    monkeypatch.setattr(image_codec, '_default_threads', lambda: 4)
    grants = []
    real_grant = image_codec._thread_grant

    @contextlib.contextmanager
    def grant_spy(requested):
        with real_grant(requested) as g:
            grants.append(g)
            yield g

    monkeypatch.setattr(image_codec, '_thread_grant', grant_spy)
    schema = Unischema('I', [
        UnischemaField('img', np.uint8, (8, 10, 3), CompressedImageCodec('png'), False),
        UnischemaField('id', np.int32, (), ScalarCodec(), False),
    ])
    url = 'file://' + str(tmp_path / 'store')
    rng = np.random.default_rng(3)
    rows = [{'img': rng.integers(0, 255, (8, 10, 3), np.uint8), 'id': i} for i in range(20)]
    write_petastorm_dataset(url, schema, iter(rows), rows_per_row_group=5)
    level = metrics.level_name()
    metrics.set_level('counters')
    try:
        before = obs.snapshot().get('counters', {}).get('fused_batches_total', 0)
        with make_reader(url, reader_pool_type=pool_type, workers_count=2,
                         shuffle_row_groups=False) as reader:
            got = {int(r.id): r.img for r in reader}
        fused = obs.snapshot().get('counters', {}).get('fused_batches_total', 0) - before
    finally:
        metrics.set_level(level)
    assert sorted(got) == list(range(20))
    for r in rows:
        np.testing.assert_array_equal(got[r['id']], r['img'])  # png is lossless
    assert fused == 4  # every row group came through the fused reader
    assert grants == [grant] * 4
