#!/usr/bin/env python
"""bench_pod.py — BASELINE.md config 5 as one command: sharded multi-host
reading + NGram sequence readout feeding a ('data','seq')-sharded step.

A CPU-simulated pod: it forces a virtual CPU mesh (default: 8 host devices,
4 simulated hosts in one process — the same strategy the reference uses to
test multi-node sharding without a cluster, reference
test_end_to_end.py:426-448). Its numbers are host-CPU numbers. On a real pod
each JAX process would run exactly one host's branch
(``cur_shard=jax.process_index()``) over the real chips.

Per simulated host it builds: make_reader(cur_shard=h, shard_count=H,
ngram=window) -> JaxDataLoader -> stack_ngram_time_axis -> [B, T, ...] batches
staged over the ('data','seq') mesh -> a jitted sequence-model step. Emits one
JSON line per host plus an aggregate:
  {"metric": "pod_host", "host": h, "examples_per_sec": .., "stall": ..}
  {"metric": "pod_aggregate", "hosts": H, "examples_per_sec_total": .., ...}

With ``--telemetry-out DIR`` each (simulated) host also appends its
host-stamped diagnostics JSONL to ``DIR/host<h>.jsonl`` — feed the directory
to ``petastorm-tpu-diagnose --pod DIR`` for the fleet view / straggler callout.

Usage: python bench_pod.py [--hosts 4] [--steps 20] [--seq-len 4]
       [--telemetry-out DIR]
       (the script forces JAX_PLATFORMS=cpu and --devices virtual devices)
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

REPO_ROOT = os.path.dirname(os.path.abspath(__file__))
if REPO_ROOT not in sys.path:
    sys.path.insert(0, REPO_ROOT)


def _ensure_devices(n):
    """The pod is simulated on virtual CPU devices: force the CPU platform
    before JAX starts, then share ``__graft_entry__``'s bring-up, which
    raises when fewer than ``n`` devices come up."""
    os.environ['JAX_PLATFORMS'] = 'cpu'
    import __graft_entry__ as g
    g._ensure_devices(n)


def build_sequence_store(url, rows, feature_dim):
    """Timestamped telemetry-style rows: NGram's native shape."""
    import numpy as np
    from petastorm_tpu.codecs import NdarrayCodec, ScalarCodec
    from petastorm_tpu.etl.dataset_metadata import write_petastorm_dataset
    from petastorm_tpu.unischema import Unischema, UnischemaField

    schema = Unischema('PodSeq', [
        UnischemaField('ts', np.int64, (), ScalarCodec(), False),
        UnischemaField('features', np.float32, (feature_dim,), NdarrayCodec(), False),
    ])
    rng = np.random.default_rng(0)
    write_petastorm_dataset(url, schema, ({
        'ts': i,
        'features': rng.standard_normal(feature_dim).astype(np.float32),
    } for i in range(rows)), rows_per_row_group=64)
    return schema


def _run_chaos(args):
    """The ``--chaos`` lane: elastic pod churn with real process death.

    Spawns host subprocesses (``petastorm_tpu.elastic._hostproc``) over one
    shared coordination directory, SIGKILLs one once the pod has committed
    ``--chaos-kill-after`` row groups, immediately joins a replacement, and
    waits for the survivors. The emitted ``pod_chaos`` line carries the
    scoreboard-derived ground truth: committed/double-committed counts, the
    final generation, and per-host commit shares — on a healthy protocol
    ``double_committed`` is 0 and ``committed`` equals the row-group count.
    """
    import subprocess

    tmpdir = tempfile.mkdtemp(prefix='bench_pod_chaos_')
    url = 'file://' + os.path.join(tmpdir, 'store')
    build_sequence_store(url, args.rows, args.feature_dim)
    coord = os.path.join(tmpdir, 'coord')
    env = dict(os.environ, JAX_PLATFORMS='cpu',
               PYTHONPATH=REPO_ROOT + os.pathsep + os.environ.get('PYTHONPATH', ''))

    def spawn(host):
        return subprocess.Popen(
            [sys.executable, '-m', 'petastorm_tpu.elastic._hostproc',
             '--url', url, '--coord', coord, '--host', host,
             '--out', os.path.join(tmpdir, host + '.jsonl'),
             '--field', 'ts', '--seed', '13', '--lease-s', '1.0',
             '--sleep-per-row', '0.002'], env=env)

    from petastorm_tpu.faults import HostChurnPlan, drive_host_churn
    initial = max(2, min(args.hosts, 4))
    procs = {'host{}'.format(h): spawn('host{}'.format(h))
             for h in range(initial)}
    plan = HostChurnPlan(kill_host='host1',
                         kill_after_commits=args.chaos_kill_after,
                         join_host='host{}'.format(initial))
    timeline = drive_host_churn(
        coord, procs, plan,
        spawn_joiner=lambda: spawn(plan.join_host), timeout_s=300)
    rcs = {h: p.wait(timeout=300) for h, p in procs.items()}

    commits = {}
    commits_dir = os.path.join(coord, 'commits')
    for name in sorted(os.listdir(commits_dir)):
        with open(os.path.join(commits_dir, name)) as f:
            for line in f:
                rec = json.loads(line)
                commits.setdefault((rec['epoch'], rec['item']), []).append(rec)
    double = sum(1 for v in commits.values() if len(v) > 1)
    per_host = {}
    for v in commits.values():
        per_host[v[0]['host']] = per_host.get(v[0]['host'], 0) + 1
    generations = len(os.listdir(os.path.join(coord, 'generations')))
    survivors_ok = all(rc == 0 for h, rc in rcs.items() if h != plan.kill_host)
    print(json.dumps({'metric': 'pod_chaos', 'hosts': initial,
                      'killed': timeline['killed'], 'joined': timeline['joined'],
                      'commits_at_kill': timeline['commits_at_kill'],
                      'committed': len(commits), 'double_committed': double,
                      'per_host_commits': per_host,
                      'generations': generations,
                      'survivor_exit_codes_ok': survivors_ok}), flush=True)
    if double or not survivors_ok:
        return 1
    return 0


def _run_fabric(args):
    """The ``--fabric`` lane: N simulated hosts sharing chunks peer-to-peer.

    Each host gets its own chunk-mirror root and a ``FabricNode`` (server +
    lease membership + client); hosts read the same ``mock-remote://`` store
    one after another with that host's fabric client installed. Host 0 finds
    no peers and reads everything from the object store; every later host
    should source (nearly) every chunk from an earlier peer's mirror — on a
    healthy N-host run the verdict reports ≈1 object-store read plus (N-1)
    LAN copies per chunk. ``--chaos net`` injects a connection reset and a
    truncated payload into the peer serves and asserts the readers still
    complete with the losses accounted as fallbacks.

    The emitted ``pod_fabric`` line carries the conservation check straight
    off the counters: every chunk-mirror miss must be satisfied exactly once,
    by a peer copy or by an object-store fallback (docs/fabric.md).
    """
    from petastorm_tpu import fabric, faults, make_reader, native
    from petastorm_tpu import observability as obs
    from petastorm_tpu.chunkstore import ChunkCacheConfig, cache_diagnostics

    if not native.is_available():
        print(json.dumps({'metric': 'pod_fabric', 'skipped': True,
                          'reason': 'native kernel unavailable (chunk mirrors '
                                    'need the page scanner)'}), flush=True)
        return 0

    obs.configure('counters')
    tmpdir = tempfile.mkdtemp(prefix='bench_pod_fabric_')
    store_path = os.path.join(tmpdir, 'store')
    build_sequence_store('file://' + store_path, args.rows, args.feature_dim)
    url = 'mock-remote://' + store_path
    coord = os.path.join(tmpdir, 'coord')
    hosts = max(2, min(args.hosts, 4))

    faults_injected = 0
    if args.chaos == 'net':
        faults_injected = 2
        faults.install_net(faults.NetFaultPlan(reset_payloads=1,
                                               truncate_payloads=1))

    def counters():
        flat = obs.flatten_snapshot(obs.snapshot())
        return {k: flat.get(k, 0) for k in ('fabric_peer_hits',
                                            'fabric_fallbacks',
                                            'fabric_bytes_from_peers',
                                            'fabric_breaker_open')}

    nodes = []
    rows_ok = True
    misses_total = 0
    t0 = time.perf_counter()
    try:
        for h in range(hosts):
            cache = ChunkCacheConfig(root=os.path.join(tmpdir, 'cache%d' % h),
                                     size_limit_bytes=1 << 30)
            node = fabric.start_node(fabric.FabricConfig(
                coord_dir=coord, host_id='host%d' % h, cache=cache))
            nodes.append(node)
            fabric.install(node)
            before = counters()
            try:
                with make_reader(url, reader_pool_type='thread',
                                 workers_count=args.workers, num_epochs=1,
                                 shuffle_row_groups=False,
                                 chunk_cache=cache) as reader:
                    rows_read = sum(1 for _ in reader)
            finally:
                fabric.uninstall()
            after = counters()
            misses = cache_diagnostics(cache)['chunk_cache_misses']
            misses_total += misses
            rows_ok = rows_ok and rows_read == args.rows
            print(json.dumps({
                'metric': 'pod_fabric_host', 'host': h, 'rows': rows_read,
                'chunk_misses': misses,
                'peer_copies': after['fabric_peer_hits'] - before['fabric_peer_hits'],
                'object_store_reads':
                    after['fabric_fallbacks'] - before['fabric_fallbacks'],
            }), flush=True)
        final = counters()
    finally:
        fabric.uninstall()
        for node in nodes:
            node.stop()
        if args.chaos == 'net':
            faults.uninstall_net()

    dt = time.perf_counter() - t0
    peer_copies = final['fabric_peer_hits']
    object_store_reads = final['fabric_fallbacks']
    # conservation: every mirror miss is satisfied exactly once — by a peer
    # copy or by an object-store fallback (never neither, never both)
    accounted = (peer_copies + object_store_reads) == misses_total
    ok = rows_ok and accounted and peer_copies > 0
    if args.chaos != 'net':
        # healthy pod: host 0 pays the object store once per chunk, every
        # later host rides the fabric
        chunks = misses_total // hosts
        ok = ok and object_store_reads == chunks \
            and peer_copies == (hosts - 1) * chunks
    print(json.dumps({
        'metric': 'pod_fabric', 'hosts': hosts, 'rows': args.rows,
        'chunk_misses': misses_total, 'peer_copies': peer_copies,
        'object_store_reads': object_store_reads,
        'bytes_from_peers': final['fabric_bytes_from_peers'],
        'breakers_tripped': final['fabric_breaker_open'],
        'chaos': args.chaos, 'faults_injected': faults_injected,
        'accounted': accounted, 'elapsed_s': round(dt, 2), 'ok': ok,
    }), flush=True)
    return 0 if ok else 1


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument('--hosts', type=int, default=4)
    parser.add_argument('--devices', type=int, default=8)
    parser.add_argument('--steps', type=int, default=20)
    parser.add_argument('--batch-size', type=int, default=16)
    parser.add_argument('--seq-len', type=int, default=4)
    parser.add_argument('--feature-dim', type=int, default=64)
    parser.add_argument('--rows', type=int, default=4096)
    parser.add_argument('--workers', type=int, default=2)
    parser.add_argument('--context', choices=('ring', 'ulysses'), default='ring',
                        help='context-parallel attention strategy over the seq axis')
    parser.add_argument('--telemetry-out', default=None, metavar='DIR',
                        help='write one host-stamped telemetry JSONL per '
                             '(simulated) host into DIR — the input format of '
                             'petastorm-tpu-diagnose --pod (docs/observability.md)')
    parser.add_argument('--chaos', nargs='?', const='churn', default=None,
                        choices=('churn', 'net'),
                        help='fault lane: bare --chaos (= "churn") runs '
                             'elastic pod churn (docs/parallelism.md) — REAL '
                             'host subprocesses, SIGKILL one mid-epoch, join '
                             'a replacement, assert exactly-once coverage '
                             'from the commit scoreboard; "--chaos net" '
                             '(with --fabric) injects connection resets and '
                             'truncated payloads into the peer transfers '
                             'instead. No devices needed.')
    parser.add_argument('--chaos-kill-after', type=int, default=4,
                        help='commit count that triggers the --chaos kill')
    parser.add_argument('--fabric', action='store_true',
                        help='peer-to-peer chunk fabric lane (docs/fabric.md): '
                             'N simulated hosts with per-host chunk mirrors '
                             'read the same remote store in turn; the verdict '
                             'reports object-store reads vs LAN peer copies '
                             '(healthy: ~1 + (N-1) copies per chunk). Combine '
                             'with --chaos net for fault injection. No '
                             'devices needed; emits a pod_fabric JSON line.')
    args = parser.parse_args(argv)

    if args.chaos == 'net' and not args.fabric:
        parser.error('--chaos net is a fabric fault lane — pass --fabric too')
    if args.fabric:
        return _run_fabric(args)
    if args.chaos:
        return _run_chaos(args)

    _ensure_devices(args.devices)

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from petastorm_tpu import make_reader
    from petastorm_tpu.jax import JaxDataLoader
    from petastorm_tpu.jax.loader import stack_ngram_time_axis
    from petastorm_tpu.ngram import NGram
    from petastorm_tpu.parallel import make_mesh
    from petastorm_tpu.unischema import UnischemaField

    tmpdir = tempfile.mkdtemp(prefix='bench_pod_')
    url = 'file://' + os.path.join(tmpdir, 'store')
    schema = build_sequence_store(url, args.rows, args.feature_dim)

    seq_axis = 2 if args.devices % 2 == 0 else 1
    data_axis = args.devices // seq_axis
    # SPMD divisibility (shard_map): fail fast with a clear message instead of
    # a deep jax error inside the transformer's attention
    if args.batch_size % data_axis:
        parser.error('--batch-size {} must be divisible by the data mesh axis ({}; '
                     '--devices {} / seq {})'.format(args.batch_size, data_axis,
                                                     args.devices, seq_axis))
    if args.seq_len % seq_axis:
        parser.error('--seq-len {} must be divisible by the seq mesh axis ({})'.format(
            args.seq_len, seq_axis))
    mesh = make_mesh(('data', 'seq'), axis_shapes=(-1, seq_axis),
                     devices=jax.devices()[:args.devices])
    batch_sharding = NamedSharding(mesh, P('data', 'seq'))

    fields = {i: [UnischemaField('ts', np.int64, ()),
                  UnischemaField('features', np.float32, (args.feature_dim,))]
              for i in range(args.seq_len)}

    # the REAL long-context training load: a ring-attention sequence
    # transformer (petastorm_tpu.models.transformer) — attention sharded over
    # mesh['seq'] (context parallelism), dp over mesh['data']
    from petastorm_tpu.models import make_sequence_transformer
    from petastorm_tpu.models.train import (create_train_state, make_train_step,
                                            shard_train_state)

    num_classes = 16
    model = make_sequence_transformer(num_classes=num_classes, mesh=mesh,
                                      d_model=64, num_layers=2,
                                      context_parallelism=args.context)
    state = create_train_state(
        model, jax.random.PRNGKey(0),
        jnp.zeros((args.batch_size, args.seq_len, args.feature_dim)))

    if args.telemetry_out:
        os.makedirs(args.telemetry_out, exist_ok=True)

    def _telemetry_snapshot(host, loader):
        """One pod-aggregator line: the loader's flat diagnostics under this
        simulated host's identity stamp (on a real pod every process writes
        its own file; here 'host<h>' keys keep the series distinct)."""
        if not args.telemetry_out:
            return
        from petastorm_tpu import observability as obs
        rec = {'ts': round(time.time(), 3),
               'host': obs.host_identity('host{}'.format(host)),
               'metrics': {k: v for k, v in loader.diagnostics.items()
                           if isinstance(v, (int, float))}}
        path = os.path.join(args.telemetry_out, 'host{}.jsonl'.format(host))
        with open(path, 'a') as f:
            f.write(json.dumps(rec) + '\n')

    total_rate = 0.0
    worst_stall = 0.0
    with mesh:
        state = shard_train_state(state, mesh)
        step = make_train_step(donate=False)
        for host in range(args.hosts):
            ngram = NGram(fields, delta_threshold=1,
                          timestamp_field=UnischemaField('ts', np.int64, ()))
            with make_reader(url, reader_pool_type='thread', workers_count=args.workers,
                             ngram=ngram, output='columnar',
                             cur_shard=host, shard_count=args.hosts,
                             shuffle_row_groups=True, seed=13, num_epochs=None) as reader:
                loader = JaxDataLoader(reader, batch_size=args.batch_size, seed=13)
                it = iter(loader)

                def stage(stacked):
                    x = jax.device_put(stacked['features'], batch_sharding)
                    labels = jnp.asarray(np.asarray(stacked['ts'][:, 0]) % num_classes)
                    return x, labels

                metrics = None
                for _ in range(3):  # warmup + compile
                    x, labels = stage(stack_ngram_time_axis(next(it)))
                    state, metrics = step(state, x, labels)
                jax.block_until_ready(metrics['loss'])
                _telemetry_snapshot(host, loader)
                wait = 0.0
                t0 = time.perf_counter()
                for _ in range(args.steps):
                    # 'stall' times ONLY the input-pipeline wait (window batch
                    # production); staging stays outside, like every other
                    # duty-cycle measurement in this repo
                    w0 = time.perf_counter()
                    stacked = stack_ngram_time_axis(next(it))
                    wait += time.perf_counter() - w0
                    x, labels = stage(stacked)
                    state, metrics = step(state, x, labels)
                jax.block_until_ready(metrics['loss'])
                dt = time.perf_counter() - t0
                _telemetry_snapshot(host, loader)
            rate = args.steps * args.batch_size / dt
            stall = wait / dt
            total_rate += rate
            worst_stall = max(worst_stall, stall)
            print(json.dumps({'metric': 'pod_host', 'host': host,
                              'examples_per_sec': round(rate, 1),
                              'stall': round(stall, 4)}), flush=True)
    print(json.dumps({'metric': 'pod_aggregate', 'hosts': args.hosts,
                      'devices': args.devices, 'seq_len': args.seq_len,
                      'examples_per_sec_total': round(total_rate, 1),
                      'worst_host_stall': round(worst_stall, 4),
                      'simulated': True,
                      'note': 'hosts run serially in one process off-pod; on a '
                              'real pod each process runs its own shard'}), flush=True)


if __name__ == '__main__':
    sys.exit(main() or 0)
