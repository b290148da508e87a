"""The readings each limit of ``correct`` is set from, on the chip.

    python3 benchmark/readings.py --workload <name> --seconds <s> --seeds <n> [<n> ...]
        [--variants control_fp8 fault_half_batch] [--control-in-place 0|1]

For each seed, in one process, a run of the cell as ``run.py`` makes it
(the program's numbers: the lower readings), and then

- for each of ``--variants``, on the same batches, the reference put in the
  program's place: ``control_fp8`` computed in fp8 as fp8 training does
  (the control), ``fault_half_batch`` with the loss over half of the batch
  (a planted fault);
- the feed's numbers with each delivered image moved one row on (an image
  delivered for another record) and each label moved one class on;
- for a store that decodes, the feed's numbers with each fault of the
  store's ``FAULTS`` planted in the decode put in the program's place:
  another resize filter, another DCT scale.

With ``--control-in-place 1`` each seed's run instead trains through the
window with the control, the reference's step in fp8, in the place of the
program's step, and its line says whether ``correct`` came out false.

A step that returns its state unchanged reads 1 by the worst-leaf measure
and needs no run. Each seed's line, and last a summary of the highest
program reading and the lowest of each other, go to standard output. The
benchmark's own runs never run this.
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRAINING = ('loss_gap', 'loss1_gap', 'grad1_gap', 'change_gap', 'grad1_worst_gap',
            'change_worst_gap', 'grad1_diff', 'change_diff')
VARIANTS = {'control_fp8': {'quant': 'fp8'}, 'fault_half_batch': {'half_batch': True}}


def control_step(config):
    """The factory of the control's step, which the configuration's model
    module (``benchmark/models/<arch>.py``) puts in the program's place."""
    from benchmark import manifest
    return manifest.model_module(config, ROOT).control_step(config)


def readings_of(variants):
    """The ``readings`` callback of ``cell.run`` for ``variants``."""

    def readings(config, seed, program, ref, ref_batches, delivered, device, store,
                 store_module, model):
        import numpy as np

        from benchmark import gaps
        out = {}
        for name in variants:
            other = model.reference(config, seed, ref_batches, device, **VARIANTS[name])
            numbers = gaps.training(other, ref)
            out[name] = {k: numbers[k] for k in TRAINING}
            out[name]['leaves'] = [numbers['_grad1_leaf'], numbers['_change_leaf']]
        moved = dict(delivered, image=np.roll(delivered['image'], 1, axis=0),
                     label=(delivered['label'] + 1) % config['model']['num_classes'])
        out['fault_wrong_record'] = store_module.check(store, config, moved)[0]
        for fault in store_module.FAULTS:
            out['fault_' + fault] = store_module.check(store, config, delivered, fault=fault)[0]
        return out

    return readings


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split('\n\n')[0])
    parser.add_argument('--workload', required=True)
    parser.add_argument('--seconds', type=float, default=3.0)
    parser.add_argument('--seeds', type=int, nargs='+', required=True)
    parser.add_argument('--variants', nargs='*', choices=sorted(VARIANTS),
                        default=sorted(VARIANTS))
    parser.add_argument('--control-in-place', type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)
    if sys.path[0] == os.path.dirname(os.path.abspath(__file__)):
        sys.path.pop(0)
    sys.path.insert(0, ROOT)
    from benchmark import cell as cell_run
    from benchmark import manifest
    cell = manifest.load_cell(args.workload, ROOT)
    lines = []
    for seed in args.seeds:
        if args.control_in_place:
            line = cell_run.run(cell, seed, args.seconds, False, time.perf_counter(), ROOT,
                                faults={'step': control_step(cell.config)})
            record = {'seed': seed, 'control_in_place': True, 'correct': line['correct'],
                      'checks': line['checks']}
        else:
            line = cell_run.run(cell, seed, args.seconds, False, time.perf_counter(), ROOT,
                                readings=readings_of(args.variants))
            record = {'seed': seed, 'correct': line['correct'], 'readings': line['readings'],
                      'metrics': line['metrics']}
        lines.append(record)
        print(json.dumps(record), flush=True)
    summary = {'workload': args.workload, 'seeds': args.seeds}
    if args.control_in_place:
        summary['correct'] = [r['correct'] for r in lines]
        summary['checks'] = {k: min(r['checks'][k]['value'] for r in lines)
                             for k in lines[0]['checks']}
    else:
        for name in lines[0]['readings']:
            numbers = [k for k, v in lines[0]['readings'][name].items()
                       if isinstance(v, (int, float))]
            pick = max if name == 'program' else min
            summary[name] = {k: pick(r['readings'][name][k] for r in lines) for k in numbers}
    print(json.dumps({'summary': summary}), flush=True)
    return 0


if __name__ == '__main__':
    sys.exit(main())
