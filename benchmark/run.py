"""Run one cell of the benchmark on the TPU this process finds.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Earlier lines report what the run saw; the last line of standard output is
the result as one JSON object. Without a TPU, or with fewer chips than the
cell asks for, it exits with code 1 and prints no result.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split('\n\n')[0])
    parser.add_argument('--workload', required=True)
    parser.add_argument('--seed', type=int, required=True)
    parser.add_argument('--seconds', type=float, required=True)
    parser.add_argument('--trace', type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)

    if sys.path[0] == os.path.dirname(os.path.abspath(__file__)):
        sys.path.pop(0)
    sys.path.insert(0, ROOT)
    try:
        import petastorm_tpu  # noqa: F401  the system under test, from this checkout
    except ImportError as e:
        print('benchmark: the program is not in this checkout: {}'.format(e), file=sys.stderr)
        return 1
    from benchmark import cell as cell_run
    from benchmark import manifest
    try:
        cell = manifest.load_cell(args.workload, ROOT)
    except (OSError, KeyError, ValueError) as e:
        print('benchmark: cannot load cell {!r}: {!r}'.format(args.workload, e),
              file=sys.stderr)
        return 1
    try:
        line = cell_run.run(cell, args.seed, args.seconds, bool(args.trace), T_START, ROOT)
    except cell_run.NoChip as e:
        print('benchmark: {}'.format(e), file=sys.stderr)
        return 1
    print(json.dumps(line), flush=True)
    return 0


if __name__ == '__main__':
    sys.exit(main())
