"""Spreads of the runs that ``sets.sh`` left: for each metric of each
set, the distance between the first and third quartile
(``statistics.quantiles(values, n=4)``) as a share of the median; the
wider of the two sets' spreads is what a bound is five times of.

    python3 benchmarks/tools/spread.py <outdir> <workload>
"""

import glob
import json
import statistics
import sys


def last_line(path):
    with open(path) as f:
        lines = [x for x in f.read().splitlines() if x.strip()]
    return json.loads(lines[-1])


def main():
    outdir, workload = sys.argv[1:3]
    widest = {}
    for s in (1, 2):
        runs = [last_line(p) for p in sorted(
            glob.glob(f'{outdir}/{workload}.s{s}.*.out'))]
        print(f'set {s}: {len(runs)} runs, correct '
              f'{[r["correct"] for r in runs]}')
        for name in runs[0]['metrics']:
            values = [r['metrics'][name]['value'] for r in runs]
            if name == 'setup_s':
                values = values[1:] if s == 1 else values
            q = statistics.quantiles(values, n=4)
            med = statistics.median(values)
            spread = (q[2] - q[0]) / med
            widest[name] = max(widest.get(name, 0.0), spread)
            print(f'  {name}: median {med:.6g}, spread {100 * spread:.3f} %'
                  f', min {min(values):.6g}, max {max(values):.6g}')
        print(f'  memory_peak_bytes: '
              f'{sorted({r["device"]["memory_peak_bytes"] for r in runs})}')
    for name, spread in widest.items():
        print(f'{name}: widest spread {100 * spread:.3f} % -> five times '
              f'{100 * 5 * spread:.2f} %')


if __name__ == '__main__':
    main()
