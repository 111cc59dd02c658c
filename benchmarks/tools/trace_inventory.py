"""Print what a profiler trace holds: planes, their lines, and for each
line the event names with most time. Look at one trace by hand with this
before writing or changing a reducer.

    python benchmarks/tools/trace_inventory.py <file.xplane.pb> [--top 25]
"""

import argparse
import collections


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument('path')
    ap.add_argument('--top', type=int, default=25)
    args = ap.parse_args()
    from jax.profiler import ProfileData
    data = ProfileData.from_file(args.path)
    for plane in data.planes:
        print(f'PLANE {plane.name!r}')
        for line in plane.lines:
            total = collections.Counter()
            count = collections.Counter()
            first = {}
            n = 0
            for e in line.events:
                n += 1
                total[e.name] += e.duration_ns
                count[e.name] += 1
                if e.name not in first:
                    first[e.name] = e
            print(f'  LINE {line.name!r}: {n} events, '
                  f'{len(total)} names')
            for name, ns in total.most_common(args.top):
                e = first[name]
                stats = {k: (str(v)[:120]) for k, v in e.stats}
                print(f'    {ns / 1e6:10.3f} ms x{count[name]:<6} '
                      f'{name[:100]!r} stats={stats}')


if __name__ == '__main__':
    main()
