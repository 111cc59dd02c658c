"""What a profiler trace says about the program's scopes: where
``op_name`` lives in this file, then self time by (class, pass) on the
busiest device, how each instruction came by its name, and the largest
``unattributed`` instructions with their whole ``op_name``. Look at a
trace with this before changing ``scope_patterns.json`` or the scopes
in the program.

    python benchmarks/tools/scope_inventory.py <file.xplane.pb> [--top 25] [--steps N]

``--steps`` divides every time by the number of steps in the trace.
"""

import argparse
import collections
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks import scopes, trace as tr  # noqa: E402


def where_names_live(data):
    for program, proto in scopes.hlo_protos(data):
        names = scopes.op_names(proto)
        own = sum(1 for _, how in names.values() if how == 'own')
        print(f'{scopes.METADATA_PLANE}: {program}: Hlo Proto of '
              f'{len(proto)} bytes, {len(names)} instructions outside '
              f'fusions, {own} with an op_name of their own')
    for plane, stat_names, metas in scopes.planes(data):
        with_op = sum(1 for m in metas if scopes.metadata_stats(
            m, stat_names)[1].get('tf_op') is not None)
        if with_op:
            print(f'{plane}: {with_op} of {len(metas)} event metadata '
                  f'carry a tf_op stat')


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument('path')
    ap.add_argument('--top', type=int, default=25)
    ap.add_argument('--steps', type=int, default=1)
    args = ap.parse_args()
    with open(args.path, 'rb') as f:
        where_names_live(f.read())
    pats = tr.patterns()
    trace = tr.load_xplane(args.path, pats)
    if not trace['devices']:
        print('no device plane with operations in this trace')
        return
    mapping = scopes.instruction_map(args.path)
    ops = [[op, start, dur, own / args.steps] for op, start, dur, own
           in trace['devices'][tr.busiest(trace, pats)]]
    by_scope = scopes.seconds_by_scope(ops, mapping, pats)
    total = sum(by_scope.values())
    print(f'self time outside collectives: {total * 1e3:.3f} ms '
          f'(times divided by {args.steps})')
    for (cls, pas), sec in sorted(by_scope.items(), key=lambda kv: -kv[1]):
        print(f'  {sec * 1e3:11.3f} ms {100 * sec / total:6.2f} %  '
              f'{cls} x {pas}')
    by_how = collections.Counter()
    loose = collections.Counter()
    for op, _, _, own in ops:
        row = mapping.get(scopes.instruction_of(op))
        by_how[row[3] if row else 'not in a program'] += own
        if row is None or row[0] == scopes.UNATTRIBUTED:
            loose[op, row[2] if row else ''] += own
    print('by where the name came from: ' + ', '.join(
        f'{how} {ns / 1e6:.3f} ms' for how, ns in by_how.most_common()))
    print(f'largest {scopes.UNATTRIBUTED}:')
    for (op, op_name), ns in loose.most_common(args.top):
        print(f'  {ns / 1e6:11.3f} ms  {op}  op_name={op_name!r}')


if __name__ == '__main__':
    main()
