"""Is it the same program? The instruction count and the opcode
histogram of an optimized HLO text (``aot_size.py --dump`` writes one
for a described v5e), and, given two, where they differ. Names and
metadata are no part of it: a scope or a kernel name may change those
and nothing else.

    python benchmarks/tools/hlo_histogram.py parent.hlo.txt [change.hlo.txt]
"""

import collections
import re
import sys

_INSTRUCTION = re.compile(r'^\s*(?:ROOT )?%?[\w.\-]+ = .*?\s([a-z][a-z0-9\-]*)\(')


def histogram(text):
    return collections.Counter(
        m.group(1) for m in map(_INSTRUCTION.match, text.splitlines()) if m)


def main(paths):
    hists = []
    for path in paths:
        with open(path) as f:
            hists.append(histogram(f.read()))
        print(f'{path}: {sum(hists[-1].values())} instructions, '
              f'{len(hists[-1])} opcodes')
    if len(hists) == 1:
        for opcode, n in hists[0].most_common():
            print(f'  {n:6d} {opcode}')
        return 0
    a, b = hists
    diff = {op: (a[op], b[op]) for op in sorted(set(a) | set(b))
            if a[op] != b[op]}
    for opcode, (x, y) in diff.items():
        print(f'  {opcode}: {x} -> {y}')
    print('same opcode histogram' if not diff else
          f'{len(diff)} opcodes differ')
    return 1 if diff else 0


if __name__ == '__main__':
    sys.exit(main(sys.argv[1:3]))
