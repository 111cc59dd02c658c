#!/bin/bash
# Two sets of six runs of one cell, the same six seeds in both, each run's
# output kept: what a bound is set from (tools/spread.py reads them).
#   bash benchmarks/tools/sets.sh <workload> <seconds> <outdir>
W=$1; SEC=$2; OUT=$3; mkdir -p "$OUT"
for SET in 1 2; do
  for SEED in 2100000011 2100000012 2100000013 2100000014 2100000015 2100000016; do
    python3 benchmarks/run.py --workload "$W" --seed $SEED --seconds "$SEC" --trace 0 \
      > "$OUT/$W.s$SET.$SEED.out" 2> "$OUT/$W.s$SET.$SEED.err"
    echo "rc=$? $(tail -n 1 "$OUT/$W.s$SET.$SEED.out" | cut -c1-420)"
  done
done
