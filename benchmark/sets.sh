#!/bin/bash
# The two full sets of a cell, as the contract's `bound` rule asks: 2 x 6 runs,
# the same six seeds in both sets, then one traced run. Result lines go to
# chiprun_out/sets/<cell>.jsonl; `python3 benchmark/spread.py <file>` reads them.
# usage: [RUNS=6] [SETS="A B"] sets.sh <seconds> <base seed> <cell> [<cell> ...]
seconds=$1; base=$2; shift 2
mkdir -p chiprun_out/sets
for cell in "$@"; do
  : > chiprun_out/sets/$cell.jsonl
  for set in ${SETS:-A B}; do
    for i in $(seq 1 ${RUNS:-6}); do
      seed=$((base + i))
      python3 benchmark/run.py --workload $cell --seed $seed --seconds $seconds --trace 0 \
        > chiprun_out/sets/$cell.$set$i.out 2> chiprun_out/sets/$cell.$set$i.err
      rc=$?
      tail -n 1 chiprun_out/sets/$cell.$set$i.out | sed "s/^{/{\"set\": \"$set\", \"seed\": $seed, \"rc\": $rc, /" \
        >> chiprun_out/sets/$cell.jsonl
      grep "^check" chiprun_out/sets/$cell.$set$i.out | tr '\n' ';'; echo " [$cell $set$i rc=$rc]"
      if [ $rc -ne 0 ] && [ $set$i = A1 ]; then   # a cell that cannot run: stop paying for it
        tail -n 30 chiprun_out/sets/$cell.$set$i.err; exit 1
      fi
    done
  done
  python3 benchmark/run.py --workload $cell --seed $((base + 7)) --seconds $seconds --trace 1 \
    > chiprun_out/sets/$cell.trace.out 2> chiprun_out/sets/$cell.trace.err
  tail -n 1 chiprun_out/sets/$cell.trace.out | cut -c1-3000
  python3 benchmark/spread.py chiprun_out/sets/$cell.jsonl
  base=$((base + 100))
done
