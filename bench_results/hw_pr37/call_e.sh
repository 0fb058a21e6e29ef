#!/bin/bash
# Call E (one chip): five more seeds of evabyte_train_32k on the final tree's committed
# files (_scratch/final), for the tail of the check numbers.
set -u
ROOT=$PWD; OUT=$ROOT/chiprun_out/hw_pr37; mkdir -p $OUT
export JAX_COMPILATION_CACHE_DIR=$ROOT/.jax_cache_call_e JAX_COMPILATION_CACHE_MAX_SIZE=-1
CELL=evabyte_train_32k
cd _scratch/final
for seed in 3700000501 3700000502 3700000503 3700000504 3700000505; do
  t0=$(date +%s)
  python3 benchmark/run.py --workload $CELL --seed $seed --seconds 40 --trace 0 > $OUT/e_s$seed.out 2> $OUT/e_s$seed.err
  rc=$?
  echo "e_s$seed: rc $rc after $(( $(date +%s) - t0 )) s"
  grep -E "^check " $OUT/e_s$seed.out | tr '\n' ';' | cut -c1-600; echo
  tail -1 $OUT/e_s$seed.out | cut -c1-400
  echo "{\"call\": \"E\", \"tree\": \"final\", \"cell\": \"$CELL\", \"seed\": $seed, \"trace\": 0, \"rc\": $rc, \"line\": $(tail -1 $OUT/e_s$seed.out)}" >> $OUT/cells_tpu.jsonl
done
exit 0
