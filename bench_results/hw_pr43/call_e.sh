#!/bin/bash
# Call E (one chip): the two accepted cells not yet paired: lm_train_b16, then evabyte_train_32k
# if the call's time holds both of its runs; parent, then the change from `git archive`
# (_scratch/final), one seed each, untraced.
set -u
ROOT=$PWD; OUT=$ROOT/chiprun_out/hw_pr43; mkdir -p $OUT
export JAX_COMPILATION_CACHE_DIR=${JAX_COMPILATION_CACHE_DIR:-$ROOT/.jax_cache_call_e} JAX_COMPILATION_CACHE_MAX_SIZE=-1
BUDGET=${BUDGET:-900}; T00=$(date +%s)
left() { echo $(( BUDGET - ($(date +%s) - T00) )); }
pair() { # cell seed needs
  if [ $(left) -lt $3 ]; then echo "skipped the pair of $1: $(left) s of the call left"; return 0; fi
  for side in parent final; do
    t0=$(date +%s)
    ( cd _scratch/$side && python3 benchmark/run.py --workload $1 --seed $2 --seconds 40 --trace 0 ) > $OUT/e_$1_$side.out 2> $OUT/e_$1_$side.err
    rc=$?
    echo "e_$1_$side: rc $rc after $(( $(date +%s) - t0 )) s"
    grep -E "^check |^memory|^train:" $OUT/e_$1_$side.out | tr '\n' ';' | cut -c1-1800; echo
    tail -1 $OUT/e_$1_$side.out | cut -c1-700
    echo "{\"call\": \"E\", \"run\": \"e_$1_$side\", \"cell\": \"$1\", \"seed\": $2, \"trace\": 0, \"rc\": $rc, \"line\": $(tail -1 $OUT/e_$1_$side.out | grep '^{' || echo null)}" >> $OUT/cells_tpu_e.jsonl
    [ $rc -ne 0 ] && tail -8 $OUT/e_$1_$side.err | cut -c1-1500
  done
}
pair lm_train_b16 4300000601 0
pair evabyte_train_32k 4300000602 560
echo "call E: $(( $(date +%s) - T00 )) s"
exit 0
