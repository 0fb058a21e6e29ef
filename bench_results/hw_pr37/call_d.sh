#!/bin/bash
# Call D (one chip): the committed files alone (_scratch/final = git archive of the final
# tree) against the parent (_scratch/parent = git archive of 49694fe): evabyte_train_32k once
# more on the final tree; then the three older catalog cells and lm_train_b16, parent then
# change on one seed a cell; then kimi_linear_train_8k traced on the parent with this PR's
# benchmark laid over it (_scratch/overlay), as the driver runs the traced runs.
set -u
ROOT=$PWD; OUT=$ROOT/chiprun_out/hw_pr37; mkdir -p $OUT
export JAX_COMPILATION_CACHE_DIR=$ROOT/.jax_cache_call_d JAX_COMPILATION_CACHE_MAX_SIZE=-1
BUDGET=${BUDGET:-3300}; T00=$(date +%s)
run() { # tree cell seed trace
  left=$(( BUDGET - ($(date +%s) - T00) ))
  if [ $left -lt 420 ]; then echo "skipped $1 $2: $left s of the call left"; return; fi
  name=d_$1_$2_s$3_t$4; t0=$(date +%s)
  ( cd $ROOT/_scratch/$1 && python3 benchmark/run.py --workload $2 --seed $3 --seconds 40 --trace $4 ) > $OUT/$name.out 2> $OUT/$name.err
  rc=$?
  echo "$name: rc $rc after $(( $(date +%s) - t0 )) s"
  grep -E "^check " $OUT/$name.out | tr '\n' ';' | cut -c1-600; echo
  tail -1 $OUT/$name.out | cut -c1-1200
  echo "{\"call\": \"D\", \"tree\": \"$1\", \"cell\": \"$2\", \"seed\": $3, \"trace\": $4, \"rc\": $rc, \"line\": $(tail -1 $OUT/$name.out)}" >> $OUT/cells_tpu.jsonl
  if [ $rc -ne 0 ]; then tail -5 $OUT/$name.err | cut -c1-1200; fi
}
run final evabyte_train_32k 3700000401 0
for pair in "kimi_linear_train_8k 3700000402" "nemotron_h_train_8k 3700000403" "lfm2_moe_train_8k 3700000404" "lm_train_b16 3700000405"; do
  set -- $pair
  run parent $1 $2 0
  run final $1 $2 0
done
rm -rf _scratch/overlay && cp -r _scratch/parent _scratch/overlay && cp _scratch/final/BENCHMARK.json _scratch/overlay/ && cp -r _scratch/final/benchmark/. _scratch/overlay/benchmark/
run overlay kimi_linear_train_8k 3700000406 1
