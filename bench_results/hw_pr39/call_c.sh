#!/bin/bash
# Call C (one chip): the five cells the benchmark had, parent (_scratch/parent = git archive
# of d19a9e5) against the change (the working tree), one seed a cell and
# kimi_linear_train_8k on a pair of seeds (parent, change, change, parent), those most at
# risk first: the cells that share mla_mixer, apply_rotary and from_config's refusals.
set -u
ROOT=$PWD; OUT=$ROOT/chiprun_out/hw_pr39; mkdir -p $OUT
export JAX_COMPILATION_CACHE_DIR=$ROOT/.jax_cache_call_c JAX_COMPILATION_CACHE_MAX_SIZE=-1
BUDGET=${BUDGET:-3300}; T00=$(date +%s)
run() { # tree cell seed
  left=$(( BUDGET - ($(date +%s) - T00) ))
  if [ $left -lt 400 ]; then echo "skipped $1 $2 $3: $left s of the call left"; return; fi
  name=c_$1_$2_s$3; t0=$(date +%s)
  dir=$ROOT; [ $1 = parent ] && dir=$ROOT/_scratch/parent
  ( cd $dir && python3 benchmark/run.py --workload $2 --seed $3 --seconds 40 --trace 0 ) > $OUT/$name.out 2> $OUT/$name.err
  rc=$?
  echo "$name: rc $rc after $(( $(date +%s) - t0 )) s"
  grep -E "^check " $OUT/$name.out | tr '\n' ';' | cut -c1-700; echo
  tail -1 $OUT/$name.out | cut -c1-600
  echo "{\"call\": \"C\", \"tree\": \"$1\", \"cell\": \"$2\", \"seed\": $3, \"trace\": 0, \"rc\": $rc, \"line\": $(tail -1 $OUT/$name.out | grep '^{' || echo null)}" >> $OUT/cells_tpu.jsonl
  [ $rc -ne 0 ] && tail -5 $OUT/$name.err | cut -c1-1200
}
run parent kimi_linear_train_8k 3900000301
run change kimi_linear_train_8k 3900000301
run change kimi_linear_train_8k 3900000302
run parent kimi_linear_train_8k 3900000302
for pair in "lfm2_moe_train_8k 3900000303" "evabyte_train_32k 3900000304" "nemotron_h_train_8k 3900000305" "lm_train_b16 3900000306"; do
  set -- $pair
  run parent $1 $2
  run change $1 $2
done
