#!/bin/bash
# Call D (one chip): the committed files alone (_scratch/final = git archive of the final
# tree): kanana2_train_8k on six more seeds, untraced, each run's epoch events kept, then one
# traced run.
set -u
ROOT=$PWD; OUT=$ROOT/chiprun_out/hw_pr39; mkdir -p $OUT
export JAX_COMPILATION_CACHE_DIR=$ROOT/.jax_cache_call_d JAX_COMPILATION_CACHE_MAX_SIZE=-1
CELL=kanana2_train_8k
BUDGET=${BUDGET:-3000}; T00=$(date +%s)
run() { # seed trace
  left=$(( BUDGET - ($(date +%s) - T00) ))
  if [ $left -lt 300 ]; then echo "skipped $1: $left s of the call left"; return; fi
  name=d_final_s$1_t$2; t0=$(date +%s)
  ( cd $ROOT/_scratch/final && python3 benchmark/run.py --workload $CELL --seed $1 --seconds 40 --trace $2 ) > $OUT/$name.out 2> $OUT/$name.err
  rc=$?
  echo "$name: rc $rc after $(( $(date +%s) - t0 )) s"
  grep -E "^check |^train:" $OUT/$name.out | tr '\n' ';' | cut -c1-1000; echo
  tail -1 $OUT/$name.out | cut -c1-1800
  grep '"event": "epoch"' $ROOT/_scratch/final/.bench_work/$CELL/telemetry.jsonl > $OUT/$name.epochs.jsonl 2>/dev/null
  [ $2 = 1 ] && cp $ROOT/_scratch/final/.bench_work/$CELL/scope_time.json $OUT/d_scope_time.json 2>/dev/null
  echo "{\"call\": \"D\", \"tree\": \"final (git archive)\", \"cell\": \"$CELL\", \"seed\": $1, \"trace\": $2, \"rc\": $rc, \"line\": $(tail -1 $OUT/$name.out | grep '^{' || echo null)}" >> $OUT/cells_tpu.jsonl
  [ $rc -ne 0 ] && tail -5 $OUT/$name.err | cut -c1-1200
}
for n in 1 2 3 4 5 6; do run 390000040$n 0; done
run 3900000407 1
