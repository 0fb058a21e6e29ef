#!/bin/bash
# Call C (one chip): the committed files alone (git archive of the final tree, unpacked by
# the caller into _scratch/final): evabyte_train_32k six times untraced, a seed each, then
# once traced. Result lines are appended to chiprun_out/hw_pr37/cells_tpu.jsonl.
set -u
OUT=$PWD/chiprun_out/hw_pr37; mkdir -p $OUT
export JAX_COMPILATION_CACHE_DIR=$PWD/.jax_cache_call_c JAX_COMPILATION_CACHE_MAX_SIZE=-1
CELL=evabyte_train_32k
cd _scratch/final
run() { # name seed trace
  t0=$(date +%s)
  python3 benchmark/run.py --workload $CELL --seed $2 --seconds 40 --trace $3 > $OUT/$1.out 2> $OUT/$1.err
  rc=$?
  echo "$1: rc $rc after $(( $(date +%s) - t0 )) s"
  grep -E "^check |^memory: [0-9]+ bytes as the first|^train:|^reference:" $OUT/$1.out | cut -c1-260
  tail -1 $OUT/$1.out | cut -c1-1500
  echo "{\"call\": \"C\", \"tree\": \"final\", \"cell\": \"$CELL\", \"seed\": $2, \"trace\": $3, \"rc\": $rc, \"line\": $(tail -1 $OUT/$1.out)}" >> $OUT/cells_tpu.jsonl
  [ $rc -ne 0 ] && tail -5 $OUT/$1.err | cut -c1-1500
}
for seed in 3700000301 3700000302 3700000303 3700000304 3700000305 3700000306; do
  run c_s$seed $seed 0
done
run c_traced_s3700000307 3700000307 1
cp .bench_work/$CELL/scope_time.json $OUT/c_scope_time.json 2>/dev/null
cp .bench_work/$CELL/telemetry.jsonl $OUT/c_telemetry.jsonl 2>/dev/null
