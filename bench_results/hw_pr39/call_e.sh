#!/bin/bash
# Call E (one chip): the selection bias's rate at 0.01 in the configuration's file (0.001 in
# calls A to D), on five of call D's seeds, the seed with the most arrived rows (…401) and
# the one with the fewest (…403) among them: does the rate still follow the seed?
set -u
ROOT=$PWD; OUT=$ROOT/chiprun_out/hw_pr39; mkdir -p $OUT
export JAX_COMPILATION_CACHE_DIR=$ROOT/.jax_cache_call_e JAX_COMPILATION_CACHE_MAX_SIZE=-1
CELL=kanana2_train_8k
BUDGET=${BUDGET:-1290}; T00=$(date +%s)
run() { # seed
  left=$(( BUDGET - ($(date +%s) - T00) ))
  if [ $left -lt 205 ]; then echo "skipped $1: $left s of the call left"; return; fi
  name=e_rate01_s$1; t0=$(date +%s)
  python3 benchmark/run.py --workload $CELL --seed $1 --seconds 40 --trace 0 > $OUT/$name.out 2> $OUT/$name.err
  rc=$?
  echo "$name: rc $rc after $(( $(date +%s) - t0 )) s"
  grep -E "^check |^train:" $OUT/$name.out | tr '\n' ';' | cut -c1-1000; echo
  tail -1 $OUT/$name.out | cut -c1-600
  grep '"event": "epoch"' .bench_work/$CELL/telemetry.jsonl > $OUT/$name.epochs.jsonl 2>/dev/null
  echo "{\"call\": \"E\", \"tree\": \"change, moe_router_bias_update_rate 0.01\", \"cell\": \"$CELL\", \"seed\": $1, \"trace\": 0, \"rc\": $rc, \"line\": $(tail -1 $OUT/$name.out | grep '^{' || echo null)}" >> $OUT/cells_tpu.jsonl
}
for s in 3900000401 3900000403 3900000402 3900000404 3900000406; do run $s; done
exit 0
