#!/bin/bash
# Calls D and C in one, with a set of six between: the embedding's backward first (it decides a
# code change), six untraced seeds of the new cell, then the parent on the new cell and the
# touched cells' pairs while the call's time lasts.
cd "$(dirname "$(readlink -f "$0")")/../.." || exit 1   # the checkout this script lies in
T0=$(date +%s); BUDGET=${BUDGET:-3400}
bash bench_results/hw_pr47/call_d.sh
out=$PWD/chiprun_out/hw_pr47
export JAX_COMPILATION_CACHE_DIR=$PWD/.jax_cache_call_d JAX_COMPILATION_CACHE_MAX_SIZE=-1
for seed in 4700000201 4700000202 4700000203 4700000204 4700000205 4700000206; do
  s=$(date +%s)
  python3 benchmark/run.py --workload falcon_h1_train_8k --seed $seed --seconds 40 --trace 0 > $out/b1_s${seed: -3}.out 2> $out/b1_s${seed: -3}.err
  echo "b1_s${seed: -3} rc=$? wall=$(( $(date +%s) - s ))s"; grep -E "^check (loss_gap|moment|delta)" $out/b1_s${seed: -3}.out | tr '\n' ';'; echo; tail -n 1 $out/b1_s${seed: -3}.out | cut -c1-400
  grep '"event": "epoch"' .bench_work/falcon_h1_train_8k/telemetry.jsonl | python3 -c "
import sys, json
print('  execute_s', [round(json.loads(l)['execute_s'], 3) for l in sys.stdin][1:])"
done
BUDGET=$(( BUDGET - ($(date +%s) - T0) )) bash bench_results/hw_pr47/call_c.sh
