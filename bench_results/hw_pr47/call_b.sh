#!/bin/bash
# Call B: the final tree's committed files alone (_scratch/final47 = git archive $(git write-tree),
# made before the call): one cold untraced run that guards the call (correct, and the rate the
# column-blocked embedding predicts), one traced run, then two sets of six untraced seeds.
root="$(cd "$(dirname "$(readlink -f "$0")")/../.." && pwd)"   # the checkout this script lies in
cd $root/_scratch/final47 || exit 1
export out=$root/chiprun_out/hw_pr47; mkdir -p $out
export JAX_COMPILATION_CACHE_DIR=$root/.jax_cache_call_b JAX_COMPILATION_CACHE_MAX_SIZE=-1
t0=$(date +%s); left() { echo $(( ${BUDGET:-3300} - ($(date +%s) - t0) )); }
run() { # name seed trace
  s=$(date +%s)
  python3 benchmark/run.py --workload falcon_h1_train_8k --seed $2 --seconds 40 --trace $3 > $out/$1.out 2> $out/$1.err
  echo "$1 rc=$? wall=$(( $(date +%s) - s ))s left=$(left)s"
  grep -E "^check |^memory: [0-9]* bytes as the first|^device time by scope" $out/$1.out | tr '\n' ';' | cut -c1-3000; echo
  tail -n 1 $out/$1.out | cut -c1-2600
  grep '"event": "epoch"' .bench_work/falcon_h1_train_8k/telemetry.jsonl | python3 -c "
import sys, json
print('  execute_s', [round(json.loads(l)['execute_s'], 3) for l in sys.stdin][1:])"
}
run f_s501_cold 4700000501 0
python3 - <<'P' || { echo "guard: the first run is not correct or slower than 3.3 examples/s: the call stops"; exit 0; }
import json, os, sys
line = json.loads(open(os.environ["out"] + "/f_s501_cold.out").read().strip().split("\n")[-1])
sys.exit(0 if line["correct"] and line["metrics"]["train_examples_per_s"]["value"] > 3.3 else 1)
P
run f_s502_traced 4700000502 1
cp .bench_work/falcon_h1_train_8k/scope_time.json $out/f_scope_time.json
grep '"event": "compile"' .bench_work/falcon_h1_train_8k/telemetry.jsonl > $out/f_compile_event.jsonl
for s in 503 504 505 506 507 511 512 513 514 515 516; do
  [ $(left) -lt 200 ] && { echo "skipped s$s: $(left) s left"; continue; }
  run f_s$s 4700000$s 0
done
echo "call B done, left=$(left)s"
