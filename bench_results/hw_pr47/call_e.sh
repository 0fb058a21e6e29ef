#!/bin/bash
# Call E, after the review: the committed limits (loss_gap 3e-6, no one_row_loss_gap) on the final
# tree's committed files alone (_scratch/final47e = git archive $(git write-tree), made before the
# call): one cold sound run that guards the call, the fp8 control, the two planted faults, one
# traced run, then further sound seeds while the call's time lasts.
root="$(cd "$(dirname "$(readlink -f "$0")")/../.." && pwd)"   # the checkout this script lies in
cd $root/_scratch/final47e || exit 1
export out=$root/chiprun_out/hw_pr47; mkdir -p $out
export JAX_COMPILATION_CACHE_DIR=$root/.jax_cache_call_e JAX_COMPILATION_CACHE_MAX_SIZE=-1
t0=$(date +%s); left() { echo $(( ${BUDGET:-1750} - ($(date +%s) - t0) )); }
run() { name=$1; shift; s=$(date +%s); "$@" > $out/$name.out 2> $out/$name.err; echo "$name rc=$? wall=$(( $(date +%s) - s ))s left=$(left)s"; grep -E "^check |^memory: [0-9]* bytes as the first|^reference:|^planted" $out/$name.out | tr '\n' ';' | cut -c1-3000; echo; tail -n 1 $out/$name.out | cut -c1-2600; grep -E "Error|refused" $out/$name.err | tail -3 | cut -c1-600; }
W="--workload falcon_h1_train_8k --seconds 40"
run e_s601_cold python3 benchmark/run.py $W --seed 4700000601 --trace 0
python3 - <<'P' || { echo "guard: the first run is not correct or slower than 3.3 examples/s: the call stops"; exit 0; }
import json, os, sys
line = json.loads(open(os.environ["out"] + "/e_s601_cold.out").read().strip().split("\n")[-1])
sys.exit(0 if line["correct"] and line["metrics"]["train_examples_per_s"]["value"] > 3.3 else 1)
P
run e_control_s603 python3 benchmark/control.py $W --seeds 4700000603
FAULT=mu run e_fault_mu_s601 python3 bench_results/hw_pr47/run_faulty.py $W --seed 4700000601 --trace 0
FAULT=key run e_fault_key_s601 python3 bench_results/hw_pr47/run_faulty.py $W --seed 4700000601 --trace 0
run e_s602_traced python3 benchmark/run.py $W --seed 4700000602 --trace 1
for s in 604 605 606 607 608; do
  [ $(left) -lt 200 ] && { echo "skipped s$s: $(left) s left"; continue; }
  run e_s$s python3 benchmark/run.py $W --seed 4700000$s --trace 0
done
echo "call E done, left=$(left)s"
