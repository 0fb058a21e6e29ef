#!/bin/bash
# Call A: the scan kernels alone, then the new cell: one cold run, one traced, the fp8 control,
# the two planted faults, further sound seeds while the call's time lasts.
cd "$(dirname "$(readlink -f "$0")")/../.." || exit 1   # the checkout this script lies in
out=chiprun_out/hw_pr47; mkdir -p $out
export JAX_COMPILATION_CACHE_DIR=${JAX_COMPILATION_CACHE_DIR:-$PWD/.jax_cache_call_a} JAX_COMPILATION_CACHE_MAX_SIZE=-1
t0=$(date +%s); left() { echo $(( ${BUDGET:-3300} - ($(date +%s) - t0) )); }
run() { name=$1; shift; s=$(date +%s); "$@" > $out/$name.out 2> $out/$name.err; echo "$name rc=$? wall=$(( $(date +%s) - s ))s left=$(left)s"; grep -E "^check |^memory|^train:|^reference:|^planted|^===" $out/$name.out | tr '\n' ';' | cut -c1-2500; echo; tail -n 1 $out/$name.out | cut -c1-3500; grep -E "Error|error|refused" $out/$name.err | tail -3 | cut -c1-600; grep '"event": "compile"' .bench_work/falcon_h1_train_8k/telemetry.jsonl > $out/$name.compile_event.jsonl 2>/dev/null; grep '"event": "epoch"' .bench_work/falcon_h1_train_8k/telemetry.jsonl > $out/$name.epochs.jsonl 2>/dev/null; }
run kernels python3 bench_results/hw_pr47/kernels_on_chip.py $out/kernels.jsonl
W="--workload falcon_h1_train_8k --seconds 40"
run a_s101_cold python3 benchmark/run.py $W --seed 4700000101 --trace 0
run a_s102_traced python3 benchmark/run.py $W --seed 4700000102 --trace 1
run a_control_s103 python3 benchmark/control.py $W --seeds 4700000103
FAULT=mu run a_fault_mu_s101 python3 bench_results/hw_pr47/run_faulty.py $W --seed 4700000101 --trace 0
FAULT=key run a_fault_key_s101 python3 bench_results/hw_pr47/run_faulty.py $W --seed 4700000101 --trace 0
for seed in 4700000104 4700000105 4700000106 4700000107 4700000108 4700000109; do
  [ $(left) -lt 400 ] && break
  run a_s${seed: -3} python3 benchmark/run.py $W --seed $seed --trace 0
done
cp .bench_work/falcon_h1_train_8k/telemetry.jsonl $out/telemetry_last.jsonl 2>/dev/null
echo "call A done, left=$(left)s"
