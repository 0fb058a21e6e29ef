#!/bin/bash
# Call F, after the benchmark check's refusal (reduced named mamba_d_ssm): the file as it now stands
# (mamba_d_ssm 4096 as published, share.mamba_channels 1024) on the final tree's committed files alone
# (_scratch/final47f = git archive $(git write-tree), made before the call; _scratch/parent47f = git
# archive 7220487 with this tree's BENCHMARK.json and benchmark/ laid over it): the parent on the new
# cell (it has to refuse at once), one cold sound run, one traced, then further sound seeds.
root="$(cd "$(dirname "$(readlink -f "$0")")/../.." && pwd)"   # the checkout this script lies in
export out=$root/chiprun_out/hw_pr47; mkdir -p $out
export JAX_COMPILATION_CACHE_DIR=$root/.jax_cache_call_f JAX_COMPILATION_CACHE_MAX_SIZE=-1
t0=$(date +%s); left() { echo $(( ${BUDGET:-1000} - ($(date +%s) - t0) )); }
run() { name=$1; shift; s=$(date +%s); "$@" > $out/$name.out 2> $out/$name.err; echo "$name rc=$? wall=$(( $(date +%s) - s ))s left=$(left)s"; grep -E "^check |^memory: [0-9]* bytes as the first|^reference:" $out/$name.out | tr '\n' ';' | cut -c1-3000; echo; tail -n 1 $out/$name.out | cut -c1-2600; grep -E "Error|refused" $out/$name.err | tail -3 | cut -c1-600; }
W="${*:---workload falcon_h1_train_8k --seconds 40}"   # the call names the cell itself, so the tool checks the manifest's form
cd $root/_scratch/parent47f || exit 1
run f_parent_s702 python3 benchmark/run.py $W --seed 4700000702 --trace 0
cd $root/_scratch/final47f || exit 1
run f_s702_cold python3 benchmark/run.py $W --seed 4700000702 --trace 0
run f_s703_traced python3 benchmark/run.py $W --seed 4700000703 --trace 1
for s in 704 705 706; do
  [ $(left) -lt 170 ] && { echo "skipped s$s: $(left) s left"; continue; }
  run f_s$s python3 benchmark/run.py $W --seed 4700000$s --trace 0
done
echo "call F done, left=$(left)s"
