#!/bin/bash
# Call C: (e) the parent on the new cell (this PR's benchmark laid over it: must exit at once,
# not 0); (f) the cells whose code was touched, parent then change on one seed, untraced, and
# nemotron_h_train_8k's change traced for ssd_scan_roofline_share. The parent is
# _scratch/base47 (git archive 7220487).
cd "$(dirname "$(readlink -f "$0")")/../.." || exit 1   # the checkout this script lies in
out=$PWD/chiprun_out/hw_pr47; mkdir -p $out
export JAX_COMPILATION_CACHE_DIR=${JAX_COMPILATION_CACHE_DIR:-$PWD/.jax_cache_call_c} JAX_COMPILATION_CACHE_MAX_SIZE=-1
t0=$(date +%s); left() { echo $(( ${BUDGET:-3300} - ($(date +%s) - t0) )); }
run() { # name dir cell seed trace
  s=$(date +%s)
  ( cd $2 && python3 benchmark/run.py --workload $3 --seed $4 --seconds 40 --trace $5 ) > $out/$1.out 2> $out/$1.err
  echo "$1 rc=$? wall=$(( $(date +%s) - s ))s left=$(left)s"
  grep -E "^check |^memory|^train:" $out/$1.out | tr '\n' ';' | cut -c1-1800; echo
  tail -n 1 $out/$1.out | cut -c1-2500; tail -n 2 $out/$1.err | cut -c1-400
}
rm -rf _scratch/overlay47 && cp -r _scratch/base47 _scratch/overlay47 && cp BENCHMARK.json _scratch/overlay47/ && cp -r benchmark/. _scratch/overlay47/benchmark/
run c_parent_on_new_cell _scratch/overlay47 falcon_h1_train_8k 4700000301 0
run c_parent_on_new_cell_traced _scratch/overlay47 falcon_h1_train_8k 4700000301 1
run c_nemotron_parent _scratch/base47 nemotron_h_train_8k 4700000311 0
run c_nemotron_change . nemotron_h_train_8k 4700000311 0
run c_nemotron_change_traced . nemotron_h_train_8k 4700000312 1
[ $(left) -gt 700 ] && run c_evabyte_parent _scratch/base47 evabyte_train_32k 4700000321 0
[ $(left) -gt 400 ] && run c_evabyte_change . evabyte_train_32k 4700000321 0
[ $(left) -gt 700 ] && run c_lfm2_parent _scratch/base47 lfm2_moe_train_8k 4700000331 0
[ $(left) -gt 400 ] && run c_lfm2_change . lfm2_moe_train_8k 4700000331 0
echo "call C done, left=$(left)s"
