#!/bin/bash
# Call D (one chip): the optimized HLO text of the epoch program, hashed without metadata, from the
# parent and from the committed files, both run from _scratch/slot, in lm_train_b16 and
# lfm2_moe_train_8k (hlo_hash.py; each run ends when the text is hashed, and starts with an empty
# compile cache, so its seconds from process start to the compiled program are a cold set-up's).
repo=/root/repo; out=$repo/chiprun_out/pr42/d; mkdir -p $out
export JAX_COMPILATION_CACHE_MAX_SIZE=-1
n=0
for step in ${@:-parent:lm_train_b16:4200000104 final:lm_train_b16:4200000104 \
    parent:lfm2_moe_train_8k:4200000204 final:lfm2_moe_train_8k:4200000204 \
    final:lfm2_moe_train_8k:4200000205 parent:lfm2_moe_train_8k:4200000205}; do
  IFS=: read tree cell seed <<< "$step"
  n=$(( n + 1 )); export JAX_COMPILATION_CACHE_DIR=$repo/.jax_cache_call_d$n     # every run cold
  rm -rf $repo/_scratch/slot; cp -a $repo/_scratch/$tree $repo/_scratch/slot
  t0=$(date +%s)
  python3 bench_results/hw_pr42/hlo_hash.py $repo/_scratch/slot $cell $seed $out/hlo.$n.$tree.jsonl > $out/$cell.$n.$tree.out 2> $out/$cell.$n.$tree.err; rc=$?
  echo "[$tree $cell rc=$rc took $(( $(date +%s) - t0 )) s] $(tail -n 1 $out/$cell.$n.$tree.out | cut -c1-600)"
  [ $rc -ne 0 ] && tail -n 20 $out/$cell.$n.$tree.err
done
exit 0
