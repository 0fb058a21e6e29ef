#!/bin/bash
# Call D (one chip): nemotron_h_train_8k, which call C's time did not reach: parent, then the
# change from `git archive $(git write-tree)` (_scratch/final), one seed, untraced.
set -u
ROOT=$PWD; OUT=$ROOT/chiprun_out/hw_pr43; mkdir -p $OUT
export JAX_COMPILATION_CACHE_DIR=${JAX_COMPILATION_CACHE_DIR:-$ROOT/.jax_cache_call_d} JAX_COMPILATION_CACHE_MAX_SIZE=-1
K=nemotron_h_train_8k
for side in parent final; do
  t0=$(date +%s)
  ( cd _scratch/$side && python3 benchmark/run.py --workload $K --seed 4300000503 --seconds 40 --trace 0 ) > $OUT/d_${K}_$side.out 2> $OUT/d_${K}_$side.err
  rc=$?
  echo "d_${K}_$side: rc $rc after $(( $(date +%s) - t0 )) s"
  grep -E "^check |^memory|^train:|^reference:|^routing:" $OUT/d_${K}_$side.out | tr '\n' ';' | cut -c1-2200; echo
  tail -1 $OUT/d_${K}_$side.out | cut -c1-700
  echo "{\"call\": \"D\", \"run\": \"d_${K}_$side\", \"cell\": \"$K\", \"seed\": 4300000503, \"trace\": 0, \"rc\": $rc, \"line\": $(tail -1 $OUT/d_${K}_$side.out | grep '^{' || echo null)}" >> $OUT/cells_tpu_d.jsonl
  [ $rc -ne 0 ] && tail -8 $OUT/d_${K}_$side.err | cut -c1-1500
done
exit 0
