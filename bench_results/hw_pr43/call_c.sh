#!/bin/bash
# Call C (one chip): the final tree from `git archive $(git write-tree)` (_scratch/final: the
# committed files alone) on qwen3_next_train_8k, one run untraced and one traced; the planted
# fault again (a copy of _scratch/final whose _qwen3_next hands rope_dim None: all 256 channels
# of a head turn) under the committed, tightened limits; then accepted cells whose code this PR
# touched (attention_mixer, sparse_ff, route), parent then change (= _scratch/final) on one seed
# each, while the call's time lasts: lfm2_moe_train_8k, kanana2_train_8k, nemotron_h_train_8k.
set -u
ROOT=$PWD; OUT=$ROOT/chiprun_out/hw_pr43; mkdir -p $OUT
export JAX_COMPILATION_CACHE_DIR=${JAX_COMPILATION_CACHE_DIR:-$ROOT/.jax_cache_call_c} JAX_COMPILATION_CACHE_MAX_SIZE=-1
CELL=qwen3_next_train_8k; FINAL=$ROOT/_scratch/final; PARENT=$ROOT/_scratch/parent
BUDGET=${BUDGET:-3000}; T00=$(date +%s)
left() { echo $(( BUDGET - ($(date +%s) - T00) )); }
run() { # name dir cell seed trace needs
  if [ $(left) -lt $6 ]; then echo "skipped $1: $(left) s of the call left"; return 1; fi
  t0=$(date +%s)
  ( cd $2 && python3 benchmark/run.py --workload $3 --seed $4 --seconds 40 --trace $5 ) > $OUT/$1.out 2> $OUT/$1.err
  rc=$?
  echo "$1: rc $rc after $(( $(date +%s) - t0 )) s"
  grep -E "^check |^memory|^train:|^reference:|^routing:" $OUT/$1.out | tr '\n' ';' | cut -c1-2200; echo
  tail -1 $OUT/$1.out | cut -c1-3800
  grep '"event": "epoch"' $2/.bench_work/$3/telemetry.jsonl > $OUT/$1.epochs.jsonl 2>/dev/null
  if [ $5 = 1 ]; then cp $2/.bench_work/$3/scope_time.json $OUT/c_scope_time.json 2>/dev/null; fi
  echo "{\"call\": \"C\", \"run\": \"$1\", \"cell\": \"$3\", \"seed\": $4, \"trace\": $5, \"rc\": $rc, \"line\": $(tail -1 $OUT/$1.out | grep '^{' || echo null)}" >> $OUT/cells_tpu_c.jsonl
  [ $rc -ne 0 ] && tail -8 $OUT/$1.err | cut -c1-1500
  return 0
}
run c_final_s401 $FINAL $CELL 4300000401 0 0
run c_final_traced_s402 $FINAL $CELL 4300000402 1 420
rm -rf _scratch/whole_head && cp -r $FINAL _scratch/whole_head
python3 - <<'P'
p = "_scratch/whole_head/csed_514_project_distributed_training_using_pytorch_tpu/models/hybrid_lm.py"
s = open(p).read()
a = 'rope_dim=int(turned), qk_norm=True,'
assert s.count(a) == 1
open(p, "w").write(s.replace(a, 'rope_dim=None, qk_norm=True,'))
P
run c_whole_head_s201 $ROOT/_scratch/whole_head $CELL 4300000201 0 400
pair() { # cell seed needs-for-both
  if [ $(left) -lt $3 ]; then echo "skipped the pair of $1: $(left) s of the call left"; return 0; fi
  run c_$1_parent $PARENT $1 $2 0 0
  run c_$1_change $FINAL $1 $2 0 0
}
pair lfm2_moe_train_8k 4300000501 560
pair kanana2_train_8k 4300000502 900
pair nemotron_h_train_8k 4300000503 820
echo "call C: $(( $(date +%s) - T00 )) s"
exit 0
