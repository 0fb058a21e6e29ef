#!/bin/bash
# Call B (one chip): qwen3_next_train_8k under the fp8 control (the reference with every
# matmul operand rounded to e4m3 in the program's place) and with the planted fault (a scratch
# copy whose _qwen3_next hands rope_dim None: all 256 channels of a head turn where the first
# 64 are due), both under the committed limits; kimi_linear_train_8k (the cell whose kernels'
# file this PR edits) parent, change, change, parent; then fresh untraced seeds of the new cell
# while the call's time lasts (with call A's they make the two sets of six).
set -u
ROOT=$PWD; OUT=$ROOT/chiprun_out/hw_pr43; mkdir -p $OUT
export JAX_COMPILATION_CACHE_DIR=${JAX_COMPILATION_CACHE_DIR:-$ROOT/.jax_cache_call_b} JAX_COMPILATION_CACHE_MAX_SIZE=-1
CELL=qwen3_next_train_8k
BUDGET=${BUDGET:-3300}; T00=$(date +%s)
left() { echo $(( BUDGET - ($(date +%s) - T00) )); }
run() { # name dir cell seed trace needs
  if [ $(left) -lt $6 ]; then echo "skipped $1: $(left) s of the call left"; return 0; fi
  t0=$(date +%s)
  ( cd $2 && python3 benchmark/run.py --workload $3 --seed $4 --seconds 40 --trace $5 ) > $OUT/$1.out 2> $OUT/$1.err
  rc=$?
  echo "$1: rc $rc after $(( $(date +%s) - t0 )) s"
  grep -E "^check |^memory|^train:|^reference:|^routing:" $OUT/$1.out | tr '\n' ';' | cut -c1-2200; echo
  tail -1 $OUT/$1.out | cut -c1-700
  grep '"event": "epoch"' $2/.bench_work/$3/telemetry.jsonl > $OUT/$1.epochs.jsonl 2>/dev/null
  echo "{\"call\": \"B\", \"run\": \"$1\", \"cell\": \"$3\", \"seed\": $4, \"trace\": $5, \"rc\": $rc, \"line\": $(tail -1 $OUT/$1.out | grep '^{' || echo null)}" >> $OUT/cells_tpu.jsonl
  [ $rc -ne 0 ] && tail -8 $OUT/$1.err | cut -c1-1500
  return 0
}
t0=$(date +%s)
python3 benchmark/control.py --workload $CELL --seeds 4300000207 --seconds 40 > $OUT/b_control.out 2> $OUT/b_control.err
echo "control: rc $? after $(( $(date +%s) - t0 )) s"
grep -E "^===|^check |^reference:|^\{" $OUT/b_control.out | cut -c1-400
tail -3 $OUT/b_control.err | cut -c1-600
rm -rf _scratch/whole_head && mkdir -p _scratch/whole_head && cp -r BENCHMARK.json benchmark csed_514_project_distributed_training_using_pytorch_tpu _scratch/whole_head/
python3 - <<'P'
p = "_scratch/whole_head/csed_514_project_distributed_training_using_pytorch_tpu/models/hybrid_lm.py"
s = open(p).read()
a = 'rope_dim=int(turned), qk_norm=True,'
assert s.count(a) == 1
open(p, "w").write(s.replace(a, 'rope_dim=None, qk_norm=True,'))
P
run b_whole_head_s201 $ROOT/_scratch/whole_head $CELL 4300000201 0 400
run b_s201 $ROOT $CELL 4300000201 0 330
K=kimi_linear_train_8k
run b_kimi_parent_s301 $ROOT/_scratch/parent $K 4300000301 0 420
run b_kimi_change_s301 $ROOT $K 4300000301 0 330
run b_kimi_change_s302 $ROOT $K 4300000302 0 330
run b_kimi_parent_s302 $ROOT/_scratch/parent $K 4300000302 0 330
for s in 202 203 204 205 206 207; do run b_s$s $ROOT $CELL 4300000$s 0 330; done
echo "call B: $(( $(date +%s) - T00 )) s"
exit 0
