#!/bin/bash
# Call B (one chip): kanana2_train_8k on six fresh seeds, untraced, for the spread; then the
# control (the plain reference at fp8 in the program's place, one seed); then the program
# with the rotation taken out (_scratch/no_rotation: `_deepseek_v3` hands `rope_theta` None,
# the benchmark and its reference as they are) on the first seed: which limit catches it.
set -u
ROOT=$PWD; OUT=$ROOT/chiprun_out/hw_pr39; mkdir -p $OUT
export JAX_COMPILATION_CACHE_DIR=$ROOT/.jax_cache_call_b JAX_COMPILATION_CACHE_MAX_SIZE=-1
CELL=kanana2_train_8k
BUDGET=${BUDGET:-3300}; T00=$(date +%s)
run() { # name dir seed
  left=$(( BUDGET - ($(date +%s) - T00) ))
  if [ $left -lt 480 ]; then echo "skipped $1: $left s of the call left"; return; fi
  t0=$(date +%s)
  ( cd $2 && python3 benchmark/run.py --workload $CELL --seed $3 --seconds 40 --trace 0 ) > $OUT/$1.out 2> $OUT/$1.err
  rc=$?
  echo "$1: rc $rc after $(( $(date +%s) - t0 )) s"
  grep -E "^check |^reference:|^routing:" $OUT/$1.out | tr '\n' ';' | cut -c1-1400; echo
  tail -1 $OUT/$1.out | cut -c1-600
  echo "{\"call\": \"B\", \"run\": \"$1\", \"cell\": \"$CELL\", \"seed\": $3, \"trace\": 0, \"rc\": $rc, \"line\": $(tail -1 $OUT/$1.out | grep '^{' || echo null)}" >> $OUT/cells_tpu.jsonl
  [ $rc -ne 0 ] && tail -5 $OUT/$1.err | cut -c1-1200
}
for n in 1 2 3 4 5 6; do run b_s20$n $ROOT 390000020$n; done
t0=$(date +%s)
python3 benchmark/control.py --workload $CELL --seeds 3900000207 --seconds 40 > $OUT/b_control.out 2> $OUT/b_control.err
echo "control: rc $? after $(( $(date +%s) - t0 )) s"
grep -E "^===|^check |^reference:|^\{" $OUT/b_control.out | cut -c1-400
tail -3 $OUT/b_control.err | cut -c1-600
rm -rf _scratch/no_rotation && mkdir -p _scratch/no_rotation && cp -r BENCHMARK.json benchmark csed_514_project_distributed_training_using_pytorch_tpu _scratch/no_rotation/
python3 - <<'P'
p = "_scratch/no_rotation/csed_514_project_distributed_training_using_pytorch_tpu/models/hybrid_lm.py"
s = open(p).read()
a = 'rope_theta=float(config["rope_theta"]),\n        rope_interleave'
assert s.count(a) == 1
open(p, "w").write(s.replace(a, 'rope_theta=None,\n        rope_interleave'))
P
run b_no_rotation_s201 $ROOT/_scratch/no_rotation 3900000201
