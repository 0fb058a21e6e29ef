#!/bin/bash
# Call F (one chip, second session): the files as committed after REVIEW.md: the selection
# bias's rate back at 0.001, `warmup_epochs` 20 in the cell's workload file, `loss_gap` 0.001.
# The rotation's three forms alone (rotary_forms.py); kanana2_train_8k on call D's two extreme
# seeds (…401: 726 k rows an epoch there, …403: 389 k), a traced run and a fresh seed; the
# program with the rotation taken out (_scratch/no_rotation, seed …201 as in call B); the fp8
# control (seed …207 as in call B); then fresh seeds while the call's time lasts.
set -u
ROOT=$PWD; OUT=$ROOT/chiprun_out/hw_pr39; mkdir -p $OUT
export JAX_COMPILATION_CACHE_DIR=${JAX_COMPILATION_CACHE_DIR:-$ROOT/.jax_cache_call_f} JAX_COMPILATION_CACHE_MAX_SIZE=-1
echo "compile cache: $JAX_COMPILATION_CACHE_DIR ($(du -sm $JAX_COMPILATION_CACHE_DIR 2>/dev/null | cut -f1) MB)"
CELL=kanana2_train_8k
BUDGET=${BUDGET:-2130}; T00=$(date +%s)
left() { echo $(( BUDGET - ($(date +%s) - T00) )); }
t0=$(date +%s)
PYTHONPATH=$ROOT python3 bench_results/hw_pr39/rotary_forms.py > $OUT/f_rotary_forms.jsonl 2> $OUT/f_rotary_forms.err
echo "rotary_forms: rc $? after $(( $(date +%s) - t0 )) s"; cat $OUT/f_rotary_forms.jsonl
run() { # name dir seed trace needs
  if [ $(left) -lt $5 ]; then echo "skipped $1: $(left) s of the call left"; return; fi
  t0=$(date +%s)
  ( cd $2 && python3 benchmark/run.py --workload $CELL --seed $3 --seconds 40 --trace $4 ) > $OUT/$1.out 2> $OUT/$1.err
  rc=$?
  echo "$1: rc $rc after $(( $(date +%s) - t0 )) s"
  grep -E "^check |^train:|^routing:" $OUT/$1.out | tr '\n' ';' | cut -c1-1300; echo
  tail -1 $OUT/$1.out | cut -c1-2600
  grep '"event": "epoch"' $2/.bench_work/$CELL/telemetry.jsonl > $OUT/$1.epochs.jsonl 2>/dev/null
  [ $4 = 1 ] && cp $2/.bench_work/$CELL/scope_time.json $OUT/f_scope_time.json 2>/dev/null
  [ $4 = 1 ] && grep '"event": "compile"' $2/.bench_work/$CELL/telemetry.jsonl > $OUT/f_compile_event.jsonl 2>/dev/null
  echo "{\"call\": \"F\", \"run\": \"$1\", \"cell\": \"$CELL\", \"seed\": $3, \"trace\": $4, \"rc\": $rc, \"line\": $(tail -1 $OUT/$1.out | grep '^{' || echo null)}" >> $OUT/cells_tpu.jsonl
  [ $rc -ne 0 ] && tail -5 $OUT/$1.err | cut -c1-1200
}
run f_s401 $ROOT 3900000401 0 330
run f_s403 $ROOT 3900000403 0 330
run f_traced_s502 $ROOT 3900000502 1 370
run f_s501 $ROOT 3900000501 0 330
rm -rf _scratch/no_rotation && mkdir -p _scratch/no_rotation && cp -r BENCHMARK.json benchmark csed_514_project_distributed_training_using_pytorch_tpu _scratch/no_rotation/
python3 - <<'P'
p = "_scratch/no_rotation/csed_514_project_distributed_training_using_pytorch_tpu/models/hybrid_lm.py"
s = open(p).read()
a = 'rope_theta=float(config["rope_theta"]),\n        rope_interleave'
assert s.count(a) == 1
open(p, "w").write(s.replace(a, 'rope_theta=None,\n        rope_interleave'))
P
run f_no_rotation_s201 $ROOT/_scratch/no_rotation 3900000201 0 460
if [ $(left) -ge 330 ]; then
  t0=$(date +%s)
  python3 benchmark/control.py --workload $CELL --seeds 3900000207 --seconds 40 > $OUT/f_control.out 2> $OUT/f_control.err
  echo "control: rc $? after $(( $(date +%s) - t0 )) s"
  grep -E "^===|^check |^reference:|^\{" $OUT/f_control.out | cut -c1-400
  tail -3 $OUT/f_control.err | cut -c1-600
else echo "skipped the control: $(left) s of the call left"; fi
for s in 3900000503 3900000504 3900000505; do run f_s$s $ROOT $s 0 330; done
echo "call F: $(( $(date +%s) - T00 )) s"
exit 0
