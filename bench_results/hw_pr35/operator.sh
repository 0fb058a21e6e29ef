#!/bin/bash
# An operator's run of train.lm with --profile and --telemetry on a cell's configuration and corpus
# (what the benchmark's last run of that cell left in .bench_work/<cell>/: model_config.json and
# corpus/), then the documented command that prints device time by scope from the two.
# usage: operator.sh <tree root> <cell> <batch> <out dir>
root=$1; cell=$2; batch=$3; out=$4; mkdir -p $out
work=$root/.bench_work/$cell
export JAX_COMPILATION_CACHE_DIR=/root/repo/.jax_cache_call JAX_COMPILATION_CACHE_MAX_SIZE=-1
t0=$(date +%s)
( cd $root && python3 -m csed_514_project_distributed_training_using_pytorch_tpu.train.lm \
    --model-config $work/model_config.json --corpus $work/corpus --mesh data=1 --epochs 3 \
    --batch-size $batch --eval-batch $batch --bf16 --remat --optimizer adamw --learning-rate 1e-6 \
    --weight-decay 0.01 --clip-grad-norm 1.0 --telemetry $out/t.jsonl --results-dir "" --generate 0 \
    --profile --profile-dir $out/prof ) > $out/train.out 2> $out/train.err; rc=$?
echo "[operator $cell rc=$rc took $(( $(date +%s) - t0 )) s]"; tail -n 4 $out/train.out
[ $rc -ne 0 ] && { tail -n 20 $out/train.err; exit 1; }
grep -h '"event": "compile"' $out/t.jsonl | python3 -c "import sys, json; e = json.loads(sys.stdin.read()); print('  compile:', {k: e.get(k) for k in ('lower_s', 'compile_s', 'scopes_s', 'scopes')})"
( cd $root && python3 benchmark/reducers/scope_time.py $out/prof $out/t.jsonl.scopes.json ) | tee $out/scope_time.txt | cut -c1-6000
rm -rf $out/prof        # the trace stays on the machine: only the table comes back
