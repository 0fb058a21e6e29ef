#!/bin/bash
# Call A (one chip): the parent on the new cell's name (its own manifest, then this PR's
# benchmark laid over it: both must exit at once, not 0); the change on qwen3_next_train_8k,
# one run untraced and one traced, a seed each, then fresh untraced seeds while the call's
# time lasts (the first six untraced are set one of the cell's spread).
set -u
ROOT=$PWD; OUT=$ROOT/chiprun_out/hw_pr43; mkdir -p $OUT
export JAX_COMPILATION_CACHE_DIR=${JAX_COMPILATION_CACHE_DIR:-$ROOT/.jax_cache_call_a} JAX_COMPILATION_CACHE_MAX_SIZE=-1
CELL=qwen3_next_train_8k
BUDGET=${BUDGET:-1900}; T00=$(date +%s)
left() { echo $(( BUDGET - ($(date +%s) - T00) )); }
t0=$(date +%s)
( cd _scratch/parent && python3 benchmark/run.py --workload $CELL --seed 4300000001 --seconds 40 --trace 0 ) > $OUT/a_parent_own.out 2> $OUT/a_parent_own.err
echo "parent, own manifest: rc $? after $(( $(date +%s) - t0 )) s: $(tail -1 $OUT/a_parent_own.err)"
t0=$(date +%s)
rm -rf _scratch/overlay && cp -r _scratch/parent _scratch/overlay && cp BENCHMARK.json _scratch/overlay/ && cp -r benchmark/. _scratch/overlay/benchmark/
( cd _scratch/overlay && python3 benchmark/run.py --workload $CELL --seed 4300000001 --seconds 40 --trace 0 ) > $OUT/a_parent_overlay.out 2> $OUT/a_parent_overlay.err
echo "parent, this PR's benchmark laid over: rc $? after $(( $(date +%s) - t0 )) s: $(tail -1 $OUT/a_parent_overlay.err)"
run() { # name seed trace needs
  if [ $(left) -lt $4 ]; then echo "skipped $1: $(left) s of the call left"; return 0; fi
  t0=$(date +%s)
  python3 benchmark/run.py --workload $CELL --seed $2 --seconds 40 --trace $3 > $OUT/$1.out 2> $OUT/$1.err
  rc=$?
  echo "$1: rc $rc after $(( $(date +%s) - t0 )) s"
  grep -E "^check |^memory|^train:|^reference:|^routing:" $OUT/$1.out | tr '\n' ';' | cut -c1-2200; echo
  tail -1 $OUT/$1.out | cut -c1-3800
  grep '"event": "epoch"' .bench_work/$CELL/telemetry.jsonl > $OUT/$1.epochs.jsonl 2>/dev/null
  if [ $3 = 1 ]; then
    cp .bench_work/$CELL/scope_time.json $OUT/a_scope_time.json 2>/dev/null
    grep '"event": "compile"' .bench_work/$CELL/telemetry.jsonl > $OUT/a_compile_event.jsonl 2>/dev/null
  fi
  echo "{\"call\": \"A\", \"run\": \"$1\", \"cell\": \"$CELL\", \"seed\": $2, \"trace\": $3, \"rc\": $rc, \"line\": $(tail -1 $OUT/$1.out | grep '^{' || echo null)}" >> $OUT/cells_tpu.jsonl
  [ $rc -ne 0 ] && tail -8 $OUT/$1.err | cut -c1-1500
  return $rc
}
if run a_s101 4300000101 0 0; then
  run a_traced_s102 4300000102 1 420
  for s in 103 104 105 106 107; do run a_s$s 4300000$s 0 330; done
fi
echo "call A: $(( $(date +%s) - T00 )) s"
exit 0
